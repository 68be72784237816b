"""The load generator: one process, one event loop, streamed
``/v1/completions`` requests through the router, each one's timeline
recorded on the host's clock.

Times in a record are seconds relative to the start of the measured
window (negative during the ramp).  Beside the records the load keeps
``arrivals``: ``[t, n]`` for every millisecond ``t`` (its start, in
seconds on the same clock) in which output tokens reached a client, and
how many, over all requests of all phases, in time order.  Tokens per
second are counted from that list (``e2e.output_tok_s``), so a run can
be counted again afterwards with the window laid elsewhere
(``phase.py``).
"""

from __future__ import annotations

import asyncio
import json
import math
import re
import time

import aiohttp

from chipbench import wordtok

# One streamed chunk carries the text of one or more tokens, each a
# word of the benchmark's tokenizer ("t9165"), with nothing between.
_TEXT = re.compile(rb'"text": "((?:t\d+)+)"')
_WORD = re.compile(rb"t\d+")


class Load:
    """Clock, sender and records of one run's traffic."""

    def __init__(self, url: str, model: str, params: dict,
                 seconds: float, sampling: dict, start_in_s: float):
        self.url = url.rstrip("/") + "/v1/completions"
        self.model = model
        self.params = params
        self.seconds = seconds
        self.sampling = sampling
        self.t0 = time.perf_counter() + start_in_s
        self.t0_unix = time.time() + start_in_s
        self.records = []
        self.arrivals = []
        self._session = None
        self._in_flight = set()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    async def sleep_until(self, t: float) -> None:
        delay = t - self.now()
        if delay > 0:
            await asyncio.sleep(delay)

    def cancel_in_flight(self) -> None:
        for task in list(self._in_flight):
            task.cancel()

    async def send(self, request: dict) -> dict:
        task = asyncio.current_task()
        self._in_flight.add(task)
        record = {"id": request["id"], "phase": request["phase"],
                  "due": request["due"],
                  "prompt_tokens": len(request["prompt_ids"]),
                  "max_tokens": request["max_tokens"],
                  "sent": None, "first": None, "last": None,
                  "ended": None,
                  "tokens": 0, "usage_tokens": None, "done": False,
                  "error": None}
        self.records.append(record)
        body = json.dumps({
            "model": self.model,
            "prompt": wordtok.text_of(request["prompt_ids"]),
            "max_tokens": request["max_tokens"], "stream": True,
            "stream_options": {"include_usage": True},
            "ignore_eos": True, **self.sampling}).encode()
        try:
            record["sent"] = self.now()
            async with self._session.post(
                    self.url, data=body,
                    headers={"content-type": "application/json",
                             "x-request-id": request["id"]}) as resp:
                if resp.status != 200:
                    record["error"] = f"http {resp.status}"
                    return record
                async for line in resp.content:
                    self._on_line(record, line)
        except asyncio.CancelledError:
            # Cut by the traffic kind: past the drain limit, or a closed
            # loop's request from after the window.
            record["error"] = "unfinished"
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
            record["error"] = f"{type(e).__name__}: {e}"
        finally:
            record["ended"] = self.now()
            self._in_flight.discard(task)
        return record

    def _on_line(self, record: dict, line: bytes) -> None:
        if not line.startswith(b"data: "):
            return
        found = _TEXT.search(line)
        if found:
            now = self.now()
            n = len(_WORD.findall(found.group(1)))
            if record["first"] is None:
                record["first"] = now
            record["last"] = now
            record["tokens"] += n
            at = math.floor(now * 1e3) / 1e3
            if self.arrivals and self.arrivals[-1][0] == at:
                self.arrivals[-1][1] += n
            else:
                self.arrivals.append([at, n])
        elif line.startswith(b"data: [DONE]"):
            record["done"] = True
        elif b'"usage": {' in line:
            usage = json.loads(line[6:]).get("usage") or {}
            record["usage_tokens"] = usage.get("completion_tokens")
            record["usage_prompt_tokens"] = usage.get("prompt_tokens")

    async def run(self, drive, requests) -> None:
        timeout = aiohttp.ClientTimeout(total=None, sock_read=120)
        connector = aiohttp.TCPConnector(limit=0)
        async with aiohttp.ClientSession(
                timeout=timeout, connector=connector) as session:
            self._session = session
            await drive(requests, self)
