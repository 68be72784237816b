"""What one run collected, as the per-layer readers see it.

Everything is a file in the run's directory, written by ``run.py``
before the readers run; a reader takes what it needs and returns
``None`` where there is nothing to read.
"""

from __future__ import annotations

import functools
import json
import os


class RunFiles:
    def __init__(self, run_dir: str):
        self.dir = run_dir

    def _json(self, name: str):
        path = os.path.join(self.dir, name)
        if not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f)

    @functools.cached_property
    def cell(self) -> dict:
        """Workload and configuration as run, the window's bounds on
        the unix clock (``t0_unix``, ``seconds``) and the profiler
        slice's (``slice_unix``)."""
        return self._json("cell.json")

    @functools.cached_property
    def records(self) -> list:
        """The client's request timelines (``client.Load.records``)."""
        return self._json("records.json") or []

    @functools.cached_property
    def arrivals(self) -> list:
        """``[t, n]``: ``n`` output tokens reached the clients in the
        millisecond from ``t`` seconds after the window's start
        (``client.Load.arrivals``)."""
        return self._json("arrivals.json") or []

    @functools.cached_property
    def spans(self) -> dict:
        """Engine spans (``--request-span-log``) by ``x-request-id``."""
        path = os.path.join(self.dir, "spans.jsonl")
        spans = {}
        if os.path.exists(path):
            with open(path) as f:
                for line in f:
                    span = json.loads(line)
                    if span.get("span") == "engine_request":
                        spans[span.get("request_id")] = span
        return spans

    @functools.cached_property
    def window_steps(self) -> list:
        """``/debug/steps`` records stamped inside the window."""
        t0 = self.cell["t0_unix"]
        return [s for s in self._json("steps.json") or []
                if t0 <= s["ts"] < t0 + self.cell["seconds"]]

    @functools.cached_property
    def compiles(self):
        """``/debug/compiles`` at the window's start (``before``) and
        end (``after``), and after the drain (``end``)."""
        return self._json("compiles.json")

    @functools.cached_property
    def memory(self):
        return self._json("memory.json")

    @functools.cached_property
    def cache_usage(self) -> list:
        """``vllm:gpu_cache_usage_perc`` polled each second."""
        return self._json("cache_usage.json") or []

    @functools.cached_property
    def trace(self):
        """``reduce.py``'s summary of the profiler slice."""
        return self._json("trace_summary.json")
