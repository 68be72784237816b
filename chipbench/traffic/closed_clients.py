"""Closed loop: ``clients`` callers, each sending its next request when
its last one ends.  Parameters (a workload file's ``traffic_params``):
``clients``, ``ramp_s``, ``pool``, ``drain_limit_s``, ``prompt_tokens``,
``output_tokens``.

Requests come from one pool of at least ``pool`` requests, taken in
turn by whichever client is free.  The pool is made of blocks of
``clients`` requests; every block holds the same sizes (the
distributions' ``clients`` mid-quantiles) in an order ``--seed`` draws,
and ``--seed`` draws what the prompts say.  So whatever number of
requests a run gets through, every seed has offered it the same sizes
but for the last block's part, and the batch in flight holds much the
same mix under every seed.  The loop starts ``ramp_s`` before the
window; a request counts for the window if it was sent inside it, and
the loop keeps running after the window until every such request has
ended, so that the last of them finish under the same load as the
first.  A request still running ``drain_limit_s`` after the window is
cut and counts as unfinished, which makes the run not correct: the
limit only bounds how long a broken run can take, and a workload file
sets it well above what its longest request needs.
"""

from __future__ import annotations

import asyncio
import random

from chipbench.traffic.lengths import sized_requests


def plan(params: dict, seconds: float, seed: int, vocab_size: int):
    order = random.Random(f"{seed}:pool:order")
    ids = random.Random(f"{seed}:pool")
    n = params["clients"]
    return [request for block in range(-(-params["pool"] // n))
            for request in sized_requests(params, n, order, ids,
                                          vocab_size, f"b{block}")]


async def drive(requests: list, load) -> None:
    params = load.params
    queue = iter(requests)
    open_window = set()
    state = {"stop": False}

    async def client(index: int) -> None:
        # Staggered starts: a closed loop whose clients all start at
        # once prefills in lockstep for the first few turns.
        await load.sleep_until(
            -params["ramp_s"] * (1 - index / params["clients"] / 2))
        while not state["stop"]:
            request = next(queue, None)
            if request is None:
                raise RuntimeError("request pool ran out: raise 'pool'")
            now = load.now()
            request["phase"] = ("ramp" if now < 0 else
                                "window" if now < load.seconds
                                else "post")
            request["due"] = now
            if request["phase"] == "window":
                open_window.add(request["id"])
            await load.send(request)
            open_window.discard(request["id"])

    async def watch() -> None:
        await load.sleep_until(load.seconds)
        while open_window and load.now() < (load.seconds
                                            + params["drain_limit_s"]):
            await asyncio.sleep(0.05)
        state["stop"] = True
        load.cancel_in_flight()

    tasks = [asyncio.ensure_future(client(i))
             for i in range(params["clients"])]
    watcher = asyncio.ensure_future(watch())
    done, _ = await asyncio.wait([watcher, *tasks],
                                 return_when=asyncio.FIRST_EXCEPTION)
    state["stop"] = True
    load.cancel_in_flight()
    for task in await asyncio.gather(watcher, *tasks,
                                     return_exceptions=True):
        if isinstance(task, Exception):
            raise task
