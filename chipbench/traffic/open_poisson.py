"""Open loop: independent users, requests sent when due, whether or
not earlier ones have finished.  Parameters (a workload file's
``traffic_params``): ``rate_per_s``, ``ramp_s``, ``drain_limit_s``,
``prompt_tokens``, ``output_tokens``.

The window's arrivals are round(rate x seconds) requests whose gaps are
the mid-quantiles of the exponential distribution: a Poisson process's
gaps.  ``--seed`` draws the order of the sizes, the order of the gaps
and what the prompts say, so every seed offers the same set of sizes
and gaps, interleaved in its own way.  The ramp before the window runs
at the same rate and is not counted.
"""

from __future__ import annotations

import asyncio
import random

from chipbench.traffic.lengths import quantile_draws, sized_requests


def plan(params: dict, seconds: float, seed: int, vocab_size: int):
    rate = params["rate_per_s"]
    phases = []
    for tag, span, sub in (("ramp", params["ramp_s"], "ramp"),
                           ("window", seconds, "window")):
        order = random.Random(f"{seed}:{sub}:order")
        n = max(1, int(round(rate * span)))
        gaps = quantile_draws({"dist": "exponential", "mean": 1 / rate},
                              n, order)
        requests = sized_requests(params, n, order,
                                  random.Random(f"{seed}:{sub}"),
                                  vocab_size, tag)
        due = -span if tag == "ramp" else 0.0
        for request, gap in zip(requests, gaps):
            due += gap
            request["due"] = due
            request["phase"] = tag
        phases += requests
    return phases


async def drive(requests: list, load) -> None:
    """Send each request at its due time; return when every one has
    ended, or ``drain_limit_s`` after the window, when those still
    running are cut and count as unfinished.  ``load.send`` records how
    late each was sent."""
    tasks = []
    for request in requests:
        await load.sleep_until(request["due"])
        tasks.append(asyncio.ensure_future(load.send(request)))
    limit = load.seconds + load.params["drain_limit_s"] - load.now()
    _, pending = await asyncio.wait(tasks, timeout=max(limit, 0.0))
    if pending:
        load.cancel_in_flight()
        await asyncio.gather(*pending)
