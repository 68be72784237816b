"""Request sizes drawn from a workload file's distributions.

Every seed gets the same set of sizes in another order: n draws from a
distribution are its n mid-quantiles, shuffled by the seed's generator.
Two seeds then offer the same work, and differ in how it is
interleaved.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def _inverse_cdf(dist: dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    if kind == "lognormal":
        return dist["median"] * math.exp(
            dist["sigma"] * NormalDist().inv_cdf(u))
    if kind == "exponential":
        return -dist["mean"] * math.log1p(-u)
    raise ValueError(f"unknown distribution {kind!r}")


def quantile_draws(dist: dict, n: int, rng: random.Random) -> list:
    """The n mid-quantiles of ``dist``, clipped, in a seeded order."""
    values = [_inverse_cdf(dist, (i + 0.5) / n) for i in range(n)]
    if "min" in dist:
        values = [max(dist["min"], v) for v in values]
    if "max" in dist:
        values = [min(dist["max"], v) for v in values]
    rng.shuffle(values)
    return values


def sized_requests(params: dict, n: int, order: random.Random,
                   ids: random.Random, vocab_size: int, tag: str) -> list:
    """n requests, their sizes in the order ``order`` gives and their
    prompt ids from ``ids``: uniform over the vocabulary, so a prompt
    is exactly its drawn length and shares no page with another."""
    prompts = quantile_draws(params["prompt_tokens"], n, order)
    outputs = quantile_draws(params["output_tokens"], n, order)
    return [{"id": f"{tag}-{i}",
             "prompt_ids": [ids.randrange(vocab_size)
                            for _ in range(int(round(p)))],
             "max_tokens": int(round(o))}
            for i, (p, o) in enumerate(zip(prompts, outputs))]
