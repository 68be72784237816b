"""From request timelines to the end-to-end metrics.

A record is what ``client.Load`` keeps for one request; times are
seconds from the window's start.  Only requests of phase ``window``
count: in an open loop those due inside the window, in a closed loop
those sent inside it.  A request that failed, came back short or ended
without ``[DONE]`` counts as failed.  A request that the run cut
because it outlasted the drain limit is unfinished; it is counted
apart, and ``run.py`` reports the run as not correct.  Neither kind
leaves a statistic: each misses every percentile and every mean as
+inf, so a stall cannot take its own slowest requests out of a tail.

Tokens per second are counted not from the records but from the
arrivals (``client.Load.arrivals``): every output token that reached a
client, of whatever phase its request, by when it arrived.
"""

from __future__ import annotations

import math


def percentile(values: list, q: float) -> float:
    """The q-th percentile by linear interpolation between closest
    ranks (numpy's default); +inf entries sort last."""
    if not values:
        raise ValueError("no values")
    ordered = sorted(values)
    k = (len(ordered) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if ordered[hi] == math.inf:
        return math.inf
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (k - lo)


def request_ok(record: dict) -> bool:
    return (record["error"] is None and record["done"]
            and record["usage_tokens"] == record["max_tokens"]
            and record["tokens"] == record["max_tokens"])


def unfinished(record: dict) -> bool:
    return record["error"] == "unfinished"


def ttft_ms(record: dict, from_key: str = "due") -> float:
    if not request_ok(record):
        return math.inf
    return (record["first"] - record[from_key]) * 1e3


def tpot_ms(record: dict) -> float:
    """(last token time - first token time) / (output tokens - 1)."""
    if not request_ok(record) or record["tokens"] < 2:
        return math.inf
    return (record["last"] - record["first"]) * 1e3 / (record["tokens"] - 1)


def in_flight(records: list, t: float) -> int:
    """Requests of any phase sent by ``t`` and not ended by then: the
    backlog, whose growth over a window says the load is above what
    the system sustains."""
    return sum(1 for r in records
               if r["sent"] is not None and r["sent"] <= t
               and (r["ended"] is None or r["ended"] > t))


def output_tok_s(arrivals: list, seconds: float) -> float:
    """Output tokens that arrived inside the window per second of
    window, with the window's edges weighted: the mean, over every
    sub-window of 0.8 x ``seconds`` that fits inside ``[0, seconds)``,
    of the tokens that arrived in the sub-window over its length.  In
    closed form a token arriving at ``t`` weighs ``min(1, t/T,
    (seconds - t)/T)`` with ``T = seconds/5``, nothing outside the
    window, and the sum is divided by ``seconds - T``.

    Tokens arrive a decode burst at a time (some 2000 together, 7% of
    a 45 s window), so a plain count over the window moves by a whole
    delivery with wherever an edge falls among them.  Under the taper
    a delivery near an edge weighs little, and an even stream still
    reads its rate exactly.  It knows nothing of bursts or cycles, and
    a stall inside the window lowers it as it lowered the count."""
    taper = seconds / 5
    weighted = sum(n * min(1.0, t / taper, (seconds - t) / taper)
                   for t, n in arrivals if 0 <= t < seconds)
    return weighted / (seconds - taper)


def summarize(records: list, arrivals: list, seconds: float) -> dict:
    window = [r for r in records if r["phase"] == "window"]
    cut = [r for r in window if unfinished(r)]
    failed = [r for r in window if not request_ok(r) and not unfinished(r)]
    out = {"attempted": len(window), "failed": len(failed),
           "unfinished": len(cut)}
    for name, values in (("ttft", [ttft_ms(r) for r in window]),
                         ("tpot", [tpot_ms(r) for r in window])):
        if values:
            out[f"{name}_p90_ms"] = percentile(values, 90)
            out[f"{name}_p50_ms"] = percentile(values, 50)
            out[f"{name}_mean_ms"] = sum(values) / len(values)
    out["output_tok_s"] = output_tok_s(arrivals, seconds)
    return out
