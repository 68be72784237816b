"""Share of the memory roofline reached by the decode burst program, per
token-step.  Per program, not per kernel.  Bound: memory.

Least time = bytes one token-step must move (``roofline.
decode_step_bytes``: projection weights and head once, K and V of the
live rows' contexts) / the chip's HBM bytes per second.  Time taken =
device time of one whole execution of the decode burst program
(``_decode_burst_impl``, or ``_decode_burst_deferred_impl`` where the
server defers K and V writes) in the profiler slice, from the trace's
``XLA Modules`` line (``reduce.whole_execution_s``), / steps in a
burst.  The live context is the mean over the slice of the contexts
of the requests then decoding, from the client's timelines."""

from chipbench import roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

PROGRAM_PREFIX = "_decode_burst"


def live_context_tokens(records: list, t: float) -> float:
    total = 0.0
    for r in records:
        if r["first"] is None or not r["first"] <= t <= r["last"]:
            continue
        span = max(r["last"] - r["first"], 1e-9)
        total += r["prompt_tokens"] + r["tokens"] * (t - r["first"]) / span
    return total


def read(run):
    bursts = [p for name, p in (run.trace or {}).get("programs", {}).items()
              if name.startswith(PROGRAM_PREFIX)]
    cell = run.cell
    if not bursts or not cell.get("slice_unix"):
        return None
    whole_s = max(p["whole_s"] for p in bursts)
    cfg = cell["config_as_run"]
    steps = cfg["chipbench"]["server_flags"]["decode-steps"]
    step_s = whole_s / steps
    lo, hi = (t - cell["t0_unix"] for t in cell["slice_unix"])
    points = [lo + (hi - lo) * (i + 0.5) / 8 for i in range(8)]
    live = sum(live_context_tokens(run.records, t) for t in points) / 8
    peak = roofline.peaks(cell["version"]["device_kind"])
    least_s = roofline.decode_step_bytes(cfg, live) / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
