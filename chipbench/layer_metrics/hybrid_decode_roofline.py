"""Share of the memory roofline reached by the decode burst program of
a hybrid model, per token-step, its bytes counted for what ran in the
slice.  Per program, not per kernel.  Bound: memory.

``decode_roofline`` hands its family's counts the configuration and the
live context only; here the step's bytes go with the rows, so this
reader hands ``counts.hybrid_decode_step_bytes`` the live rows (mean
``decode_rows`` of the burst records in the slice), the experts hit a
layer (the program's ``moe_experts_hit`` counter, same records) and the
live context (the client's timelines, as ``decode_roofline`` takes
it): every row's ``S`` read and written in the linear layers, the
experts some row chose, K and V of the live tokens in the full layers,
the other weights and the head once.  Time taken = device time of one
whole execution of the burst program / steps in a burst."""

from chipbench import family, hybrid_slice, roofline
from chipbench.layer_metrics.decode_roofline import live_context_tokens

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"


def read(run):
    found = hybrid_slice.burst(run)
    if found is None:
        return None
    program, steps = found
    means = hybrid_slice.burst_means(run, "decode_rows", "moe_experts_hit")
    if None in means.values():
        return None
    cell = run.cell
    cfg = cell["config_as_run"]
    lo, hi = (t - cell["t0_unix"] for t in cell["slice_unix"])
    points = [lo + (hi - lo) * (i + 0.5) / 8 for i in range(8)]
    live = sum(live_context_tokens(run.records, t) for t in points) / 8
    moved = family.module("counts", cfg).hybrid_decode_step_bytes(
        cfg, means["decode_rows"], means["moe_experts_hit"], live)
    share, _ = roofline.kernel_roofline(
        program["whole_s"] / steps, 0.0, moved,
        cell["version"]["device_kind"])
    return share
