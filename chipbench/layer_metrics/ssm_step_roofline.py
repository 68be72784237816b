"""Share of the memory roofline reached by the decode burst program of
a model with selective-scan layers, per token-step, its bytes counted
with the rows.  Per program, not per kernel.  Bound: memory.

``decode_roofline`` hands its family's counts the configuration and the
live context only, and ``hybrid_decode_roofline`` needs an experts
counter; here the step's bytes are ``counts.ssm_step_bytes`` of the
live rows (mean ``decode_rows`` of the burst records in the slice) and
the live context (the client's timelines, as ``decode_roofline`` takes
it): every row's ``h`` read and written in the Mamba layers, K and V of
the live tokens in the attention layers, the weights and the head once.
Time taken = device time of one whole execution of the burst program /
steps in a burst."""

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
    rows = hybrid_slice.burst_means(run, "decode_rows")["decode_rows"]
    counts = family.module("counts", run.cell["config_as_run"])
    if not rows or not hasattr(counts, "ssm_step_bytes"):
        return None
    cell = run.cell
    lo, hi = (t - cell["t0_unix"] for t in cell["slice_unix"])
    points = [lo + (hi - lo) * (i + 0.5) / 8 for i in range(8)]
    live = sum(live_context_tokens(run.records, t) for t in points) / 8
    moved = counts.ssm_step_bytes(cell["config_as_run"], rows, live)
    share, _ = roofline.kernel_roofline(
        program["whole_s"] / steps, 0.0, moved,
        cell["version"]["device_kind"])
    return share
