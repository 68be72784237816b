"""Share of its roofline reached by the attention call of a
block-diffusion burst's pass, the ``block_attention`` scope: the
``block`` queries of every row, folded into the group axis of the paged
decode kernel, against the row's pages, the burst's tail of finished
blocks and the block itself.  Bound: memory (K and V of the live
context, read once for the whole block).

Least time = ``counts.block_attention`` for the live rows (mean
``decode_rows`` of the burst records in the slice) and the live context
(the client's timelines, as ``decode_roofline`` takes it) x layers x
the passes the slice saw, over the chip's peaks.  Time taken = the
device seconds the trace gives under the name in the burst program.
None where the program has no such name (another family, a parent
without it)."""

from chipbench import family, hybrid_slice, roofline
from chipbench.layer_metrics.decode_roofline import live_context_tokens

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "block_attention"


def read(run):
    seconds, events = hybrid_slice.scope_seconds(
        run.trace, SCOPE, hybrid_slice.BURST_PREFIX)
    steps = hybrid_slice.token_steps(run) if events else 0.0
    if not events or not steps:
        return None
    rows = hybrid_slice.burst_means(run, "decode_rows")["decode_rows"]
    cell = run.cell
    cfg = cell["config_as_run"]
    counts = family.module("counts", cfg)
    if not rows or not hasattr(counts, "block_attention"):
        return None
    lo, hi = (t - cell["t0_unix"] for t in cell["slice_unix"])
    points = [lo + (hi - lo) * (i + 0.5) / 8 for i in range(8)]
    live = sum(live_context_tokens(run.records, t) for t in points) / 8
    flops, moved = counts.block_attention(cfg, rows, live)
    layer_steps = steps * counts.num_attention(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * layer_steps, moved * layer_steps,
        cell["version"]["device_kind"])
    return share
