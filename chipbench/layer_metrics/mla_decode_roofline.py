"""Share of its roofline reached by the latent attention (MLA) of a
decode burst, the ``mla_decode`` scope: the step's latent into the
sublayer's tail, the query's absorption (``q_nope`` through ``W_UK``),
the walk over the rows' latent pages with both products off the one
copy of a page, the merge with the tail's state and the values'
up-projection (``W_UV``).  (The low-rank projections, the norms, the
rotary and the output projection come before and after the name and
are not in it.)  Bound: whichever peak is slower: at the published
sizes 64 heads share each cached value twice, 121 operations a byte
where the ridge is 240, so memory bounds it; ``kernel_roofline`` takes
the slower and the count does not assume so.

Least time = ``counts.mla_decode`` for the live rows (mean
``decode_rows`` of the burst records in the slice) and the live context
(the client's timelines, as ``decode_roofline`` takes it: the latent of
the live tokens read once a sublayer) x sublayers x the token-steps the
slice saw, over the chip's peaks.  Time taken = the device seconds the
trace gives under the name in the burst program.  None where the
program has no such name (a family without latent attention)."""

from chipbench import family, hybrid_slice, roofline
from chipbench.layer_metrics.decode_roofline import live_context_tokens

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "mla_decode"


def read(run):
    seconds, events = hybrid_slice.scope_seconds(
        run.trace, SCOPE, hybrid_slice.BURST_PREFIX)
    steps = hybrid_slice.token_steps(run) if events else 0.0
    if not events or not steps:
        return None
    rows = hybrid_slice.burst_means(run, "decode_rows")["decode_rows"]
    if not rows:
        return None
    cell = run.cell
    cfg = cell["config_as_run"]
    counts = family.module("counts", cfg)
    lo, hi = (t - cell["t0_unix"] for t in cell["slice_unix"])
    points = [lo + (hi - lo) * (i + 0.5) / 8 for i in range(8)]
    live = sum(live_context_tokens(run.records, t) for t in points) / 8
    flops, moved = counts.mla_decode(cfg, rows, live)
    sublayer_steps = steps * counts.num_sublayers(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * sublayer_steps, moved * sublayer_steps,
        cell["version"]["device_kind"])
    return share
