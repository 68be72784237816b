"""Share of its roofline reached by the attention call of an LFM2-MoE
model's attention layers in the decode burst, the ``qknorm_attn``
scope: the step's K and V into the layer's tail, the gather of the
rows' pages, the two contractions over pages and tail.  (The two head
norms and the rotary come before the name and are not in it.)  Bound:
memory (a query group of 4 heads shares each cached element: 8
operations a byte, against the ridge's 240).

Least time = ``counts.attn_decode`` for the live rows (mean
``decode_rows`` of the burst records in the slice) and the live context
(the client's timelines, as ``decode_roofline`` takes it: K and V of
the live tokens read once a layer) x attention layers x the token-steps
the slice saw, over the chip's peaks.  Time taken = the device seconds
the trace gives under the name in the burst program."""

from chipbench import family, hybrid_slice, roofline
from chipbench.layer_metrics.decode_roofline import live_context_tokens

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "qknorm_attn"


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
    flops, moved = counts.attn_decode(cfg, rows, live)
    layer_steps = steps * counts.num_attention(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * layer_steps, moved * layer_steps,
        cell["version"]["device_kind"])
    return share
