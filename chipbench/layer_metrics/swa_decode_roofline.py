"""Share of its roofline reached by a windowed layer's attention in a
decode step, the ``swa_decode`` scope of the decode burst: the step's
K/V into the tail, the gather of the rows' rings and the softmax over
ring and tail.  Bound: memory (a row's K ring and V ring,
524 288 B at the published widths, against 8.4 MFLOP).

Least time = ``counts.swa_decode`` for the live rows (mean
``decode_rows`` of the burst records in the slice) x windowed layers x
the token-steps the slice saw, over the chip's peaks.  Time taken = the
device seconds the trace gives under the name in the burst program.
None where the program has no such scope or the family's counts no
such function."""

from chipbench import family, hybrid_slice, roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "swa_decode"


def read(run):
    seconds, events = hybrid_slice.scope_seconds(
        run.trace, SCOPE, hybrid_slice.BURST_PREFIX)
    steps = hybrid_slice.token_steps(run) if events else 0.0
    if not events or not steps:
        return None
    rows = hybrid_slice.burst_means(run, "decode_rows")["decode_rows"]
    cfg = run.cell["config_as_run"]
    counts = family.module("counts", cfg)
    if not rows or not hasattr(counts, "swa_decode"):
        return None
    flops, moved = counts.swa_decode(cfg, rows)
    layer_steps = steps * counts.num_windowed(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * layer_steps, moved * layer_steps,
        run.cell["version"]["device_kind"])
    return share
