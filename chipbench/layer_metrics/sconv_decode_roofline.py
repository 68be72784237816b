"""Share of its roofline reached by the gated short convolution's
decode step, the ``sconv_decode`` scope of the decode burst: the whole
operator, ``in_proj``, the two gates, the K taps over the row's held
inputs and ``out_proj``.  Bound: memory under the ridge of 240 rows
(the two projections are read for a row's 33.6 MFLOP), compute above
it.

Least time = ``counts.sconv_decode`` for the live rows (mean
``decode_rows`` of the burst records in the slice: the projections and
the taps once, each live row's tail read and written) x conv layers x
the token-steps the slice saw, over the chip's peaks.  Time taken = the
device seconds the trace gives under the name in the burst program."""

from chipbench import family, hybrid_slice, roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "sconv_decode"


def read(run):
    seconds, events = hybrid_slice.scope_seconds(
        run.trace, SCOPE, hybrid_slice.BURST_PREFIX)
    steps = hybrid_slice.token_steps(run) if events else 0.0
    if not events or not steps:
        return None
    rows = hybrid_slice.burst_means(run, "decode_rows")["decode_rows"]
    if not rows:
        return None
    cfg = run.cell["config_as_run"]
    counts = family.module("counts", cfg)
    flops, moved = counts.sconv_decode(cfg, rows)
    layer_steps = steps * counts.num_conv(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * layer_steps, moved * layer_steps,
        run.cell["version"]["device_kind"])
    return share
