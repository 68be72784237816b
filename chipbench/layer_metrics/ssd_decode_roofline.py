"""Share of its roofline reached by the Mamba-2 recurrence's decode
step, the ``ssd_decode`` scope of the decode burst: everything that
touches a row's state ``h`` (the kernel over the pool and what makes
its operands; in the XLA form the gather from the pool, the step, the
scatter back).  Bound: memory (5 operations an element against 8
bytes).

Least time = ``counts.ssd_decode`` for the live rows (mean
``decode_rows`` of the burst records in the slice) x Mamba layers x the
token-steps the slice saw, over the chip's peaks.  Time taken = the
device seconds the trace gives under the name in the burst program.
None where the program has no such scope or the family's counts no
such function."""

from chipbench import family, hybrid_slice, roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "ssd_decode"


def read(run):
    seconds, events = hybrid_slice.scope_seconds(
        run.trace, SCOPE, hybrid_slice.BURST_PREFIX)
    steps = hybrid_slice.token_steps(run) if events else 0.0
    if not events or not steps:
        return None
    rows = hybrid_slice.burst_means(run, "decode_rows")["decode_rows"]
    cfg = run.cell["config_as_run"]
    counts = family.module("counts", cfg)
    if not rows or not hasattr(counts, "ssd_decode"):
        return None
    flops, moved = counts.ssd_decode(cfg, rows)
    layer_steps = steps * counts.num_mamba(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * layer_steps, moved * layer_steps,
        run.cell["version"]["device_kind"])
    return share
