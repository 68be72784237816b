"""Share of its roofline reached by the sampler of a block-diffusion
burst's denoising pass, the ``unmask_block`` scope: at every place of
every row's block a draw from the place's distribution, its confidence,
and the choice of the places to commit.  Bound: memory (the float32
logits of ``block`` places a row over the whole vocabulary, read once).

Least time = ``counts.unmask`` for the live rows (mean ``decode_rows``
of the burst records in the slice) x the DENOISING passes the slice saw
(the passes it saw times the records' ``denoise_passes`` over
``window``), over the chip's peaks.  Time taken = the device seconds
the trace gives under the name in the burst program.  None where the
program has no such name (another family, a parent without it)."""

from chipbench import family, hybrid_slice, roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "unmask_block"


def read(run):
    seconds, events = hybrid_slice.scope_seconds(
        run.trace, SCOPE, hybrid_slice.BURST_PREFIX)
    steps = hybrid_slice.token_steps(run) if events else 0.0
    if not events or not steps:
        return None
    means = hybrid_slice.burst_means(run, "decode_rows", "denoise_passes",
                                     "window")
    cfg = run.cell["config_as_run"]
    counts = family.module("counts", cfg)
    if (None in means.values() or not means["window"]
            or not hasattr(counts, "unmask")):
        return None
    flops, moved = counts.unmask(cfg, means["decode_rows"])
    passes = steps * means["denoise_passes"] / means["window"]
    share, _ = roofline.kernel_roofline(
        seconds, flops * passes, moved * passes,
        run.cell["version"]["device_kind"])
    return share
