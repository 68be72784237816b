"""Share of its roofline reached by the multi-token-prediction module
inside the decode burst, the ``mtp_draft`` scope: the committed
tokens' embeddings and the main model's hidden states through ``enorm``,
``hnorm`` and ``eh_proj``, the module's own decoder layer (latent
attention over its own cache entry and tail, its router, routed and
shared experts), its norm, the head's second read of the iteration,
and the draw of the next draft from the module's distribution.  Bound:
memory at decode sizes (the layer's weights, the experts hit and the
head for a few hundred positions).

Least time = ``counts.mtp_draft`` for the live rows (mean
``decode_rows`` of the burst records in the slice), the positions the
module ran (rows x (1 + accepted / drafts), same records), the experts
hit a layer (``moe_experts_hit``) and the live context (the client's
timelines, as ``decode_roofline`` takes it) x the iterations the slice
saw, over the chip's peaks.  Time taken = the device seconds under the
name in the burst program.  None where the program has no such name (a
family without the module, drafting off, a parent without it)."""

from chipbench import family, hybrid_slice, roofline
from chipbench.layer_metrics.decode_roofline import live_context_tokens

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "mtp_draft"


def read(run):
    seconds, events = hybrid_slice.scope_seconds(
        run.trace, SCOPE, hybrid_slice.BURST_PREFIX)
    steps = hybrid_slice.token_steps(run) if events else 0.0
    if not events or not steps:
        return None
    means = hybrid_slice.burst_means(
        run, "decode_rows", "moe_experts_hit", "drafts", "accepted")
    if None in means.values() or not means["decode_rows"]:
        return None
    cell = run.cell
    cfg = cell["config_as_run"]
    counts = family.module("counts", cfg)
    if not hasattr(counts, "mtp_draft"):
        return None
    rows = means["decode_rows"]
    positions = rows * (1.0 + means["accepted"] / max(means["drafts"], 1.0))
    lo, hi = (t - cell["t0_unix"] for t in cell["slice_unix"])
    points = [lo + (hi - lo) * (i + 0.5) / 8 for i in range(8)]
    live = sum(live_context_tokens(run.records, t) for t in points) / 8
    flops, moved = counts.mtp_draft(cfg, rows, positions,
                                    means["moe_experts_hit"], live)
    share, _ = roofline.kernel_roofline(
        seconds, flops * steps, moved * steps,
        cell["version"]["device_kind"])
    return share
