"""Share of its roofline reached by the routed expert layer in the
decode burst, the ``moe_experts`` scope: sorting the (token, choice)
pairs by expert, gathering their rows, the two grouped products and the
sum back over a token's choices.  Bound: memory at decode sizes (a few
rows an expert, under the ridge of 240).

``moe_experts_roofline``'s arithmetic with the layer and expert counts
asked of the family's ``counts`` (``num_expert_layers(cfg)``,
``held_experts(cfg)``) and not of the keys ``num_hidden_layers`` /
``num_experts``, which not every configuration has: one reader for any
family whose counts give those two and ``moe_experts``.

Least time = ``counts.moe_experts`` for what the program's counters say
ran (``moe_experts_hit`` experts read and ``moe_tokens_per_expert_mean``
x held experts pairs, means over the burst records in the slice) x
expert layers x the token-steps the slice saw, over the chip's peaks.
Time taken = the device seconds under the name in the burst program."""

from chipbench import family, hybrid_slice, roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "moe_experts"


def read(run):
    seconds, events = hybrid_slice.scope_seconds(
        run.trace, SCOPE, hybrid_slice.BURST_PREFIX)
    steps = hybrid_slice.token_steps(run) if events else 0.0
    if not events or not steps:
        return None
    means = hybrid_slice.burst_means(
        run, "moe_experts_hit", "moe_tokens_per_expert_mean")
    if None in means.values():
        return None
    cfg = run.cell["config_as_run"]
    counts = family.module("counts", cfg)
    if not hasattr(counts, "held_experts"):
        return None
    held_choices = (means["moe_tokens_per_expert_mean"]
                    * counts.held_experts(cfg))
    flops, moved = counts.moe_experts(cfg, held_choices,
                                      means["moe_experts_hit"])
    layer_steps = steps * counts.num_expert_layers(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * layer_steps, moved * layer_steps,
        run.cell["version"]["device_kind"])
    return share
