"""Share of its roofline reached by the routed expert layer of an
LFM2-MoE model in the decode burst, the ``moe_experts`` scope: sorting
the (token, choice) pairs by expert, gathering their rows, the two
grouped products and the sum back over a token's choices.  Bound:
memory at decode sizes (a few dozen rows an expert, under the ridge of
240).

``moe_experts_roofline`` with the layers counted as they are: the
family's first ``num_dense_layers`` feed-forwards are dense and run no
expert, so the step's count is ``counts.moe_experts`` x
``counts.num_expert_layers`` (22 of 24), not x ``num_hidden_layers``.

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
    held_choices = means["moe_tokens_per_expert_mean"] * cfg["num_experts"]
    flops, moved = counts.moe_experts(cfg, held_choices,
                                      means["moe_experts_hit"])
    layer_steps = steps * counts.num_expert_layers(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * layer_steps, moved * layer_steps,
        run.cell["version"]["device_kind"])
    return share
