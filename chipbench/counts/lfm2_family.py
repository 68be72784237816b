"""Operations and bytes of the LFM2-MoE family of hybrid decoders: gated
short convolutions whose only state a sequence is the K-1 inputs before
the current one, QK-normed grouped-query attention over a K/V cache
where ``layer_types`` says ``full_attention``, dense SwiGLU
feed-forwards in the first ``num_dense_layers`` layers and a router
over all published experts with the held experts' part of the top-k sum
in the others.

``cfg`` is a configuration file's content; ``num_experts`` counts the
experts HELD (``expert_parallel_size`` times as many are published and
routed over).  What the *algorithm* needs, not what a formulation does:
an expert is read when a token chose it (hit), not because it is held;
a row's tail is read once and written once a step; 2 bytes a weight, a
tail element and a K/V element (bfloat16).
"""

from __future__ import annotations

WEIGHT_BYTES = 2
STATE_BYTES = 2


def layer_is_conv(cfg: dict) -> list:
    return [kind == "conv" for kind in cfg["layer_types"]]


def num_conv(cfg: dict) -> int:
    return sum(layer_is_conv(cfg))


def num_attention(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - num_conv(cfg)


def num_expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def head_dim(cfg: dict) -> int:
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def conv_params(cfg: dict) -> int:
    """One conv operator: in_proj (hidden -> 3 x hidden), the taps,
    out_proj; no bias."""
    h = cfg["hidden_size"]
    return h * 3 * h + cfg["conv_L_cache"] * h + h * h


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one attention layer, no bias, and the two head
    norms."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv + 2 * d


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_width(cfg: dict) -> int:
    return cfg["num_experts"] * cfg.get("expert_parallel_size", 1)


def router_params(cfg: dict) -> int:
    """One expert layer's router and its bias."""
    return (cfg["hidden_size"] + 1) * router_width(cfg)


def norm_params(cfg: dict) -> int:
    """Two norms a layer and the final one."""
    return (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]


def head_params(cfg: dict) -> int:
    """The embedding, which is the head."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def dense_params(cfg: dict) -> int:
    """Every weight a decode step reads whatever the routing: all
    layers outside their routed experts, the norms and the head (the
    embedding is the head, read once as the head and a row a token)."""
    return (num_conv(cfg) * conv_params(cfg)
            + num_attention(cfg) * attention_params(cfg)
            + cfg["num_dense_layers"] * dense_mlp_params(cfg)
            + num_expert_layers(cfg) * router_params(cfg)
            + norm_params(cfg) + head_params(cfg))


def param_count(cfg: dict) -> int:
    """What the program's init makes for this configuration."""
    return (dense_params(cfg)
            + num_expert_layers(cfg) * cfg["num_experts"]
            * expert_params(cfg))


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    """K and V of one token over the attention layers."""
    return (2 * num_attention(cfg) * cfg["num_key_value_heads"]
            * head_dim(cfg) * kv_itemsize)


def tail_elements(cfg: dict) -> int:
    """One sequence's tail in one conv layer."""
    return (cfg["conv_L_cache"] - 1) * cfg["hidden_size"]


def state_bytes_per_sequence(cfg: dict) -> int:
    return num_conv(cfg) * tail_elements(cfg) * STATE_BYTES


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """The floor of a decode token-step that needs no row count: every
    weight outside the routed experts and the head once, and K and V
    of the live context in the attention layers.  The experts hit and
    the tails go with the rows: ``hybrid_decode_step_bytes``."""
    if cfg["chipbench"]["quantization"] != "none":
        raise ValueError("counted for weights that are not quantized")
    return (dense_params(cfg) * WEIGHT_BYTES
            + kv_bytes_per_token(cfg) * live_context_tokens)


def hybrid_decode_step_bytes(cfg: dict, rows: float, experts_hit: float,
                             live_context_tokens: float) -> float:
    """Bytes one decode token-step of ``rows`` live rows must move: the
    floor above, ``experts_hit`` experts (the mean over the expert
    layers of the held experts some row chose) in every expert layer,
    and every row's tail read and written in every conv layer."""
    experts = (num_expert_layers(cfg) * experts_hit * expert_params(cfg)
               * WEIGHT_BYTES)
    tails = num_conv(cfg) * rows * 2 * tail_elements(cfg) * STATE_BYTES
    return decode_step_bytes(cfg, live_context_tokens) + experts + tails


def moe_experts(cfg: dict, held_choices: float,
                experts_hit: float) -> tuple:
    """(operations, bytes) of the routed experts of ONE expert layer
    for one step: ``held_choices`` (token, choice) pairs that fell on
    held experts, each through one expert (2 operations a weight), and
    ``experts_hit`` experts read; a pair's hidden state in and out in
    2 bytes."""
    return (2.0 * held_choices * expert_params(cfg),
            experts_hit * expert_params(cfg) * WEIGHT_BYTES
            + held_choices * 2 * cfg["hidden_size"] * 2)


def sconv_decode(cfg: dict, rows: float) -> tuple:
    """(operations, bytes) of ONE conv operator for one decode step of
    ``rows`` rows: the two projections at 2 operations a weight a row,
    the two gates and the K taps a channel; in_proj, the taps and
    out_proj read once, each row's tail read and written, its hidden
    state in and out."""
    h, k = cfg["hidden_size"], cfg["conv_L_cache"]
    return (rows * (2.0 * (3 * h * h + h * h) + (2 * k + 1) * h),
            conv_params(cfg) * WEIGHT_BYTES
            + rows * 2 * tail_elements(cfg) * STATE_BYTES
            + rows * 2 * h * 2)


def sconv_prefill(cfg: dict, chunks: list, steps: float = 1) -> tuple:
    """(operations, bytes) of ONE conv operator over the prompt chunks
    (token counts, one entry a row a step) of ``steps`` prefill steps:
    the projections, gates and taps a token; the weights once a step,
    a tail read and written a chunk, a token's hidden state in and
    out."""
    h, k = cfg["hidden_size"], cfg["conv_L_cache"]
    tokens = float(sum(chunks))
    return (tokens * (2.0 * (3 * h * h + h * h) + (2 * k + 1) * h),
            steps * conv_params(cfg) * WEIGHT_BYTES
            + len(chunks) * 2 * tail_elements(cfg) * STATE_BYTES
            + tokens * 2 * h * 2)


def attn_decode(cfg: dict, rows: float,
                live_context_tokens: float) -> tuple:
    """(operations, bytes) of the attention call of ONE attention layer
    for one decode step: a row's query against the keys of its context
    and the weighted sum of their values, 4 operations a head
    dimension a query head a cached token; K and V of the live context
    read once (a layer's share of ``kv_bytes_per_token``), a row's
    query in and output out."""
    d = head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    return (4.0 * q * live_context_tokens,
            kv_bytes_per_token(cfg) / num_attention(cfg)
            * live_context_tokens + rows * 2 * q * 2)


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``: 2 a weight a token through every layer outside
    its routed experts and through the token's held choices (the
    expected share of its ``num_experts_per_tok``: held / routed-over),
    causal attention over the context so far in the attention layers,
    and the head for the one sampled position of a prompt's last
    chunk."""
    per_token = (num_conv(cfg) * conv_params(cfg)
                 + num_attention(cfg) * attention_params(cfg)
                 + cfg["num_dense_layers"] * dense_mlp_params(cfg)
                 + num_expert_layers(cfg) * (
                     router_params(cfg)
                     + cfg["num_experts_per_tok"] * cfg["num_experts"]
                     / router_width(cfg) * expert_params(cfg)))
    total = 0.0
    for start, tokens, last in chunks:
        total += 2.0 * per_token * tokens
        attended = tokens * start + tokens * (tokens + 1) / 2
        total += (4.0 * num_attention(cfg) * cfg["num_attention_heads"]
                  * head_dim(cfg) * attended)
        if last:
            total += 2.0 * head_params(cfg)
    return total
