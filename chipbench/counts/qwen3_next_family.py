"""Operations and bytes of the Qwen3-Next family of hybrid decoders:
Gated DeltaNet layers with a recurrent state a sequence, gated full
attention over a K/V cache every ``full_attention_interval``-th layer,
and in every layer a router over all published experts, the held
experts' part of the top-k sum and a shared expert.

``cfg`` is a configuration file's content; ``num_experts`` counts the
experts HELD (``expert_parallel_size`` times as many are published and
routed over).  What the *algorithm* needs, not what a formulation
does: an expert is read when a token chose it (hit), not because it is
held; the recurrent state is read once and written once a row a step;
2 bytes a weight (bfloat16), 4 a state element (float32).
"""

from __future__ import annotations

WEIGHT_BYTES = 2
STATE_BYTES = 4


def layer_is_linear(cfg: dict) -> list:
    n = cfg["full_attention_interval"]
    return [(i + 1) % n != 0 for i in range(cfg["num_hidden_layers"])]


def num_linear(cfg: dict) -> int:
    return sum(layer_is_linear(cfg))


def num_full(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - num_linear(cfg)


def full_attention_params(cfg: dict) -> int:
    """q with its gate, k, v and o of one full-attention layer, and the
    two per-head norms."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return h * 2 * q + 2 * h * kv + q * h + 2 * d


def linear_attention_params(cfg: dict) -> int:
    """One Gated DeltaNet layer: q, k, v, z, b, a projections, the
    convolution, A_log, dt_bias, the output norm and projection."""
    h = cfg["hidden_size"]
    key = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    hv = cfg["linear_num_value_heads"]
    value = hv * cfg["linear_value_head_dim"]
    conv = cfg["linear_conv_kernel_dim"] * (2 * key + value)
    return (h * (2 * key + 2 * value + 2 * hv) + conv + 2 * hv
            + cfg["linear_value_head_dim"] + value * h)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_width(cfg: dict) -> int:
    return cfg["num_experts"] * cfg.get("expert_parallel_size", 1)


def sparse_shared_params(cfg: dict) -> int:
    """What every token uses of one sparse block: the router, the
    shared expert and its gate, and the layer's two norms."""
    h = cfg["hidden_size"]
    return (h * router_width(cfg)
            + 3 * h * cfg["shared_expert_intermediate_size"] + h + 2 * h)


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def dense_params(cfg: dict) -> int:
    """Every weight a decode step reads whatever the routing: all
    layers outside their routed experts, the final norm and the head
    (the embedding is read a row a token)."""
    return (num_linear(cfg) * linear_attention_params(cfg)
            + num_full(cfg) * full_attention_params(cfg)
            + cfg["num_hidden_layers"] * sparse_shared_params(cfg)
            + cfg["hidden_size"] + head_params(cfg))


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    """K and V of one token over the full-attention layers."""
    return (2 * num_full(cfg) * cfg["num_key_value_heads"]
            * cfg["head_dim"] * kv_itemsize)


def state_elements(cfg: dict) -> int:
    """One sequence's ``S`` in one linear layer."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """The floor of a decode token-step that needs no row count: every
    weight outside the routed experts and the head once, and K and V
    of the live context in the full-attention layers.  The experts hit
    and the recurrent state go with the rows: ``hybrid_decode_step_
    bytes``."""
    if cfg["chipbench"]["quantization"] != "none":
        raise ValueError("counted for weights that are not quantized")
    return (dense_params(cfg) * WEIGHT_BYTES
            + kv_bytes_per_token(cfg) * live_context_tokens)


def hybrid_decode_step_bytes(cfg: dict, rows: float, experts_hit: float,
                             live_context_tokens: float) -> float:
    """Bytes one decode token-step of ``rows`` live rows must move:
    the floor above, ``experts_hit`` experts (the mean over the layers
    of the held experts some row chose) in every layer, and every
    row's ``S`` read and written in every linear layer."""
    experts = (cfg["num_hidden_layers"] * experts_hit
               * expert_params(cfg) * WEIGHT_BYTES)
    state = (num_linear(cfg) * rows * 2 * state_elements(cfg)
             * STATE_BYTES)
    return decode_step_bytes(cfg, live_context_tokens) + experts + state


def gdn_decode(cfg: dict, rows: float) -> tuple:
    """(operations, bytes) of the delta rule's step for ``rows`` rows
    in ONE linear layer: ``S`` read and written once a row; a decay, a
    read-out by k, a rank-one write and a read-out by q, 7 operations
    an element of ``S``."""
    s = state_elements(cfg)
    return 7.0 * rows * s, 2.0 * rows * s * STATE_BYTES


def gdn_prefill(cfg: dict, chunks: list) -> tuple:
    """(operations, bytes) of the delta rule over prompt chunks (token
    counts, one entry a row a step) in ONE linear layer: the
    recurrence's 7 operations an element of ``S`` a token, ``S`` read
    and written once a chunk, and q, k, v in and o out a token in 2
    bytes."""
    s = state_elements(cfg)
    hv = cfg["linear_num_value_heads"]
    per_token = (2 * hv * cfg["linear_key_head_dim"]
                 + 2 * hv * cfg["linear_value_head_dim"]) * 2
    tokens = float(sum(chunks))
    return (7.0 * tokens * s,
            len(chunks) * 2.0 * s * STATE_BYTES + tokens * per_token)


def moe_experts(cfg: dict, held_choices: float,
                experts_hit: float) -> tuple:
    """(operations, bytes) of the routed experts of ONE layer for one
    step: ``held_choices`` (token, choice) pairs that fell on held
    experts, each through one expert (2 operations a weight), and
    ``experts_hit`` experts read; a pair's hidden state in and out in
    2 bytes."""
    return (2.0 * held_choices * expert_params(cfg),
            experts_hit * expert_params(cfg) * WEIGHT_BYTES
            + held_choices * 2 * cfg["hidden_size"] * 2)


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``: 2 a weight a token through every layer outside
    its routed experts, through the token's held choices (the expected
    share of its ``num_experts_per_tok``: held / routed-over), the
    recurrence of the linear layers, causal attention over the context
    so far in the full ones, and the head for the one sampled position
    of a prompt's last chunk."""
    per_token = (num_linear(cfg) * linear_attention_params(cfg)
                 + num_full(cfg) * full_attention_params(cfg)
                 + cfg["num_hidden_layers"] * (
                     sparse_shared_params(cfg)
                     + cfg["num_experts_per_tok"] * cfg["num_experts"]
                     / router_width(cfg) * expert_params(cfg)))
    total = 0.0
    for start, tokens, last in chunks:
        total += 2.0 * per_token * tokens
        total += num_linear(cfg) * 7.0 * tokens * state_elements(cfg)
        attended = tokens * start + tokens * (tokens + 1) / 2
        total += (4.0 * num_full(cfg) * cfg["num_attention_heads"]
                  * cfg["head_dim"] * attended)
        if last:
            total += 2.0 * head_params(cfg)
    return total
