"""Operations and bytes of the Granite-MoE-hybrid family of decoders:
Mamba-2 mixers with a recurrent state a sequence, no-position
grouped-query attention over a K/V cache where ``layer_types`` says
``attention``, and in every layer a softmax router over all published
experts with the held experts' part of the top-k sum beside a shared
expert added whole.

``cfg`` is a configuration file's content; ``num_local_experts`` counts
the experts HELD (``expert_parallel_size`` times as many are published
and routed over), and ``intermediate_size`` is one expert's width.
What the *algorithm* needs, not what a formulation does: the
recurrence's state ``h`` is read once and written once a row a step;
an expert is read when a token chose it (hit), not because it is held;
2 bytes a weight and a K/V element (bfloat16), 4 a state element
(float32).  The convolution's tail (three inputs a channel in 2 bytes,
an eightieth of ``h``) is left out of the state's bytes a step, so a
share reads a little low and never high.
"""

from __future__ import annotations

WEIGHT_BYTES = 2
STATE_BYTES = 4
# A state element a token: decay * h, dx * B, +, * C, the sum. The
# decay's exponential is one a head, not one an element.
SSD_OPS = 5.0


def layer_is_mamba(cfg: dict) -> list:
    return [kind == "mamba" for kind in cfg["layer_types"]]


def num_mamba(cfg: dict) -> int:
    return sum(layer_is_mamba(cfg))


def num_attention(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - num_mamba(cfg)


def num_expert_layers(cfg: dict) -> int:
    """Every layer has the experts."""
    return cfg["num_hidden_layers"]


def held_experts(cfg: dict) -> int:
    return cfg["num_local_experts"]


def router_width(cfg: dict) -> int:
    return held_experts(cfg) * cfg.get("expert_parallel_size", 1)


def d_inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def conv_channels(cfg: dict) -> int:
    """``x | B | C``."""
    return d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def head_dim(cfg: dict) -> int:
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def mamba_params(cfg: dict) -> int:
    """One Mamba-2 mixer: in_proj (z | xBC | dt), the convolution and
    its bias, dt_bias, A_log and D a head, the gated norm, out_proj."""
    h, di, heads = cfg["hidden_size"], d_inner(cfg), cfg["mamba_n_heads"]
    conv = conv_channels(cfg)
    return (h * (di + conv + heads) + cfg["mamba_d_conv"] * conv + conv
            + 3 * heads + di + di * h)


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one attention layer, no bias."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * router_width(cfg)


def layer_shared_params(cfg: dict) -> int:
    """What every layer has beside its mixer and its routed experts:
    the router, the shared expert and two norms."""
    return (router_params(cfg) + shared_params(cfg)
            + 2 * cfg["hidden_size"])


def head_params(cfg: dict) -> int:
    """The embedding, which is the head."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def dense_params(cfg: dict) -> int:
    """Every weight a decode step reads whatever the routing: the
    mixers, the routers, the shared experts, the norms and the head
    (the embedding is the head, read once as the head and a row a
    token)."""
    return (num_mamba(cfg) * mamba_params(cfg)
            + num_attention(cfg) * attention_params(cfg)
            + cfg["num_hidden_layers"] * layer_shared_params(cfg)
            + cfg["hidden_size"] + head_params(cfg))


def param_count(cfg: dict) -> int:
    """What the program's init makes for this configuration."""
    return (dense_params(cfg)
            + num_expert_layers(cfg) * held_experts(cfg)
            * expert_params(cfg))


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    """K and V of one token over the attention layers."""
    return (2 * num_attention(cfg) * cfg["num_key_value_heads"]
            * head_dim(cfg) * kv_itemsize)


def state_elements(cfg: dict) -> int:
    """One sequence's ``h`` in one Mamba layer."""
    return d_inner(cfg) * cfg["mamba_d_state"]


def state_bytes_per_sequence(cfg: dict, tail_itemsize: int = 2) -> int:
    """What one sequence holds of the state pool: ``h`` and the
    convolution's tail in every Mamba layer."""
    tail = (cfg["mamba_d_conv"] - 1) * conv_channels(cfg) * tail_itemsize
    return num_mamba(cfg) * (state_elements(cfg) * STATE_BYTES + tail)


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """The floor of a decode token-step that needs no row count: every
    weight outside the routed experts and the head once, and K and V
    of the live context in the attention layers.  The experts hit and
    the recurrent state go with the rows:
    ``hybrid_decode_step_bytes``."""
    if cfg["chipbench"]["quantization"] != "none":
        raise ValueError("counted for weights that are not quantized")
    return (dense_params(cfg) * WEIGHT_BYTES
            + kv_bytes_per_token(cfg) * live_context_tokens)


def hybrid_decode_step_bytes(cfg: dict, rows: float, experts_hit: float,
                             live_context_tokens: float) -> float:
    """Bytes one decode token-step of ``rows`` live rows must move: the
    floor above, ``experts_hit`` experts (the mean over the layers of
    the held experts some row chose) in every layer, and every row's
    ``h`` read and written in every Mamba layer."""
    experts = (num_expert_layers(cfg) * experts_hit * expert_params(cfg)
               * WEIGHT_BYTES)
    state = num_mamba(cfg) * rows * 2 * state_elements(cfg) * STATE_BYTES
    return decode_step_bytes(cfg, live_context_tokens) + experts + state


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


def ssd_decode(cfg: dict, rows: float) -> tuple:
    """(operations, bytes) of the recurrence's step for ``rows`` rows
    in ONE Mamba layer: ``h`` read and written once a row, 5
    operations an element."""
    s = state_elements(cfg)
    return SSD_OPS * rows * s, 2.0 * rows * s * STATE_BYTES


def ssd_prefill(cfg: dict, chunks: list) -> tuple:
    """(operations, bytes) of the recurrence over prompt chunks (token
    counts, one entry a row a step) in ONE Mamba layer: 5 operations
    an element of ``h`` a token, which is the recurrence's own count
    (the matrix form's is more: 2 Q (d_state + channels) + 4 d_state x
    channels a token at a chunk of Q); ``h`` read and written once a
    chunk, and a token's x, B, C and dt in and y out in 2 bytes."""
    s = state_elements(cfg)
    per_token = (2 * d_inner(cfg) + 2 * cfg["mamba_d_state"]
                 + cfg["mamba_n_heads"]) * 2
    tokens = float(sum(chunks))
    return (SSD_OPS * tokens * s,
            len(chunks) * 2.0 * s * STATE_BYTES + tokens * per_token)


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``: 2 a weight a token through every layer outside
    its routed experts and through the token's held choices (the
    expected share of its ``num_experts_per_tok``: held / routed-over),
    the recurrence of the Mamba layers, causal attention over the
    context so far in the attention layers, and the head for the one
    sampled position of a prompt's last chunk."""
    per_token = (num_mamba(cfg) * mamba_params(cfg)
                 + num_attention(cfg) * attention_params(cfg)
                 + cfg["num_hidden_layers"] * (
                     layer_shared_params(cfg)
                     + cfg["num_experts_per_tok"] * held_experts(cfg)
                     / router_width(cfg) * expert_params(cfg)))
    total = 0.0
    for start, tokens, last in chunks:
        total += 2.0 * per_token * tokens
        total += num_mamba(cfg) * SSD_OPS * tokens * state_elements(cfg)
        attended = tokens * start + tokens * (tokens + 1) / 2
        total += (4.0 * num_attention(cfg) * cfg["num_attention_heads"]
                  * head_dim(cfg) * attended)
        if last:
            total += 2.0 * head_params(cfg)
    return total
