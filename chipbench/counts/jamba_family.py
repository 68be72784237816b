"""Operations and bytes of the Jamba family of hybrid decoders (dense):
Mamba-1 mixers with a recurrent state a sequence, attention over a K/V
cache where ``i % attn_layer_period == attn_layer_offset``, and in
every layer a SwiGLU MLP.

``cfg`` is a configuration file's content.  What the *algorithm* needs,
not what a formulation does: the scan's state ``h`` is read once and
written once a row a step; 2 bytes a weight (bfloat16), 4 a state
element (float32).  The convolution's tail (three inputs a channel in
2 bytes, a tenth of ``h``) is left out of the state's bytes, so a share
reads a little low and never high.
"""

from __future__ import annotations

WEIGHT_BYTES = 2
STATE_BYTES = 4
# A state element a token: delta * A, exp, * h, dx * B, +, * C, the sum.
SCAN_OPS = 7.0


def layer_is_mamba(cfg: dict) -> list:
    return [i % cfg["attn_layer_period"] != cfg["attn_layer_offset"]
            for i in range(cfg["num_hidden_layers"])]


def num_mamba(cfg: dict) -> int:
    return sum(layer_is_mamba(cfg))


def num_attention(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - num_mamba(cfg)


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def head_dim(cfg: dict) -> int:
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def mamba_params(cfg: dict) -> int:
    """One Mamba mixer: in_proj, the convolution and its bias, x_proj,
    the three small norms, dt_proj and its bias, A_log, D, out_proj."""
    h, di, n, r = (cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"],
                   cfg["mamba_dt_rank"])
    return (h * 2 * di + di * cfg["mamba_d_conv"] + di
            + di * (r + 2 * n) + (r + 2 * n) + r * di + di
            + di * n + di + di * h)


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one attention layer, no bias."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv


def layer_shared_params(cfg: dict) -> int:
    """What every layer has beside its mixer: the MLP and two norms."""
    h = cfg["hidden_size"]
    return 3 * h * cfg["intermediate_size"] + 2 * h


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """Every parameter: the layers, the final norm, the embedding, and
    the head where it is not the embedding again."""
    head = 1 if cfg.get("tie_word_embeddings") else 2
    return (num_mamba(cfg) * mamba_params(cfg)
            + num_attention(cfg) * attention_params(cfg)
            + cfg["num_hidden_layers"] * layer_shared_params(cfg)
            + cfg["hidden_size"] + head * head_params(cfg))


def decode_params(cfg: dict) -> int:
    """Every weight a decode step reads: all layers, the final norm and
    the head once (the embedding is read a row a token)."""
    return (num_mamba(cfg) * mamba_params(cfg)
            + num_attention(cfg) * attention_params(cfg)
            + cfg["num_hidden_layers"] * layer_shared_params(cfg)
            + cfg["hidden_size"] + head_params(cfg))


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
    tail = (cfg["mamba_d_conv"] - 1) * d_inner(cfg) * tail_itemsize
    return num_mamba(cfg) * (state_elements(cfg) * STATE_BYTES + tail)


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """The floor of a decode token-step that needs no row count: every
    weight and the head once, and K and V of the live context in the
    attention layers.  The recurrent state goes with the rows:
    ``ssm_step_bytes``."""
    if cfg["chipbench"]["quantization"] != "none":
        raise ValueError("counted for weights that are not quantized")
    return (decode_params(cfg) * WEIGHT_BYTES
            + kv_bytes_per_token(cfg) * live_context_tokens)


def ssm_step_bytes(cfg: dict, rows: float,
                   live_context_tokens: float) -> float:
    """Bytes one decode token-step of ``rows`` live rows must move: the
    floor above, and every row's ``h`` read and written in every Mamba
    layer."""
    state = num_mamba(cfg) * rows * 2 * state_elements(cfg) * STATE_BYTES
    return decode_step_bytes(cfg, live_context_tokens) + state


def ssm_decode(cfg: dict, rows: float) -> tuple:
    """(operations, bytes) of the selective scan's step for ``rows``
    rows in ONE Mamba layer: ``h`` read and written once a row, 7
    operations an element."""
    s = state_elements(cfg)
    return SCAN_OPS * rows * s, 2.0 * rows * s * STATE_BYTES


def ssm_prefill(cfg: dict, chunks: list) -> tuple:
    """(operations, bytes) of the selective scan over prompt chunks
    (token counts, one entry a row a step) in ONE Mamba layer: 7
    operations an element of ``h`` a token, ``h`` read and written once
    a chunk, and a token's x, delta, B and C in and y out in 2 bytes."""
    s = state_elements(cfg)
    per_token = (3 * d_inner(cfg) + 2 * cfg["mamba_d_state"]) * 2
    tokens = float(sum(chunks))
    return (SCAN_OPS * tokens * s,
            len(chunks) * 2.0 * s * STATE_BYTES + tokens * per_token)


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``: 2 a weight a token through every layer, the scan
    of the Mamba layers, causal attention over the context so far in
    the attention layers, and the head for the one sampled position of
    a prompt's last chunk."""
    per_token = (num_mamba(cfg) * mamba_params(cfg)
                 + num_attention(cfg) * attention_params(cfg)
                 + cfg["num_hidden_layers"] * layer_shared_params(cfg))
    total = 0.0
    for start, tokens, last in chunks:
        total += 2.0 * per_token * tokens
        total += num_mamba(cfg) * SCAN_OPS * tokens * state_elements(cfg)
        attended = tokens * start + tokens * (tokens + 1) / 2
        total += (4.0 * num_attention(cfg) * cfg["num_attention_heads"]
                  * head_dim(cfg) * attended)
        if last:
            total += 2.0 * head_params(cfg)
    return total
