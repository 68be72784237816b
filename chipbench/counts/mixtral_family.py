"""Operations and bytes of the Mixtral family of decoders: the Llama
family's attention (four projections a layer over a K/V cache) and
output head, and in place of the one feed-forward ``E`` SwiGLU experts
of three matrices each behind a router, ``k`` of them a token.

What the *algorithm* needs, not what a formulation does: a token goes
through its ``k`` experts, so a prefill chunk pays ``k`` experts'
products per token (the program's dense formulation runs all ``E`` and
masks; that shows as a lower share of the roofline, as it should), and
a decode step reads every expert: a served batch of a few dozen rows,
each drawing ``k`` of ``E``, leaves none out.
"""

from __future__ import annotations

from chipbench.counts.llama_family import (
    head_dim, head_params, kv_bytes_per_token)


def attention_params(cfg: dict) -> int:
    """Weights of one layer's q, k, v and o projections."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h


def expert_params(cfg: dict) -> int:
    """Weights of one expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_local_experts"]


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """Bytes one decode token-step must move (2 bytes a weight): the
    attention projections, the router and every expert of every layer,
    the output head, and K and V of every live row's context."""
    if cfg["chipbench"]["quantization"] != "none":
        raise ValueError("counted for weights that are not quantized")
    per_layer = (attention_params(cfg) + router_params(cfg)
                 + cfg["num_local_experts"] * expert_params(cfg))
    weights = (cfg["num_hidden_layers"] * per_layer + head_params(cfg)) * 2
    return weights + kv_bytes_per_token(cfg) * live_context_tokens


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``: 2 per weight per token through the attention
    projections, the router and the token's ``k`` experts, causal
    attention over the context so far, and the output head for the one
    sampled position of a prompt's last chunk."""
    layers, d = cfg["num_hidden_layers"], head_dim(cfg)
    heads, k = cfg["num_attention_heads"], cfg["num_experts_per_tok"]
    per_token = (attention_params(cfg) + router_params(cfg)
                 + k * expert_params(cfg))
    total = 0.0
    for start, tokens, last in chunks:
        total += 2.0 * layers * per_token * tokens
        attended = tokens * start + tokens * (tokens + 1) / 2
        total += 4.0 * layers * heads * d * attended
        if last:
            total += 2.0 * head_params(cfg)
    return total
