"""Operations and bytes of the Llama family of decoders (Llama,
Mistral, Qwen2 shapes): seven projections a layer, grouped-query
attention over a K/V cache, one output head.

``cfg`` is a configuration file's content.  ``roofline.py`` hands
``decode_step_bytes`` and ``prefill_flops`` on to the module a
configuration's family names; the others are theirs to use and the
tests' to check by hand.
"""

from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's seven projections."""
    h, ffn, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h + 3 * h * ffn


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    """K and V of one token over all layers."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * kv_itemsize)


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """Bytes one decode token-step must move: every projection weight
    once (1 byte each under weight-only int8, else 2), the output head
    once (2 bytes; for a tied head the embedding matrix read as the
    head), and K and V of every live row's context."""
    itemsize = 1 if cfg["chipbench"]["quantization"] == "int8" else 2
    weights = (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
               * itemsize + head_params(cfg) * 2)
    return weights + kv_bytes_per_token(cfg) * live_context_tokens


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``: 2 per weight per token through the projections,
    causal attention over the context so far (QK^T and PV: 4 x head_dim
    per query head per attended position), and the output head for the
    one sampled position of a prompt's last chunk."""
    layers, d = cfg["num_hidden_layers"], head_dim(cfg)
    heads = cfg["num_attention_heads"]
    total = 0.0
    for start, tokens, last in chunks:
        total += 2.0 * layers * layer_matmul_params(cfg) * tokens
        attended = tokens * start + tokens * (tokens + 1) / 2
        total += 4.0 * layers * heads * d * attended
        if last:
            total += 2.0 * head_params(cfg)
    return total
