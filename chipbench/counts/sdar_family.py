"""Operations and bytes of the SDAR-MoE family of block-diffusion
decoders: the Qwen3-MoE layer (QK-normed grouped-query attention over a
K/V cache, a softmax router over all published experts and the held
experts' part of the top-k sum, in every layer) run a BLOCK of
``diffusion_block_length`` positions a row a forward pass, and a
sampler that draws at every place of the block.

``cfg`` is a configuration file's content; ``num_experts`` counts the
experts HELD (``expert_parallel_size`` times as many are published and
routed over).  What the *algorithm* needs, not what a formulation does:
an expert is read when a token chose it (hit), not because it is held;
2 bytes a weight and a K/V element (bfloat16), 4 a logit (float32).

A "step" here is one forward PASS of the burst (``--decode-steps``
counts passes): of every ``diffusion_steps + 1`` passes a block takes,
``diffusion_steps`` denoise (head and sampler) and one stores (neither),
so a pass reads the head ``diffusion_steps / (diffusion_steps + 1)``
times on average.
"""

from __future__ import annotations

WEIGHT_BYTES = 2
LOGIT_BYTES = 4


def block(cfg: dict) -> int:
    return cfg.get("diffusion_block_length", 4)


def denoise_share(cfg: dict) -> float:
    """Share of a block's passes that run the head and the sampler."""
    steps = cfg.get("diffusion_steps", 4)
    return steps / (steps + 1.0)


def num_expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def num_attention(cfg: dict) -> int:
    return cfg["num_hidden_layers"]


def held_experts(cfg: dict) -> int:
    return cfg["num_experts"]


def head_dim(cfg: dict) -> int:
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def attention_params(cfg: dict) -> int:
    """q, k, v and o of one layer, no bias, and the two head norms."""
    h, d = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv + 2 * d


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_width(cfg: dict) -> int:
    return cfg["num_experts"] * cfg.get("expert_parallel_size", 1)


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * router_width(cfg)


def norm_params(cfg: dict) -> int:
    """Two norms a layer and the final one."""
    return (2 * cfg["num_hidden_layers"] + 1) * cfg["hidden_size"]


def head_params(cfg: dict) -> int:
    """The head; the embedding is as large again (untied) and a pass
    reads a row of it a position."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def layer_params(cfg: dict) -> int:
    """One layer outside its routed experts."""
    return attention_params(cfg) + router_params(cfg)


def param_count(cfg: dict) -> int:
    """What the program's init makes for this configuration."""
    tied = cfg.get("tie_word_embeddings", False)
    return (cfg["num_hidden_layers"] * (
        layer_params(cfg) + cfg["num_experts"] * expert_params(cfg))
        + norm_params(cfg) + (1 if tied else 2) * head_params(cfg))


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    """K and V of one token over the layers."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * kv_itemsize)


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """The floor of one pass that needs no row count: every weight
    outside the routed experts once, the head its share of the passes,
    and K and V of the live context.  The experts hit go with the
    rows: ``hybrid_decode_step_bytes``."""
    if cfg["chipbench"]["quantization"] != "none":
        raise ValueError("counted for weights that are not quantized")
    dense = (cfg["num_hidden_layers"] * layer_params(cfg)
             + norm_params(cfg) + denoise_share(cfg) * head_params(cfg))
    return (dense * WEIGHT_BYTES
            + kv_bytes_per_token(cfg) * live_context_tokens)


def hybrid_decode_step_bytes(cfg: dict, rows: float, experts_hit: float,
                             live_context_tokens: float) -> float:
    """Bytes one pass of ``rows`` live rows must move: the floor above
    and ``experts_hit`` experts (the mean over the layers of the held
    experts some position chose) in every layer."""
    del rows  # a block's hidden states are noise beside the weights
    return (decode_step_bytes(cfg, live_context_tokens)
            + num_expert_layers(cfg) * experts_hit * expert_params(cfg)
            * WEIGHT_BYTES)


def moe_experts(cfg: dict, held_choices: float,
                experts_hit: float) -> tuple:
    """(operations, bytes) of the routed experts of ONE layer for one
    pass: ``held_choices`` (position, choice) pairs that fell on held
    experts, each through one expert (2 operations a weight), and
    ``experts_hit`` experts read; a pair's hidden state in and out in
    2 bytes."""
    return (2.0 * held_choices * expert_params(cfg),
            experts_hit * expert_params(cfg) * WEIGHT_BYTES
            + held_choices * 2 * cfg["hidden_size"] * 2)


def block_attention(cfg: dict, rows: float,
                    live_context_tokens: float) -> tuple:
    """(operations, bytes) of the attention call of ONE layer for one
    pass: the ``block`` queries of a row against the keys of its
    context (the block itself among them) and the weighted sum of
    their values, 4 operations a head dimension a query head a query a
    cached token; K and V of the live context read ONCE for the whole
    block (a layer's share of ``kv_bytes_per_token``), the block's
    queries in and outputs out."""
    d = head_dim(cfg)
    q = cfg["num_attention_heads"] * d
    b = block(cfg)
    return (4.0 * q * b * (live_context_tokens + rows * b),
            kv_bytes_per_token(cfg) / num_attention(cfg)
            * (live_context_tokens + rows * b) + rows * b * 2 * q * 2)


def unmask(cfg: dict, rows: float) -> tuple:
    """(operations, bytes) of the sampler of ONE denoising pass: at
    each of a row's ``block`` places the float32 logits over the
    vocabulary read once, and 4 operations a logit (the temperature's
    product, the exponent and its sum, the noise's sum and the
    comparison of the draw)."""
    logits = rows * block(cfg) * cfg["vocab_size"]
    return 4.0 * logits, logits * LOGIT_BYTES


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``: 2 a weight a token through every layer outside
    its routed experts and through the token's held choices (the
    expected share of its ``num_experts_per_tok``: held / routed-over),
    and attention under sight by block: a token sees the context
    before its chunk and the chunk up to the END of its block.  No
    head: a prefill of this family samples nothing."""
    per_token = cfg["num_hidden_layers"] * (
        layer_params(cfg) + cfg["num_experts_per_tok"]
        * cfg["num_experts"] / router_width(cfg) * expert_params(cfg))
    b = block(cfg)
    total = 0.0
    for start, tokens, _ in chunks:
        total += 2.0 * per_token * tokens
        attended = tokens * start + sum(
            min(tokens, (i | (b - 1)) + 1) for i in range(tokens))
        total += (4.0 * num_attention(cfg) * cfg["num_attention_heads"]
                  * head_dim(cfg) * attended)
    return total
