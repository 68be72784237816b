"""Operations and bytes of the LongCat-Flash family of decoders: a
layer is two latent-attention (MLA) sublayers, each with its own latent
cache, two dense SwiGLU feed-forwards, and one routed-expert branch
whose router chooses among the routed experts and, after them, the
zero-compute (identity) ones.

``cfg`` is a configuration file's content; ``n_routed_experts`` counts
the routed experts HELD (``expert_parallel_size`` times as many are
published and routed over) and ``num_layers`` the layers run. What the
*algorithm* needs, not what a formulation does: an expert is read when
a token chose it (hit), not because it is held; a cached token costs
its latent once a sublayer (``kv_lora_rank + qk_rope_head_dim``
values: the latent's rows are the values too), never per-head keys or
values; a zero-compute expert costs nothing; 2 bytes a weight and a
cached value (bfloat16).
"""

from __future__ import annotations

WEIGHT_BYTES = 2


def num_sublayers(cfg: dict) -> int:
    return 2 * cfg["num_layers"]


def num_expert_layers(cfg: dict) -> int:
    """One expert branch a layer."""
    return cfg["num_layers"]


def held_experts(cfg: dict) -> int:
    return cfg["n_routed_experts"]


def latent_width(cfg: dict) -> int:
    """Values a cached token keeps in one sublayer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def q_head_dim(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def kvb_params(cfg: dict) -> int:
    """The latent's up-projection to per-head keys and values."""
    return (cfg["kv_lora_rank"] * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]))


def mla_params(cfg: dict) -> int:
    """One attention sublayer: the query's low-rank pair and its norm,
    the latent's down-projection and its norm, the up-projection, the
    output projection; no bias."""
    h, n = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (h * rq + rq + rq * n * q_head_dim(cfg)
            + h * latent_width(cfg) + r + kvb_params(cfg)
            + n * cfg["v_head_dim"] * h)


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_width(cfg: dict) -> int:
    return (cfg["n_routed_experts"] * cfg.get("expert_parallel_size", 1)
            + cfg.get("zero_expert_num", 0))


def router_params(cfg: dict) -> int:
    """One layer's router and its bias."""
    return (cfg["hidden_size"] + 1) * router_width(cfg)


def norm_params(cfg: dict) -> int:
    """Four norms a layer and the final one."""
    return (4 * cfg["num_layers"] + 1) * cfg["hidden_size"]


def head_params(cfg: dict) -> int:
    """The head; the embedding, untied, is as large again."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def dense_params(cfg: dict) -> int:
    """Every weight a decode step reads whatever the routing: all
    sublayers, the routers, the norms and the head (of the embedding a
    row a token)."""
    return (num_sublayers(cfg) * (mla_params(cfg) + dense_mlp_params(cfg))
            + cfg["num_layers"] * router_params(cfg)
            + norm_params(cfg) + head_params(cfg))


def param_count(cfg: dict) -> int:
    """What the program's init makes for this configuration."""
    return (dense_params(cfg) + head_params(cfg)
            + cfg["num_layers"] * held_experts(cfg) * expert_params(cfg))


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    """The latent of one token over every sublayer."""
    return num_sublayers(cfg) * latent_width(cfg) * kv_itemsize


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """The floor of a decode token-step that needs no row count: every
    weight outside the routed experts and the head once, and the latent
    of the live context once a sublayer. The experts hit go with the
    rows: ``hybrid_decode_step_bytes``."""
    if cfg["chipbench"]["quantization"] != "none":
        raise ValueError("counted for weights that are not quantized")
    return (dense_params(cfg) * WEIGHT_BYTES
            + kv_bytes_per_token(cfg) * live_context_tokens)


def hybrid_decode_step_bytes(cfg: dict, rows: float, experts_hit: float,
                             live_context_tokens: float) -> float:
    """Bytes one decode token-step of ``rows`` live rows must move: the
    floor above, ``experts_hit`` experts (the mean over the layers of
    the held experts some row chose) in every layer, and every row's
    hidden state in and out of each sublayer's attention, each dense
    feed-forward and the expert branch."""
    experts = (num_expert_layers(cfg) * experts_hit * expert_params(cfg)
               * WEIGHT_BYTES)
    activations = (rows * cfg["hidden_size"] * 2 * 2
                   * (2 * num_sublayers(cfg) + num_expert_layers(cfg)))
    return (decode_step_bytes(cfg, live_context_tokens) + experts
            + activations)


def moe_experts(cfg: dict, held_choices: float,
                experts_hit: float) -> tuple:
    """(operations, bytes) of the routed experts of ONE layer for one
    step: ``held_choices`` (token, choice) pairs that fell on held
    experts, each through one expert (2 operations a weight), and
    ``experts_hit`` experts read; a pair's hidden state in and out in
    2 bytes. A choice of a zero-compute expert is no pair."""
    return (2.0 * held_choices * expert_params(cfg),
            experts_hit * expert_params(cfg) * WEIGHT_BYTES
            + held_choices * 2 * cfg["hidden_size"] * 2)


def mla_decode(cfg: dict, rows: float,
               live_context_tokens: float) -> tuple:
    """(operations, bytes) of the attention of ONE sublayer for one
    decode step, absorbed: a row's ``q_nope`` through ``W_UK`` a head,
    its query against the latent of each cached token (all of its
    rows) and the weighted sum of the latents' compressed rows, the
    sum through ``W_UV`` a head; the latent of the live context read
    once, ``W_kvb`` once, a row's query in, its latent into the tail
    and its output out."""
    n, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    w = latent_width(cfg)
    per_row = 2.0 * n * r * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
    per_pair = 2.0 * n * (w + r)
    return (rows * per_row + live_context_tokens * per_pair,
            live_context_tokens * w * 2 + kvb_params(cfg) * WEIGHT_BYTES
            + rows * (n * q_head_dim(cfg) + w + n * cfg["v_head_dim"]) * 2)


def mla_prefill(cfg: dict, chunks: list, steps: float = 1) -> tuple:
    """(operations, bytes) of the attention of ONE sublayer over the
    prompt chunks, each ``(start, tokens)``, of ``steps`` prefill
    steps, absorbed as the program runs it: a chunk's tokens against
    the ``start`` cached tokens and causally against themselves, the
    decode step's operations a (query, key) pair and a query; the
    attended latents once a chunk, ``W_kvb`` once a step, a token's
    query in, its latent written and its output out."""
    n, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    w = latent_width(cfg)
    pairs = sum(t * s + t * (t + 1) / 2 for s, t in chunks)
    tokens = float(sum(t for _, t in chunks))
    attended = float(sum(s + t for s, t in chunks))
    return (tokens * 2.0 * n * r
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + pairs * 2.0 * n * (w + r),
            attended * w * 2 + steps * kvb_params(cfg) * WEIGHT_BYTES
            + tokens * (n * q_head_dim(cfg) + w
                        + n * cfg["v_head_dim"]) * 2)


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``: 2 a weight a token through every sublayer, the
    routers and the token's held choices (the expected share of its
    ``moe_topk``: held / routed-over), causal attention over the
    context so far in every sublayer (per-head keys and values, the
    least a pair needs: the program's absorbed form spends 3.4 times
    that), and the head for
    the one sampled position of a prompt's last chunk."""
    per_token = (num_sublayers(cfg) * (mla_params(cfg)
                                       + dense_mlp_params(cfg))
                 + cfg["num_layers"] * (
                     router_params(cfg)
                     + cfg["moe_topk"] * held_experts(cfg)
                     / router_width(cfg) * expert_params(cfg)))
    total = 0.0
    for start, tokens, last in chunks:
        total += 2.0 * per_token * tokens
        attended = tokens * start + tokens * (tokens + 1) / 2
        total += (2.0 * num_sublayers(cfg) * cfg["num_attention_heads"]
                  * (q_head_dim(cfg) + cfg["v_head_dim"]) * attended)
        if last:
            total += 2.0 * head_params(cfg)
    return total
