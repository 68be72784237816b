"""Operations and bytes of the GLM-4 MoE lite family of decoders
(GLM-4.7-Flash): one latent-attention (MLA) sublayer a layer with its
own latent cache, a dense SwiGLU in the first ``first_k_dense_replace``
layers, after them sigmoid-routed experts beside one shared expert,
and a multi-token-prediction module (one more expert layer with a
cache entry of its own, ``eh_proj`` and three norms) that drafts inside
the decode burst.

``cfg`` is a configuration file's content; ``num_hidden_layers`` counts
the main layers run. What the *algorithm* needs, not what a
formulation does: an expert is read when a token chose it (hit), not
because it is held; a cached token costs its latent once an entry
(``kv_lora_rank + qk_rope_head_dim`` values: the latent's rows are the
values too), never per-head keys or values; 2 bytes a weight and a
cached value (bfloat16).

**A step here is a burst ITERATION.** Where the module drafts
(``drafting(cfg)``), an iteration runs two positions a row through the
main layers (the last committed token and its draft), commits ``1 + a``
tokens a row (``a``: the share of drafts accepted) and runs the module
on the committed positions: every weight is read once and the head
twice (the module's distribution is over the main model's head). The
readers that count token-steps (``hybrid_slice.token_steps``) count
iterations, so ``decode_step_ms`` on such a cell is an iteration of
``1 + a`` tokens.
"""

from __future__ import annotations

WEIGHT_BYTES = 2


def module_layers(cfg: dict) -> int:
    """Prediction layers the server keeps: the configuration's, unless
    its flags switch the module off."""
    flags = cfg.get("chipbench", {}).get("server_flags", {})
    if flags.get("draft-module", "auto") == "off":
        return 0
    return int(cfg.get("num_nextn_predict_layers", 0))


def drafting(cfg: dict) -> bool:
    return module_layers(cfg) >= 1


def positions_per_row(cfg: dict) -> int:
    """Positions a live row runs through the main layers in one burst
    iteration."""
    return 2 if drafting(cfg) else 1


def num_sublayers(cfg: dict) -> int:
    """Latent cache entries: one a main layer, one a prediction
    layer."""
    return cfg["num_hidden_layers"] + module_layers(cfg)


def num_dense_layers(cfg: dict) -> int:
    return cfg.get("first_k_dense_replace", 0)


def num_expert_layers(cfg: dict) -> int:
    return num_sublayers(cfg) - num_dense_layers(cfg)


def held_experts(cfg: dict) -> int:
    return cfg["n_routed_experts"]


def latent_width(cfg: dict) -> int:
    """Values a cached token keeps in one entry."""
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
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert (the shared one is as large): gate, up and
    down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """One layer's router and its bias."""
    return (cfg["hidden_size"] + 1) * held_experts(cfg) * cfg.get(
        "expert_parallel_size", 1)


def outside_experts_params(cfg: dict) -> int:
    """An expert layer without its routed experts: attention, the
    shared expert, the router and its bias, two norms."""
    return (mla_params(cfg) + cfg.get("n_shared_experts", 1)
            * expert_params(cfg) + router_params(cfg)
            + 2 * cfg["hidden_size"])


def expert_layer_params(cfg: dict) -> int:
    return outside_experts_params(cfg) + held_experts(cfg) * expert_params(
        cfg)


def dense_layer_params(cfg: dict) -> int:
    return mla_params(cfg) + dense_mlp_params(cfg) + 2 * cfg["hidden_size"]


def module_own_params(cfg: dict) -> int:
    """What a prediction layer has beyond an expert layer: ``eh_proj``
    (2H -> H), ``enorm``, ``hnorm`` and the norm before the head."""
    h = cfg["hidden_size"]
    return 2 * h * h + 3 * h


def module_params(cfg: dict) -> int:
    return expert_layer_params(cfg) + module_own_params(cfg)


def head_params(cfg: dict) -> int:
    """The head; the embedding, untied, is as large again."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def main_dense_params(cfg: dict) -> int:
    """Every weight of the main model that a step reads whatever the
    routing: the layers without their routed experts, the final norm
    and the head (of the embedding a row a token)."""
    dense = num_dense_layers(cfg)
    return (dense * dense_layer_params(cfg)
            + (cfg["num_hidden_layers"] - dense)
            * outside_experts_params(cfg)
            + cfg["hidden_size"] + head_params(cfg))


def dense_params(cfg: dict) -> int:
    """``main_dense_params`` and, where the module drafts, the module
    without its routed experts and the head a second time."""
    module = module_layers(cfg) * (outside_experts_params(cfg)
                                   + module_own_params(cfg)
                                   + head_params(cfg))
    return main_dense_params(cfg) + module


def param_count(cfg: dict) -> int:
    """What the program's init makes for this configuration."""
    dense = num_dense_layers(cfg)
    return (dense * dense_layer_params(cfg)
            + (cfg["num_hidden_layers"] - dense) * expert_layer_params(cfg)
            + module_layers(cfg) * module_params(cfg)
            + cfg["hidden_size"] + 2 * head_params(cfg))


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = 2) -> int:
    """The latent of one token over every entry."""
    return num_sublayers(cfg) * latent_width(cfg) * kv_itemsize


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """The floor of a burst iteration that needs no row count: every
    weight outside the routed experts once (the head twice where the
    module drafts), and the latent of the live context once an entry.
    The experts hit go with the rows: ``hybrid_decode_step_bytes``."""
    if cfg["chipbench"]["quantization"] != "none":
        raise ValueError("counted for weights that are not quantized")
    return (dense_params(cfg) * WEIGHT_BYTES
            + kv_bytes_per_token(cfg) * live_context_tokens)


def hybrid_decode_step_bytes(cfg: dict, rows: float, experts_hit: float,
                             live_context_tokens: float) -> float:
    """Bytes one burst iteration of ``rows`` live rows must move: the
    floor above, ``experts_hit`` experts (the mean over the expert
    layers, the module's among them, of the experts some position
    chose) in every expert layer, and every position's hidden state in
    and out of each entry's attention and each feed-forward."""
    experts = (num_expert_layers(cfg) * experts_hit * expert_params(cfg)
               * WEIGHT_BYTES)
    activations = (rows * positions_per_row(cfg) * cfg["hidden_size"]
                   * 2 * 2 * 2 * num_sublayers(cfg))
    return (decode_step_bytes(cfg, live_context_tokens) + experts
            + activations)


def moe_experts(cfg: dict, held_choices: float,
                experts_hit: float) -> tuple:
    """(operations, bytes) of the routed experts of ONE layer for one
    iteration: ``held_choices`` (position, choice) pairs, each through
    one expert (2 operations a weight), and ``experts_hit`` experts
    read; a pair's hidden state in and out in 2 bytes. The shared
    expert is outside the name and outside this count."""
    return (2.0 * held_choices * expert_params(cfg),
            experts_hit * expert_params(cfg) * WEIGHT_BYTES
            + held_choices * 2 * cfg["hidden_size"] * 2)


def mla_decode(cfg: dict, rows: float,
               live_context_tokens: float) -> tuple:
    """(operations, bytes) of the attention of ONE entry for one burst
    iteration, absorbed, at ``positions_per_row`` query positions a
    row: a position's ``q_nope`` through ``W_UK`` a head, its query
    against the latent of each cached token and the weighted sum of the
    latents' compressed rows, the sum through ``W_UV`` a head; the
    latent of the live context read ONCE for all of a row's positions
    (one walk of its pages), ``W_kvb`` once, a position's query in, its
    latent into the tail and its output out."""
    n, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    w = latent_width(cfg)
    t = positions_per_row(cfg)
    per_position = 2.0 * n * r * (cfg["qk_nope_head_dim"]
                                  + cfg["v_head_dim"])
    per_pair = 2.0 * n * (w + r)
    return (t * (rows * per_position + live_context_tokens * per_pair),
            live_context_tokens * w * 2 + kvb_params(cfg) * WEIGHT_BYTES
            + t * rows * (n * q_head_dim(cfg) + w
                          + n * cfg["v_head_dim"]) * 2)


def mla_prefill(cfg: dict, chunks: list, steps: float = 1) -> tuple:
    """(operations, bytes) of the attention of ONE entry over the
    prompt chunks, each ``(start, tokens)``, of ``steps`` prefill
    steps, absorbed as the program runs it (the prediction layer runs
    the same chunks over its own entry)."""
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


def mtp_draft(cfg: dict, rows: float, positions: float,
              experts_hit: float, live_context_tokens: float) -> tuple:
    """(operations, bytes) of the prediction module for one burst
    iteration of ``rows`` live rows, ``positions`` of which (rows x (1
    + the accepted share)) it runs its layer on: ``eh_proj`` and the
    layer outside its routed experts once, ``experts_hit`` of its
    experts, the head once more (its second read of the iteration) for
    one position a row, and its own entry's latent of the live
    context; 2 operations a weight a position, ``num_experts_per_tok``
    routed experts and the shared one a position, attention as
    ``mla_decode`` counts a pair."""
    n, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    w = latent_width(cfg)
    h = cfg["hidden_size"]
    per_position = (outside_experts_params(cfg) - 2 * h
                    + 2 * h * h
                    + cfg["num_experts_per_tok"] * expert_params(cfg))
    flops = (2.0 * positions * per_position
             + 2.0 * rows * head_params(cfg)
             + positions / max(rows, 1.0) * live_context_tokens
             * 2.0 * n * (w + r))
    moved = ((outside_experts_params(cfg) + module_own_params(cfg)
              + head_params(cfg) + experts_hit * expert_params(cfg))
             * WEIGHT_BYTES
             + live_context_tokens * w * 2
             + rows * cfg["vocab_size"] * 4)
    return flops, moved


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``: 2 a weight a token through every layer (its
    ``num_experts_per_tok`` routed experts and the shared one in an
    expert layer; the prediction layer and ``eh_proj`` where the module
    is kept, whose cache the prefill fills), causal attention over the
    context so far in every entry (per-head keys and values, the least
    a pair needs), and the head for the one sampled position of a
    prompt's last chunk."""
    h = cfg["hidden_size"]
    sparse = (outside_experts_params(cfg)
              + cfg["num_experts_per_tok"] * expert_params(cfg))
    dense = num_dense_layers(cfg)
    per_token = (dense * dense_layer_params(cfg)
                 + (cfg["num_hidden_layers"] - dense) * sparse
                 + module_layers(cfg) * (sparse + 2 * h * h))
    total = 0.0
    for start, tokens, last in chunks:
        total += 2.0 * per_token * tokens
        attended = tokens * start + tokens * (tokens + 1) / 2
        total += (2.0 * num_sublayers(cfg) * cfg["num_attention_heads"]
                  * (q_head_dim(cfg) + cfg["v_head_dim"]) * attended)
        if last:
            total += 2.0 * head_params(cfg)
    return total
