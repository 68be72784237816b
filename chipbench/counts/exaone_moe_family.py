"""Operations and bytes of the EXAONE-MoE family of decoders:
grouped-query attention in every layer, over a window's ring of K/V a
sequence where ``layer_types`` says ``sliding_attention`` and over the
whole row's K/V cache where it says ``full_attention``; a dense SwiGLU
in the first ``first_k_dense_replace`` layers, and after them a sigmoid
router over all published experts with the held experts' part of the
top-k sum beside a shared expert added whole.

``cfg`` is a configuration file's content; ``num_experts`` counts the
experts HELD (``expert_parallel_size`` times as many are published and
routed over).  What the *algorithm* needs, not what a formulation does:
a windowed layer reads a window's K and V a row a step whatever the
row's length, a full layer the row's live tokens'; an expert is read
when a token chose it (hit), not because it is held; 2 bytes a weight
and a K/V element (bfloat16).
"""

from __future__ import annotations

WEIGHT_BYTES = 2
KV_BYTES = 2


def layer_is_windowed(cfg: dict) -> list:
    return [kind == "sliding_attention" for kind in cfg["layer_types"]]


def num_windowed(cfg: dict) -> int:
    return sum(layer_is_windowed(cfg))


def num_full(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - num_windowed(cfg)


def num_dense_layers(cfg: dict) -> int:
    return cfg.get("first_k_dense_replace", 0)


def num_expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - num_dense_layers(cfg)


def held_experts(cfg: dict) -> int:
    return cfg["num_experts"]


def router_width(cfg: dict) -> int:
    return held_experts(cfg) * cfg.get("expert_parallel_size", 1)


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


def shared_params(cfg: dict) -> int:
    return expert_params(cfg) * cfg.get("num_shared_experts", 1)


def router_params(cfg: dict) -> int:
    """The router's matrix and its bias."""
    return (cfg["hidden_size"] + 1) * router_width(cfg)


def expert_layer_shared_params(cfg: dict) -> int:
    """An expert layer outside its routed experts: attention, the two
    post-norms, the shared expert, the router and its bias."""
    return (attention_params(cfg) + 2 * cfg["hidden_size"]
            + shared_params(cfg) + router_params(cfg))


def dense_layer_params(cfg: dict) -> int:
    return (attention_params(cfg) + 2 * cfg["hidden_size"]
            + 3 * cfg["hidden_size"] * cfg["intermediate_size"])


def head_params(cfg: dict) -> int:
    """The head; the embedding is as large again."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def dense_params(cfg: dict) -> int:
    """Every weight a decode step reads whatever the routing: the
    dense layers, the expert layers outside their routed experts, the
    final norm and the head (of the embedding a step reads a row a
    token)."""
    return (num_dense_layers(cfg) * dense_layer_params(cfg)
            + num_expert_layers(cfg) * expert_layer_shared_params(cfg)
            + cfg["hidden_size"] + head_params(cfg))


def param_count(cfg: dict) -> int:
    """What the program's init makes for this configuration: the
    embedding beside the untied head."""
    return (dense_params(cfg) + head_params(cfg)
            + num_expert_layers(cfg) * held_experts(cfg)
            * expert_params(cfg))


def kv_bytes_per_token(cfg: dict, kv_itemsize: int = KV_BYTES) -> int:
    """K and V of one token over the FULL layers: the windowed layers
    keep no pages."""
    return (2 * num_full(cfg) * cfg["num_key_value_heads"]
            * head_dim(cfg) * kv_itemsize)


def ring_bytes(cfg: dict, kv_itemsize: int = KV_BYTES) -> int:
    """One sequence's K ring and V ring in one windowed layer."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg)
            * cfg["sliding_window"] * kv_itemsize)


def state_bytes_per_sequence(cfg: dict,
                             kv_itemsize: int = KV_BYTES) -> int:
    """What one sequence holds of the state pool: the rings of every
    windowed layer."""
    return num_windowed(cfg) * ring_bytes(cfg, kv_itemsize)


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """The floor of a decode token-step that needs no row count: every
    weight outside the routed experts once, and K and V of the live
    context in the full layers.  The experts hit and the rings go with
    the rows: ``hybrid_decode_step_bytes``."""
    if cfg["chipbench"]["quantization"] != "none":
        raise ValueError("counted for weights that are not quantized")
    return (dense_params(cfg) * WEIGHT_BYTES
            + kv_bytes_per_token(cfg) * live_context_tokens)


def hybrid_decode_step_bytes(cfg: dict, rows: float, experts_hit: float,
                             live_context_tokens: float) -> float:
    """Bytes one decode token-step of ``rows`` live rows must move: the
    floor above, ``experts_hit`` experts (the mean over the expert
    layers of the held experts some row chose) in every expert layer,
    and every row's rings read once in every windowed layer (a row
    shorter than the window reads less; the rows of the benchmark's
    traffic are longer)."""
    experts = (num_expert_layers(cfg) * experts_hit * expert_params(cfg)
               * WEIGHT_BYTES)
    rings = num_windowed(cfg) * rows * ring_bytes(cfg)
    return decode_step_bytes(cfg, live_context_tokens) + experts + rings


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


def swa_decode(cfg: dict, rows: float) -> tuple:
    """(operations, bytes) of ONE windowed layer's attention for one
    decode step of ``rows`` rows: a window's keys and values a query
    head (2 operations a product element, scores and values), and the
    row's K ring and V ring read once; q in and the output out in 2
    bytes."""
    heads, d = cfg["num_attention_heads"], head_dim(cfg)
    window = cfg["sliding_window"]
    return (4.0 * rows * heads * d * window,
            rows * (ring_bytes(cfg) + 2 * heads * d * 2))


def swa_prefill(cfg: dict, chunks: list) -> tuple:
    """(operations, bytes) of ONE windowed layer's attention over
    prompt chunks (token counts, one entry a row a step): every token's
    query heads against a window's keys and values (a prompt's first
    tokens see fewer: counted whole, so the share reads a little high
    on a chunk that starts a prompt, never over what a window's keys
    cost); a chunk reads the row's rings once and writes at most a
    window's K and V back, and a token's q, k, v in and its output out
    in 2 bytes."""
    heads, kv, d = (cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], head_dim(cfg))
    window = cfg["sliding_window"]
    tokens = float(sum(chunks))
    per_token = (2 * heads + 2 * kv) * d * 2
    return (4.0 * tokens * heads * d * window,
            len(chunks) * 2.0 * ring_bytes(cfg) + tokens * per_token)


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``: 2 a weight a token through every layer outside
    its routed experts and through the token's held choices (the
    expected share of its ``num_experts_per_tok``: held / routed-over),
    attention over a window's keys in the windowed layers and over the
    context so far in the full layers, and the head for the one
    sampled position of a prompt's last chunk."""
    per_token = (num_dense_layers(cfg) * dense_layer_params(cfg)
                 + num_expert_layers(cfg) * (
                     expert_layer_shared_params(cfg)
                     + cfg["num_experts_per_tok"] * held_experts(cfg)
                     / router_width(cfg) * expert_params(cfg)))
    heads, d = cfg["num_attention_heads"], head_dim(cfg)
    window = cfg["sliding_window"]
    total = 0.0
    for start, tokens, last in chunks:
        total += 2.0 * per_token * tokens
        attended = tokens * start + tokens * (tokens + 1) / 2
        total += 4.0 * num_full(cfg) * heads * d * attended
        seen = sum(min(start + t + 1, window) for t in range(tokens))
        total += 4.0 * num_windowed(cfg) * heads * d * seen
        if last:
            total += 2.0 * head_params(cfg)
    return total
