"""LFM2-MoE hybrid decoders (LiquidAI LFM2-8B-A1B style).

Two kinds of layer in the order ``config.layer_types`` lists (the
published list follows no period): ``full_attention`` is grouped-query
attention over the paged cache with an RMS norm over each head's
dimensions on q and on k before a full rotary embedding, and ``conv``
is a gated short convolution::

    B | C | x = h @ in_proj          u = B * x
    c_t = sum_j w[j] * u_{t-(K-1)+j}     (depthwise, causal, no bias,
                                          no activation)
    y = (C * c) @ out_proj

which keeps per sequence the last ``conv_L_cache - 1`` values of ``u``
and nothing else: no recurrence, no pages. The first
``num_dense_layers`` feed-forwards are SwiGLU MLPs, the rest a router
over all published experts (a sigmoid a expert, the choice by score +
a learned bias, the weights the scores alone: ``ops/moe.py``
``route_sigmoid``) and the held experts' part of the top-k sum
(``held_experts``). No shared expert. Norms are plain (``x / rms(x) *
w``); the head is the embedding.

Same contract as ``models.qwen3_next.forward``: per-layer cache tuples,
of a conv layer ``k_cache[i]`` ``None`` (the family declares the tail
alone, ``models/registry.py``) and ``v_cache[i]`` the convolution tails
``[slots, K-1, hidden]``; ``state_slots [B]`` says which slot each
row's sequence owns (slot 0 is the trash slot of padded rows). A row
whose block starts at position 0 starts from a zero tail whatever its
slot holds. ``k_cache`` carries one entry more than there are layers,
the six counters of the expert layers' decode steps (``count_step``;
``layer_steps`` counts expert layers). With ``kv_tail`` (a
deferred-write decode burst) the attention layers append to tails and
leave their planes unwritten; with ``conv_tail`` the conv layers take
their rows' held inputs dense from the burst's carry and leave the
tail pool alone.

Parameters are four stacks beside the two norms over all layers:
``c_*`` over the conv layers, ``wq/wk/wv/wo/q_norm/k_norm`` over the
attention layers, ``w_gate/w_up/w_down`` over the dense feed-forwards,
``router/expert_bias`` over the expert layers, whose experts are one
array a layer (``w_gate_up_<i>``, ``w_down_<i>``, ``i`` the layer: a
slice of a stack handed to the grouped product's kernel is first
copied out). ``c_in`` is ``B | C | x`` as published; gate | up is this
program's own layout.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models.llama import (
    hybrid_attention,
    hybrid_kernel_impl,
    rms_norm,
)
from production_stack_tpu.ops.gated_delta import slot_causal_conv
from production_stack_tpu.ops.moe import (
    count_step,
    held_experts,
    route_sigmoid,
)
from production_stack_tpu.ops.rope import apply_rope

Params = Dict[str, jnp.ndarray]

COMMON = ("op_norm", "ffn_norm")
CONV = ("c_in", "c_conv", "c_out")
ATTENTION = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
DENSE = ("w_gate", "w_up", "w_down")
ROUTED = ("router", "expert_bias")
EXPERTS = ("w_gate_up", "w_down")   # one array a layer: <name>_<i>


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random parameters. What a zero or a one would switch off is
    drawn: every norm's weight (the two head norms among them) 1 +
    N(0, 0.1), the convolution's taps U(-0.5, 0.5), and ``expert_bias``
    N(0, 0.1), a third of the spread of the scores it is added to, so
    that it changes which experts a visible share of tokens choose."""
    c = config
    h, d = c.hidden_size, c.head_dim
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    f, fe = c.intermediate_size, c.moe_intermediate_size
    layers = c.num_hidden_layers
    conv = c.layer_is_linear.count(True)
    attn = layers - conv
    dense_layers = c.num_dense_layers
    routed = layers - dense_layers
    kk = c.conv_L_cache
    dtype = c.jax_dtype
    keys = iter(jax.random.split(key, 32 + 2 * layers))

    def dense(shape, scale=0.02):
        # One leaf at a time: dispatched all at once, the float32
        # draws of every leaf are live together and the init alone
        # peaks at the device's limit (models/qwen3_next.py).
        return jax.block_until_ready(
            (scale * jax.random.normal(next(keys), shape, jnp.float32)
             ).astype(dtype))

    def near_one(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    params: Params = {
        "embed": dense((c.vocab_size, h)),
        "final_norm": near_one((h,)),
        "lm_head": dense((h, c.vocab_size)),
        "op_norm": near_one((layers, h)),
        "ffn_norm": near_one((layers, h)),
        "c_in": dense((conv, h, 3 * h)),
        "c_conv": jax.random.uniform(
            next(keys), (conv, kk, h), jnp.float32, -0.5, 0.5
        ).astype(dtype),
        "c_out": dense((conv, h, h)),
        "wq": dense((attn, h, nh * d)),
        "wk": dense((attn, h, nkv * d)),
        "wv": dense((attn, h, nkv * d)),
        "wo": dense((attn, nh * d, h)),
        "q_norm": near_one((attn, d)),
        "k_norm": near_one((attn, d)),
        "w_gate": dense((dense_layers, h, f)),
        "w_up": dense((dense_layers, h, f)),
        "w_down": dense((dense_layers, f, h)),
        "router": dense((routed, h, c.router_width)),
        "expert_bias": 0.1 * jax.random.normal(
            next(keys), (routed, c.router_width), jnp.float32),
    }
    for i in range(dense_layers, layers):
        params[f"w_gate_up_{i}"] = dense((c.num_experts, h, 2 * fe))
        params[f"w_down_{i}"] = dense((c.num_experts, fe, h))
    if c.tie_word_embeddings:
        del params["lm_head"]
    return params


def _attention(config, lp, x, positions, page_table, kv_lens, valid,
               k_cache, v_cache, layer, kv_tail=None):
    """Grouped-query attention, q and k normed a head, then rotary."""
    nh, nkv, d = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    b, t, _ = x.shape
    q = (x @ lp["wq"]).reshape(b, t, nh, d)
    k = (x @ lp["wk"]).reshape(b, t, nkv, d)
    v = (x @ lp["wv"]).reshape(b, t, nkv, d)
    q = rms_norm(q, lp["q_norm"], config.rms_norm_eps)
    k = rms_norm(k, lp["k_norm"], config.rms_norm_eps)
    q = apply_rope(q, positions, config.rope_theta)
    k = apply_rope(k, positions, config.rope_theta)
    with jax.named_scope("qknorm_attn"):
        attn, k_cache, v_cache = hybrid_attention(
            config, q, k, v, k_cache, v_cache, page_table, positions,
            kv_lens, valid, layer, kv_tail)
    return attn.reshape(b, t, nh * d) @ lp["wo"], k_cache, v_cache


def _short_conv(lp, x, fresh, valid, slots, tail_pool, conv_tail=None):
    """One gated short convolution, projections included, under one
    name. With ``conv_tail`` (a deferred burst: this layer's K-1 held
    inputs, a ``[B, hidden]`` array each) the shifted ones come back in
    ``tail_pool``'s place (``slot_causal_conv``)."""
    b, t, h = x.shape
    with jax.named_scope("sconv_decode" if t == 1 else "sconv_prefill"):
        # One token a row: the operator runs on [B, hidden] arrays,
        # which lie in whole (8, 128) tiles where [B, 1, hidden] ones
        # do not (PERF.md section 6, PR 35).
        flat = x.reshape(b * t, h) if t == 1 else x
        bcx = flat @ lp["c_in"]
        gate_b, gate_c, xs = (bcx[..., :h], bcx[..., h:2 * h],
                              bcx[..., 2 * h:])
        u = (gate_b * xs).reshape(b, t, h)
        conv, tail_pool = slot_causal_conv(
            u, lp["c_conv"], fresh, valid, slots, tail_pool, conv_tail)
        y = (gate_c * conv.reshape(gate_c.shape)) @ lp["c_out"]
        return y.reshape(b, t, h), tail_pool


def sparse_block(config: ModelConfig, lp, x, valid, moe_impl="xla"):
    """x [B, T, H] normalised -> (y [B, T, H], load [E]: real tokens
    that chose each held expert)."""
    b, t, h = x.shape
    flat = x.reshape(b * t, h)
    weights, ids = route_sigmoid(flat, lp["router"], lp["expert_bias"],
                                 config.num_experts_per_tok)
    y, load = held_experts(
        flat, weights, ids, lp["w_gate_up"], lp["w_down"],
        config.expert_parallel_rank * config.num_experts,
        valid=valid.reshape(b * t), impl=moe_impl,
        router_width=config.router_width)
    return y.reshape(b, t, h), load


def forward(params: Params, config: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, page_table: jnp.ndarray,
            kv_lens: jnp.ndarray, valid: jnp.ndarray,
            k_cache, v_cache, lora=None, lora_ids=None,
            kv_tail=None, state_slots=None, conv_tail=None,
            ) -> Tuple[jnp.ndarray, tuple, tuple]:
    """Same contract as models.qwen3_next.forward: ``state_slots [B]``
    (None: every row the trash slot), per-layer caches and the counters
    after them, with ``kv_tail`` the attention layers' planes replaced
    by their updated tails in what comes back, and with ``conv_tail``
    the conv layers' ``v_cache`` entries replaced by their shifted
    convolution tails. A conv layer's ``k_cache`` entry is ``None``
    and stays so. No LoRA targets."""
    if lora is not None:
        raise NotImplementedError("lfm2_moe has no LoRA targets")
    if not isinstance(k_cache, (list, tuple)):
        raise ValueError("lfm2_moe keeps per-layer caches "
                         "(cache_layout='per_layer')")
    b, t = tokens.shape
    if state_slots is None:
        state_slots = jnp.zeros((b,), jnp.int32)
    layers = config.num_hidden_layers
    stats = k_cache[layers]
    k_cache, v_cache = list(k_cache[:layers]), list(v_cache)
    fresh = (positions[:, 0] == 0) & valid[:, 0]
    impl = hybrid_kernel_impl(config)
    eps = config.rms_norm_eps

    x = params["embed"][tokens]
    n_attn = n_conv = 0
    for layer, conv in enumerate(config.layer_is_linear):
        common = {k: params[k][layer] for k in COMMON}
        a_in = rms_norm(x, common["op_norm"], eps)
        if conv:
            lp = {k: params[k][n_conv] for k in CONV}
            n_conv += 1
            mixed, v_cache[layer] = _short_conv(
                lp, a_in, fresh, valid, state_slots, v_cache[layer],
                None if conv_tail is None else conv_tail[layer])
        else:
            lp = {k: params[k][n_attn] for k in ATTENTION}
            n_attn += 1
            mixed, kc, vc = _attention(
                config, lp, a_in, positions, page_table, kv_lens, valid,
                tuple(k_cache), tuple(v_cache), layer, kv_tail)
            k_cache, v_cache = list(kc), list(vc)
        x = x + mixed
        m_in = rms_norm(x, common["ffn_norm"], eps)
        if layer < config.num_dense_layers:
            lp = {k: params[k][layer] for k in DENSE}
            x = x + (jax.nn.silu(m_in @ lp["w_gate"])
                     * (m_in @ lp["w_up"])) @ lp["w_down"]
            continue
        routed = layer - config.num_dense_layers
        lp = {k: params[k][routed] for k in ROUTED}
        lp.update({k: params[f"{k}_{layer}"] for k in EXPERTS})
        y, load = sparse_block(config, lp, m_in, valid, impl)
        if t == 1:
            stats = count_step(stats, config.num_experts_per_tok, load,
                               valid, config.router_width)
        x = x + y

    x = rms_norm(x, params["final_norm"], eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = (x @ head).astype(jnp.float32)
    return logits, tuple(k_cache) + (stats,), tuple(v_cache)
