"""Qwen3-Next hybrid decoders (Qwen3-Next-80B-A3B style).

Two kinds of layer in one static pattern: layer ``i`` is gated full
attention over the paged cache when ``(i + 1) %
full_attention_interval == 0`` and a Gated DeltaNet (linear attention)
layer otherwise, which keeps per sequence a recurrent state
``S [value heads, d_k, d_v]`` (float32) and the last three inputs of
its depthwise convolution instead of pages. Every layer's feed-forward
is a sparse block: a router over all published experts, the held
experts' part of the top-k sum (ops/moe.py) and a gated shared expert.
Norms are zero-centred (``x / rms(x) * (1 + w)``).

Same contract as ``models.llama.forward`` plus ``state_slots``: the
caches are per-layer tuples (``cache_layout='per_layer'``), and where
a full-attention layer has its page buffers a linear layer has its
state pools, ``k_cache[i]`` the ``S`` pool ``[slots, Hv, d_k, d_v]``
and ``v_cache[i]`` the convolution tails ``[slots, K-1, channels]``;
``state_slots [B]`` says which slot each row's sequence owns (slot 0 is
the trash slot of padded rows, as page 0 is the trash page). A row
whose block starts at position 0 starts from a zero state whatever its
slot holds, so a slot needs no clearing between sequences or before a
recompute. ``k_cache`` carries one entry more than there are layers:
``k_cache[L]``, six float32 counters of the expert layer's decode
steps that the runner reads and zeroes (the family's ``counters``,
``models/registry.py``; ``ops/moe.py`` ``count_step`` fills them in that
order). With
``kv_tail`` (a deferred-write decode burst) the full-attention layers
append to tails and leave their planes unwritten, and with
``conv_tail`` the linear layers take their rows' convolution tails
dense from the burst's carry (``forward``).

Parameters are two stacks beside the common one: ``gdn_*`` over the
linear layers and ``wqg/wk/wv/wo/q_norm/k_norm`` over the full ones,
everything else over all layers, except the routed experts, which are
one array a layer (``w_gate_up_<i>``, ``w_down_<i>``): the grouped
product is a kernel call, and a slice of a stack handed to a kernel is
first copied out (0.8 GB a layer a step at the published widths).
The layout of the fused projections is this program's own (q | gate,
q | k | v, b | a, gate | up).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models.llama import (
    hybrid_attention,
    hybrid_kernel_impl,
)
from production_stack_tpu.ops.gated_delta import (
    gated_delta_chunked,
    gated_delta_step,
    l2_normalize,
    slot_causal_conv,
)
from production_stack_tpu.ops.gated_delta_pallas import gated_delta_decode
from production_stack_tpu.ops.moe import (
    count_step,
    held_experts,
    route,
    swiglu,
)
from production_stack_tpu.ops.rope import apply_rope

Params = Dict[str, jnp.ndarray]

COMMON = ("attn_norm", "mlp_norm", "router", "shared_gate_up",
          "shared_down", "shared_gate")
EXPERTS = ("w_gate_up", "w_down")   # one array a layer: <name>_<i>
FULL = ("wqg", "wk", "wv", "wo", "q_norm", "k_norm")
LINEAR = ("gdn_qkv", "gdn_z", "gdn_ba", "gdn_conv", "gdn_A_log",
          "gdn_dt_bias", "gdn_norm", "gdn_out")


def rms_norm(x, weight, eps):
    """Zero-centred RMSNorm, in float32: x / rms(x) * (1 + w)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random parameters. What a zero or a one would switch off is
    drawn: the norm offsets, the convolution, the decay (``A_log`` =
    log U(0, 16), ``dt_bias`` 1, as the published init) and the
    shared expert's gate."""
    c = config
    h, d = c.hidden_size, c.head_dim
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    layers = c.num_hidden_layers
    lin = c.layer_is_linear.count(True)
    full = layers - lin
    f, fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
    dtype = c.jax_dtype
    keys = iter(jax.random.split(key, 32 + 2 * layers))

    def dense(shape, scale=0.02):
        # One leaf at a time: dispatched all at once, the float32
        # draws of forty leaves (1 GB for a layer's experts) are live
        # together and the init alone peaks at the device's limit.
        return jax.block_until_ready(
            (scale * jax.random.normal(next(keys), shape, jnp.float32)
             ).astype(dtype))

    conv_c = 2 * hk * dk + hv * dv
    kk = c.linear_conv_kernel_dim
    params: Params = {
        "embed": dense((c.vocab_size, h)),
        "final_norm": dense((h,), 0.1),
        "lm_head": dense((h, c.vocab_size)),
        "attn_norm": dense((layers, h), 0.1),
        "mlp_norm": dense((layers, h), 0.1),
        "router": dense((layers, h, c.router_width)),
        "shared_gate_up": dense((layers, h, 2 * fs)),
        "shared_down": dense((layers, fs, h)),
        "shared_gate": dense((layers, h)),
        "wqg": dense((full, h, 2 * nh * d)),
        "wk": dense((full, h, nkv * d)),
        "wv": dense((full, h, nkv * d)),
        "wo": dense((full, nh * d, h)),
        "q_norm": dense((full, d), 0.1),
        "k_norm": dense((full, d), 0.1),
        "gdn_qkv": dense((lin, h, conv_c)),
        "gdn_z": dense((lin, h, hv * dv)),
        "gdn_ba": dense((lin, h, 2 * hv)),
        "gdn_conv": jax.random.uniform(
            next(keys), (lin, kk, conv_c), jnp.float32,
            -kk ** -0.5, kk ** -0.5).astype(dtype),
        "gdn_A_log": jnp.log(jax.random.uniform(
            next(keys), (lin, hv), jnp.float32, 1e-3, 16.0)),
        "gdn_dt_bias": jnp.ones((lin, hv), jnp.float32),
        "gdn_norm": (1.0 + 0.1 * jax.random.normal(
            next(keys), (lin, dv), jnp.float32)).astype(dtype),
        "gdn_out": dense((lin, hv * dv, h)),
    }
    for i in range(layers):
        params[f"w_gate_up_{i}"] = dense((c.num_experts, h, 2 * f))
        params[f"w_down_{i}"] = dense((c.num_experts, f, h))
    if c.tie_word_embeddings:
        del params["lm_head"]
    return params


def _gated_attention(config, lp, x, positions, page_table, kv_lens,
                     valid, k_cache, v_cache, layer, kv_tail=None):
    nh, nkv, d = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    b, t, _ = x.shape
    qg = (x @ lp["wqg"]).reshape(b, t, 2, nh, d)
    q, gate = qg[:, :, 0], qg[:, :, 1]
    k = (x @ lp["wk"]).reshape(b, t, nkv, d)
    v = (x @ lp["wv"]).reshape(b, t, nkv, d)
    q = rms_norm(q, lp["q_norm"], config.rms_norm_eps)
    k = rms_norm(k, lp["k_norm"], config.rms_norm_eps)
    rotary = int(d * config.partial_rotary_factor)
    q = apply_rope(q, positions, config.rope_theta, rotary)
    k = apply_rope(k, positions, config.rope_theta, rotary)
    with jax.named_scope("gated_attn"):
        attn, k_cache, v_cache = hybrid_attention(
            config, q, k, v, k_cache, v_cache, page_table, positions,
            kv_lens, valid, layer, kv_tail)
    attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
        attn.dtype)
    return attn.reshape(b, t, nh * d) @ lp["wo"], k_cache, v_cache


def _gated_delta_net(config, lp, x, fresh, valid, slots, s_pool,
                     tail_pool, impl="xla", conv_tail=None):
    """One linear layer. With ``conv_tail`` (a deferred burst: this
    layer's K-1 held inputs, a ``[B, channels]`` array each) the shifted
    ones come back in ``tail_pool``'s place (``slot_causal_conv``)."""
    c = config
    hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
    dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
    b, t, _ = x.shape
    rep = hv // hk

    qkv, tail_pool = slot_causal_conv(
        x @ lp["gdn_qkv"], lp["gdn_conv"], fresh, valid, slots, tail_pool,
        conv_tail)
    qkv = jax.nn.silu(qkv.astype(jnp.float32))
    q = qkv[..., :hk * dk].reshape(b, t, hk, dk)
    k = qkv[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
    v = qkv[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    q = jnp.repeat(l2_normalize(q) * dk ** -0.5, rep, axis=2)
    k = jnp.repeat(l2_normalize(k), rep, axis=2)
    ba = (x @ lp["gdn_ba"]).astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = (-jnp.exp(lp["gdn_A_log"].astype(jnp.float32))
         * jax.nn.softplus(ba[..., hv:]
                           + lp["gdn_dt_bias"].astype(jnp.float32)))
    # A token that is not real neither writes nor fades the state.
    beta = jnp.where(valid[..., None], beta, 0.0)
    g = jnp.where(valid[..., None], g, 0.0)

    # Everything that touches S runs under one name, so that the
    # device trace charges the kernel with the reads and writes of
    # its state: the gather from the pool, the rule, the scatter back.
    with jax.named_scope("gdn_decode" if t == 1 else "gdn_prefill"):
        # A row whose block starts at position 0 starts from zero
        # whatever its slot holds. A row with no real token (the trash
        # slot's, or a sequence that stopped inside a burst) has beta
        # 0 and log-decay 0 throughout, so what it writes back is
        # what it read, to the last bit: neither needs a pass of its
        # own over the state.
        keep = 1.0 - fresh.astype(jnp.float32)
        if t == 1 and impl != "xla":
            # One kernel over the pool: S read and written once a row.
            o, s_pool = gated_delta_decode(
                q[:, 0], k[:, 0], v[:, 0],
                jnp.exp(g[:, 0]) * keep[:, None], beta[:, 0], s_pool,
                slots, interpret=impl == "pallas-interpret")
            o = o[:, None]
        else:
            state = s_pool[slots]
            if t == 1:
                o, new_state = gated_delta_step(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    state, keep=keep)
                o = o[:, None]
            else:
                o, new_state = gated_delta_chunked(
                    q, k, v, g, beta, state * keep[:, None, None, None])
            s_pool = s_pool.at[slots].set(new_state)

    # Output norm per head over d_v (weight not zero-centred), gated.
    z = (x @ lp["gdn_z"]).reshape(b, t, hv, dv).astype(jnp.float32)
    o = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                           + c.rms_norm_eps)
         * lp["gdn_norm"].astype(jnp.float32) * jax.nn.silu(z))
    out = o.reshape(b, t, hv * dv).astype(x.dtype) @ lp["gdn_out"]
    return out, s_pool, tail_pool


def sparse_block(config: ModelConfig, lp, x, valid, moe_impl="xla"):
    """x [B, T, H] normalised -> (y [B, T, H], load [E]: real tokens
    that chose each held expert)."""
    b, t, h = x.shape
    flat = x.reshape(b * t, h)
    weights, ids = route(flat, lp["router"], config.num_experts_per_tok,
                         config.norm_topk_prob)
    y, load = held_experts(
        flat, weights, ids, lp["w_gate_up"], lp["w_down"],
        config.expert_parallel_rank * config.num_experts,
        valid=valid.reshape(b * t), impl=moe_impl,
        router_width=config.router_width)
    share = jax.nn.sigmoid(
        (flat.astype(jnp.float32)
         @ lp["shared_gate"].astype(jnp.float32))[:, None])
    y = y + (share * swiglu(flat, lp["shared_gate_up"],
                            lp["shared_down"])).astype(y.dtype)
    return y.reshape(b, t, h), load


def forward(params: Params, config: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, page_table: jnp.ndarray,
            kv_lens: jnp.ndarray, valid: jnp.ndarray,
            k_cache, v_cache, lora=None, lora_ids=None,
            kv_tail=None, state_slots=None, conv_tail=None,
            ) -> Tuple[jnp.ndarray, tuple, tuple]:
    """Same contract as models.llama.forward, with ``state_slots [B]``
    (None: every row the trash slot). No LoRA targets.

    ``kv_tail`` (a deferred-write decode burst, T == 1) is
    ``(k_tails, v_tails)``, each indexed by layer and read at the
    full-attention layers alone: a linear layer has no pages and so no
    tail, and its entry is whatever the caller keeps there. A full
    layer then appends this step's K (after ``k_norm`` and the rotary)
    and V to its tails, attends over its page planes, which it does
    not write, and the tails, with ``kv_lens`` the frozen pre-burst
    count; the caches come back with each full layer's planes replaced
    by its updated tails. The linear layers' ``S`` pools and the
    counters are read and written every step either way. The runner
    flushes the tails to the planes once a burst.

    ``conv_tail`` (the same burst) is indexed by layer and read at the
    linear layers alone: the K-1 inputs each row's convolution holds,
    a ``[B, channels]`` array each, oldest first, which the runner
    gathered from the tail pool before the burst. A linear layer then
    shifts them in one pass (``causal_conv_step``), its ``v_cache``
    entry comes back replaced by the shifted ones, and the tail pool is
    neither read nor written; the runner scatters them back once a
    burst."""
    if lora is not None:
        raise NotImplementedError("qwen3_next has no LoRA targets")
    if not isinstance(k_cache, (list, tuple)):
        raise ValueError("qwen3_next keeps per-layer caches "
                         "(cache_layout='per_layer')")
    b, t = tokens.shape
    if state_slots is None:
        state_slots = jnp.zeros((b,), jnp.int32)
    layers = config.num_hidden_layers
    stats = k_cache[layers]
    k_cache, v_cache = list(k_cache[:layers]), list(v_cache)
    fresh = (positions[:, 0] == 0) & valid[:, 0]
    impl = hybrid_kernel_impl(config)

    x = params["embed"][tokens]
    n_full = n_lin = 0
    for layer, linear in enumerate(config.layer_is_linear):
        common = {k: params[k][layer] for k in COMMON}
        common.update({k: params[f"{k}_{layer}"] for k in EXPERTS})
        a_in = rms_norm(x, common["attn_norm"], config.rms_norm_eps)
        if linear:
            lp = {k: params[k][n_lin] for k in LINEAR}
            n_lin += 1
            mixed, k_cache[layer], v_cache[layer] = _gated_delta_net(
                config, lp, a_in, fresh, valid, state_slots,
                k_cache[layer], v_cache[layer], impl,
                None if conv_tail is None else conv_tail[layer])
        else:
            lp = {k: params[k][n_full] for k in FULL}
            n_full += 1
            mixed, kc, vc = _gated_attention(
                config, lp, a_in, positions, page_table, kv_lens, valid,
                tuple(k_cache), tuple(v_cache), layer, kv_tail)
            k_cache, v_cache = list(kc), list(vc)
        x = x + mixed
        m_in = rms_norm(x, common["mlp_norm"], config.rms_norm_eps)
        y, load = sparse_block(config, common, m_in, valid, impl)
        if t == 1:
            stats = count_step(stats, config.num_experts_per_tok, load,
                               valid, config.router_width)
        x = x + y

    x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = (x @ head).astype(jnp.float32)
    return logits, tuple(k_cache) + (stats,), tuple(v_cache)
