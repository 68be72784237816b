"""Granite-MoE-hybrid decoders (IBM Granite-4.0-H style).

Two kinds of layer in the order ``config.layer_types`` lists:
``attention`` is grouped-query attention over the paged cache with no
position term (the Mamba layers carry the order) and scores scaled by
``attention_multiplier``, which is not ``head_dim ** -0.5``; ``mamba``
is a Mamba-2 mixer::

    z | xBC | dt = u @ in_proj
    xBC = silu(conv1d_depthwise_causal(xBC) + conv_bias)
    x | B | C = xBC              x as heads of d_head; B, C one group
    dt = softplus(dt + dt_bias)  a head; A = -exp(A_log), a head
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t
    y_t = h_t C_t + D x_t        (ops/ssd.py: step and matrix form)
    out = (rms_norm(y * silu(z)) * norm_weight) @ out_proj

which keeps per sequence the recurrence's state ``h`` (float32) and
the last ``mamba_d_conv - 1`` inputs of its convolution instead of
pages. Every layer's feed-forward is a router over all published
experts (softmax over the chosen ``top_k`` logits, which is softmax
over all, top-k, renormalised: ``ops/moe.route``), the held experts'
part of the top-k sum (``held_experts``), and a shared SwiGLU expert
added whole, with no gate of its own. Four scalars: the embedding
times ``embedding_multiplier``, each sublayer's output times
``residual_multiplier`` before it joins the residual, the attention
scores, and the logits over ``logits_scaling``. Norms are plain
(``x / rms(x) * w``); the head is the embedding.

Same contract as ``models.qwen3_next.forward``: per-layer cache tuples,
``k_cache[i]`` of a Mamba layer the ``h`` pool ``[slots, d_state,
heads * d_head]`` (the channels along the lanes: ``ops/ssd.py`` says
why) and ``v_cache[i]`` the convolution tails ``[slots, K-1, channels
+ 2 * d_state]``; ``state_slots [B]`` says which slot each row's
sequence owns (slot 0 is the trash slot of padded rows). A row whose
block starts at position 0 starts from a zero state whatever its slot
holds. ``k_cache`` carries one entry more than there are layers, the
six counters of the expert layers' decode steps (``count_step``).
With ``kv_tail`` (a deferred-write decode burst) the attention layers
append to tails and leave their planes unwritten; with ``conv_tail``
the Mamba layers take their rows' convolution tails dense from the
burst's carry and leave the tail pool alone.

Parameters are stacks: ``m_*`` over the Mamba layers, ``wq/wk/wv/wo``
over the attention layers, the two norms, the router and the shared
expert over all layers; the experts are one array a layer
(``w_gate_up_<i>``, ``w_down_<i>``: a slice of a stack handed to the
grouped product's kernel is first copied out). ``m_in`` is ``z | xBC |
dt`` as published; gate | up side by side is this program's layout
for the experts and the published one for the shared expert.
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
    route,
    swiglu,
)
from production_stack_tpu.ops.ssd import rows_of, ssd_chunked, ssd_step
from production_stack_tpu.ops.ssd_pallas import ssd_decode

Params = Dict[str, jnp.ndarray]

COMMON = ("attn_norm", "ffn_norm", "router", "shared_gate_up",
          "shared_down")
ATTENTION = ("wq", "wk", "wv", "wo")
MAMBA = ("m_in", "m_conv", "m_conv_b", "m_dt_b", "m_A_log", "m_D",
         "m_norm", "m_out")
EXPERTS = ("w_gate_up", "w_down")   # one array a layer: <name>_<i>


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random parameters. What a zero or a one would switch off is
    drawn: every norm's weight 1 + N(0, 0.1), the gated norm among
    them, ``D`` near one, the convolution and its bias uniform,
    ``A_log`` = log U(1, 16), and ``dt``'s bias by the published init:
    the inverse softplus of a step drawn log-uniformly in
    [1e-3, 1e-1]."""
    c = config
    h, d = c.hidden_size, c.head_dim
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    heads, n, kk = c.mamba_n_heads, c.mamba_d_state, c.mamba_d_conv
    di = c.mamba_d_inner
    conv = di + 2 * n
    fe, fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
    layers = c.num_hidden_layers
    mamba = c.layer_is_linear.count(True)
    attn = layers - mamba
    dtype = c.jax_dtype
    keys = iter(jax.random.split(key, 32 + 2 * layers))

    def dense(shape, scale=0.02):
        # One leaf at a time: dispatched all at once, the float32
        # draws of every leaf are live together and the init alone
        # peaks at the device's limit (models/qwen3_next.py).
        return jax.block_until_ready(
            (scale * jax.random.normal(next(keys), shape, jnp.float32)
             ).astype(dtype))

    def near_one(shape, dtype=dtype):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    def uniform(shape):
        return jax.random.uniform(
            next(keys), shape, jnp.float32, -kk ** -0.5, kk ** -0.5
        ).astype(dtype)

    step = jnp.exp(jax.random.uniform(
        next(keys), (mamba, heads), jnp.float32,
        jnp.log(1e-3), jnp.log(1e-1)))
    params: Params = {
        "embed": dense((c.vocab_size, h)),
        "final_norm": near_one((h,)),
        "lm_head": dense((h, c.vocab_size)),
        "attn_norm": near_one((layers, h)),
        "ffn_norm": near_one((layers, h)),
        "router": dense((layers, h, c.router_width)),
        "shared_gate_up": dense((layers, h, 2 * fs)),
        "shared_down": dense((layers, fs, h)),
        "wq": dense((attn, h, nh * d)),
        "wk": dense((attn, h, nkv * d)),
        "wv": dense((attn, h, nkv * d)),
        "wo": dense((attn, nh * d, h)),
        "m_in": dense((mamba, h, di + conv + heads)),
        "m_conv": uniform((mamba, kk, conv)),
        "m_conv_b": uniform((mamba, conv)),
        # softplus(m_dt_b) = step.
        "m_dt_b": step + jnp.log(-jnp.expm1(-step)),
        "m_A_log": jnp.log(jax.random.uniform(
            next(keys), (mamba, heads), jnp.float32, 1.0, 16.0)),
        "m_D": near_one((mamba, heads), jnp.float32),
        "m_norm": near_one((mamba, di)),
        "m_out": dense((mamba, di, h)),
    }
    for i in range(layers):
        params[f"w_gate_up_{i}"] = dense((c.num_experts, h, 2 * fe))
        params[f"w_down_{i}"] = dense((c.num_experts, fe, h))
    if c.tie_word_embeddings:
        del params["lm_head"]
    return params


def _attention(config, lp, x, positions, page_table, kv_lens, valid,
               k_cache, v_cache, layer, kv_tail=None):
    """Grouped-query attention with no position term. The kernels
    scale the scores by ``head_dim ** -0.5``; the published scale is
    ``attention_multiplier``, so q carries the ratio of the two."""
    nh, nkv, d = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    b, t, _ = x.shape
    q = (jnp.matmul(x, lp["wq"], preferred_element_type=jnp.float32)
         * (config.attention_multiplier * d ** 0.5)
         ).astype(x.dtype).reshape(b, t, nh, d)
    k = (x @ lp["wk"]).reshape(b, t, nkv, d)
    v = (x @ lp["wv"]).reshape(b, t, nkv, d)
    with jax.named_scope("nope_attn"):
        attn, k_cache, v_cache = hybrid_attention(
            config, q, k, v, k_cache, v_cache, page_table, positions,
            kv_lens, valid, layer, kv_tail)
    return attn.reshape(b, t, nh * d) @ lp["wo"], k_cache, v_cache


def gated_norm(y, z, weight, eps):
    """``rms_norm(y * silu(z)) * weight`` over all the channels (one
    group), in float32."""
    return rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), weight, eps)


def _mamba(config, lp, x, fresh, valid, slots, h_pool, tail_pool,
           impl="xla", conv_tail=None):
    """One mixer. With ``conv_tail`` (a deferred burst: this layer's
    K-1 held inputs, a ``[B, channels + 2 * d_state]`` array each) the
    shifted ones come back in ``tail_pool``'s place
    (``slot_causal_conv``)."""
    c = config
    heads, p, n = c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state
    di = c.mamba_d_inner
    conv = di + 2 * n
    b, t, _ = x.shape
    f32 = jnp.float32

    zxd = x @ lp["m_in"]
    z, xbc, dt = zxd[..., :di], zxd[..., di:di + conv], zxd[..., di + conv:]
    xbc, tail_pool = slot_causal_conv(xbc, lp["m_conv"], fresh, valid,
                                      slots, tail_pool, conv_tail)
    xbc = jax.nn.silu(xbc.astype(f32) + lp["m_conv_b"].astype(f32))
    xs = xbc[..., :di].reshape(b, t, heads, p)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt.astype(f32) + lp["m_dt_b"])
    # A token that is not real neither writes nor fades the state.
    dt = jnp.where(valid[..., None], dt, 0.0)
    a = -jnp.exp(lp["m_A_log"])

    # Everything that touches h runs under one name, so that the
    # device trace charges the recurrence with the reads and writes of
    # its state: the gather from the pool, the step, the scatter back.
    with jax.named_scope("ssd_decode" if t == 1 else "ssd_prefill"):
        # A row whose block starts at position 0 starts from zero
        # whatever its slot holds. A row with no real token has dt 0
        # throughout, so what it writes back is what it read: neither
        # needs a pass of its own over the state.
        keep = 1.0 - fresh.astype(f32)
        if t == 1 and impl != "xla":
            # One kernel over the pool: h read and written once a row.
            y, h_pool = ssd_decode(
                xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], h_pool,
                slots, keep, interpret=impl == "pallas-interpret")
            y = y[:, None]
        elif t == 1:
            y, state = ssd_step(xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                                h_pool[slots], keep=keep)
            y = y[:, None]
            h_pool = h_pool.at[slots].set(state)
        else:
            # A prefill step's few rows: a slice a row, not a gather.
            y, state = ssd_chunked(
                xs, dt, a, bm, cm,
                rows_of(h_pool, slots) * keep[:, None, None],
                c.mamba_chunk_size)
            h_pool = h_pool.at[slots].set(state)

    y = (y + lp["m_D"][:, None] * xs).reshape(b, t, di)
    y = gated_norm(y, z, lp["m_norm"], c.rms_norm_eps)
    return y.astype(x.dtype) @ lp["m_out"], h_pool, tail_pool


def sparse_block(config: ModelConfig, lp, x, valid, moe_impl="xla"):
    """x [B, T, H] normalised -> (y [B, T, H]: the held experts' part
    of the routed sum and the shared expert whole, load [E]: real
    tokens that chose each held expert)."""
    b, t, h = x.shape
    flat = x.reshape(b * t, h)
    weights, ids = route(flat, lp["router"], config.num_experts_per_tok,
                         norm_topk=True)
    y, load = held_experts(
        flat, weights, ids, lp["w_gate_up"], lp["w_down"],
        config.expert_parallel_rank * config.num_experts,
        valid=valid.reshape(b * t), impl=moe_impl,
        router_width=config.router_width)
    y = y + swiglu(flat, lp["shared_gate_up"], lp["shared_down"])
    return y.reshape(b, t, h), load


def _joined(x, branch, multiplier):
    """``x + multiplier * branch``, rounded once."""
    return (x.astype(jnp.float32)
            + multiplier * branch.astype(jnp.float32)).astype(x.dtype)


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
    the Mamba layers' ``v_cache`` entries replaced by their shifted
    convolution tails. No LoRA targets."""
    if lora is not None:
        raise NotImplementedError("granitemoehybrid has no LoRA targets")
    if not isinstance(k_cache, (list, tuple)):
        raise ValueError("granitemoehybrid keeps per-layer caches "
                         "(cache_layout='per_layer')")
    b, t = tokens.shape
    if state_slots is None:
        state_slots = jnp.zeros((b,), jnp.int32)
    layers = config.num_hidden_layers
    stats = k_cache[layers]
    k_cache, v_cache = list(k_cache[:layers]), list(v_cache)
    fresh = (positions[:, 0] == 0) & valid[:, 0]
    impl = hybrid_kernel_impl(config)
    eps, res = config.rms_norm_eps, config.residual_multiplier

    x = params["embed"][tokens]
    x = (x.astype(jnp.float32) * config.embedding_multiplier
         ).astype(x.dtype)
    n_attn = n_mamba = 0
    for layer, mamba in enumerate(config.layer_is_linear):
        common = {k: params[k][layer] for k in COMMON}
        a_in = rms_norm(x, common["attn_norm"], eps)
        if mamba:
            lp = {k: params[k][n_mamba] for k in MAMBA}
            n_mamba += 1
            mixed, k_cache[layer], v_cache[layer] = _mamba(
                config, lp, a_in, fresh, valid, state_slots,
                k_cache[layer], v_cache[layer], impl,
                None if conv_tail is None else conv_tail[layer])
        else:
            lp = {k: params[k][n_attn] for k in ATTENTION}
            n_attn += 1
            mixed, kc, vc = _attention(
                config, lp, a_in, positions, page_table, kv_lens, valid,
                tuple(k_cache), tuple(v_cache), layer, kv_tail)
            k_cache, v_cache = list(kc), list(vc)
        x = _joined(x, mixed, res)
        m_in = rms_norm(x, common["ffn_norm"], eps)
        common.update({k: params[f"{k}_{layer}"] for k in EXPERTS})
        y, load = sparse_block(config, common, m_in, valid, impl)
        if t == 1:
            stats = count_step(stats, config.num_experts_per_tok, load,
                               valid, config.router_width)
        x = _joined(x, y, res)

    x = rms_norm(x, params["final_norm"], eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = (x @ head).astype(jnp.float32) / config.logits_scaling
    return logits, tuple(k_cache) + (stats,), tuple(v_cache)
