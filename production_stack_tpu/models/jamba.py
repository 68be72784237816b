"""Jamba hybrid decoders (AI21-Jamba2-3B style: dense, no experts).

Two kinds of layer in one static pattern: layer ``i`` is attention over
the paged cache when ``i % attn_layer_period == attn_layer_offset``
(grouped queries over few KV heads, no rotary and no other position
term: the Mamba layers carry the order) and a Mamba-1 mixer otherwise,
which keeps per sequence the selective scan's state ``h`` (float32) and
the last ``mamba_d_conv - 1`` inputs of its depthwise convolution
instead of pages. Every layer's feed-forward is a SwiGLU MLP. Norms are
plain (``x / rms(x) * w``), and the mixer has three small ones of its
own, on ``dt``, ``B`` and ``C``.

Same contract as ``models.qwen3_next.forward``: per-layer cache tuples,
``k_cache[i]`` of a Mamba layer the ``h`` pool ``[slots, d_state,
d_inner]`` (transposed, the channels along the lanes:
``ops/selective_scan.py`` says why) and ``v_cache[i]`` the convolution
tails ``[slots, K-1, d_inner]``; ``state_slots [B]`` says which slot
each row's sequence owns (slot 0 is the trash slot of padded rows). A
row whose block starts at position 0 starts from a zero state whatever
its slot holds, so a slot needs no clearing. With ``kv_tail`` (a
deferred-write decode burst) the attention layers append to tails and
leave their planes unwritten; with ``conv_tail`` the Mamba layers take
their rows' convolution tails dense from the burst's carry and leave
the tail pool alone. The family keeps no counters.

Parameters are two stacks beside the common one: ``m_*`` over the Mamba
layers and ``wq/wk/wv/wo`` over the attention layers, the norms and the
MLP over all layers. ``m_in`` is ``x | z`` and ``m_x`` is
``dt | B | C``, as published; ``m_A_log`` is kept ``[d_state,
d_inner]``, transposed as the state is.
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
from production_stack_tpu.ops.selective_scan import (
    selective_scan_block,
    selective_scan_step,
)
from production_stack_tpu.ops.selective_scan_pallas import (
    selective_scan_decode,
)

Params = Dict[str, jnp.ndarray]

COMMON = ("attn_norm", "mlp_norm", "w_gate", "w_up", "w_down")
ATTENTION = ("wq", "wk", "wv", "wo")
MAMBA = ("m_in", "m_conv", "m_conv_b", "m_x", "m_dt_norm", "m_b_norm",
         "m_c_norm", "m_dt", "m_dt_b", "m_A_log", "m_D", "m_out")


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random parameters. What a zero or a one would switch off is
    drawn: every norm's weight, ``D``, the convolution and its bias,
    ``A_log`` = log U(1, 16), and ``dt``'s bias by the published init:
    the inverse softplus of a step drawn log-uniformly in
    [1e-3, 1e-1]."""
    c = config
    h, f, d = c.hidden_size, c.intermediate_size, c.head_dim
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    di, n, r, kk = (c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank,
                    c.mamba_d_conv)
    layers = c.num_hidden_layers
    mamba = c.layer_is_linear.count(True)
    attn = layers - mamba
    dtype = c.jax_dtype
    keys = iter(jax.random.split(key, 32))

    def dense(shape, scale=0.02):
        return (scale * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def near_one(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    step = jnp.exp(jax.random.uniform(
        next(keys), (mamba, di), jnp.float32,
        jnp.log(1e-3), jnp.log(1e-1)))
    params: Params = {
        "embed": dense((c.vocab_size, h)),
        "final_norm": near_one((h,)),
        "lm_head": dense((h, c.vocab_size)),
        "attn_norm": near_one((layers, h)),
        "mlp_norm": near_one((layers, h)),
        "w_gate": dense((layers, h, f)),
        "w_up": dense((layers, h, f)),
        "w_down": dense((layers, f, h)),
        "wq": dense((attn, h, nh * d)),
        "wk": dense((attn, h, nkv * d)),
        "wv": dense((attn, h, nkv * d)),
        "wo": dense((attn, nh * d, h)),
        "m_in": dense((mamba, h, 2 * di)),
        "m_conv": jax.random.uniform(
            next(keys), (mamba, kk, di), jnp.float32,
            -kk ** -0.5, kk ** -0.5).astype(dtype),
        "m_conv_b": jax.random.uniform(
            next(keys), (mamba, di), jnp.float32,
            -kk ** -0.5, kk ** -0.5).astype(dtype),
        "m_x": dense((mamba, di, r + 2 * n)),
        "m_dt_norm": near_one((mamba, r)),
        "m_b_norm": near_one((mamba, n)),
        "m_c_norm": near_one((mamba, n)),
        "m_dt": dense((mamba, r, di), r ** -0.5),
        # softplus(m_dt_b) = step.
        "m_dt_b": step + jnp.log(-jnp.expm1(-step)),
        "m_A_log": jnp.log(jax.random.uniform(
            next(keys), (mamba, n, di), jnp.float32, 1.0, 16.0)),
        "m_D": 1.0 + 0.1 * jax.random.normal(next(keys), (mamba, di),
                                             jnp.float32),
        "m_out": dense((mamba, di, h)),
    }
    if c.tie_word_embeddings:
        del params["lm_head"]
    return params


def _attention(config, lp, x, positions, page_table, kv_lens, valid,
               k_cache, v_cache, layer, kv_tail=None):
    """Grouped-query attention with no position term."""
    nh, nkv, d = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    b, t, _ = x.shape
    q = (x @ lp["wq"]).reshape(b, t, nh, d)
    k = (x @ lp["wk"]).reshape(b, t, nkv, d)
    v = (x @ lp["wv"]).reshape(b, t, nkv, d)
    with jax.named_scope("mqa_attn"):
        attn, k_cache, v_cache = hybrid_attention(
            config, q, k, v, k_cache, v_cache, page_table, positions,
            kv_lens, valid, layer, kv_tail)
    return attn.reshape(b, t, nh * d) @ lp["wo"], k_cache, v_cache


def _mamba(config, lp, x, fresh, valid, slots, h_pool, tail_pool,
           impl="xla", conv_tail=None):
    """One mixer. With ``conv_tail`` (a deferred burst: this layer's
    K-1 held inputs, a ``[B, d_inner]`` array each) the shifted ones
    come back in ``tail_pool``'s place (``slot_causal_conv``)."""
    c = config
    di, n, r = c.mamba_d_inner, c.mamba_d_state, c.mamba_dt_rank
    b, t, _ = x.shape
    f32 = jnp.float32

    xz = x @ lp["m_in"]
    xs, z = xz[..., :di], xz[..., di:]
    xs, tail_pool = slot_causal_conv(xs, lp["m_conv"], fresh, valid,
                                     slots, tail_pool, conv_tail)
    xs = jax.nn.silu(xs.astype(f32) + lp["m_conv_b"].astype(f32))

    dbc = xs.astype(x.dtype) @ lp["m_x"]
    eps = c.rms_norm_eps
    dt = rms_norm(dbc[..., :r], lp["m_dt_norm"], eps)
    bm = rms_norm(dbc[..., r:r + n], lp["m_b_norm"], eps).astype(f32)
    cm = rms_norm(dbc[..., r + n:], lp["m_c_norm"], eps).astype(f32)
    delta = jax.nn.softplus(
        jnp.matmul(dt, lp["m_dt"], preferred_element_type=f32)
        + lp["m_dt_b"])
    # A token that is not real neither writes nor fades the state.
    delta = jnp.where(valid[..., None], delta, 0.0)
    dx = delta * xs
    a_t = -jnp.exp(lp["m_A_log"])

    # Everything that touches h runs under one name, so that the
    # device trace charges the scan with the reads and writes of its
    # state: the gather from the pool, the scan, the scatter back.
    with jax.named_scope("ssm_decode" if t == 1 else "ssm_prefill"):
        # A row whose block starts at position 0 starts from zero
        # whatever its slot holds. A row with no real token has delta
        # 0 throughout, so what it writes back is what it read:
        # neither needs a pass of its own over the state.
        keep = 1.0 - fresh.astype(f32)
        if t == 1 and impl != "xla":
            # One kernel over the pool: h read and written once a row.
            y, h_pool = selective_scan_decode(
                delta[:, 0], dx[:, 0], bm[:, 0], cm[:, 0], a_t, h_pool,
                slots, keep, interpret=impl == "pallas-interpret")
            y = y[:, None]
        else:
            state = h_pool[slots]
            if t == 1:
                y, state = selective_scan_step(
                    delta[:, 0], dx[:, 0], bm[:, 0], cm[:, 0], a_t,
                    state, keep=keep)
                y = y[:, None]
            else:
                y, state = selective_scan_block(
                    delta, dx, bm, cm, a_t,
                    state * keep[:, None, None])
            h_pool = h_pool.at[slots].set(state)

    y = (y + lp["m_D"] * xs) * jax.nn.silu(z.astype(f32))
    return y.astype(x.dtype) @ lp["m_out"], h_pool, tail_pool


def forward(params: Params, config: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, page_table: jnp.ndarray,
            kv_lens: jnp.ndarray, valid: jnp.ndarray,
            k_cache, v_cache, lora=None, lora_ids=None,
            kv_tail=None, state_slots=None, conv_tail=None,
            ) -> Tuple[jnp.ndarray, tuple, tuple]:
    """Same contract as models.qwen3_next.forward: ``state_slots [B]``
    (None: every row the trash slot), per-layer caches, with
    ``kv_tail`` the attention layers' planes replaced by their updated
    tails in what comes back, and with ``conv_tail`` the Mamba layers'
    ``v_cache`` entries replaced by their shifted convolution tails.
    No LoRA targets."""
    if lora is not None:
        raise NotImplementedError("jamba has no LoRA targets")
    if not isinstance(k_cache, (list, tuple)):
        raise ValueError("jamba keeps per-layer caches "
                         "(cache_layout='per_layer')")
    b, t = tokens.shape
    if state_slots is None:
        state_slots = jnp.zeros((b,), jnp.int32)
    k_cache, v_cache = list(k_cache), list(v_cache)
    fresh = (positions[:, 0] == 0) & valid[:, 0]
    impl = hybrid_kernel_impl(config)

    x = params["embed"][tokens]
    n_attn = n_mamba = 0
    for layer, mamba in enumerate(config.layer_is_linear):
        common = {k: params[k][layer] for k in COMMON}
        a_in = rms_norm(x, common["attn_norm"], config.rms_norm_eps)
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
        x = x + mixed
        m_in = rms_norm(x, common["mlp_norm"], config.rms_norm_eps)
        x = x + (jax.nn.silu(m_in @ common["w_gate"])
                 * (m_in @ common["w_up"])) @ common["w_down"]

    x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = (x @ head).astype(jnp.float32)
    return logits, tuple(k_cache), tuple(v_cache)
