"""GLM-4 MoE "lite" decoders (zai-org's GLM-4.7-Flash): one latent-
attention (MLA) sublayer a layer over a paged latent cache, a leading
dense layer, sigmoid-routed experts beside a shared expert, and a
multi-token-prediction module that drafts the token after next.

*Layer*: ``h' = h + MLA(N1(h))``, ``out = h' + F(N2(h'))``. ``F`` is a
dense SwiGLU of ``intermediate_size`` in the first
``num_dense_layers`` layers and the expert block after them. MLA is
``models/longcat_flash.py`` ``mla`` with both low-rank scales 1: the
same absorbed form over one latent plane an entry, the same kernels.

*Expert block* on ``u``: ``s = sigmoid(u W_r)`` over all routed experts
in float32; the ``top_k`` largest of ``s + b`` are chosen (``b`` the
learned ``e_score_correction_bias``, for the choice alone; one group,
so no group limit); weights ``routed_scaling_factor * s_i / (sum of the
chosen s + 1e-20)`` (``ops/moe.py`` ``route_sigmoid``); ``F(u) = sum_i
w_i E_i(u) + E_shared(u)``, the shared expert a SwiGLU of
``shared_expert_intermediate_size`` added whole beside
``held_experts``' part.

*Prediction module* (DeepSeek-V3's MTP, one layer; the checkpoints'
names are ``enorm``, ``hnorm``, ``eh_proj``, ``shared_head.norm``): for
position ``i`` with the main model's last-layer hidden state ``h_i``
(before the final norm) and the NEXT token ``t_{i+1}``::

    z_i = [enorm(emb(t_{i+1})) ; hnorm(h_i)] W_eh        (2H -> H)
    one decoder layer of the expert kind on z at rotary position i,
    with its OWN latent cache entry;
    q_{i+2} = lm_head(shared_head.norm(.))

the module's distribution for token ``i + 2``. Embedding and head are
the main model's. ``draft`` is that module; the runner fills its cache
over the prompt in the prefill step and runs it inside the burst on
what each iteration committed (engine/model_runner.py).

Cache contract (``models/registry.py``): ``k_cache`` is one latent
plane a main layer (entries ``0 .. L-1``), then one for the module
where ``num_nextn_predict_layers`` is 1 (entry ``L``), then the
family's counters: ``count_step``'s six over the expert layers of a
burst iteration (the module's among them) and the burst's drafts
offered and accepted. Every ``v_cache`` entry is ``None``. ``forward``
leaves the module's entry as it is; ``draft`` leaves the main ones.

Parameters: the attention matrices and the two norms a layer are
stacks over the ``L`` main layers and, last, the module's layer; the
dense layers' feed-forwards are a stack over the dense layers; routers,
their biases and the shared experts stacks over the expert layers (the
module's last), the routed experts one array a layer
(``e_w_gate_up_<i>``, ``e_w_down_<i>``, ``i`` the layer's index, the
module's ``L``). ``W_kvb`` is kept as ``w_uk`` / ``w_uv`` and gate | up
side by side, as ``longcat_flash`` keeps them.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models.llama import hybrid_kernel_impl, rms_norm
from production_stack_tpu.models.longcat_flash import mla
from production_stack_tpu.ops.moe import (
    count_step,
    held_experts,
    route_sigmoid,
    swiglu,
)

Params = Dict[str, jnp.ndarray]

ATTENTION = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
             "w_uk", "w_uv", "wo", "ffn_norm")
ROUTED = ("router", "router_bias", "shared_gate_up", "shared_down")
EXPERTS = ("w_gate_up", "w_down")   # one array a layer: e_<name>_<i>
ROUTER_EPS = 1e-20


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random parameters. What a zero or a one would switch off is
    drawn: every norm's weight 1 + N(0, 0.1), and ``router_bias``
    N(0, 1e-2) in float32: at the published widths (a router of 2048 x
    64 of N(0, 0.02)) a token's fourth and fifth sigmoid scores are
    1.3e-2 apart at the median (2e-3 to 4.1e-2 from the tenth to the
    ninetieth percentile), so a bias of that order moves 7.8% of the
    choices, 31% of the tokens' chosen sets
    (tests/test_glm4_moe_lite.py), and leaves the scores a say. The
    module's weights come from a key of their own, so the main model
    is the same with the module and without."""
    c = config
    h, n = c.hidden_size, c.num_attention_heads
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    rq, rkv = c.q_lora_rank, c.kv_lora_rank
    f, fe, fs = (c.intermediate_size, c.moe_intermediate_size,
                 c.shared_expert_intermediate_size)
    layers, nd = c.num_hidden_layers, c.num_dense_layers
    mtp = c.num_nextn_predict_layers
    bodies, sparse = layers + mtp, layers - nd + mtp
    dtype = c.jax_dtype
    streams = {"main": iter(jax.random.split(key, 32 + 2 * layers)),
               "mtp": iter(jax.random.split(
                   jax.random.fold_in(key, 1), 32))}

    def normal(shape, which, scale, offset=0.0, to=dtype):
        # One leaf at a time (models/longcat_flash.py).
        return jax.block_until_ready(
            (offset + scale * jax.random.normal(
                next(streams[which]), shape, jnp.float32)).astype(to))

    def drawn(shape, count, stream, **how):
        # A stack over the layers draws the main layers' part and the
        # module's apart, so that the first is the same without the
        # second.
        if not count:
            return normal(shape, stream, **how)
        main = normal((count - mtp,) + shape, "main", **how)
        if not mtp:
            return main
        return jnp.concatenate([main, normal((mtp,) + shape, "mtp", **how)])

    def dense(shape, count=0, stream="main"):
        return drawn(shape, count, stream, scale=0.02)

    def near_one(shape, count=0, stream="main"):
        return drawn(shape, count, stream, scale=0.1, offset=1.0)

    params: Params = {
        "embed": dense((c.vocab_size, h)),
        "final_norm": near_one((h,)),
        "lm_head": dense((h, c.vocab_size)),
        "attn_norm": near_one((h,), bodies),
        "q_a": dense((h, rq), bodies),
        "q_a_norm": near_one((rq,), bodies),
        "q_b": dense((rq, n * (dn + dr)), bodies),
        "kv_a": dense((h, rkv + dr), bodies),
        "kv_a_norm": near_one((rkv,), bodies),
        "w_uk": dense((n, dn, rkv), bodies),
        "w_uv": dense((n, rkv, dv), bodies),
        "wo": dense((n * dv, h), bodies),
        "ffn_norm": near_one((h,), bodies),
        "w_gate_up": dense((nd, h, 2 * f)),
        "w_down": dense((nd, f, h)),
        "router": dense((h, c.router_width), sparse),
        "shared_gate_up": dense((h, 2 * fs), sparse),
        "shared_down": dense((fs, h), sparse),
    }
    params["router_bias"] = drawn((c.router_width,), sparse, "main",
                                  scale=1e-2, to=jnp.float32)
    for i in range(nd, bodies):
        which = "main" if i < layers else "mtp"
        params[f"e_w_gate_up_{i}"] = dense((c.num_experts, h, 2 * fe),
                                           stream=which)
        params[f"e_w_down_{i}"] = dense((c.num_experts, fe, h),
                                        stream=which)
    if mtp:
        params["mtp_enorm"] = near_one((h,), stream="mtp")
        params["mtp_hnorm"] = near_one((h,), stream="mtp")
        params["mtp_eh_proj"] = dense((2 * h, h), stream="mtp")
        params["mtp_head_norm"] = near_one((h,), stream="mtp")
    if c.tie_word_embeddings:
        del params["lm_head"]
    return params


def expert_block(config: ModelConfig, lp, u, valid, moe_impl="xla"):
    """u [B, T, H] normalised -> (F(u) [B, T, H], load [E]: real tokens
    that chose each held expert)."""
    c = config
    b, t, h = u.shape
    flat = u.reshape(b * t, h)
    weights, ids = route_sigmoid(
        flat, lp["router"], lp["router_bias"], c.num_experts_per_tok,
        scale=c.routed_scaling_factor, eps=ROUTER_EPS)
    y, load = held_experts(
        flat, weights, ids, lp["w_gate_up"], lp["w_down"],
        c.expert_parallel_rank * c.num_experts, valid=valid.reshape(b * t),
        impl=moe_impl, router_width=c.router_width)
    with jax.named_scope("shared_expert"):
        y = y + swiglu(flat, lp["shared_gate_up"], lp["shared_down"])
    return y.reshape(b, t, h), load


def _layer(config, params, body, x, positions, page_table, kv_lens, valid,
           plane, tail, stats, counted, moe_impl):
    """Layer body ``body`` (a main layer's index, or ``L`` for the
    module's) on ``x [B, T, H]``: ``(out, plane or tail, stats)``."""
    c = config
    eps = c.rms_norm_eps
    lp = {k: params[k][body] for k in ATTENTION}
    y, kept = mla(c, lp, rms_norm(x, lp["attn_norm"], eps), positions,
                  page_table, kv_lens, valid, plane, tail)
    x = x + y
    u = rms_norm(x, lp["ffn_norm"], eps)
    if body < c.num_dense_layers:
        with jax.named_scope("dense_ffn"):
            return (x + swiglu(u, params["w_gate_up"][body],
                               params["w_down"][body]), kept, stats)
    sparse = body - c.num_dense_layers
    rp = {k: params[k][sparse] for k in ROUTED}
    rp.update({k: params[f"e_{k}_{body}"] for k in EXPERTS})
    y, load = expert_block(c, rp, u, valid, moe_impl)
    if counted:
        stats = count_step(stats, c.num_experts_per_tok, load, valid,
                           c.router_width)
    return x + y, kept, stats


def _head(params: Params) -> jnp.ndarray:
    """The main model's head, which the module shares."""
    head = params.get("lm_head")
    return params["embed"].T if head is None else head


def _caches(config, k_cache, kv_tail):
    """``(planes, tails or None, kept, stats)``: ``kept`` is what goes
    back for the entries, the tails where there are tails and the
    planes otherwise, each replaced by the layer that writes it."""
    if not isinstance(k_cache, (list, tuple)):
        raise ValueError("glm4_moe_lite keeps per-entry caches "
                         "(cache_layout='per_layer')")
    entries = config.num_hidden_layers + config.num_nextn_predict_layers
    planes = list(k_cache[:entries])
    tails = None if kv_tail is None else list(kv_tail[0][:entries])
    return (planes, tails, list(planes if tails is None else tails),
            k_cache[entries])


def forward(params: Params, config: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, page_table: jnp.ndarray,
            kv_lens: jnp.ndarray, valid: jnp.ndarray,
            k_cache, v_cache, lora=None, lora_ids=None,
            kv_tail=None, return_hidden: bool = False,
            position_major: bool = False):
    """The main model. ``models.longcat_flash.forward``'s contract with
    per-entry caches (the module's text); with ``kv_tail`` the layers'
    planes are replaced by their updated tails in what comes back, and
    T may be 2: a committed token and the draft after it. With
    ``return_hidden`` the last layer's output before the final norm
    comes back after the logits: what ``draft`` reads. With
    ``position_major`` the logits come back ``[T, B, vocab]``, each
    position a dense ``[B, vocab]`` plane (what the drafting burst's
    sampler reads: ``ops/sampling.py`` ``verify_proposal``): the
    normalised hidden state is transposed before the head product,
    1.3 MB at the cell's shapes where the logits are 198 MB. No LoRA
    targets."""
    if lora is not None:
        raise NotImplementedError("glm4_moe_lite has no LoRA targets")
    c = config
    planes, tails, kept, stats = _caches(c, k_cache, kv_tail)
    impl = hybrid_kernel_impl(c)
    x = params["embed"][tokens]
    for layer in range(c.num_hidden_layers):
        x, kept[layer], stats = _layer(
            c, params, layer, x, positions, page_table, kv_lens, valid,
            planes[layer], None if tails is None else tails[layer],
            stats, kv_tail is not None or tokens.shape[1] == 1, impl)
    last = rms_norm(x, params["final_norm"], c.rms_norm_eps)
    if position_major:
        last = jnp.swapaxes(last, 0, 1)
    logits = (last @ _head(params)).astype(jnp.float32)
    out = (tuple(kept) + (stats,), tuple(v_cache))
    return (logits, x) + out if return_hidden else (logits,) + out


def draft(params: Params, config: ModelConfig, hidden: jnp.ndarray,
          next_tokens: jnp.ndarray, positions: jnp.ndarray,
          page_table: jnp.ndarray, kv_lens: jnp.ndarray,
          valid: jnp.ndarray, k_cache, kv_tail=None, head_index=None):
    """The prediction module on ``hidden [B, T, H]`` (the main model's
    ``return_hidden`` at ``positions``) and ``next_tokens [B, T]`` (the
    token AFTER each position): its layer's latents go to its own
    cache entry (or, with ``kv_tail``, to that entry's tail) and the
    caches come back with the main entries as they were. With
    ``head_index [B]`` the module's logits ``[B, vocab]`` float32 at
    that position of each row come first (its distribution for the
    token two after it), with ``head_index="all"`` those of every
    position ``[B, T, vocab]``; without, ``None`` does: a prefill step
    fills the cache and drafts nothing."""
    c = config
    entry = c.num_hidden_layers
    planes, tails, kept, stats = _caches(c, k_cache, kv_tail)
    eps = c.rms_norm_eps
    with jax.named_scope("mtp_draft"):
        z = jnp.concatenate(
            [rms_norm(params["embed"][next_tokens], params["mtp_enorm"],
                      eps),
             rms_norm(hidden, params["mtp_hnorm"], eps)],
            axis=-1) @ params["mtp_eh_proj"]
        x, kept[entry], stats = _layer(
            c, params, entry, z, positions, page_table, kv_lens, valid,
            planes[entry], None if tails is None else tails[entry],
            stats, kv_tail is not None, hybrid_kernel_impl(c))
        logits = None
        if head_index is not None:
            last = (x if isinstance(head_index, str)
                    else x[jnp.arange(x.shape[0]), head_index])
            logits = (rms_norm(last, params["mtp_head_norm"], eps)
                      @ _head(params)).astype(jnp.float32)
    return logits, tuple(kept) + (stats,)
