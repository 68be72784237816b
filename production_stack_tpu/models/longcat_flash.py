"""LongCat-Flash decoders (the language model of meituan-longcat's
LongCat-Flash-Omni).

A layer is two latent-attention (MLA) sublayers ``A1, A2``, each with
its own cache, two dense SwiGLU feed-forwards ``F1, F2`` and one
routed-expert branch that reads the first sublayer's normalised output
and is added back only at the layer's end (shortcut-connected MoE)::

    h1 = h + A1(N1(h));  u = N2(h1);  m = moe(u);  h2 = h1 + F1(u)
    h3 = h2 + A2(N3(h2));  out = h3 + F2(N4(h3)) + m

*MLA* on a normalised ``x`` at position t: ``q = s_q * (norm(x W_qa)
W_qb)`` as heads of ``dn + dr``, the last ``dr`` turned by an
interleaved rotary embedding; ``[c_raw | k_r] = x W_kva``, ``c = s_kv *
norm(c_raw)``, ``k_rope = rotary(k_r)``, one head shared by all. The
cache holds ``(c | k_rope)``, ``rank + dr`` values a token a sublayer in
ONE plane (``models/registry.py`` ``PageCache``), and nothing else;
attention is the absorbed form over that plane, for a prefill chunk
and for a decode step alike (ops/mla_attention.py; on a TPU the decode
step's Pallas kernel, ops/mla_attention_pallas.py).

*Router*: softmax over the routed experts and, after them,
``zero_expert_num`` zero-compute experts; the ``top_k`` largest of
score + a learned bias are chosen and weighted ``routed_scaling_factor``
times the scores alone, not renormalised (``ops/moe.py``
``route_softmax_bias``). ``moe(u)`` is the held experts' part of the
sum (``held_experts``, which drops every id outside its block, the zero
experts' among them) plus ``u`` times the weights of the chosen zero
experts (identity experts hold nothing, so every rank has them whole).
No shared expert. Norms are plain (``x / rms(x) * w``).

Same contract as ``models.lfm2_moe.forward`` with per-entry caches:
``k_cache`` is one latent plane a sublayer (entry ``2 * layer`` is
``A1``'s, ``2 * layer + 1`` ``A2``'s) and after them the seven counters
of the expert branches' decode steps (``count_step``'s six and the
choices that fell on zero experts); every ``v_cache`` entry is ``None``
and stays so. With ``kv_tail`` (a deferred-write decode burst) a
sublayer appends its latent to its tail and leaves its plane unwritten.

Parameters are stacks over the sublayers (``attn_norm``, the MLA
matrices, ``ffn_norm``, the dense feed-forwards) and over the layers
(``router``, ``router_bias``), the experts one array a layer
(``w_gate_up_<i>``, ``w_down_<i>``: a slice of a stack handed to the
grouped product's kernel is first copied out). ``W_kvb`` is kept as
its two halves a head, ``w_uk [n, dn, rank]`` and ``w_uv [n, rank,
dv]``, and gate | up side by side: this program's own layouts.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models.llama import hybrid_kernel_impl, rms_norm
from production_stack_tpu.ops.attention import (
    write_run_to_pages,
    write_to_pages,
    write_to_tail,
)
from production_stack_tpu.ops.mla_attention import latent_paged_attention
from production_stack_tpu.ops.moe import (
    count_step,
    held_experts,
    identity_weight,
    route_softmax_bias,
    swiglu,
)
from production_stack_tpu.ops.rope import apply_rope_interleaved

Params = Dict[str, jnp.ndarray]

SUBLAYER = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
            "w_uk", "w_uv", "wo", "ffn_norm", "w_gate_up", "w_down")
ROUTED = ("router", "router_bias")
EXPERTS = ("w_gate_up", "w_down")   # one array a layer: e_<name>_<i>

def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random parameters. What a zero or a one would switch off is
    drawn: every norm's weight 1 + N(0, 0.1), and ``router_bias``
    N(0, 5e-4) in float32: the softmax scores over the router's width
    lie around 1 / width (1.3e-3 at 768), and at the published widths a
    token's twelfth and thirteenth outputs are 4.1e-4 apart at the
    median (6e-5 to 1.4e-3 from the tenth to the ninetieth percentile
    over the benchmark's check sequences), so a bias of that order
    changes a third of the choices (tests/test_longcat_flash.py) and
    still leaves the scores a say."""
    c = config
    h, n = c.hidden_size, c.num_attention_heads
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    rq, rkv = c.q_lora_rank, c.kv_lora_rank
    f, fe = c.intermediate_size, c.moe_intermediate_size
    layers = c.num_hidden_layers
    sub = 2 * layers
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
        "attn_norm": near_one((sub, h)),
        "q_a": dense((sub, h, rq)),
        "q_a_norm": near_one((sub, rq)),
        "q_b": dense((sub, rq, n * (dn + dr))),
        "kv_a": dense((sub, h, rkv + dr)),
        "kv_a_norm": near_one((sub, rkv)),
        "w_uk": dense((sub, n, dn, rkv)),
        "w_uv": dense((sub, n, rkv, dv)),
        "wo": dense((sub, n * dv, h)),
        "ffn_norm": near_one((sub, h)),
        "w_gate_up": dense((sub, h, 2 * f)),
        "w_down": dense((sub, f, h)),
        "router": dense((layers, h, c.router_width)),
        "router_bias": 5e-4 * jax.random.normal(
            next(keys), (layers, c.router_width), jnp.float32),
    }
    for i in range(layers):
        params[f"e_w_gate_up_{i}"] = dense((c.num_experts, h, 2 * fe))
        params[f"e_w_down_{i}"] = dense((c.num_experts, fe, h))
    if c.tie_word_embeddings:
        del params["lm_head"]
    return params


def mla(config: ModelConfig, lp, x, positions, page_table, kv_lens,
        valid, plane, tail=None):
    """One latent-attention sublayer on a normalised ``x [B, T, H]``.
    Returns ``(y [B, T, H], plane or tail)``: the plane with this
    block's latents written, or with ``tail`` (a deferred-write burst:
    T == 1, or a committed token and its drafts at consecutive
    positions) the tail with this step's appended at each position's
    own slot and the plane left as it is (``kv_lens`` is then the
    frozen pre-burst count)."""
    c = config
    b, t, _ = x.shape
    n, dn, dr = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
    rank, eps = c.kv_lora_rank, c.rms_norm_eps
    scale = (dn + dr) ** -0.5
    f32 = jnp.float32
    c_q = rms_norm(x @ lp["q_a"], lp["q_a_norm"], eps)
    q = (c_q @ lp["q_b"]).reshape(b, t, n, dn + dr)
    if c.mla_q_scale != 1.0:
        q = (q * c.mla_q_scale).astype(q.dtype)
    q = jnp.concatenate(
        [q[..., :dn],
         apply_rope_interleaved(q[..., dn:], positions, c.rope_theta)],
        axis=-1)
    kv = x @ lp["kv_a"]  # [B, T, rank + dr]
    # The scale goes into the norm's weight in float32, so that the
    # cached latent is rounded once.
    latent = jnp.concatenate(
        [rms_norm(kv[..., :rank],
                  lp["kv_a_norm"].astype(f32) * c.mla_kv_scale, eps
                  )[:, :, None],
         apply_rope_interleaved(kv[..., None, rank:], positions,
                                c.rope_theta)],
        axis=-1)  # [B, T, 1, rank + dr]
    impl = c.attention_impl_decode or c.attention_impl
    # A burst iteration is the decode step at any T: with a tail, T is
    # the committed token and the drafts verified beside it.
    decode = t == 1 or tail is not None
    with jax.named_scope("mla_decode" if decode else "mla_prefill"):
        if tail is not None:
            for j in range(t):
                tail = write_to_tail(
                    tail, latent if t == 1 else latent[:, j:j + 1],
                    positions[:, j] - kv_lens, valid[:, j])
        elif t > 1:
            # A chunk's rows are runs: page-wise, in place.
            with jax.named_scope("kv_write"):
                plane = write_run_to_pages(
                    plane, latent, page_table, positions[:, 0],
                    jnp.sum(valid, axis=1))
        else:
            plane = write_to_pages(plane, latent, page_table, positions,
                                   valid)
        if t == 1 and impl.startswith("pallas"):
            from production_stack_tpu.ops.mla_attention_pallas import (
                latent_paged_decode_attention,
            )
            attn = latent_paged_decode_attention(
                q[:, 0], plane, page_table, kv_lens, lp["w_uk"],
                lp["w_uv"], scale, tail=tail,
                q_positions=None if tail is None else positions[:, 0],
                interpret=impl == "pallas-interpret")[:, None]
        elif decode and impl.startswith("pallas"):
            from production_stack_tpu.ops.mla_attention_pallas import (
                latent_paged_verify_attention,
            )
            attn = latent_paged_verify_attention(
                q, plane, page_table, kv_lens, lp["w_uk"], lp["w_uv"],
                scale, tail=tail, q_positions=positions,
                interpret=impl == "pallas-interpret")
        else:
            attn = latent_paged_attention(
                q, plane, page_table, positions, kv_lens, lp["w_uk"],
                lp["w_uv"], scale, tail=tail)
    y = attn.reshape(b, t, n * c.v_head_dim) @ lp["wo"]
    return y, (plane if tail is None else tail)


def moe_branch(config: ModelConfig, lp, x, valid, moe_impl="xla"):
    """x [B, T, H] normalised -> (m [B, T, H], load [E]: real tokens
    that chose each held expert, zero choices of the real tokens)."""
    c = config
    b, t, h = x.shape
    flat = x.reshape(b * t, h)
    real = valid.reshape(b * t)
    weights, ids = route_softmax_bias(
        flat, lp["router"], lp["router_bias"], c.num_experts_per_tok,
        c.routed_scaling_factor)
    y, load = held_experts(
        flat, weights, ids, lp["w_gate_up"], lp["w_down"],
        c.expert_parallel_rank * c.num_experts, valid=real, impl=moe_impl,
        router_width=c.router_width)
    kept, zero = identity_weight(weights, ids,
                                 c.router_width - c.zero_expert_num)
    y = y + (kept[:, None] * flat.astype(jnp.float32)).astype(y.dtype)
    zero = jnp.sum(jnp.where(real, zero, 0)).astype(jnp.float32)
    return y.reshape(b, t, h), load, zero


def forward(params: Params, config: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, page_table: jnp.ndarray,
            kv_lens: jnp.ndarray, valid: jnp.ndarray,
            k_cache, v_cache, lora=None, lora_ids=None,
            kv_tail=None) -> Tuple[jnp.ndarray, tuple, tuple]:
    """Per-entry caches (see the module's text); with ``kv_tail`` the
    sublayers' planes are replaced by their updated tails in what
    comes back. ``v_cache`` goes through untouched. No LoRA targets."""
    if lora is not None:
        raise NotImplementedError("longcat_flash has no LoRA targets")
    if not isinstance(k_cache, (list, tuple)):
        raise ValueError("longcat_flash keeps per-entry caches "
                         "(cache_layout='per_layer')")
    c = config
    b, t = tokens.shape
    sub = 2 * c.num_hidden_layers
    stats = k_cache[sub]
    planes = list(k_cache[:sub])
    tails = None if kv_tail is None else kv_tail[0]
    impl = hybrid_kernel_impl(c)
    eps = c.rms_norm_eps

    def attend(entry, x):
        lp = {k: params[k][entry] for k in SUBLAYER}
        y, planes[entry] = mla(
            c, lp, rms_norm(x, lp["attn_norm"], eps), positions,
            page_table, kv_lens, valid, planes[entry],
            None if tails is None else tails[entry])
        return lp, x + y

    def dense_ffn(lp, u):
        with jax.named_scope("dense_ffn"):
            return swiglu(u, lp["w_gate_up"], lp["w_down"])

    x = params["embed"][tokens]
    for layer in range(c.num_hidden_layers):
        lp, h1 = attend(2 * layer, x)
        u = rms_norm(h1, lp["ffn_norm"], eps)
        rp = {k: params[k][layer] for k in ROUTED}
        rp.update({k: params[f"e_{k}_{layer}"] for k in EXPERTS})
        m, load, zero = moe_branch(c, rp, u, valid, impl)
        if t == 1:
            stats = count_step(stats, c.num_experts_per_tok, load, valid,
                               c.router_width).at[-1].add(zero)
        h2 = h1 + dense_ffn(lp, u)
        lp, h3 = attend(2 * layer + 1, h2)
        x = h3 + dense_ffn(lp, rms_norm(h3, lp["ffn_norm"], eps)) + m

    x = rms_norm(x, params["final_norm"], eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = (x @ head).astype(jnp.float32)
    return logits, tuple(planes) + (stats,), tuple(v_cache)
