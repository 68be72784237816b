"""Pipeline-parallel SERVING forward: paged-KV layer stages over ``pp``.

The reference stack exposes no pipeline parallelism (SURVEY.md §2.6 —
vLLM TP only via --tensor-parallel-size pass-through); this is the
TPU-native extension that serves models deeper than one chip/slice's
HBM. Unlike ``parallel/pipeline.py`` (a dense training-style forward),
this implements the ENGINE's forward contract — paged KV cache writes,
chunked prefill, decode — so ``--pipeline-parallel-size N`` is a real
serving flag (engine/server.py).

Design (idiomatic JAX, static shapes):
- Layer-stacked params and the KV caches shard their leading L axis
  over the ``pp`` mesh axis; each stage owns L/S layers and those
  layers' KV pages. Embedding/head replicate.
- pp composes with tp (round-2 gap): within a stage, projections are
  column/row-sharded over the ``tp`` mesh axis exactly as the plain
  TP path (parallel/mesh.py param_specs) places them; the body runs
  head-local attention (the KV cache shards its kv-head axis) and
  psums the row-parallel projections over ``tp``.
- One ``shard_map`` body runs a static tick loop (M microbatches over
  the batch rows, S stages, M+S-1 ticks). At tick i, stage s runs its
  local layer scan on microbatch i-s; activations hop stage-to-stage
  with ``ppermute`` over ICI/DCN.
- The batch is padded to a multiple of S so M == S always (round-2
  weakness: batch % stages != 0 silently degraded to M=1, a pure
  fill/drain bubble); padded rows carry valid=False so their KV
  writes land on the trash page.
- Bubble ticks compute on don't-care data; their KV writes are masked
  via the ``valid`` mask, which ``ops.attention.write_to_pages``
  redirects to the trash page (page 0) — no cache corruption, no
  dynamic shapes.
- The final hidden states (NOT logits: H << vocab, 16x less traffic)
  are returned to every stage with one masked psum; each stage then
  computes the logits locally (all-gathering over ``tp`` when the LM
  head is column-sharded). This replaces the training pipeline's
  full-activation psum the round-1 review flagged.

Families: the llama body covers llama/mistral/qwen2; gpt2 has its own
layer body (layer_norm + learned positions + gelu — round-2 gap:
pp was llama-only).

Ragged unified step (docs/unified_step.md, docs/parallelism.md): the
forward is shape-generic in T, so the unified [R, W] mixed block and
the spec-verify span ride the SAME staged body — ragged rows become
microbatches and the per-row descriptor triple (kv_lens, last_index
via positions/valid, draft spans) reshapes into the per-tick
microbatch views, threading through every ppermute handoff
unchanged. QuantKV int8 caches cross the shard_map boundary with a
pytree spec (data + head_dim-less scale sharded congruently), which
is what dissolved the int8 x pp exclusivity rule.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models.llama import (
    _layer_param_names,
    dispatch_attention,
    rms_norm,
)
from production_stack_tpu.models.gpt2 import (
    GPT2_LAYER_NAMES,
    layer_norm,
)
from production_stack_tpu.ops.attention import write_to_pages
from production_stack_tpu.ops.rope import apply_rope
from production_stack_tpu.parallel.mesh import (
    _on_mesh,
    cache_spec as mesh_cache_spec,
    param_specs,
)

Params = Dict[str, jnp.ndarray]


def _psum_tp(x, tp: int):
    return jax.lax.psum(x, "tp") if tp > 1 else x


def _lora_mm(x, w, ll, target, lora_ids, lora_scale):
    """Projection with optional LoRA delta, shared by the pp and sp
    shard_map bodies. Under tp the adapter stacks arrive sharded like
    their base projections (engine/lora.py lora_stack_specs):
    column-parallel targets add a local out/tp-wide delta to the local
    base; row-parallel targets contract a LOCAL input shard against
    the A shard, so base and delta are both partials the caller's
    psum closes together. ``w`` may be an int8 (weight, scale) pair:
    lora_matmul owns the dense/dequant dispatch and returns the plain
    base matmul when ``ll`` is None."""
    if ll is None and not isinstance(w, tuple):
        return x @ w  # skip the helper import on the hot plain path
    from production_stack_tpu.engine.lora import lora_matmul
    return lora_matmul(x, w, ll, target, lora_ids, lora_scale)


def _stage_layer(lp, i):
    """Slice layer ``i`` off each stage-local stack; int8 params are
    (weight, scale) pairs whose members slice together."""
    return {name: ((s[0][i], s[1][i]) if isinstance(s, tuple)
                   else s[i])
            for name, s in lp.items()}


def _local_layers_llama(x, lp, k_local, v_local, page_table, positions,
                        kv_lens, valid, config: ModelConfig, tp: int,
                        lora=None, lora_ids=None, lora_scale=None):
    """One stage's layer scan — the paged layer math of
    models/llama.py:forward (layer_step) with tp-local head counts."""
    nh = config.num_attention_heads // tp
    nkv = config.num_key_value_heads // tp
    d = config.head_dim
    b, t = positions.shape

    # Static loop over the stage's local layers, in-place cache
    # scatters at a static index (see models.llama.forward).
    for i in range(k_local.shape[0]):
        lp_i = _stage_layer(lp, i)
        ll = (None if lora is None
              else jax.tree.map(lambda s: s[i], lora))
        a_in = rms_norm(x, lp_i["attn_norm"], config.rms_norm_eps)
        q = _lora_mm(a_in, lp_i["wq"], ll, "wq", lora_ids, lora_scale)
        k = _lora_mm(a_in, lp_i["wk"], ll, "wk", lora_ids, lora_scale)
        v = _lora_mm(a_in, lp_i["wv"], ll, "wv", lora_ids, lora_scale)
        if config.attention_bias:
            q, k, v = q + lp_i["bq"], k + lp_i["bk"], v + lp_i["bv"]
        q = apply_rope(q.reshape(b, t, nh, d), positions,
                       config.rope_theta)
        k = apply_rope(k.reshape(b, t, nkv, d), positions,
                       config.rope_theta)
        v = v.reshape(b, t, nkv, d)
        k_local = write_to_pages(k_local, k, page_table, positions,
                                 valid, layer=i)
        v_local = write_to_pages(v_local, v, page_table, positions,
                                 valid, layer=i)
        attn, k_local, v_local = dispatch_attention(
            config, q, k_local, v_local, page_table, positions,
            kv_lens, layer=i,
        )
        x = x + _psum_tp(
            _lora_mm(attn.reshape(b, t, nh * d), lp_i["wo"], ll, "wo",
                     lora_ids, lora_scale), tp)
        m_in = rms_norm(x, lp_i["mlp_norm"], config.rms_norm_eps)
        x = x + _psum_tp(
            _lora_mm(
                jax.nn.silu(_lora_mm(m_in, lp_i["w_gate"], ll,
                                     "w_gate", lora_ids, lora_scale))
                * _lora_mm(m_in, lp_i["w_up"], ll, "w_up", lora_ids,
                           lora_scale),
                lp_i["w_down"], ll, "w_down", lora_ids, lora_scale),
            tp)
    return x, k_local, v_local


def _local_layers_gpt2(x, lp, k_local, v_local, page_table, positions,
                       kv_lens, valid, config: ModelConfig, tp: int,
                       lora=None, lora_ids=None, lora_scale=None):
    """GPT-2 stage body: pre-LN, learned positions are added before
    the first stage (embed path), gelu MLP, per-projection biases.
    Column biases (bq/bk/bv/fc1_b) arrive tp-sharded with their
    projections; row outputs psum over tp before the replicated
    bo/fc2_b is added once."""
    nh = config.num_attention_heads // tp
    d = config.head_dim
    b, t = positions.shape

    # Static loop over the stage's local layers, in-place cache
    # scatters at a static index (see models.llama.forward).
    for i in range(k_local.shape[0]):
        lp_i = _stage_layer(lp, i)
        ll = (None if lora is None
              else jax.tree.map(lambda s: s[i], lora))
        a_in = layer_norm(x, lp_i["attn_norm_w"], lp_i["attn_norm_b"])
        q = (_lora_mm(a_in, lp_i["wq"], ll, "wq", lora_ids, lora_scale)
             + lp_i["bq"]).reshape(b, t, nh, d)
        k = (_lora_mm(a_in, lp_i["wk"], ll, "wk", lora_ids, lora_scale)
             + lp_i["bk"]).reshape(b, t, nh, d)
        v = (_lora_mm(a_in, lp_i["wv"], ll, "wv", lora_ids, lora_scale)
             + lp_i["bv"]).reshape(b, t, nh, d)
        k_local = write_to_pages(k_local, k, page_table, positions,
                                 valid, layer=i)
        v_local = write_to_pages(v_local, v, page_table, positions,
                                 valid, layer=i)
        attn, k_local, v_local = dispatch_attention(
            config, q, k_local, v_local, page_table, positions,
            kv_lens, layer=i,
        )
        x = x + (_psum_tp(
            _lora_mm(attn.reshape(b, t, nh * d), lp_i["wo"], ll, "wo",
                     lora_ids, lora_scale), tp) + lp_i["bo"])
        m_in = layer_norm(x, lp_i["mlp_norm_w"], lp_i["mlp_norm_b"])
        hidden = jax.nn.gelu(
            _lora_mm(m_in, lp_i["fc1"], ll, "fc1", lora_ids,
                     lora_scale) + lp_i["fc1_b"],
            approximate=True)
        x = x + (_psum_tp(_lora_mm(hidden, lp_i["fc2"], ll, "fc2",
                                   lora_ids, lora_scale), tp)
                 + lp_i["fc2_b"])
    return x, k_local, v_local


def _embed(shared_p, config, tokens, positions, dtype):
    x = shared_p["embed"][tokens].astype(dtype)
    if config.architecture == "gpt2":
        x = x + shared_p["pos_embed"][positions].astype(dtype)
    return x


def _head(shared_p, config, hidden, tp: int):
    if config.architecture == "gpt2":
        x = layer_norm(hidden, shared_p["final_norm_w"],
                       shared_p["final_norm_b"])
        return (x @ shared_p["embed"].T).astype(jnp.float32)
    x = rms_norm(hidden, shared_p["final_norm"], config.rms_norm_eps)
    head = shared_p.get("lm_head")
    if head is None:
        return (x @ shared_p["embed"].T).astype(jnp.float32)
    # lm_head is column-sharded over tp (mesh.py _LLAMA_SPECS):
    # assemble the full vocab axis from the local shards.
    logits = (x @ head).astype(jnp.float32)
    if tp > 1:
        logits = jax.lax.all_gather(
            logits, "tp", axis=logits.ndim - 1, tiled=True)
    return logits


_LOCAL_LAYER_BODIES = {
    "llama": _local_layers_llama,
    "mistral": _local_layers_llama,
    "qwen2": _local_layers_llama,
    "gpt2": _local_layers_gpt2,
}

PP_FAMILIES = tuple(_LOCAL_LAYER_BODIES)


def pp_paged_forward(params: Params, config: ModelConfig,
                     tokens: jnp.ndarray, positions: jnp.ndarray,
                     page_table: jnp.ndarray, kv_lens: jnp.ndarray,
                     valid: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lora=None, lora_ids=None,
                     *, mesh: Mesh,
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Engine forward contract (models/llama.py:forward signature) with
    layers pipelined over the mesh's ``pp`` axis (and projections
    sharded over ``tp`` within each stage).

    k_cache/v_cache carry their GLOBAL shape [L, kv, pages, d, ps] but
    are sharded P('pp', 'tp') on (L, kv); inside the shard_map body
    each stage sees its local [L/S, kv/tp, ...] slice.
    """
    S = mesh.shape["pp"]
    tp = mesh.shape["tp"] if "tp" in mesh.axis_names else 1
    b, t = tokens.shape

    # Pad the batch to a multiple of S so M == S always (every stage
    # busy outside fill/drain); padded rows are valid=False.
    pad = (-b) % S
    if pad:
        tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
        positions = jnp.pad(positions, ((0, pad), (0, 0)))
        page_table = jnp.pad(page_table, ((0, pad), (0, 0)))
        kv_lens = jnp.pad(kv_lens, ((0, pad),))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
    bp = b + pad
    M = min(S, bp)
    mb = bp // M

    local_layers = _LOCAL_LAYER_BODIES[config.architecture]
    layer_names = (list(GPT2_LAYER_NAMES)
                   if config.architecture == "gpt2"
                   else _layer_param_names(config))
    layer_params = {k: params[k] for k in layer_names}
    shared = {k: v for k, v in params.items() if k not in layer_names}
    max_pages = page_table.shape[1]
    # LoRA adapter stacks shard their leading L axis over pp with the
    # other layer params; scaling/ids replicate. Padded batch rows run
    # as base model (slot 0 is the all-zeros adapter).
    lora_ab = (None if lora is None
               else {"a": lora["a"], "b": lora["b"]})
    if lora_ids is not None and pad:
        lora_ids = jnp.pad(lora_ids, ((0, pad),))
    lora_scale = (None if lora is None
                  else lora["scaling"][lora_ids])

    def body(lp, shared_p, kc, vc, tokens, positions, page_table,
             kv_lens, valid, lora_ab, lora_ids, lora_scale):
        stage = jax.lax.axis_index("pp")
        mtok = tokens.reshape(M, mb, t)
        mpos = positions.reshape(M, mb, t)
        mpt = page_table.reshape(M, mb, max_pages)
        mkv = kv_lens.reshape(M, mb)
        mvalid = valid.reshape(M, mb, t)
        mlid = (None if lora_ids is None
                else lora_ids.reshape(M, mb))
        mlsc = (None if lora_scale is None
                else lora_scale.reshape(M, mb))
        h = config.hidden_size
        dtype = shared_p["embed"].dtype
        ticks = M + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, i):
            x_recv, kc, vc, collected = carry
            # Stage s processes microbatch i - s at tick i.
            m_s = jnp.clip(i - stage, 0, M - 1)
            active = (i >= stage) & (i - stage < M)
            emb = _embed(shared_p, config, mtok[m_s], mpos[m_s], dtype)
            x_in = jnp.where(stage == 0, emb, x_recv)
            # Bubble ticks must not touch the cache: a False valid
            # redirects the write to the trash page (ops/attention.py
            # write_to_pages).
            v_mask = mvalid[m_s] & active
            x_new, kc, vc = local_layers(
                x_in, lp, kc, vc, mpt[m_s], mpos[m_s], mkv[m_s],
                v_mask, config, tp,
                lora=lora_ab,
                lora_ids=None if mlid is None else mlid[m_s],
                lora_scale=None if mlsc is None else mlsc[m_s],
            )
            # Last stage banks microbatch i - (S - 1) once it's real.
            take = (stage == S - 1) & (i >= S - 1)
            banked = collected.at[jnp.clip(i - (S - 1), 0, M - 1)].set(
                x_new)
            collected = jnp.where(take, banked, collected)
            x_send = jax.lax.ppermute(x_new, "pp", perm)
            return (x_send, kc, vc, collected), None

        init = (
            jnp.zeros((mb, t, h), dtype),
            kc, vc,
            jnp.zeros((M, mb, t, h), dtype),
        )
        (_, kc, vc, collected), _ = jax.lax.scan(
            tick, init, jnp.arange(ticks)
        )
        # Return the final HIDDEN states to every stage (one masked
        # psum of [B, T, H] — serving shapes keep this small) and
        # compute the logits locally.
        collected = jnp.where(stage == S - 1, collected, 0.0)
        hidden = jax.lax.psum(collected, "pp").reshape(bp, t, h)
        return _head(shared_p, config, hidden, tp), kc, vc

    # Layer params keep their TP column/row specs with the leading L
    # axis mapped to 'pp' — exactly how shard_params placed them. A
    # mesh without a 'tp' axis (pp-only callers) must still work:
    # drop axis names the mesh doesn't have.
    def on_mesh(spec: P) -> P:
        return _on_mesh(spec, mesh)

    specs = param_specs(config)

    def lp_spec(k):
        spec = on_mesh(P("pp", *specs[k][1:]))
        if isinstance(layer_params[k], tuple):
            # int8 (weight [L, in, out], scale [L, out]): the scale
            # follows the weight's layer + output-channel axes
            # (mirrors parallel/mesh.py shard_params).
            return (spec, P(spec[0], spec[2]))
        return spec

    lp_specs = {k: lp_spec(k) for k in layer_params}
    shared_specs = {k: on_mesh(specs.get(k, P())) for k in shared}
    cache_spec = on_mesh(mesh_cache_spec(mesh))
    # QuantKV caches (int8 pages + per-slot f32 scales) cross the
    # shard_map boundary as a pytree spec: the 4-D scale leaf lacks
    # the head_dim axis, so its spec drops that entry — congruent
    # data+scale sharding, mirroring parallel/mesh.py shard_cache.
    from production_stack_tpu.ops.quant_kv import QuantKV
    if isinstance(k_cache, QuantKV):
        cache_spec = QuantKV(cache_spec,
                             P(*cache_spec[:3], cache_spec[4]))
    repl = P()
    # Adapter stacks: leading L over pp; under tp each target shards
    # like its base projection (the shared rule —
    # engine/lora.py lora_stack_specs). ids/scaling replicate.
    # _on_mesh drops 'tp' on pp-only meshes, degrading every spec to
    # the old P('pp').
    if lora_ab is None:
        lora_ab_spec = P("pp")
    else:
        from production_stack_tpu.engine.lora import lora_stack_specs
        lora_ab_spec = lora_stack_specs(lora_ab, "pp", on_mesh)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(lp_specs, shared_specs, cache_spec, cache_spec,
                  repl, repl, repl, repl, repl,
                  lora_ab_spec, repl, repl),
        out_specs=(repl, cache_spec, cache_spec),
        check_vma=False,
    )
    logits, kc, vc = fn(layer_params, shared, k_cache, v_cache, tokens,
                        positions, page_table, kv_lens, valid,
                        lora_ab, lora_ids, lora_scale)
    return logits[:b], kc, vc
