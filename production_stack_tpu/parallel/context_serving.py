"""Context-parallel SERVING prefill: one dispatch, sequence over ``sp``.

The reference has no sequence/context parallelism (SURVEY.md §2.6);
its long-context story is flag pass-through to vLLM. The standalone
ring-attention forward (parallel/context.py) proved the math in rounds
1-2 but was unreachable from the engine. This module implements the
ENGINE's prefill contract over the ``sp`` mesh axis, so
``--context-parallel-size N`` is a real serving flag
(engine/server.py):

- A long prompt prefills in ONE device program instead of a chunk
  loop: tokens shard [B, T/n] per device, attention runs as ring
  attention (ops/ring_attention.py — K/V hop the ring via ppermute
  over ICI, flash-style online softmax), everything else is local.
- The paged KV cache stays REPLICATED across sp: each layer
  all-gathers the freshly computed K/V shards (T x kv x d — small
  next to the O(T^2) attention the ring just distributed) and every
  device performs the identical ``write_to_pages`` scatter, so after
  prefill any shard can serve the decode steps on the standard
  engine path ("decode on the owning shard").
- Padding rows to T % sp == 0 carry valid=False; their KV writes land
  on the trash page (ops/attention.write_to_pages) and their ring
  outputs are discarded.
- Only the final hidden state leaves the body sharded; the LM-head
  matmul runs once on the [B, H] last-token rows outside shard_map —
  logits for T tokens are never materialized.

Scope: llama-family (llama/mistral/qwen2) + gpt2 architectures,
first-touch prompts (no prefix-cache hit). sp composes with tp
(round-5): weights enter the shard_map with their GSPMD layouts
(parallel/mesh.py param_specs — column projections sliced over 'tp'),
each device runs its local heads through the ring, and the
row-parallel matmuls (wo / w_down / fc2) finish with an explicit
``psum`` over 'tp' — the same collective GSPMD inserts on the
decode path, so sp x tp prefill and plain-tp decode agree bit-for-bit
on the replicated activations. sp also composes with dp (replicated
batch rows); pp composition is still rejected loudly by the
model_runner gate.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models.llama import (
    _layer_param_names,
    rms_norm,
)
from production_stack_tpu.models.gpt2 import (
    GPT2_LAYER_NAMES,
    layer_norm,
)
from production_stack_tpu.ops.attention import write_to_pages
from production_stack_tpu.ops.ring_attention import ring_attention
from production_stack_tpu.ops.rope import apply_rope
from production_stack_tpu.parallel.pipeline_serving import (
    _lora_mm,
    _stage_layer,
)

Params = Dict[str, jnp.ndarray]

# llama body covers llama/mistral/qwen2; gpt2 has its own layer body
# (learned positions, LayerNorm, biased projections, gelu MLP — the
# round-3 "second family" widening).
SP_FAMILIES = ("llama", "mistral", "qwen2", "gpt2")


def shard_w_forward(forward, mesh: Mesh):
    """Wrap the engine forward so multi-token dispatches shard their W
    (token) axis over ``sp``.

    The cp runner's unified ragged step (docs/unified_step.md) and
    spec-verify program route through the PLAIN forward — without a
    constraint GSPMD replicates the whole [R, W] block on every ring
    device. Pinning tokens/positions/valid to P(None, 'sp') makes the
    partitioner split the W axis (QK^T's query axis — parallel, not a
    reduction), so the math and therefore the greedy byte stream are
    unchanged while each device computes W/sp columns. Single-token
    decode dispatches (W == 1) pass through unsharded — nothing to
    split."""
    from jax.sharding import NamedSharding

    from production_stack_tpu.parallel.mesh import _on_mesh

    w_sharding = NamedSharding(mesh, _on_mesh(P(None, "sp"), mesh))

    def wrapped(params, config, tokens, positions, page_table,
                kv_lens, valid, k_cache, v_cache,
                lora=None, lora_ids=None):
        if tokens.shape[1] > 1:
            constrain = (
                lambda x: jax.lax.with_sharding_constraint(
                    x, w_sharding))
            tokens = constrain(tokens)
            positions = constrain(positions)
            valid = constrain(valid)
        return forward(params, config, tokens, positions, page_table,
                       kv_lens, valid, k_cache, v_cache,
                       lora=lora, lora_ids=lora_ids)

    return wrapped


def sp_prefill_forward(params: Params, config: ModelConfig,
                       tokens: jnp.ndarray, page_table: jnp.ndarray,
                       valid: jnp.ndarray, last_index: jnp.ndarray,
                       k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                       lora=None, lora_ids=None,
                       *, mesh: Mesh,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Whole-prompt prefill with the sequence sharded over ``sp``.

    Args:
      tokens:     [B, T] prompt tokens, T % sp == 0 (runner pads)
      page_table: [B, max_pages] physical pages for the whole prompt
      valid:      [B, T] mask of real tokens (False = padding)
      last_index: [B] index of each prompt's final token
      k/v_cache:  [L, kv, pages, d, page_size], replicated over sp
      lora:       optional adapter stacks (engine/lora.py) — the LoRA
                  delta is a per-row map over tokens, so sequence
                  sharding passes through it untouched; under tp each
                  target shards like its base projection (row-parallel
                  targets shard A's input axis so x@A stays a local
                  partial the existing psum closes; column-parallel
                  targets shard B's output axis). Round-5 widening.
      lora_ids:   [B] adapter slot per batch row (0 = base model)

    Returns (row_logits [B, vocab] at last_index, new_k, new_v).
    """
    from production_stack_tpu.parallel.mesh import (
        _on_mesh,
        param_specs,
    )

    # A caller-built mesh may carry only an 'sp' axis (build_mesh
    # always has all four): without 'tp', weights stay replicated and
    # the psums are skipped entirely.
    has_tp = "tp" in mesh.axis_names
    tp = mesh.shape["tp"] if has_tp else 1
    nh, nkv, d = (config.num_attention_heads // tp,
                  config.num_key_value_heads // tp, config.head_dim)
    b, t = tokens.shape
    gpt2 = config.architecture == "gpt2"
    layer_names = (GPT2_LAYER_NAMES if gpt2
                   else _layer_param_names(config))
    layer_params = {k: params[k] for k in layer_names}
    shared = {k: v for k, v in params.items() if k not in layer_names}
    # Weights keep their serving GSPMD layouts inside the shard_map
    # (no resharding at the boundary): column-parallel projections are
    # 'tp' slices, so the body below works on nh/nkv LOCAL heads and
    # closes each row-parallel matmul with a psum over 'tp'.
    specs = param_specs(config)

    def on_mesh(spec: P) -> P:
        return _on_mesh(spec, mesh)

    def psum_tp(x):
        return jax.lax.psum(x, "tp") if has_tp else x

    def llama_layer(x, lp_i, ll, ids, sc, positions_l):
        bl, tl = positions_l.shape
        a_in = rms_norm(x, lp_i["attn_norm"], config.rms_norm_eps)
        q = _lora_mm(a_in, lp_i["wq"], ll, "wq", ids, sc)
        k = _lora_mm(a_in, lp_i["wk"], ll, "wk", ids, sc)
        v = _lora_mm(a_in, lp_i["wv"], ll, "wv", ids, sc)
        if config.attention_bias:
            q, k, v = (q + lp_i["bq"], k + lp_i["bk"],
                       v + lp_i["bv"])
        q = apply_rope(q.reshape(bl, tl, nh, d), positions_l,
                       config.rope_theta)
        k = apply_rope(k.reshape(bl, tl, nkv, d), positions_l,
                       config.rope_theta)
        v = v.reshape(bl, tl, nkv, d)
        return x, q, k, v

    def llama_post(x, attn, lp_i, ll, ids, sc):
        bl, tl = attn.shape[:2]
        # wo / w_down are row-parallel ('tp' slices of the input dim):
        # each device holds a partial sum until the psum.
        x = x + psum_tp(
            _lora_mm(attn.reshape(bl, tl, nh * d), lp_i["wo"], ll,
                     "wo", ids, sc))
        m_in = rms_norm(x, lp_i["mlp_norm"], config.rms_norm_eps)
        return x + psum_tp(
            _lora_mm(
                jax.nn.silu(_lora_mm(m_in, lp_i["w_gate"], ll,
                                     "w_gate", ids, sc))
                * _lora_mm(m_in, lp_i["w_up"], ll, "w_up", ids, sc),
                lp_i["w_down"], ll, "w_down", ids, sc))

    def gpt2_layer(x, lp_i, ll, ids, sc, positions_l):
        bl, tl = positions_l.shape
        a_in = layer_norm(x, lp_i["attn_norm_w"], lp_i["attn_norm_b"])
        q = (_lora_mm(a_in, lp_i["wq"], ll, "wq", ids, sc)
             + lp_i["bq"]).reshape(bl, tl, nh, d)
        k = (_lora_mm(a_in, lp_i["wk"], ll, "wk", ids, sc)
             + lp_i["bk"]).reshape(bl, tl, nkv, d)
        v = (_lora_mm(a_in, lp_i["wv"], ll, "wv", ids, sc)
             + lp_i["bv"]).reshape(bl, tl, nkv, d)
        return x, q, k, v

    def gpt2_post(x, attn, lp_i, ll, ids, sc):
        bl, tl = attn.shape[:2]
        # Row-parallel wo/fc2 close with a psum; their biases are
        # replicated and must be added exactly once (after the psum).
        x = x + (psum_tp(
            _lora_mm(attn.reshape(bl, tl, nh * d), lp_i["wo"], ll,
                     "wo", ids, sc))
            + lp_i["bo"])
        m_in = layer_norm(x, lp_i["mlp_norm_w"], lp_i["mlp_norm_b"])
        hidden = jax.nn.gelu(
            _lora_mm(m_in, lp_i["fc1"], ll, "fc1", ids, sc)
            + lp_i["fc1_b"], approximate=True)
        return x + (psum_tp(_lora_mm(hidden, lp_i["fc2"], ll, "fc2",
                                     ids, sc))
                    + lp_i["fc2_b"])

    qkv_fn, post_fn = ((gpt2_layer, gpt2_post) if gpt2
                       else (llama_layer, llama_post))

    lora_ab = (None if lora is None
               else {"a": lora["a"], "b": lora["b"]})
    lora_scale = (None if lora is None
                  else lora["scaling"][lora_ids])

    def body(lp, shared_p, kc, vc, tokens_l, valid_l, page_table,
             lora_ab, lora_ids, lora_scale):
        idx = jax.lax.axis_index("sp")
        bl, tl = tokens_l.shape
        positions_l = idx * tl + jnp.broadcast_to(
            jnp.arange(tl)[None, :], (bl, tl))
        # Global (replicated) views for the page writes.
        positions_full = jnp.broadcast_to(
            jnp.arange(t)[None, :], (b, t))
        valid_full = jax.lax.all_gather(
            valid_l, "sp", axis=1, tiled=True)

        x = shared_p["embed"][tokens_l]
        if gpt2:
            # Learned positions are indexed by GLOBAL position, so
            # each shard embeds its own offset range.
            x = x + shared_p["pos_embed"][positions_l]

        # Static loop over layers, in-place cache scatters at a
        # static index (see models.llama.forward).
        for layer in range(config.num_hidden_layers):
            lp_i = _stage_layer(lp, layer)
            ll = (None if lora_ab is None
                  else jax.tree.map(lambda s: s[layer], lora_ab))
            x, q, k, v = qkv_fn(x, lp_i, ll, lora_ids, lora_scale,
                                positions_l)
            # O(T^2) mixing distributed around the ring; K/V shards
            # stay put, blocks rotate via ppermute.
            attn = ring_attention(q, k, v, "sp")
            # The cache is replicated: gather the full-sequence K/V
            # (linear in T) and do the identical scatter everywhere.
            k_full = jax.lax.all_gather(k, "sp", axis=1, tiled=True)
            v_full = jax.lax.all_gather(v, "sp", axis=1, tiled=True)
            kc = write_to_pages(kc, k_full, page_table,
                                positions_full, valid_full,
                                layer=layer)
            vc = write_to_pages(vc, v_full, page_table,
                                positions_full, valid_full,
                                layer=layer)
            x = post_fn(x, attn, lp_i, ll, lora_ids, lora_scale)
        if gpt2:
            return (layer_norm(x, shared_p["final_norm_w"],
                               shared_p["final_norm_b"]), kc, vc)
        return (rms_norm(x, shared_p["final_norm"],
                         config.rms_norm_eps), kc, vc)

    repl = P()
    # KV cache shards its head axis over 'tp' (parallel/mesh.py
    # cache_spec): each device scatters the K/V heads it computed.
    # QuantKV caches carry a pytree spec — the 4-D scale leaf drops
    # the (always-replicated) head_dim entry, congruent with how
    # shard_cache places the two leaves.
    cache_sp = on_mesh(P(None, "tp", None, None, None))
    from production_stack_tpu.ops.quant_kv import QuantKV
    if isinstance(k_cache, QuantKV):
        cache_sp = QuantKV(cache_sp,
                           P(*cache_sp[:3], cache_sp[4]))
    def lp_spec(k):
        spec = on_mesh(specs.get(k, repl))
        if isinstance(layer_params[k], tuple):
            # int8 (weight [L, in, out], scale [L, out]): the scale
            # follows the weight's layer + output-channel axes
            # (mirrors parallel/mesh.py shard_params).
            return (spec, P(spec[0], spec[2]))
        return spec

    # Adapter stacks replicate over sp (layers local everywhere);
    # under tp each target shards like its base projection — the ONE
    # sharding rule shared with pp x tp (engine/lora.py
    # lora_stack_specs).
    if lora_ab is None:
        lora_ab_spec = repl
    else:
        from production_stack_tpu.engine.lora import lora_stack_specs
        lora_ab_spec = lora_stack_specs(lora_ab, None, on_mesh)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=({k: lp_spec(k) for k in layer_params},
                  {k: on_mesh(specs.get(k, repl)) for k in shared},
                  cache_sp, cache_sp, P(None, "sp"), P(None, "sp"),
                  repl, lora_ab_spec, repl, repl),
        out_specs=(P(None, "sp", None), cache_sp, cache_sp),
        check_vma=False,
    )
    hidden, new_k, new_v = fn(layer_params, shared, k_cache, v_cache,
                              tokens, valid, page_table,
                              lora_ab, lora_ids, lora_scale)
    # LM head on the last-token rows only (B x H @ H x V).
    last_h = hidden[jnp.arange(b), last_index]
    head = shared.get("lm_head")
    if head is None:
        head = shared["embed"].T
    return (last_h @ head).astype(jnp.float32), new_k, new_v
