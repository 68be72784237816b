"""Llama-family model (Llama 2/3, Mistral, Qwen2-style GQA decoders).

Re-designed TPU-first rather than ported: parameters are stacked along a
leading layer axis and the decoder loop is STATICALLY UNROLLED so every
KV-cache update is an in-place scatter at a static layer index (scanning
layers with the cache as xs/ys makes XLA copy whole layer caches per
step); attention reads and writes the paged KV cache (ops/attention.py)
so prefill chunks and decode steps share one numerics path.

Capability parity: serves the model families the reference deploys via
vLLM (helm/values.yaml modelSpec examples: Llama-3, Mistral, TinyLlama).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.ops.attention import (
    fold_block_queries,
    paged_attention,
    unfold_block_queries,
    write_block_to_tail,
    write_run_to_pages,
    write_to_pages,
    write_to_tail,
)
from production_stack_tpu.ops.quant_kv import QuantKV
from production_stack_tpu.ops.rope import apply_rope

Params = Dict[str, jnp.ndarray]


def dispatch_attention(config: ModelConfig, q, k_cache, v_cache,
                       page_table, positions, kv_lens, layer=None,
                       block: int = 0):
    """Pick the attention implementation for this step shape.

    Under the pallas impl both shapes use page-walking kernels: decode
    (T==1) the online-softmax decode kernel, prefill chunks the
    chunked-prefill kernel (no materialized page gather). The XLA
    gather-based implementation is the CPU path and the ground truth.

    ``k_cache``/``v_cache`` are per-layer [kv, pages, d, p] slices
    when ``layer`` is None, or the full stacked [L, ...] caches with
    ``layer`` a static int — the stacked form is what the (unrolled)
    model loops use: the XLA path fuses the static slice into its
    gather and the Pallas kernels take the layer index through SMEM,
    so neither materializes a per-layer copy.

    Returns ``(attn, k_cache, v_cache)``. The returned caches are the
    inputs passed THROUGH the Pallas custom calls (input/output
    aliased, layer form only) — callers must use the returned caches
    for subsequent layers so the buffer chain stays linear and XLA's
    copy-insertion never duplicates the cache around the custom call.

    ``block`` (a power of two; a block-diffusion family's chunk): sight
    is by block and not causal, a query at ``t`` sees the keys up to
    ``t | (block - 1)``, all of them in the pages; the chunk forms
    serve it whatever the step's length.
    """
    if q.shape[1] == 1 and not block:
        impl = config.attention_impl_decode or config.attention_impl
        if impl.startswith("pallas"):
            from production_stack_tpu.ops.paged_attention_pallas import (
                paged_decode_attention,
            )
            res = paged_decode_attention(
                q[:, 0], k_cache, v_cache, page_table, kv_lens,
                layer=layer,
                interpret=impl == "pallas-interpret",
            )
            if layer is not None:
                out, k_cache, v_cache = res
            else:
                out = res
            return out[:, None], k_cache, v_cache
    else:
        impl = config.attention_impl_prefill or config.attention_impl
        if impl.startswith("pallas_ragged") and not block:
            # Fused unified-step kernel: rebuild the row descriptors
            # from the planner's layout invariant (docs/unified_step.md
            # — every row kind satisfies positions[:, 0] == kv_lens - 1
            # - last_index, so last_index is recoverable losslessly and
            # nothing new threads through the family forwards).
            from production_stack_tpu.ops.ragged_attention_pallas import (
                paged_ragged_attention,
            )
            last_index = kv_lens - 1 - positions[:, 0]
            res = paged_ragged_attention(
                q, k_cache, v_cache, page_table, kv_lens, last_index,
                layer=layer,
                interpret=impl.endswith("-interpret"),
            )
            if layer is not None:
                out, k_cache, v_cache = res
            else:
                out = res
            return out, k_cache, v_cache
        if impl.startswith("pallas"):
            from production_stack_tpu.ops.prefill_attention_pallas import (
                paged_prefill_attention,
            )
            res = paged_prefill_attention(
                q, k_cache, v_cache, page_table, positions, kv_lens,
                layer=layer, block=block,
                interpret=impl == "pallas-interpret",
            )
            if layer is not None:
                out, k_cache, v_cache = res
            else:
                out = res
            return out, k_cache, v_cache
    return paged_attention(
        q, k_cache, v_cache, page_table,
        positions | (block - 1) if block else positions, kv_lens,
        layer=layer,
    ), k_cache, v_cache


def cached_attention(config: ModelConfig, q, k, v, k_cache, v_cache,
                     page_table, positions, kv_lens, valid, layer: int,
                     block: int = 0):
    """Write one layer's K/V into the paged cache and attend.

    The single place both cache layouts are handled
    (engine/config.py CacheConfig.cache_layout), shared by every model
    family's unrolled layer loop:

      stacked:   ``k_cache``/``v_cache`` are the full [L, kv, pages,
                 d, page_size] arrays; writes are in-place scatters at
                 the static ``layer`` index and the kernels take the
                 stacked cache with the layer index through SMEM.
      per_layer: tuples of L [kv, pages, d, page_size] buffers; this
                 layer's buffer is updated and the tuple rebuilt, so
                 each write's/kernel's operand is ONE layer's buffer
                 and jit donation aliases the L buffers 1:1. A step's
                 rows are runs (``positions[:, 0]`` on, the first
                 ``sum(valid)`` places) and go to their pages in place
                 (ops/attention.write_run_to_pages).

    Returns ``(attn, k_cache, v_cache)``; callers must thread the
    returned caches so the buffer chain stays linear (see
    dispatch_attention). ``block``: sight by block
    (dispatch_attention).
    """
    if isinstance(k_cache, (list, tuple)):
        kc, vc = k_cache[layer], v_cache[layer]
        with jax.named_scope("kv_write"):
            if positions.shape[1] > 1 and not isinstance(kc, QuantKV):
                # A step of more than a token a row is a step of runs
                # (a chunk, a token and its drafts): page-wise, both
                # planes under the one table. One eager token a row
                # and int8 pages keep the scatter.
                kc, vc = write_run_to_pages(
                    (kc, vc), (k, v), page_table, positions[:, 0],
                    jnp.sum(valid, axis=1))
            else:
                kc = write_to_pages(kc, k, page_table, positions, valid)
                vc = write_to_pages(vc, v, page_table, positions, valid)
        attn, kc, vc = dispatch_attention(
            config, q, kc, vc, page_table, positions, kv_lens,
            layer=None, block=block)
        k_cache = (tuple(k_cache[:layer]) + (kc,)
                   + tuple(k_cache[layer + 1:]))
        v_cache = (tuple(v_cache[:layer]) + (vc,)
                   + tuple(v_cache[layer + 1:]))
        return attn, k_cache, v_cache
    k_cache = write_to_pages(k_cache, k, page_table, positions, valid,
                             layer=layer)
    v_cache = write_to_pages(v_cache, v, page_table, positions, valid,
                             layer=layer)
    return dispatch_attention(config, q, k_cache, v_cache, page_table,
                              positions, kv_lens, layer=layer,
                              block=block)


def block_attention(config: ModelConfig, q, k, v, k_cache, v_cache,
                    page_table, positions, kv_lens, valid, layer: int,
                    kv_tail):
    """One attention layer of one pass of a block-diffusion burst: the
    T positions of each row's block (``positions [B, T]``, a whole
    block; the rows go block by block in lockstep) write their K/V to
    the tail slots the block owns, over whatever an earlier pass left
    there, and attend the row's pages, the tail's finished blocks and
    the block itself, in both directions.

    All T queries of a block see exactly the same keys, so they are
    ``T x group`` query rows of each KV head: folded into the group
    axis, the decode forms serve them as one token a row (the Pallas
    paged decode kernel under its impl, else the XLA form), the tail's
    positional mask fed the block's LAST position. The planes are read
    and never written; ``kv_lens`` is the frozen pre-burst count.

    Returns ``(attn [B, T, q_heads, d], k_tail, v_tail)``."""
    t = q.shape[1]
    nkv = k.shape[2]
    slot = (positions[0, 0] - kv_lens[0]).astype(jnp.int32)
    act = valid[:, 0]
    kt = write_block_to_tail(kv_tail[0][layer], k, slot, act)
    vt = write_block_to_tail(kv_tail[1][layer], v, slot, act)
    kc, vc = k_cache[layer], v_cache[layer]
    last = positions[:, -1]
    folded = fold_block_queries(q, nkv)
    impl = config.attention_impl_decode or config.attention_impl
    with jax.named_scope("block_attention"):
        if impl.startswith("pallas"):
            from production_stack_tpu.ops.paged_attention_pallas import (
                paged_decode_attention,
            )
            out = paged_decode_attention(
                folded, kc, vc, page_table, kv_lens, k_tail=kt,
                v_tail=vt, q_positions=last,
                interpret=impl == "pallas-interpret")
        else:
            out = paged_attention(
                folded[:, None], kc, vc, page_table, last[:, None],
                kv_lens, k_tail=kt, v_tail=vt)[:, 0]
    return unfold_block_queries(out, t, nkv), kt, vt


def deferred_attention(config: ModelConfig, q, k, v, k_cache, v_cache,
                       page_table, positions, kv_lens, valid, layer: int,
                       kv_tail):
    """One attention layer of a deferred-write decode burst (T == 1):
    this step's K/V go to the layer's tail, and the query attends the
    row's pages and the tail in one softmax.

    The planes are read and never written (the runner flushes the
    tails once a burst), ``kv_lens`` is the frozen pre-burst count, and
    ``k_cache``/``v_cache`` are the stacked [L, ...] caches or tuples of
    per-layer buffers. Under the pallas decode impl the pages are read
    in place by the paged decode kernel and the tail's state is merged
    beside it; the XLA form gathers them. Shared by the Llama family's
    forward and the hybrids' attention layers.

    Returns ``(attn, k_tail, v_tail)``: the layer's updated tails."""
    slot, act = positions[:, 0] - kv_lens, valid[:, 0]
    kt = write_to_tail(kv_tail[0][layer], k, slot, act)
    vt = write_to_tail(kv_tail[1][layer], v, slot, act)
    per_layer = isinstance(k_cache, (list, tuple))
    kc, vc = ((k_cache[layer], v_cache[layer]) if per_layer
              else (k_cache, v_cache))
    plane = None if per_layer else layer
    impl = config.attention_impl_decode or config.attention_impl
    if impl.startswith("pallas"):
        from production_stack_tpu.ops.paged_attention_pallas import (
            paged_decode_attention,
        )
        attn = paged_decode_attention(
            q[:, 0], kc, vc, page_table, kv_lens, layer=plane,
            k_tail=kt, v_tail=vt, q_positions=positions[:, 0],
            interpret=impl == "pallas-interpret")[:, None]
    else:
        attn = paged_attention(q, kc, vc, page_table, positions, kv_lens,
                               layer=plane, k_tail=kt, v_tail=vt)
    return attn, kt, vt


def hybrid_attention(config: ModelConfig, q, k, v, k_cache, v_cache,
                     page_table, positions, kv_lens, valid, layer: int,
                     kv_tail=None):
    """``cached_attention`` for one attention layer of a hybrid model
    (per-layer cache tuples in which only some entries are pages),
    under eager or deferred K/V writes.

    With ``kv_tail`` (a deferred-write decode burst, T == 1) this
    step's K/V go to the layer's tail, the planes are read and not
    written, and the updated tails come back in the layer's cache
    entries; ``kv_lens`` is then the frozen pre-burst count."""
    if kv_tail is None:
        return cached_attention(config, q, k, v, k_cache, v_cache,
                                page_table, positions, kv_lens, valid,
                                layer)
    attn, kt, vt = deferred_attention(
        config, q, k, v, k_cache, v_cache, page_table, positions,
        kv_lens, valid, layer, kv_tail)
    return (attn, k_cache[:layer] + (kt,) + k_cache[layer + 1:],
            v_cache[:layer] + (vt,) + v_cache[layer + 1:])


def hybrid_kernel_impl(config: ModelConfig) -> str:
    """Which form a hybrid model's own kernels take (a recurrence's
    decode step over the state pool, a grouped expert product): the
    Pallas kernels where the attention's are Pallas and a TPU is there
    to run them (the runner resolves ``auto`` so), their interpreter
    where the tests ask for it, else plain XLA."""
    impl = config.attention_impl
    if impl == "pallas-interpret":
        return impl
    if impl.startswith("pallas") and jax.default_backend() == "tpu":
        return "pallas"
    return "xla"


def slice_layer_params(params: Params, names, layer: int) -> Params:
    """One layer's weights out of the layer-stacked param dict.

    tree.map, not plain indexing: a projection may be a quantized
    (int8, scale) pytree pair rather than a bare array
    (engine/quantization.py), and every model family's unrolled layer
    loop must slice both forms identically.
    """
    return {k: jax.tree.map(lambda s: s[layer], params[k])
            for k in names}


def slice_layer_lora(lora_stacked, layer: int):
    """One layer's adapter stacks (or None when LoRA is off)."""
    if lora_stacked is None:
        return None
    return jax.tree.map(lambda s: s[layer], lora_stacked)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray,
             eps: float) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random-init parameters (for tests/benchmarks and cold starts)."""
    h = config.hidden_size
    ffn = config.intermediate_size
    nh, nkv, d = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    layers = config.num_hidden_layers
    dtype = config.jax_dtype

    def dense(key, shape, scale=0.02):
        return (scale * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    keys = iter(jax.random.split(key, 16))
    params: Params = {
        "embed": dense(next(keys), (config.vocab_size, h)),
        "final_norm": jnp.ones((h,), dtype),
        "attn_norm": jnp.ones((layers, h), dtype),
        "wq": dense(next(keys), (layers, h, nh * d)),
        "wk": dense(next(keys), (layers, h, nkv * d)),
        "wv": dense(next(keys), (layers, h, nkv * d)),
        "wo": dense(next(keys), (layers, nh * d, h)),
        "mlp_norm": jnp.ones((layers, h), dtype),
        "w_gate": dense(next(keys), (layers, h, ffn)),
        "w_up": dense(next(keys), (layers, h, ffn)),
        "w_down": dense(next(keys), (layers, ffn, h)),
    }
    if config.attention_bias:  # Qwen2-style q/k/v biases
        params["bq"] = jnp.zeros((layers, nh * d), dtype)
        params["bk"] = jnp.zeros((layers, nkv * d), dtype)
        params["bv"] = jnp.zeros((layers, nkv * d), dtype)
    if not config.tie_word_embeddings:
        params["lm_head"] = dense(next(keys), (h, config.vocab_size))
    return params


def _layer_param_names(config: ModelConfig):
    names = ["attn_norm", "wq", "wk", "wv", "wo",
             "mlp_norm", "w_gate", "w_up", "w_down"]
    if config.attention_bias:
        names += ["bq", "bk", "bv"]
    return names


def forward(params: Params, config: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, page_table: jnp.ndarray,
            kv_lens: jnp.ndarray, valid: jnp.ndarray,
            k_cache: jnp.ndarray, v_cache: jnp.ndarray,
            lora=None, lora_ids=None, kv_tail=None,
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One model invocation over a (possibly padded) token block.

    Args:
      tokens:     [B, T] token ids
      positions:  [B, T] absolute positions (0 for padded slots)
      page_table: [B, max_pages] physical page ids (page 0 = trash)
      kv_lens:    [B] valid cached tokens AFTER this block is written
                  (deferred mode: the FROZEN pre-burst count — tail
                  slots sit above it)
      valid:      [B, T] mask of real (non-padding) tokens
      k_cache/v_cache: [L, kv_heads, num_pages, head_dim, page_size]
      lora:       optional adapter stacks (engine/lora.py), layer-leading
      lora_ids:   [B] adapter slot per batch row (0 = base model)
      kv_tail:    optional deferred-write burst tails
                  ((k_tails, v_tails): L-tuples of [B, S, kv, d]).
                  When given (decode bursts, T == 1), this step's K/V
                  are appended to the tails instead of scattered into
                  the pages (ops/attention.write_to_tail) and
                  attention covers pages + tail; the caches return
                  UNCHANGED and the updated tails are returned in the
                  cache slots of the result tuple. The model runner
                  flushes tails to pages once per burst.

    Returns (logits [B, T, vocab], new_k_cache, new_v_cache) — or
    (logits, new_k_tails, new_v_tails) in deferred mode.
    """
    from production_stack_tpu.engine.lora import lora_matmul

    nh, nkv, d = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    b, t = tokens.shape

    x = params["embed"][tokens]  # [B, T, H]

    lora_scale = (None if lora is None
                  else lora["scaling"][lora_ids])  # [B]
    lora_stacked = (None if lora is None
                    else {"a": lora["a"], "b": lora["b"]})

    # STATIC layer loop, caches updated in place at a static layer
    # index. Threading per-layer cache slices through lax.scan xs/ys
    # (the round-1/2 structure) made XLA dynamic-slice each 10s-of-MB
    # layer in and dynamic-update-slice a copy back out every layer of
    # every step — measured ~20 ms/decode-step on v5e for the 1B bench
    # config vs ~1.3 ms for this chained-scatter form. Weights are
    # read whole either way, so unrolling costs only HLO size.
    for layer in range(config.num_hidden_layers):
        lp = slice_layer_params(params, _layer_param_names(config),
                                layer)
        ll = slice_layer_lora(lora_stacked, layer)
        # Attention block
        a_in = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        q = lora_matmul(a_in, lp["wq"], ll, "wq", lora_ids, lora_scale)
        k = lora_matmul(a_in, lp["wk"], ll, "wk", lora_ids, lora_scale)
        v = lora_matmul(a_in, lp["wv"], ll, "wv", lora_ids, lora_scale)
        if config.attention_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = q.reshape(b, t, nh, d)
        k = k.reshape(b, t, nkv, d)
        v = v.reshape(b, t, nkv, d)
        q = apply_rope(q, positions, config.rope_theta)
        k = apply_rope(k, positions, config.rope_theta)
        if kv_tail is not None:
            k_tails, v_tails = kv_tail
            attn, kt, vt = deferred_attention(
                config, q, k, v, k_cache, v_cache, page_table,
                positions, kv_lens, valid, layer, kv_tail)
            k_tails = (tuple(k_tails[:layer]) + (kt,)
                       + tuple(k_tails[layer + 1:]))
            v_tails = (tuple(v_tails[:layer]) + (vt,)
                       + tuple(v_tails[layer + 1:]))
            kv_tail = (k_tails, v_tails)
        else:
            attn, k_cache, v_cache = cached_attention(
                config, q, k, v, k_cache, v_cache, page_table,
                positions, kv_lens, valid, layer,
            )
        x = x + lora_matmul(attn.reshape(b, t, nh * d), lp["wo"], ll,
                            "wo", lora_ids, lora_scale)
        # MLP block (SwiGLU)
        m_in = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
        gate = jax.nn.silu(lora_matmul(m_in, lp["w_gate"], ll, "w_gate",
                                       lora_ids, lora_scale))
        up = lora_matmul(m_in, lp["w_up"], ll, "w_up", lora_ids,
                         lora_scale)
        x = x + lora_matmul(gate * up, lp["w_down"], ll, "w_down",
                            lora_ids, lora_scale)
    if kv_tail is not None:
        new_k, new_v = kv_tail
    else:
        new_k, new_v = k_cache, v_cache

    x = rms_norm(x, params["final_norm"], config.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = (x @ head).astype(jnp.float32)
    return logits, new_k, new_v


def forward_train(params: Params, config: ModelConfig,
                  tokens: jnp.ndarray) -> jnp.ndarray:
    """Cache-free dense causal forward for training/fine-tuning flows.

    Same weights/numerics as the serving path but attends within the
    batch (no paged cache), so it is cleanly differentiable.
    Returns logits [B, T, vocab].
    """
    x = encode(params, config, tokens)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return (x @ head).astype(jnp.float32)


def encode(params: Params, config: ModelConfig,
           tokens: jnp.ndarray) -> jnp.ndarray:
    """Dense causal forward returning final-norm hidden states.

    The /v1/embeddings path (engine/embeddings.py) pools these; the
    reference delegates embeddings to vLLM pooling models
    (src/vllm_router/routers/main_router.py:54-60 routes
    /v1/embeddings to engine pods).

    Returns [B, T, hidden].
    """
    nh, nkv, d = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    b, t = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    x = params["embed"][tokens]

    layer_params = {
        k: params[k] for k in _layer_param_names(config)
    }
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer_step(x, lp):
        a_in = rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        q, k, v = a_in @ lp["wq"], a_in @ lp["wk"], a_in @ lp["wv"]
        if config.attention_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        q = apply_rope(q.reshape(b, t, nh, d),
                       positions, config.rope_theta)
        k = apply_rope(k.reshape(b, t, nkv, d),
                       positions, config.rope_theta)
        v = v.reshape(b, t, nkv, d)
        group = nh // nkv
        qg = q.reshape(b, t, nkv, group, d)
        scores = jnp.einsum(
            "btkgd,bskd->bkgts", qg.astype(jnp.float32),
            k.astype(jnp.float32),
        ) / jnp.sqrt(jnp.asarray(d, jnp.float32))
        scores = jnp.where(causal[None, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum(
            "bkgts,bskd->btkgd", probs, v.astype(jnp.float32)
        ).reshape(b, t, nh * d).astype(x.dtype)
        x = x + attn @ lp["wo"]
        m_in = rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
        x = x + (jax.nn.silu(m_in @ lp["w_gate"])
                 * (m_in @ lp["w_up"])) @ lp["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer_step, x, layer_params)
    return rms_norm(x, params["final_norm"], config.rms_norm_eps)
