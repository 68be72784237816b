"""Pallas TPU kernel: paged prefill (chunked) attention.

The XLA reference path (ops/attention.py) materializes every page of a
sequence's context as a gathered [B, S, KV, D] array per prefill chunk
— HBM traffic proportional to the page-table width regardless of the
real context length. This kernel walks the page list instead, exactly
like the decode kernel (ops/paged_attention_pallas.py), with a chunk
of T query tokens per sequence:

- grid (batch, kv_head); the whole page walk runs *inside* the kernel
  as a STATIC unroll over the page-table width with ``pl.when``
  guards on the row's real chunk count (the round-2 grid-per-page
  design paid a fixed cost per tiny BlockSpec DMA and lost to the
  XLA gather on-chip; a dynamic fori_loop bound hung Mosaic's AOT
  compiler — see ops/paged_attention_pallas.py),
- KV pages live in HBM and are copied in double-buffered bursts of C
  pages via manual async DMAs; pages are stored token-minor
  ([head_dim, page_size]) so the slices are tile-aligned and K needs
  no transpose before the ``q @ k^T`` MXU contraction,
- queries arrive flattened [G*T, D] so both matmuls stay plain 2D MXU
  contractions, zero-padded to true (8, 128) tile multiples — the
  whole-dim block escape hatch the Python lowering rules allow is not
  honored by Mosaic's machine-code pass for small-head models
  (head_dim=64 lowered cross-platform and then failed on chip),
  so the wrapper pads rows/head_dim outright and the
  kernel zeroes the matching KV-scratch pad sublanes,
- causal masking is rebuilt in-kernel from a scalar-prefetched per-row
  chunk start: query positions within a prefill chunk are contiguous
  (engine/model_runner.py run_prefill), so ``start + iota`` recovers
  them without shipping a [B, T] positions array through VMEM (a
  (1, T) int32 VMEM block violates Mosaic's (8, 128) tiling rule —
  the round-2 on-chip compile failure),
- flash-style online softmax in VMEM scratch across the page walk.

Contract matches ops.attention.paged_attention for contiguous per-row
q_positions (the engine's chunked-prefill shape); parity is tested in
tests/test_pallas_attention.py and compiled lowering is checked by
tests/test_pallas_lowering.py (TPU cross-lowering, no chip needed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.paged_kv_common import (
    LANE_TILE,
    NEG_INF,
    SUBLANE_TILE,
    cache_alias_map,
    dma_semaphore_shapes,
    hbm_block_spec,
    kv_scratch_shapes,
    make_page_dma,
    pad_page_table,
    pad_query_rows,
    passthrough_out_shapes,
    rewrap_cache_outputs,
    run_page_walk,
    tile_pad,
    unwrap_cache,
    validate_layer_arg,
    zero_pad_sublanes,
)

# Pages per DMA burst (2 x 128-token pages = a 256-token KV tile per
# compute step — prefill scores are [G*T, tile], so a fatter tile
# costs VMEM quadratically while the MXU is already saturated).
_PAGES_PER_CHUNK = 2


def _prefill_kernel(page_table_ref, kv_lens_ref, q_start_ref,
                    layer_ref, first_key_ref, q_ref,
                    k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
                    m_ref, l_ref, acc_ref,
                    k_scratch, v_scratch, ks_scratch, vs_scratch,
                    sem, ssem, *,
                    page_size: int, pages_per_chunk: int,
                    chunk: int, head_dim: int, head_dim_pad: int,
                    rows_pad: int, max_pages: int,
                    has_layer: bool, quantized: bool,
                    window: "int | None", block: int = 0):
    # first_key_ref is None but under ``window``: the first position
    # of the row's table that holds a key at all. ``block``: a query
    # sees the keys up to the end of its block of that many positions.
    # ks_hbm/vs_hbm carry the per-slot f32 dequant scales of an int8
    # cache (ops/quant_kv.py), pre-reshaped by the wrapper to
    # [.., pages, 1, page_size]; None for a full-precision cache.
    b = pl.program_id(0)
    h = pl.program_id(1)
    c = pages_per_chunk
    chunk_tokens = c * page_size
    max_chunks = max_pages // c  # static unroll bound

    kv_len = kv_lens_ref[b]
    q_start = q_start_ref[b]
    num_chunks = (kv_len + chunk_tokens - 1) // chunk_tokens

    issue, wait = make_page_dma(
        b=b, h=h, page_table_ref=page_table_ref, layer_ref=layer_ref,
        k_hbm=k_hbm, v_hbm=v_hbm, ks_hbm=ks_hbm, vs_hbm=vs_hbm,
        k_scratch=k_scratch, v_scratch=v_scratch,
        ks_scratch=ks_scratch, vs_scratch=vs_scratch,
        sem=sem, ssem=ssem, pages_per_chunk=c, page_size=page_size,
        has_layer=has_layer, quantized=quantized,
        dma_sublanes=(head_dim if head_dim_pad != head_dim else None),
    )

    # Padded rows (kv_len == 0 -> num_chunks == 0) must not issue the
    # warmup DMAs: the loop never waits them, and an unwaited DMA
    # leaks its semaphore signal into the next grid step's waits.
    @pl.when(num_chunks > 0)
    def _warmup():
        issue(0, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    zero_pad_sublanes(k_scratch, v_scratch, head_dim, head_dim_pad)

    q = q_ref[0, 0].astype(jnp.float32)  # [rows_pad, D_pad]

    # Row r of the flattened queries is (g, t) = (r // T, r % T) whose
    # absolute position is q_start + t (chunk positions contiguous).
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (rows_pad, chunk_tokens), 0
    ) % chunk  # [rows_pad, C*P]

    def mask_fn(token_pos):
        # Causal over the chunk's own tokens plus everything cached
        # before it — exactly the ragged mixed-length contract: each
        # row masks independently off its scalar-prefetched start.
        # A block (a power of two) ends at ``q_pos | (block - 1)``.
        sight = q_pos | (block - 1) if block else q_pos
        in_sight = (token_pos <= sight) & (token_pos < kv_len)
        if window is not None:
            in_sight = (in_sight & (token_pos > q_pos - window)
                        & (token_pos >= first_key_ref[b]))
        return in_sight

    run_page_walk(
        q=q, kv_len=kv_len, num_chunks=num_chunks,
        max_chunks=max_chunks, chunk_tokens=chunk_tokens,
        head_dim=head_dim, issue=issue, wait=wait,
        k_scratch=k_scratch, v_scratch=v_scratch,
        ks_scratch=ks_scratch, vs_scratch=vs_scratch,
        m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref,
        mask_fn=mask_fn,
        quantized=quantized,
    )

    denom = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "block", "interpret"))
def paged_prefill_attention(q: jnp.ndarray, k_cache_layer: jnp.ndarray,
                            v_cache_layer: jnp.ndarray,
                            page_table: jnp.ndarray,
                            q_positions: jnp.ndarray,
                            kv_lens: jnp.ndarray,
                            layer: "jnp.ndarray | int | None" = None,
                            window: "int | None" = None,
                            first_key: "jnp.ndarray | None" = None,
                            block: int = 0,
                            interpret: bool = False) -> jnp.ndarray:
    """Chunked-prefill attention against a sequence's cached pages.

    Args:
      q:           [B, T, num_q_heads, head_dim] (chunk, padded)
      k/v_cache_layer: [num_kv_heads, num_pages, head_dim, page_size],
                   or the full stacked [L, ...] cache with ``layer``
                   given (scalar; reaches the kernel via SMEM prefetch
                   so no per-layer slice is ever materialized)
      page_table:  [B, max_pages] int32 physical page ids
      q_positions: [B, T] int32 absolute positions of the queries;
                   must be contiguous per row (positions[i] =
                   start_i + arange(T)), the engine's chunked-prefill
                   shape — only row starts reach the kernel (SMEM)
      kv_lens:     [B] int32 valid cached tokens (incl. this chunk)
      window:      static; with it a key is in sight iff it is also
                   under ``window`` positions behind the query and at
                   or after ``first_key`` [B] int32 (a table whose
                   first positions hold no key: a step's own plane
                   over a ring, ops/window_attention.py). Without it
                   nothing of the kernel or its operands changes
      block:       static, a power of two; with it sight is by block
                   and not causal: a query at ``t`` sees every key up
                   to the end of its block, ``key <= t | (block - 1)``
                   (block-diffusion prefill, models/sdar_moe.py; the
                   block's keys are in the pages, ``kv_lens`` covers
                   them). 0: nothing of the kernel changes
      interpret:   run in interpreter mode (CPU testing)

    Returns [B, T, num_q_heads, head_dim] for the 4D per-layer cache
    form; ``(out, k_cache, v_cache)`` for the stacked 5D form (caches
    pass through the kernel aliased — see paged_decode_attention).
    """
    has_layer = validate_layer_arg(k_cache_layer, layer)
    (quantized, k_data, v_data,
     k_scale, v_scale, scale_shape) = unwrap_cache(
        k_cache_layer, v_cache_layer)
    layer_arr = jnp.asarray(
        [0 if layer is None else layer], jnp.int32)
    b, t, num_q_heads, head_dim = q.shape
    num_kv_heads, _, _, page_size = k_data.shape[-4:]
    group = num_q_heads // num_kv_heads
    c = _PAGES_PER_CHUNK

    page_table, max_pages = pad_page_table(page_table, c)

    # [B, T, KV, G, D] -> [B, KV, G*T, D]: rows of one kv head's
    # queries, flattened so kernel matmuls are 2D, then tile-padded
    # to true (8, 128) multiples. Mosaic's machine-code pass is
    # stricter than the Python lowering rules about whole-dim q/o
    # blocks (the small-head failure: head_dim=64 lowered
    # cross-platform and failed on chip), so the wrapper pads and the
    # kernel zeroes the matching KV-scratch sublanes.
    rows = group * t
    rows_pad = max(tile_pad(rows, SUBLANE_TILE), SUBLANE_TILE)
    d_pad = tile_pad(head_dim, LANE_TILE)
    qg = (q.reshape(b, t, num_kv_heads, group, head_dim)
          .transpose(0, 2, 3, 1, 4)
          .reshape(b, num_kv_heads, rows, head_dim))
    qg = pad_query_rows(qg, rows_pad, d_pad)

    # Only the per-row chunk start crosses into the kernel (SMEM
    # scalar prefetch); positions are rebuilt as start + iota.
    q_start = q_positions[:, 0]

    base_kernel = functools.partial(
        _prefill_kernel, page_size=page_size, pages_per_chunk=c,
        chunk=t, head_dim=head_dim, head_dim_pad=d_pad,
        rows_pad=rows_pad, max_pages=max_pages,
        has_layer=has_layer, quantized=quantized, window=window,
        block=block,
    )
    if block & (block - 1):
        raise ValueError(f"block {block} is not a power of two")
    if (window is None) != (first_key is None):
        raise ValueError(
            "a window and the first key that exists go together "
            f"(window {window!r}, first_key given: "
            f"{first_key is not None})")
    windowed = [] if window is None else [first_key]
    n_prefetch = 4 + len(windowed)
    n_cache_in = 4 if quantized else 2
    # Stacked-form pass-through cache outputs exist only for the
    # input/output aliasing (see paged_decode_attention); the kernel
    # never touches them, so this adapter strips them (and splices
    # None for the quant-only refs) before the canonical signature.
    n_pass = n_cache_in if has_layer else 0

    def kernel(pt, kl, qs, la, *refs):
        fk = None
        if window is not None:
            fk, *refs = refs
        q_ref, *refs = refs
        cache_in = refs[:n_cache_in]
        o_ref = refs[n_cache_in]
        scratch = refs[n_cache_in + 1 + n_pass:]
        if quantized:
            k, v, ks, vs = cache_in
            (m, l, acc, k_s, v_s, ks_s, vs_s, sem, ssem) = scratch
        else:
            k, v = cache_in
            ks = vs = ks_s = vs_s = ssem = None
            (m, l, acc, k_s, v_s, sem) = scratch
        base_kernel(pt, kl, qs, la, fk, q_ref, k, v, ks, vs, o_ref,
                    m, l, acc, k_s, v_s, ks_s, vs_s, sem, ssem)

    hbm = hbm_block_spec()
    scratch_shapes = [
        pltpu.VMEM((rows_pad, 1), jnp.float32),  # m
        pltpu.VMEM((rows_pad, 1), jnp.float32),  # l
        pltpu.VMEM((rows_pad, d_pad), jnp.float32),  # acc
    ]
    scratch_shapes += kv_scratch_shapes(
        d_pad, c, page_size, k_data.dtype, v_data.dtype, quantized)
    scratch_shapes += dma_semaphore_shapes(c, quantized)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # page_table, kv_lens, q_start, layer (and a window's first key)
        num_scalar_prefetch=n_prefetch,
        grid=(b, num_kv_heads),
        in_specs=[
            pl.BlockSpec(
                (1, 1, rows_pad, d_pad),
                lambda bi, hi, *_: (bi, hi, 0, 0),
            ),
        ] + [hbm] * n_cache_in,
        out_specs=[
            pl.BlockSpec(
                (1, 1, rows_pad, d_pad),
                lambda bi, hi, *_: (bi, hi, 0, 0),
            ),
        ] + [hbm] * n_pass,
        scratch_shapes=scratch_shapes,
    )

    out_shape = [jax.ShapeDtypeStruct(
        (b, num_kv_heads, rows_pad, d_pad), q.dtype)]
    operands = [page_table, kv_lens, q_start, layer_arr, *windowed, qg,
                k_data, v_data]
    if quantized:
        operands += [k_scale, v_scale]
    if has_layer:
        out_shape += passthrough_out_shapes(
            k_data, v_data, k_scale, v_scale, quantized)
    aliases = cache_alias_map(n_prefetch, n_cache_in, has_layer)
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        input_output_aliases=aliases,
        interpret=interpret,
    )(*operands)
    out = (res[0][:, :, :rows, :head_dim]
           .reshape(b, num_kv_heads, group, t, head_dim)
           .transpose(0, 3, 1, 2, 4)
           .reshape(b, t, num_q_heads, head_dim))
    if has_layer:
        kc, vc = rewrap_cache_outputs(res, scale_shape, quantized)
        return out, kc, vc
    return out
