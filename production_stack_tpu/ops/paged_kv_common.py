"""Shared grid/index-map + DMA layer for the paged-KV Pallas kernels.

The prefill (ops/prefill_attention_pallas.py) and the unified ragged
(ops/ragged_attention_pallas.py, docs/unified_step.md) kernels are the
same machine with a different query block: grid (batch, kv_head), the
whole page walk inside one kernel instance as a static unroll, KV
pages double-buffer DMA'd from HBM in bursts of C token-minor pages,
int8 dequant scales streamed alongside as (1, page_size) tiles,
flash-style online softmax in VMEM scratch. Historically each kernel
carried its own copy of that machinery; this module is the single
definition both import. Kernel-specific remains only the query layout
and the score mask.

The decode kernel (ops/paged_attention_pallas.py) shares the
wrapper-level helpers (operand unwrap, table padding, the stacked
form's pass-through aliasing) and walks the pages its own way: one
instance a row over all kv heads, its buffers and semaphores carried
across grid steps, which ``make_page_dma``/``run_page_walk``'s
(row, kv head) instances cannot express. They are left as the prefill
and the ragged kernel lower them.

Everything here is either called at trace time from inside a
pallas_call kernel body (the closures built by ``make_page_dma`` /
``run_page_walk``) or at wrapper level before the call (operand
unwrap/pad helpers); nothing allocates device memory itself.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.quant_kv import QuantKV

NEG_INF = -1e30

# Mosaic's VMEM tile for 32-bit (and the floor for narrower) types:
# a block's last two dims must each be a multiple of these or equal to
# the whole array dim — and the *backend* (machine-code) pass is
# stricter than the Python lowering rules about the "or equal" escape
# hatch for the query/output blocks (head_dim=64 block
# shapes lowered fine cross-platform and then failed on the chip).
# The query-side kernels therefore pad to true tile multiples.
SUBLANE_TILE = 8
LANE_TILE = 128


def tile_pad(n: int, tile: int) -> int:
    """Round ``n`` up to a multiple of ``tile``."""
    return -(-n // tile) * tile


def hbm_block_spec():
    """A BlockSpec that keeps the operand un-blocked in HBM (the
    kernel DMAs pages itself)."""
    return pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)


# ---- wrapper-level operand helpers -------------------------------------


def validate_layer_arg(k_cache_layer, layer) -> bool:
    """Check the stacked-cache/layer-index contract shared by every
    paged kernel wrapper; returns ``has_layer``."""
    has_layer = k_cache_layer.ndim == 5
    if has_layer != (layer is not None):
        raise ValueError(
            "layer index and cache rank must agree: pass a stacked "
            "[L, ...] cache WITH layer, or a per-layer [kv, ...] "
            f"cache WITHOUT (got ndim={k_cache_layer.ndim}, "
            f"layer={layer!r})")
    return has_layer


def unwrap_cache(k_cache_layer, v_cache_layer):
    """Split a possibly-quantized cache operand pair into DMA-able
    arrays.

    Returns (quantized, k_data, v_data, k_scale, v_scale,
    scale_shape). For an int8 QuantKV cache the [.., pages, ps]
    scales are reshaped to [.., pages, 1, ps] so each page's scale
    row DMAs as the same 2-D (sublane, lane) tile shape as the data
    pages (pure bitcast — the last axis is contiguous either way);
    ``scale_shape`` is the original shape for re-wrapping outputs.
    For a full-precision cache the scale slots are None.
    """
    if isinstance(k_cache_layer, QuantKV):
        scale_shape = k_cache_layer.scale.shape
        sshape = scale_shape[:-1] + (1, scale_shape[-1])
        return (True, k_cache_layer.data, v_cache_layer.data,
                k_cache_layer.scale.reshape(sshape),
                v_cache_layer.scale.reshape(sshape), scale_shape)
    return False, k_cache_layer, v_cache_layer, None, None, None


def pad_page_table(page_table: jnp.ndarray, pages_per_chunk: int):
    """Pad the page-table width to a chunk multiple so the DMA loop's
    static unroll (max_pages // c chunks) never indexes off the row;
    padded entries point at the trash page and are masked. Returns
    (page_table, max_pages)."""
    max_pages = page_table.shape[1]
    if max_pages % pages_per_chunk:
        page_table = jnp.pad(
            page_table,
            ((0, 0), (0, pages_per_chunk - max_pages % pages_per_chunk)),
        )
        max_pages = page_table.shape[1]
    return page_table, max_pages


def pad_query_rows(qg: jnp.ndarray, rows_pad: int, d_pad: int):
    """Zero-pad a [B, KV, rows, D] flattened query block to the Mosaic
    tile-aligned [B, KV, rows_pad, d_pad] the kernels take. Zero pad
    lanes contribute nothing to the q·k contraction (0 × anything
    accumulates 0 once the matching k-scratch sublanes are zeroed —
    ``zero_pad_sublanes``), and pad rows are sliced back off the
    output by the wrapper."""
    b, kv, rows, d = qg.shape
    if rows_pad == rows and d_pad == d:
        return qg
    return jnp.pad(qg, ((0, 0), (0, 0),
                        (0, rows_pad - rows), (0, d_pad - d)))


def zero_pad_sublanes(k_scratch, v_scratch, head_dim: int,
                      head_dim_pad: int) -> None:
    """Zero the KV scratch sublanes past ``head_dim`` once per kernel
    instance (both DMA slots, both sides). The page DMAs only ever
    fill ``[:head_dim]``, and uninitialized VMEM can hold NaNs —
    0 (pad q lane) × NaN (pad k sublane) would poison the scores
    accumulator. ``head_dim`` is a sublane multiple (the page tile's
    own layout requires it), so the slice is tile-legal."""
    if head_dim_pad == head_dim:
        return
    pad = head_dim_pad - head_dim
    width = k_scratch.shape[-1]
    for side in (k_scratch, v_scratch):
        for slot in range(2):
            side[slot, pl.ds(head_dim, pad), :] = jnp.zeros(
                (pad, width), side.dtype)


def kv_scratch_shapes(head_dim: int, pages_per_chunk: int,
                      page_size: int, k_dtype, v_dtype,
                      quantized: bool):
    """Double-buffered KV (+ int8 scale) VMEM scratch: [slot, d, C*P]
    per side — each page lands in its own 128-aligned lane window, so
    after C copies the buffer IS the [D, chunk_tokens] tile."""
    shapes = [
        pltpu.VMEM((2, head_dim, pages_per_chunk * page_size), k_dtype),
        pltpu.VMEM((2, head_dim, pages_per_chunk * page_size), v_dtype),
    ]
    if quantized:
        shapes += [
            pltpu.VMEM((2, 1, pages_per_chunk * page_size), jnp.float32),
            pltpu.VMEM((2, 1, pages_per_chunk * page_size), jnp.float32),
        ]
    return shapes


def dma_semaphore_shapes(pages_per_chunk: int, quantized: bool):
    """[kv side, slot, page-in-chunk] DMA semaphores, one extra set
    for the scale streams of a quantized cache."""
    shapes = [pltpu.SemaphoreType.DMA((2, 2, pages_per_chunk))]
    if quantized:
        shapes += [pltpu.SemaphoreType.DMA((2, 2, pages_per_chunk))]
    return shapes


def cache_alias_map(num_scalar_prefetch: int, n_cache_in: int,
                    has_layer: bool):
    """Input/output alias map threading the stacked cache THROUGH the
    custom call: cache operands follow the scalar-prefetch operands
    and the query, outputs follow the attention output. Only the
    stacked (engine) form aliases — 4D callers keep using their
    caches afterwards, and aliasing a still-live value would force
    the copy aliasing exists to avoid."""
    if not has_layer:
        return {}
    base = num_scalar_prefetch + 1  # prefetch scalars + q
    return {base + i: 1 + i for i in range(n_cache_in)}


def passthrough_out_shapes(k_data, v_data, k_scale, v_scale,
                           quantized: bool):
    """ShapeDtypeStructs for the aliased cache pass-through outputs
    (stacked form only; the kernel never touches them)."""
    shapes = [
        jax.ShapeDtypeStruct(k_data.shape, k_data.dtype),
        jax.ShapeDtypeStruct(v_data.shape, v_data.dtype),
    ]
    if quantized:
        shapes += [
            jax.ShapeDtypeStruct(k_scale.shape, k_scale.dtype),
            jax.ShapeDtypeStruct(v_scale.shape, v_scale.dtype),
        ]
    return shapes


def rewrap_cache_outputs(res, scale_shape, quantized: bool):
    """Re-wrap the stacked form's pass-through cache outputs (res[1:])
    for the caller's thread-the-cache contract."""
    if quantized:
        return (QuantKV(res[1], res[3].reshape(scale_shape)),
                QuantKV(res[2], res[4].reshape(scale_shape)))
    return res[1], res[2]


# ---- in-kernel page-walk machinery -------------------------------------


def make_page_dma(*, b, h, page_table_ref, layer_ref,
                  k_hbm, v_hbm, ks_hbm, vs_hbm,
                  k_scratch, v_scratch, ks_scratch, vs_scratch,
                  sem, ssem, pages_per_chunk: int, page_size: int,
                  has_layer: bool, quantized: bool,
                  dma_sublanes: "int | None" = None):
    """Build the (issue, wait) pair for the double-buffered page-burst
    DMA shared by every paged kernel.

    ``issue(slot, chunk_idx)`` starts the async copies of chunk
    ``chunk_idx``'s C pages (K, V and — for an int8 cache — their
    dequant scale rows) into buffer ``slot``; ``wait(slot,
    chunk_idx)`` blocks on the same set. With a stacked [L, ...]
    cache the layer index arrives as a prefetched scalar, so ONE
    compiled kernel serves every layer and the caller never slices
    (an HLO slice feeding a pallas custom-call materializes the
    whole 10s-of-MB layer as a copy).

    ``dma_sublanes`` bounds the destination's sublane window when the
    KV scratch is padded past the page tile's head_dim (small-head
    fix: the scratch is lane/sublane tile-padded while the HBM pages
    keep their real [head_dim, page_size] shape).
    """
    c = pages_per_chunk

    def dst(scratch, slot, j):
        win = pl.ds(j * page_size, page_size)
        if dma_sublanes is None:
            return scratch.at[slot, :, win]
        return scratch.at[slot, pl.ds(0, dma_sublanes), win]

    def dma(slot, chunk_idx, j):
        pid = page_table_ref[b, chunk_idx * c + j]
        if has_layer:
            k_src = k_hbm.at[layer_ref[0], h, pid]
            v_src = v_hbm.at[layer_ref[0], h, pid]
        else:
            k_src = k_hbm.at[h, pid]
            v_src = v_hbm.at[h, pid]
        copies = [
            pltpu.make_async_copy(
                k_src, dst(k_scratch, slot, j), sem.at[0, slot, j],
            ),
            pltpu.make_async_copy(
                v_src, dst(v_scratch, slot, j), sem.at[1, slot, j],
            ),
        ]
        if quantized:
            if has_layer:
                ks_src = ks_hbm.at[layer_ref[0], h, pid]
                vs_src = vs_hbm.at[layer_ref[0], h, pid]
            else:
                ks_src = ks_hbm.at[h, pid]
                vs_src = vs_hbm.at[h, pid]
            copies += [
                pltpu.make_async_copy(
                    ks_src,
                    ks_scratch.at[
                        slot, :, pl.ds(j * page_size, page_size)],
                    ssem.at[0, slot, j],
                ),
                pltpu.make_async_copy(
                    vs_src,
                    vs_scratch.at[
                        slot, :, pl.ds(j * page_size, page_size)],
                    ssem.at[1, slot, j],
                ),
            ]
        return copies

    def issue(slot, chunk_idx):
        for j in range(c):
            for cp in dma(slot, chunk_idx, j):
                cp.start()

    def wait(slot, chunk_idx):
        for j in range(c):
            for cp in dma(slot, chunk_idx, j):
                cp.wait()

    return issue, wait


def run_page_walk(*, q, kv_len, num_chunks, max_chunks: int,
                  chunk_tokens: int, head_dim: int,
                  issue, wait,
                  k_scratch, v_scratch, ks_scratch, vs_scratch,
                  m_ref, l_ref, acc_ref, mask_fn, quantized: bool):
    """The shared flash-attention page walk: a STATIC unroll over the
    page-table width with ``pl.when`` guards on the row's real chunk
    count — skipped chunks issue no DMAs and run no compute, so work
    scales with the context actually cached. (A dynamic ``fori_loop``
    bound would be tighter code, but dynamic trip counts + DMA
    semaphores push Mosaic down a rarely-exercised path — observed
    hanging the AOT compiler on v5e — while the static unroll is the
    standard public-Pallas shape.)

    ``q`` is the [rows, D] f32 query block; ``mask_fn(token_pos)``
    returns the validity mask for a [rows, C*P] absolute-token-
    position tile (decode: ``pos < kv_len``; prefill/ragged adds the
    causal ``pos <= q_pos`` term). Caller issues the warmup DMA for
    chunk 0 (guarded on ``num_chunks > 0`` — padded rows must issue
    nothing: an unwaited DMA leaks its semaphore signal into the
    next grid step's waits) and normalizes acc/l at the end.
    """
    del kv_len  # masking is mask_fn's job; kept for signature clarity
    scale = 1.0 / (head_dim ** 0.5)

    for chunk_idx in range(max_chunks):
        @pl.when(chunk_idx < num_chunks)
        def _chunk(chunk_idx=chunk_idx):
            slot = chunk_idx % 2

            @pl.when(chunk_idx + 1 < num_chunks)
            def _prefetch():
                issue(1 - slot, chunk_idx + 1)

            wait(slot, chunk_idx)

            k = k_scratch[slot].astype(jnp.float32)  # [D, C*P]
            v = v_scratch[slot].astype(jnp.float32)  # [D, C*P]
            scores = jax.lax.dot_general(
                q, k,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, C*P]
            if quantized:
                # Fold the k dequant scales into the logits: exact,
                # since each scale is constant along the contracted
                # head_dim axis. [1, C*P] broadcasts over the rows.
                scores = scores * ks_scratch[slot]

            token_pos = (chunk_idx * chunk_tokens
                         + jax.lax.broadcasted_iota(
                             jnp.int32, scores.shape, 1))
            scores = jnp.where(mask_fn(token_pos), scores, NEG_INF)

            m_prev = m_ref[...]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=-1, keepdims=True)
            )
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(scores - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(
                probs, axis=-1, keepdims=True
            )
            if quantized:
                # v dequant folds into the probabilities before the
                # pv contraction (per-token scales, constant along d).
                probs = probs * vs_scratch[slot]
            pv = jax.lax.dot_general(
                probs, v,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, D]
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = m_new
