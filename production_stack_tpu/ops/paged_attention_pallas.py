"""Pallas TPU kernel: paged decode attention.

The decode hot loop attends one query token a row against the row's
cached pages. The XLA form (ops/attention.py) gathers the bucket's
pages for every row into HBM and contracts the copies; this kernel
walks each row's page list and reads only the pages the row holds,
in place.

Shaped for many short rows (a decode batch of 64-256 rows holding a
few pages each), where an instance a (row, kv head) moves too little
to hide a DMA's latency:

- the grid is the rows, run in order; one instance covers ALL kv
  heads of a row: a page is one strided copy of ``plane[:, page]``
  (``[kv, d, page_size]``), so a row's K and V are two copies a page
  whatever the head count,
- the semaphores, the double buffer and a slot counter live in
  scratch that outlives a grid step, and a row's last chunk issues
  the NEXT live row's first chunk before its own products: no
  instance but the first starts cold,
- only the pages a row holds are copied (a guard a page on the start
  and on the wait); the chunk's other lanes keep finite stale values
  and are masked,
- the heads share one product: the query arrives block-diagonal,
  ``[rows, kv * d]`` with row ``h * group + g`` holding query head
  ``(h, g)`` in columns ``h * d .. (h + 1) * d``, and contracts the
  chunk's ``[kv * d, tokens]`` in the cache's dtype with a float32
  accumulator. The stationary operand is K (or V) either way, so the
  zeros cost the matrix unit nothing, heads narrower than its 128
  rows fill it in pairs, and every vector operation runs on dense
  ``[rows, tokens]`` tiles with no group axis padded to a sublane
  tile,
- the chunk (pages a product) follows the bytes of a page over all
  kv heads, ``CHUNK_BYTES`` a side: four pages at 128 KB a page
  (more where the table is wider than ``MAX_CHUNKS`` such chunks),
- the page loop is a STATIC unroll over the table's width with
  ``pl.when`` guards on the row's chunk count (dynamic trip counts
  with DMA semaphores hung the AOT compiler on a v5e:
  ops/paged_kv_common.py ``run_page_walk``).

The kernel returns the softmax's running state (maximum, sum and
weighted values, float32) and the wrapper finishes it: normalised as
it is, or merged first with the state of a deferred-write burst's
tail (``k_tail``/``v_tail``: the tokens of the burst not yet in the
pages, ops/attention.py ``tail_softmax_state``), the sums
``paged_attention`` makes in the other order.

Pages are token-minor (``[head_dim, page_size]``) so a page's slice is
(sublane, lane)-tile-aligned for the DMA and K arrives transposed for
``q @ k^T``. Pad rows (``kv_lens`` 0) issue no copy and give maximum
NEG_INF, sum 0, values 0.

Contract matches ops.attention.paged_attention at T=1; parity is
tested in tests/test_pallas_attention.py (interpret mode) and compiled
lowering in tests/test_pallas_lowering.py.

Replaces: vLLM's paged_attention CUDA kernels (external to the
reference repo; provisioned via its Helm chart
helm/templates/deployment-vllm-multi.yaml), re-thought for TPU's
DMA+VMEM model.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.attention import tail_softmax_state
from production_stack_tpu.ops.paged_kv_common import (
    LANE_TILE,
    NEG_INF,
    hbm_block_spec,
    pad_page_table,
    passthrough_out_shapes,
    rewrap_cache_outputs,
    tile_pad,
    unwrap_cache,
    validate_layer_arg,
)

# Bytes of K (and of V) a chunk holds over all kv heads; two slots a
# side are kept. 512 KB is 0.6 us of HBM time a side, a few times a
# grid step's own cost, and keeps a short row's masked lanes few.
CHUNK_BYTES = 512 * 1024

# The query rows are padded to the packed sublane tile of a 16-bit
# operand, so one rule serves bfloat16 and float32 queries.
_ROW_TILE = 16


# Chunks the static unroll of a row's walk may take. The compiler
# keeps stack for every unrolled body: 128 chunks (8 kv heads of 128
# under a table of 32k tokens) asked 18 MB of the 16 MB of scoped VMEM
# where 64 compile, so a wider table takes larger chunks instead.
MAX_CHUNKS = 64


def pages_per_chunk(num_kv_heads: int, head_dim: int, page_size: int,
                    itemsize: int, max_pages: int) -> int:
    """Pages a chunk holds: CHUNK_BYTES over the bytes of one page
    across all kv heads, more where the table's width would otherwise
    take over MAX_CHUNKS chunks; at least one, at most the table's
    width."""
    page_bytes = num_kv_heads * head_dim * page_size * itemsize
    by_bytes = CHUNK_BYTES // page_bytes
    by_unroll = -(-max_pages // MAX_CHUNKS)
    return min(max_pages, max(1, by_bytes, by_unroll))


def _expand_heads(per_head, rows: int, group: int):
    """[kv, 1, N] per-head rows to the [rows, N] of the block-diagonal
    layout (row ``h * group + g`` takes head ``h``'s; pad rows 0)."""
    kv, _, n = per_head.shape
    row_head = jax.lax.broadcasted_iota(jnp.int32, (rows, n), 0) // group
    out = jnp.zeros((rows, n), per_head.dtype)
    for h in range(kv):
        out = jnp.where(row_head == h, per_head[h], out)
    return out


def _decode_kernel(page_table_ref, kv_lens_ref, layer_ref, q_ref,
                   k_hbm, v_hbm, ks_hbm, vs_hbm,
                   acc_out, stats_out,
                   k_buf, v_buf, ks_buf, vs_buf,
                   m_ref, l_ref, acc_ref, walk_ref, sem, ssem, *,
                   page_size: int, pages_per_chunk: int, group: int,
                   head_dim: int, max_pages: int, has_layer: bool,
                   quantized: bool):
    # ks_hbm/vs_hbm carry the per-slot f32 dequant scales of an int8
    # cache (ops/quant_kv.py), reshaped by the wrapper to
    # [.., pages, 1, page_size] so a page's scale rows DMA as the same
    # (sublane, lane) tiles as its data; they (and their buffers and
    # semaphores) are None for a full-precision cache.
    b = pl.program_id(0)
    rows_total = pl.num_programs(0)
    c = pages_per_chunk
    chunk_tokens = c * page_size
    max_chunks = max_pages // c  # static unroll bound
    num_kv_heads = k_buf.shape[1]
    rows = q_ref.shape[1]
    slab = acc_out.shape[2]  # lanes of one output slab
    heads_per_slab = slab // head_dim
    scale = 1.0 / (head_dim ** 0.5)
    # Products in the cache's dtype (the MXU's native form) with a
    # float32 accumulator; an int8 cache is widened, its scales are
    # float32 and fold into the scores and the weights.
    compute_dtype = jnp.float32 if quantized else k_buf.dtype

    def row_pages(row):
        return (kv_lens_ref[row] + page_size - 1) // page_size

    def page_copies(row, slot, chunk_idx, j):
        pid = page_table_ref[row, chunk_idx * c + j]
        win = pl.ds(j * page_size, page_size)

        def src(hbm):
            return (hbm.at[layer_ref[0], :, pid] if has_layer
                    else hbm.at[:, pid])

        copies = [
            pltpu.make_async_copy(
                src(k_hbm), k_buf.at[slot, :, :, win], sem.at[0, slot, j]),
            pltpu.make_async_copy(
                src(v_hbm), v_buf.at[slot, :, :, win], sem.at[1, slot, j]),
        ]
        if quantized:
            copies += [
                pltpu.make_async_copy(
                    src(ks_hbm), ks_buf.at[slot, :, :, win],
                    ssem.at[0, slot, j]),
                pltpu.make_async_copy(
                    src(vs_hbm), vs_buf.at[slot, :, :, win],
                    ssem.at[1, slot, j]),
            ]
        return copies

    def for_held_pages(row, slot, chunk_idx, act):
        """``act`` on the copies of each page of the chunk the row
        holds: a page past the row's last is neither started nor
        waited for."""
        held = row_pages(row)
        for j in range(c):
            @pl.when(chunk_idx * c + j < held)
            def _page(j=j):
                for cp in page_copies(row, slot, chunk_idx, j):
                    act(cp)

    def issue(row, slot, chunk_idx):
        for_held_pages(row, slot, chunk_idx, lambda cp: cp.start())

    def wait(row, slot, chunk_idx):
        for_held_pages(row, slot, chunk_idx, lambda cp: cp.wait())

    # walk_ref[0]: chunks walked so far by every row before this one
    # (its parity is the slot this row's first chunk lands in);
    # walk_ref[1]: the row whose first chunk is already in flight.
    @pl.when(b == 0)
    def _first_row():
        walk_ref[0] = 0
        walk_ref[1] = -1
        # A lane no copy has filled must hold no NaN: a masked weight
        # of exactly 0 times it would poison the values' product.
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        if quantized:
            ks_buf[...] = jnp.zeros_like(ks_buf)
            vs_buf[...] = jnp.zeros_like(vs_buf)

    kv_len = kv_lens_ref[b]
    num_chunks = (row_pages(b) + c - 1) // c
    walked = walk_ref[0]

    @pl.when((num_chunks > 0) & (walk_ref[1] != b))
    def _cold_start():
        issue(b, walked % 2, 0)

    # The next row that holds anything (pad rows issue nothing).
    last = rows_total - 1
    nxt = jax.lax.while_loop(
        lambda r: (r < rows_total)
        & (kv_lens_ref[jnp.minimum(r, last)] == 0),
        lambda r: r + 1, b + 1)
    has_next = nxt < rows_total
    nxt = jnp.minimum(nxt, last)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(compute_dtype)  # [rows, kv * d]

    for chunk_idx in range(max_chunks):
        @pl.when(chunk_idx < num_chunks)
        def _chunk(chunk_idx=chunk_idx):
            slot = (walked + chunk_idx) % 2

            @pl.when(chunk_idx + 1 < num_chunks)
            def _prefetch():
                issue(b, 1 - slot, chunk_idx + 1)

            @pl.when((chunk_idx + 1 == num_chunks) & has_next)
            def _prefetch_next_row():
                issue(nxt, 1 - slot, 0)
                walk_ref[1] = nxt

            wait(b, slot, chunk_idx)

            k = k_buf[slot].reshape(
                num_kv_heads * head_dim, chunk_tokens)
            v = v_buf[slot].reshape(
                num_kv_heads * head_dim, chunk_tokens)
            scores = jax.lax.dot_general(
                q, k.astype(compute_dtype),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, C*P]
            if quantized:
                # Exact: a scale is constant along the contracted
                # head_dim axis.
                scores = scores * _expand_heads(
                    ks_buf[slot], rows, group)
            token_pos = (chunk_idx * chunk_tokens
                         + jax.lax.broadcasted_iota(
                             jnp.int32, scores.shape, 1))
            scores = jnp.where(token_pos < kv_len, scores, NEG_INF)

            m_prev = m_ref[...]
            m_new = jnp.maximum(
                m_prev, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            probs = jnp.exp(scores - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(
                probs, axis=-1, keepdims=True)
            if quantized:
                probs = probs * _expand_heads(vs_buf[slot], rows, group)
            pv = jax.lax.dot_general(
                probs.astype(compute_dtype), v.astype(compute_dtype),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, kv * d]; row h*group+g wants columns h*d..
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = m_new

    walk_ref[0] = walked + num_chunks

    # Each row's own head out of the block-diagonal product, a slab of
    # whole lane tiles at a time (a head narrower than a tile shares
    # its slab: the wrapper takes its columns).
    acc = acc_ref[...]
    row_slab = (jax.lax.broadcasted_iota(jnp.int32, (rows, slab), 0)
                // (group * heads_per_slab))
    out = jnp.zeros((rows, slab), jnp.float32)
    for s in range(acc.shape[1] // slab):
        out = jnp.where(row_slab == s,
                        acc[:, s * slab:(s + 1) * slab], out)
    acc_out[0] = out
    lane = jax.lax.broadcasted_iota(jnp.int32, stats_out.shape[1:], 1)
    stats_out[0] = jnp.where(lane == 0, m_ref[...],
                             jnp.where(lane == 1, l_ref[...], 0.0))


def _slab_lanes(num_kv_heads: int, head_dim: int) -> int:
    """Lanes of one slab of the kernel's output: whole lane tiles
    holding whole heads (one head of 128 or 256, two of 64), or every
    head where all of them are narrower than one tile."""
    kv_width = num_kv_heads * head_dim
    if head_dim % LANE_TILE == 0:
        return head_dim
    if LANE_TILE % head_dim == 0 and kv_width % LANE_TILE == 0:
        return LANE_TILE
    if kv_width < LANE_TILE:
        return kv_width
    raise ValueError(
        f"the paged decode kernel cannot lay {num_kv_heads} kv heads "
        f"of {head_dim} out in whole {LANE_TILE}-lane slabs")


def _block_diagonal_queries(q, num_kv_heads: int, rows: int):
    """[B, q_heads, d] to [B, rows, kv * d]: row ``h * group + g`` holds
    query head ``(h, g)`` in columns ``h * d .. (h + 1) * d`` and zeros
    elsewhere; rows past ``q_heads`` are zero."""
    b, num_q_heads, head_dim = q.shape
    group = num_q_heads // num_kv_heads
    qg = q.reshape(b, num_kv_heads, group, 1, head_dim)
    eye = jnp.eye(num_kv_heads, dtype=bool)[None, :, None, :, None]
    qd = jnp.where(eye, qg, jnp.zeros((), q.dtype)).reshape(
        b, num_q_heads, num_kv_heads * head_dim)
    return jnp.pad(qd, ((0, 0), (0, rows - num_q_heads), (0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q: jnp.ndarray, k_cache_layer: jnp.ndarray,
                           v_cache_layer: jnp.ndarray,
                           page_table: jnp.ndarray,
                           kv_lens: jnp.ndarray,
                           layer: "jnp.ndarray | int | None" = None,
                           k_tail: "jnp.ndarray | None" = None,
                           v_tail: "jnp.ndarray | None" = None,
                           q_positions: "jnp.ndarray | None" = None,
                           interpret: bool = False) -> jnp.ndarray:
    """Single-token paged attention.

    Args:
      q:           [B, num_q_heads, head_dim]
      k/v_cache_layer: [num_kv_heads, num_pages, head_dim, page_size],
                   or the full stacked [L, ...] cache with ``layer``
                   given (scalar; reaches the kernel via SMEM prefetch
                   so no per-layer slice is ever materialized)
      page_table:  [B, max_pages] int32 physical page ids
      kv_lens:     [B] int32 valid cached tokens per sequence
      k_tail/v_tail: optional [B, S, num_kv_heads, head_dim] tails of a
                   deferred-write burst (tokens not yet in the pages:
                   slot s is position ``kv_lens + s``), folded into the
                   same softmax; need ``q_positions`` [B]
      interpret:   run in interpreter mode (CPU testing)

    Returns [B, num_q_heads, head_dim] for the 4D per-layer cache
    form. For the stacked 5D form without a tail returns
    ``(out, k_cache, v_cache)`` — the caches are passed THROUGH the
    kernel via input/output aliasing and the caller must thread them
    (models/llama.py layer loop); this keeps the cache buffer chain
    linear so XLA's copy-insertion never duplicates it. With a tail
    the planes are read and never written by the burst, nothing is
    aliased, and the output comes back alone.
    """
    has_layer = validate_layer_arg(k_cache_layer, layer)
    if (k_tail is None) != (q_positions is None):
        raise ValueError(
            "a burst tail and the queries' positions go together "
            f"(k_tail given: {k_tail is not None}, q_positions given: "
            f"{q_positions is not None})")
    (quantized, k_data, v_data,
     k_scale, v_scale, scale_shape) = unwrap_cache(
        k_cache_layer, v_cache_layer)
    layer_arr = jnp.asarray(
        [0 if layer is None else layer], jnp.int32)
    b, num_q_heads, head_dim = q.shape
    num_kv_heads, _, _, page_size = k_data.shape[-4:]
    group = num_q_heads // num_kv_heads
    slab = _slab_lanes(num_kv_heads, head_dim)
    heads_per_slab = slab // head_dim
    rows = tile_pad(num_q_heads, _ROW_TILE)
    c = pages_per_chunk(num_kv_heads, head_dim, page_size,
                        k_data.dtype.itemsize, page_table.shape[1])
    page_table, max_pages = pad_page_table(page_table, c)

    # Pass-through cache outputs (stacked form, no tail) exist only so
    # the caller can thread the cache THROUGH the custom call via
    # input/output aliasing: without it the cache buffer is both a
    # custom-call operand and the target of the next layer's scatter,
    # and XLA's copy-insertion breaks the apparent interference with a
    # full-cache copy per layer (measured ~158 ms/decode-step on v5e
    # for the 1B bench config). The kernel never touches them.
    threaded = has_layer and k_tail is None
    n_cache_in = 4 if quantized else 2
    n_pass = n_cache_in if threaded else 0
    base_kernel = functools.partial(
        _decode_kernel, page_size=page_size, pages_per_chunk=c,
        group=group, head_dim=head_dim, max_pages=max_pages,
        has_layer=has_layer, quantized=quantized,
    )

    def kernel(pt, kl, la, q_ref, *refs):
        cache_in = refs[:n_cache_in]
        acc_out, stats_out = refs[n_cache_in:n_cache_in + 2]
        scratch = refs[n_cache_in + 2 + n_pass:]
        if quantized:
            k, v, ks, vs = cache_in
            (k_b, v_b, ks_b, vs_b, m, l, acc, walk, sem, ssem) = scratch
        else:
            k, v = cache_in
            ks = vs = ks_b = vs_b = ssem = None
            (k_b, v_b, m, l, acc, walk, sem) = scratch
        base_kernel(pt, kl, la, q_ref, k, v, ks, vs, acc_out, stats_out,
                    k_b, v_b, ks_b, vs_b, m, l, acc, walk, sem, ssem)

    hbm = hbm_block_spec()
    chunk_tokens = c * page_size
    kv_width = num_kv_heads * head_dim
    scratch_shapes = [
        pltpu.VMEM((2, num_kv_heads, head_dim, chunk_tokens),
                   k_data.dtype),
        pltpu.VMEM((2, num_kv_heads, head_dim, chunk_tokens),
                   v_data.dtype),
    ]
    if quantized:
        scratch_shapes += [
            pltpu.VMEM((2, num_kv_heads, 1, chunk_tokens), jnp.float32),
            pltpu.VMEM((2, num_kv_heads, 1, chunk_tokens), jnp.float32),
        ]
    scratch_shapes += [
        pltpu.VMEM((rows, 1), jnp.float32),  # m
        pltpu.VMEM((rows, 1), jnp.float32),  # l
        pltpu.VMEM((rows, kv_width), jnp.float32),  # acc
        pltpu.SMEM((2,), jnp.int32),  # chunks walked, row in flight
        pltpu.SemaphoreType.DMA((2, 2, c)),  # [k|v, slot, page]
    ]
    if quantized:
        scratch_shapes += [pltpu.SemaphoreType.DMA((2, 2, c))]

    def row_block(width):
        return pl.BlockSpec((1, rows, width),
                            lambda bi, pt, kl, la: (bi, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # page_table, kv_lens, layer
        grid=(b,),
        # The full KV cache (and int8 scales) stays in HBM; the kernel
        # DMAs pages itself.
        in_specs=[row_block(kv_width)] + [hbm] * n_cache_in,
        out_specs=[row_block(slab), row_block(LANE_TILE)]
        + [hbm] * n_pass,
        scratch_shapes=scratch_shapes,
    )
    out_shape = [jax.ShapeDtypeStruct((b, rows, slab), jnp.float32),
                 jax.ShapeDtypeStruct((b, rows, LANE_TILE), jnp.float32)]
    operands = [page_table, kv_lens, layer_arr,
                _block_diagonal_queries(q, num_kv_heads, rows),
                k_data, v_data]
    if quantized:
        operands += [k_scale, v_scale]
    if threaded:
        out_shape += passthrough_out_shapes(
            k_data, v_data, k_scale, v_scale, quantized)
    # Operands: three prefetched scalars and the queries come first;
    # outputs: the values and the statistics come first.
    aliases = {4 + i: 2 + i for i in range(n_pass)}
    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=grid_spec,
        input_output_aliases=aliases,
        # The rows run in order: a row's last chunk starts the next
        # row's first, and the slot counter rides the scratch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*operands)

    # Row h*group+g of the kernel's slab holds head (h, g) in the
    # columns its kv head has in a shared slab.
    stat = (b, num_kv_heads, group)
    acc = res[0][:, :num_q_heads].reshape(
        *stat, heads_per_slab, head_dim)
    mine = (jnp.arange(num_kv_heads)[:, None] % heads_per_slab
            == jnp.arange(heads_per_slab)[None, :])
    acc = jnp.sum(jnp.where(mine[None, :, None, :, None], acc, 0.0),
                  axis=3)  # [B, kv, group, d]
    m = res[1][:, :num_q_heads, 0].reshape(stat)
    denom = res[1][:, :num_q_heads, 1].reshape(stat)
    if k_tail is not None:
        qg = q.reshape(b, 1, num_kv_heads, group, head_dim)
        t_m, t_denom, t_acc = tail_softmax_state(
            qg, k_tail, v_tail, q_positions[:, None], kv_lens)
        t_m, t_denom, t_acc = t_m[..., 0], t_denom[..., 0], t_acc[..., 0, :]
        m_all = jnp.maximum(m, t_m)
        keep, t_keep = jnp.exp(m - m_all), jnp.exp(t_m - m_all)
        denom = denom * keep + t_denom * t_keep
        acc = acc * keep[..., None] + t_acc * t_keep[..., None]
    out = (acc / jnp.maximum(denom, 1e-30)[..., None]).reshape(
        b, num_q_heads, head_dim).astype(q.dtype)
    if threaded:
        kc, vc = rewrap_cache_outputs(res[1:], scale_shape, quantized)
        return out, kc, vc
    return out
