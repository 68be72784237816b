"""Pallas TPU kernel: paged latent (MLA) decode attention, absorbed.

One query token a row, ``n`` heads, against the row's cached latents
read from the pages in place. A page of the latent plane
(``[rank + rope, page_size]``, token-minor) is one copy, and that one
copy serves both products: the scores contract all ``rank + rope`` rows
with the absorbed query (``q_nope W_UK^T | q_rope``), the weighted sum
takes the first ``rank`` rows as the values. There is no second plane
and nothing is expanded to heads: 1152 B a cached token serve 64 heads
(121 operations a byte at the published sizes, where a GQA decode is at
4 to 16).

The walk is ops/paged_attention_pallas.py's, for one plane of one
head: the grid is the rows, run in order; a chunk of
``pages_per_chunk`` pages lands in one of two buffer slots; the
semaphores, the buffers and the slot counter outlive a grid step, and
a row's last chunk starts the next live row's first; only the pages a
row holds are copied, and the chunk's other lanes are masked; the page
loop is a static unroll under ``pl.when`` guards. What differs: the
query's rows are the ``n`` heads themselves (64 rows fill half the
matrix unit's 128 without the block-diagonal layout, which exists to
share one product between kv heads and there is one), and the
accumulator is ``[n, rank]``.

The kernel returns the softmax's running state (maximum, sum, weighted
latents, float32); the wrapper merges the state of a deferred-write
burst's latent tail (ops/mla_attention.py ``latent_tail_state``),
normalises and up-projects the values a head (``W_UV``). A second
kernel file and not a form of the K/V kernel: that one's body is built
around two planes of one head size, their int8 scales and the
block-diagonal queries, and a latent form would fork each of them.

``latent_paged_verify_attention`` is the same kernel body for ``T``
query positions a row inside a deferred-write burst (a committed token
and the drafts verified beside it, models/glm4_moe_lite.py): the pages
hold only pre-burst tokens, which every position sees, so the ``T x n``
heads are the query's rows of ONE walk of the row's pages, and the
causal cut between the positions lies in the tail's state.

Contract matches ops.mla_attention.latent_paged_attention, at T = 1
and, with a tail, at any T; parity is tested in
tests/test_longcat_flash.py and tests/test_glm4_moe_lite.py (interpret
mode) and the compiled lowering in tests/test_pallas_lowering.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.mla_attention import (
    absorb_queries,
    latent_tail_state,
    merge_softmax_states,
    up_project_values,
)
from production_stack_tpu.ops.paged_attention_pallas import (
    pages_per_chunk,
)
from production_stack_tpu.ops.paged_kv_common import (
    LANE_TILE,
    NEG_INF,
    hbm_block_spec,
    pad_page_table,
    tile_pad,
)

# The query's rows (heads) are padded to the packed sublane tile of a
# 16-bit operand.
_ROW_TILE = 16


def _latent_decode_kernel(page_table_ref, kv_lens_ref, q_ref, plane_hbm,
                          acc_out, stats_out,
                          buf, m_ref, l_ref, acc_ref, walk_ref, sem, *,
                          page_size: int, pages_per_chunk: int, rank: int,
                          max_pages: int, scale: float):
    b = pl.program_id(0)
    rows_total = pl.num_programs(0)
    c = pages_per_chunk
    chunk_tokens = c * page_size
    max_chunks = max_pages // c  # static unroll bound
    width = buf.shape[2]

    def row_pages(row):
        return (kv_lens_ref[row] + page_size - 1) // page_size

    def for_held_pages(row, slot, chunk_idx, act):
        """``act`` on the copy of each page of the chunk the row
        holds: a page past the row's last is neither started nor
        waited for."""
        held = row_pages(row)
        for j in range(c):
            @pl.when(chunk_idx * c + j < held)
            def _page(j=j):
                pid = page_table_ref[row, chunk_idx * c + j]
                act(pltpu.make_async_copy(
                    plane_hbm.at[:, pid],
                    buf.at[slot, :, :, pl.ds(j * page_size, page_size)],
                    sem.at[slot, j]))

    def issue(row, slot, chunk_idx):
        for_held_pages(row, slot, chunk_idx, lambda cp: cp.start())

    def wait(row, slot, chunk_idx):
        for_held_pages(row, slot, chunk_idx, lambda cp: cp.wait())

    # walk_ref[0]: chunks walked by every row before this one (its
    # parity is the slot this row's first chunk lands in); walk_ref[1]:
    # the row whose first chunk is already in flight.
    @pl.when(b == 0)
    def _first_row():
        walk_ref[0] = 0
        walk_ref[1] = -1
        # A lane no copy has filled must hold no NaN: a masked weight
        # of exactly 0 times it would poison the values' product.
        buf[...] = jnp.zeros_like(buf)

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

    q = q_ref[0]  # [rows, rank + rope], the cache's dtype

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

            lat = buf[slot].reshape(width, chunk_tokens)
            scores = jax.lax.dot_general(
                q, lat, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [rows, C*P]
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
            # The values are the latent's own first ``rank`` rows.
            pv = jax.lax.dot_general(
                probs.astype(lat.dtype), lat[:rank],
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, rank]
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = m_new

    walk_ref[0] = walked + num_chunks

    acc_out[0] = acc_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, stats_out.shape[1:], 1)
    stats_out[0] = jnp.where(lane == 0, m_ref[...],
                             jnp.where(lane == 1, l_ref[...], 0.0))


def _walk_pages(qa: jnp.ndarray, plane: jnp.ndarray,
                page_table: jnp.ndarray, kv_lens: jnp.ndarray, rank: int,
                scale: float, interpret: bool):
    """The kernel over absorbed queries ``qa [B, R, rank + dr]``: the
    ``R`` query rows of a batch row (the heads of one position, or of
    ``T`` positions that all see every cached token) against its
    pages in ONE walk. Returns the running state with the rows padded
    to the sublane tile: weighted latents ``[B, rows, rank]`` and
    ``[B, rows, LANE_TILE]`` holding the maximum in lane 0 and the sum
    in lane 1, float32."""
    b, r, _ = qa.shape
    _, _, width, page_size = plane.shape
    rows = tile_pad(r, _ROW_TILE)
    c = pages_per_chunk(1, width, page_size, plane.dtype.itemsize,
                        page_table.shape[1])
    page_table, max_pages = pad_page_table(page_table, c)

    def row_block(lanes):
        return pl.BlockSpec((1, rows, lanes),
                            lambda bi, pt, kl: (bi, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, kv_lens
        grid=(b,),
        # The plane stays in HBM; the kernel DMAs pages itself.
        in_specs=[row_block(width), hbm_block_spec()],
        out_specs=[row_block(rank), row_block(LANE_TILE)],
        scratch_shapes=[
            pltpu.VMEM((2, 1, width, c * page_size), plane.dtype),
            pltpu.VMEM((rows, 1), jnp.float32),  # m
            pltpu.VMEM((rows, 1), jnp.float32),  # l
            pltpu.VMEM((rows, rank), jnp.float32),  # acc
            pltpu.SMEM((2,), jnp.int32),  # chunks walked, row in flight
            pltpu.SemaphoreType.DMA((2, c)),  # [slot, page]
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _latent_decode_kernel, page_size=page_size,
            pages_per_chunk=c, rank=rank, max_pages=max_pages,
            scale=scale),
        out_shape=[jax.ShapeDtypeStruct((b, rows, rank), jnp.float32),
                   jax.ShapeDtypeStruct((b, rows, LANE_TILE),
                                        jnp.float32)],
        grid_spec=grid_spec,
        # The rows run in order: a row's last chunk starts the next
        # row's first, and the slot counter rides the scratch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table, kv_lens,
      jnp.pad(qa.astype(plane.dtype), ((0, 0), (0, rows - r), (0, 0))),
      plane)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_paged_decode_attention(
        q: jnp.ndarray, plane: jnp.ndarray, page_table: jnp.ndarray,
        kv_lens: jnp.ndarray, w_uk: jnp.ndarray, w_uv: jnp.ndarray,
        scale: float, tail: "jnp.ndarray | None" = None,
        q_positions: "jnp.ndarray | None" = None,
        interpret: bool = False) -> jnp.ndarray:
    """Single-token paged latent attention.

    Args:
      q:          [B, n, dn + dr], the rotary part already turned
      plane:      [1, num_pages, rank + dr, page_size]
      page_table: [B, max_pages] int32; kv_lens [B] int32
      w_uk:       [n, dn, rank]; w_uv [n, rank, dv]
      scale:      the scores' scale, ``(dn + dr) ** -0.5``
      tail:       optional [B, S, 1, rank + dr] latent tail of a
                  deferred-write burst (slot s at ``kv_lens + s``),
                  folded into the same softmax; needs ``q_positions``
                  [B]

    Returns [B, n, dv] in q's dtype. The plane is read and never
    written.
    """
    if (tail is None) != (q_positions is None):
        raise ValueError(
            "a burst tail and the queries' positions go together "
            f"(tail given: {tail is not None}, q_positions given: "
            f"{q_positions is not None})")
    n = q.shape[1]
    dn, rank = w_uk.shape[1], w_uk.shape[2]
    qa = absorb_queries(q[..., :dn], q[..., dn:], w_uk)  # [B, n, W]
    acc, stats = _walk_pages(qa, plane, page_table, kv_lens, rank, scale,
                             interpret)
    state = (stats[:, :n, 0, None], stats[:, :n, 1, None],
             acc[:, :n, None])  # [B, n, T=1(, rank)]
    if tail is not None:
        state = merge_softmax_states(state, latent_tail_state(
            qa[:, None], tail, q_positions[:, None], kv_lens, scale,
            rank))
    _, denom, acc = state
    o_lat = (acc / jnp.maximum(denom, 1e-30)[..., None]).astype(q.dtype)
    return up_project_values(o_lat, w_uv, "bntr")[:, 0]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_paged_verify_attention(
        q: jnp.ndarray, plane: jnp.ndarray, page_table: jnp.ndarray,
        kv_lens: jnp.ndarray, w_uk: jnp.ndarray, w_uv: jnp.ndarray,
        scale: float, tail: jnp.ndarray, q_positions: jnp.ndarray,
        interpret: bool = False) -> jnp.ndarray:
    """``latent_paged_decode_attention`` for ``T`` query positions a
    row inside a deferred-write burst (a committed token and the
    drafts after it): the pages hold only pre-burst tokens, every one
    of which every position sees, so the ``T x n`` heads are the
    query's rows of ONE walk of the row's pages (the kernel body is
    the decode step's: it masks by ``kv_lens`` alone); the causal cut
    between the positions lies in the tail, whose state
    ``latent_tail_state`` takes per position.

    Args as the decode form's, but ``q [B, T, n, dn + dr]`` and
    ``q_positions [B, T]``; the tail is required (without one the
    positions' own latents would be in the pages, under a causal cut
    the walk does not make). Returns ``[B, T, n, dv]``.
    """
    b, t, n, _ = q.shape
    dn, rank = w_uk.shape[1], w_uk.shape[2]
    qa = absorb_queries(q[..., :dn], q[..., dn:], w_uk)  # [B, T, n, W]
    acc, stats = _walk_pages(qa.reshape(b, t * n, -1), plane, page_table,
                             kv_lens, rank, scale, interpret)

    def per_position(x):  # [B, T * n, ...] -> [B, n, T, ...]
        return jnp.swapaxes(x.reshape((b, t, n) + x.shape[2:]), 1, 2)

    state = merge_softmax_states(
        (per_position(stats[:, :t * n, 0]),
         per_position(stats[:, :t * n, 1]),
         per_position(acc[:, :t * n])),
        latent_tail_state(qa, tail, q_positions, kv_lens, scale, rank))
    _, denom, acc = state
    o_lat = (acc / jnp.maximum(denom, 1e-30)[..., None]).astype(q.dtype)
    return up_project_values(o_lat, w_uv, "bntr")
