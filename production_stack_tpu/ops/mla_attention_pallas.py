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

The walk is ops/paged_attention_pallas.py's in kind, for one plane of
one head: the grid is the rows, run in order; a chunk of pages (a
*link*) lands in a buffer slot by one copy a page; the semaphores, the
buffers and the walk's counters outlive a grid step; only the pages a
row holds are copied. What differs, each for the one plane (PERF.md
section 6, PR 45): the query's rows are the ``n`` heads themselves (64
rows fill half the matrix unit's 128 without the block-diagonal layout,
which exists to share one product between kv heads and there is one)
and the accumulator is ``[n, rank]``; a link is sized by this file's
own rule, ``latent_pages_per_chunk`` (``LATENT_CHUNK_BYTES`` of the
plane, 12 pages at the published width: the K/V kernel's
``CHUNK_BYTES`` is the bytes of one SIDE of two, so under it a latent
link carried half a K/V link's bytes against the same fixed cost); the
links of ALL rows are one sequence whose copies run ``_SLOTS - 1``
links ahead of the products, across rows (a cursor in SMEM says which
link starts next; with one link ahead a row's first link was started
under the short last link of the row before and waited for); the links
are a ``fori_loop`` and not an unroll (every link but a row's last is
full by construction and is not masked at all), and the last link runs
its two products at the least of a few static widths (``_GRANULE``
pages apart) that holds its pages, masked by ``kv_len``.

A row's running softmax (maximum, sum, weighted latents, float32) stays
in VMEM from its first key to its output. The latent tail of a
deferred-write burst ``[S, rank + rope]`` arrives as a block of the row,
like the query, and is the walk's FIRST link: ``q . tail^T``, a slot
visible to a query row where ``kv_len + s`` is at most the row's
position (the positions ride the scalar prefetch), the values the
tail's first ``rank`` columns; it sets the running state where a walk
without a tail (a single step: no tail block, no positions, the same
kernel) clears it, in the order ops/mla_attention.py's reference folds
them. After the last page the kernel divides by the sum (floored at
1e-30) and writes the normalised weighted latents ``[rows, rank]``
ONCE, in the query's dtype; the wrapper up-projects them a head
(``W_UV``, in XLA: a product a head with one row is no work for the
matrix unit). The scores' scale goes into the query's block once a row
where it is a power of two (exact in any dtype); any other scale stays
on the float32 scores, so that the query is not rounded twice. A
second kernel file and not a form of the K/V kernel: that one's body is
built around two planes of one head size, their int8 scales and the
block-diagonal queries, and a latent form would fork each of them.

``latent_paged_verify_attention`` is the same kernel for ``T`` query
positions a row inside a deferred-write burst (a committed token and
the drafts verified beside it, models/glm4_moe_lite.py): the pages hold
only pre-burst tokens, which every position sees, so the ``T x n``
heads are the query's rows of ONE walk of the row's pages, and the
causal cut between the positions lies in the tail's link, where the
rows of position ``t`` are the static band ``t * n .. (t + 1) * n``.

Contract matches ops.mla_attention.latent_paged_attention, at T = 1
and, with a tail, at any T; parity is tested in
tests/test_longcat_flash.py and tests/test_glm4_moe_lite.py (interpret
mode) and the compiled lowering in tests/test_pallas_lowering.py;
benchmarks/latent_walk_iteration.py times one sublayer's call alone.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.mla_attention import (
    absorb_queries,
    up_project_values,
)
from production_stack_tpu.ops.paged_kv_common import (
    NEG_INF,
    hbm_block_spec,
    tile_pad,
)

# The query's rows (heads) are padded to the packed sublane tile of a
# 16-bit operand; the tail's slots too.
_ROW_TILE = 16

# Bytes of the ONE plane a link of the walk holds. A link's fixed cost
# (the starts and waits of its pages with their scalar reads, one
# update of the running softmax with the accumulator's pass) is paid
# once a link, and a row that needs one link more pays it whole:
# benchmarks/latent_walk_iteration.py read links of 3 to 17 pages over
# rows of 2 to 32 pages on a v5e, and 12 pages of 576 x 128 bfloat16
# read best on all three of the cells' calls (PERF.md section 6, PR 45).
LATENT_CHUNK_BYTES = 1728 * 1024

# Buffer slots: the copies run ``_SLOTS - 1`` links ahead of the
# products, ACROSS rows. With two, a row's first link was started
# only under the row before's last, which is short: its copy stood
# exposed (one page more than a link cost a quarter of a call).
_SLOTS = 3

# Pages: the static widths a row's LAST link runs its products at
# (3, 6, 9, 12 of a link of 12), so the matrix unit's work on masked
# lanes is under a granule a row whatever the link's size.
_GRANULE = 3


def latent_pages_per_chunk(width: int, page_size: int, itemsize: int,
                           max_pages: int) -> int:
    """Pages a link of the latent walk holds: LATENT_CHUNK_BYTES over
    the bytes of one page of the one plane; at least one, at most the
    table's width. (The links are a loop and not an unroll, so a wide
    table asks for no larger links as it does of the K/V kernel.)"""
    by_bytes = LATENT_CHUNK_BYTES // (width * page_size * itemsize)
    return min(max_pages, max(1, by_bytes))


def _latent_decode_kernel(*refs, page_size: int, pages_per_chunk: int,
                          rank: int, scale: float,
                          heads: int, positions: int):
    """``positions`` is 0 without a burst tail; with one it is the
    query positions a row (bands of ``heads`` query rows each), and the
    positions' scalars and the tail's block are among ``refs``."""
    if positions:
        (page_table_ref, kv_lens_ref, q_pos_ref, q_ref, tail_ref,
         plane_hbm, out_ref, buf, m_ref, l_ref, acc_ref, walk_ref,
         sem) = refs
    else:
        (page_table_ref, kv_lens_ref, q_ref, plane_hbm, out_ref, buf,
         m_ref, l_ref, acc_ref, walk_ref, sem) = refs
    b = pl.program_id(0)
    rows_total = pl.num_programs(0)
    c = pages_per_chunk
    chunk_tokens = c * page_size
    granule = min(_GRANULE, c)
    slots = buf.shape[0]

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

    def row_links(row):
        return (row_pages(row) + c - 1) // c

    def live_row_from(row):
        """The first row at or after ``row`` that holds anything (pad
        rows issue nothing); ``rows_total`` where there is none."""
        last = rows_total - 1
        return jax.lax.while_loop(
            lambda r: (r < rows_total)
            & (kv_lens_ref[jnp.minimum(r, last)] == 0),
            lambda r: r + 1, row)

    # The links of all rows are one sequence, and the copies run
    # ``slots - 1`` links ahead of the products, across rows: link g
    # lands in slot ``g % slots``. walk_ref[0]: links folded by every
    # row before this one; walk_ref[1], [2]: the row and the link of
    # the next one to start; walk_ref[3]: links started so far.
    def start_next_link():
        row = walk_ref[1]

        @pl.when(row < rows_total)
        def _start():
            link_idx = walk_ref[2]
            started = walk_ref[3]
            issue(row, started % slots, link_idx)
            walk_ref[3] = started + 1
            more = link_idx + 1 < row_links(row)
            walk_ref[2] = jnp.where(more, link_idx + 1, 0)

            @pl.when(jnp.logical_not(more))
            def _next_row():
                walk_ref[1] = live_row_from(row + 1)

    @pl.when(b == 0)
    def _first_row():
        # A lane no copy has filled must hold no NaN: a masked weight
        # of exactly 0 times it would poison the values' product.
        buf[...] = jnp.zeros_like(buf)
        walk_ref[0] = 0
        walk_ref[1] = live_row_from(0)
        walk_ref[2] = 0
        walk_ref[3] = 0
        for _ in range(slots - 1):
            start_next_link()

    kv_len = kv_lens_ref[b]
    num_chunks = row_links(b)
    walked = walk_ref[0]

    q = q_ref[0]  # [rows, rank + rope], the cache's dtype
    # A scale that is a power of two goes into the query once a row:
    # the products and their float32 sums are then the scaled ones bit
    # for bit. Any other scale would round the query a second time, so
    # it stays on the float32 scores.
    scale_in_query = math.frexp(scale)[0] == 0.5
    if scale_in_query:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def scores_of(keys, contract):
        scores = jax.lax.dot_general(
            q, keys, dimension_numbers=(((1,), (contract,)), ((), ())),
            preferred_element_type=jnp.float32)
        return scores if scale_in_query else scores * scale

    def fold(scores, weighted):
        """One link into the row's running softmax: masked float32
        ``scores [rows, K]`` and ``weighted(probs)``, the values'
        product ``[rows, rank]`` of the link's ``K`` keys."""
        m_prev = m_ref[...]
        m_new = jnp.maximum(
            m_prev, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(scores - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(
            probs, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + weighted(probs)
        m_ref[...] = m_new

    if positions:
        # The burst's tail is the walk's first link and sets the
        # running state: slot s holds position ``kv_len + s``, and the
        # rows of band t see the slots up to their own position (a
        # row's own slot among them, so its maximum is a real score).
        tail = tail_ref[0]  # [S, rank + rope]
        scores = scores_of(tail, 1)  # [rows, S]
        row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        slot_pos = kv_len + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        limit = q_pos_ref[b * positions + positions - 1]
        for t in reversed(range(positions - 1)):
            limit = jnp.where(row < (t + 1) * heads,
                              q_pos_ref[b * positions + t], limit)
        scores = jnp.where(slot_pos <= limit, scores, NEG_INF)
        m_tail = jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores - m_tail)
        m_ref[...] = m_tail
        l_ref[...] = jnp.sum(probs, axis=-1, keepdims=True)
        acc_ref[...] = jnp.dot(probs.astype(tail.dtype), tail[:, :rank],
                               preferred_element_type=jnp.float32)
    else:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def advance(chunk_idx):
        """Start the link ``slots - 1`` ahead of this one, then wait
        for this one's pages; the slot they are in."""
        start_next_link()
        slot = (walked + chunk_idx) % slots
        wait(b, slot, chunk_idx)
        return slot

    def fold_pages(slot, pages, first_token=None):
        """The link's first ``pages`` pages (static) into the running
        softmax; with ``first_token`` (the link's first position) the
        lanes from ``kv_len`` on are masked."""
        tokens = pages * page_size
        lat = buf[slot, 0, :, 0:tokens]  # [rank + rope, tokens]
        scores = scores_of(lat, 0)
        if first_token is not None:
            token_pos = first_token + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1)
            scores = jnp.where(token_pos < kv_len, scores, NEG_INF)
        # The values are the latent's own first ``rank`` rows.
        fold(scores, lambda probs: jax.lax.dot_general(
            probs.astype(lat.dtype), lat[:rank],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))

    # Every link but the row's last is full by construction: no mask.
    def full_link(chunk_idx, carry):
        fold_pages(advance(chunk_idx), c)
        return carry
    jax.lax.fori_loop(0, num_chunks - 1, full_link, 0)

    # The last link holds 1 to c pages: the products run over the
    # fewest granules that hold them, one static width each, so the
    # matrix unit's waste on masked lanes is under one granule a row.
    @pl.when(num_chunks > 0)
    def _last_link():
        chunk_idx = num_chunks - 1
        slot = advance(chunk_idx)
        held = row_pages(b) - chunk_idx * c
        widths = [min(c, k * granule)
                  for k in range(1, -(-c // granule) + 1)]
        for narrower, pages in zip([0] + widths, widths):
            pl.when((held > narrower) & (held <= pages))(
                functools.partial(fold_pages, slot, pages,
                                  chunk_idx * chunk_tokens))

    walk_ref[0] = walked + num_chunks

    out_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                  ).astype(out_ref.dtype)


def _walk(q: jnp.ndarray, plane: jnp.ndarray, page_table: jnp.ndarray,
          kv_lens: jnp.ndarray, w_uk: jnp.ndarray, w_uv: jnp.ndarray,
          scale: float, tail, q_positions, interpret: bool) -> jnp.ndarray:
    """The kernel over ``q [B, T, n, dn + dr]``: the ``T x n`` absorbed
    query rows of a batch row against its tail, where there is one,
    and then its pages in ONE walk; the normalised weighted
    latents ``[B, rows, rank]`` (rows padded to the sublane tile) come
    back in ``q``'s dtype and are up-projected a head here."""
    if (tail is None) != (q_positions is None):
        raise ValueError(
            "a burst tail and the queries' positions go together "
            f"(tail given: {tail is not None}, q_positions given: "
            f"{q_positions is not None})")
    b, t, n, _ = q.shape
    dn, rank = w_uk.shape[1], w_uk.shape[2]
    _, _, width, page_size = plane.shape
    qa = absorb_queries(q[..., :dn], q[..., dn:], w_uk).reshape(
        b, t * n, width)
    rows = tile_pad(t * n, _ROW_TILE)
    c = latent_pages_per_chunk(width, page_size, plane.dtype.itemsize,
                               page_table.shape[1])

    def row_block(sublanes, lanes):
        return pl.BlockSpec((1, sublanes, lanes),
                            lambda bi, *scalars: (bi, 0, 0))

    scalars = [page_table, kv_lens]
    operands = [jnp.pad(qa.astype(plane.dtype),
                        ((0, 0), (0, rows - t * n), (0, 0)))]
    in_specs = [row_block(rows, width)]
    if tail is not None:
        # A pad slot's position is past any query's, so it is masked.
        slots = tile_pad(tail.shape[1], _ROW_TILE)
        scalars.append(q_positions.reshape(b * t).astype(jnp.int32))
        operands.append(jnp.pad(
            tail[:, :, 0].astype(plane.dtype),
            ((0, 0), (0, slots - tail.shape[1]), (0, 0))))
        in_specs.append(row_block(slots, width))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b,),
        # The plane stays in HBM; the kernel DMAs pages itself.
        in_specs=in_specs + [hbm_block_spec()],
        out_specs=row_block(rows, rank),
        scratch_shapes=[
            pltpu.VMEM((_SLOTS, 1, width, c * page_size), plane.dtype),
            pltpu.VMEM((rows, 1), jnp.float32),  # m
            pltpu.VMEM((rows, 1), jnp.float32),  # l
            pltpu.VMEM((rows, rank), jnp.float32),  # acc
            pltpu.SMEM((4,), jnp.int32),  # the walk's counters
            pltpu.SemaphoreType.DMA((_SLOTS, c)),  # [slot, page]
        ],
    )
    o_lat = pl.pallas_call(
        functools.partial(
            _latent_decode_kernel, page_size=page_size,
            pages_per_chunk=c, rank=rank,
            scale=scale, heads=n,
            positions=0 if tail is None else t),
        out_shape=jax.ShapeDtypeStruct((b, rows, rank), q.dtype),
        grid_spec=grid_spec,
        # The rows run in order: the copies run ahead across rows,
        # and the walk's counters ride the scratch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*scalars, *operands, plane)
    return up_project_values(
        o_lat[:, :t * n].reshape(b, t, n, rank), w_uv)


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
                  the walk's first link; needs ``q_positions`` [B]

    Returns [B, n, dv] in q's dtype. The plane is read and never
    written.
    """
    return _walk(q[:, None], plane, page_table, kv_lens, w_uk, w_uv,
                 scale, tail,
                 None if q_positions is None else q_positions[:, None],
                 interpret)[:, 0]


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
    query's rows of ONE walk of the row's pages; the causal cut
    between the positions lies in the tail, where the rows of
    position ``t`` (a static band of ``n``) see the slots up to their
    own.

    Args as the decode form's, but ``q [B, T, n, dn + dr]`` and
    ``q_positions [B, T]``; the tail is required (without one the
    positions' own latents would be in the pages, under a causal cut
    the walk does not make). Returns ``[B, T, n, dv]``.
    """
    return _walk(q, plane, page_table, kv_lens, w_uk, w_uv, scale, tail,
                 q_positions, interpret)
