"""Latent attention (MLA) over a paged latent cache: the XLA forms.

A sublayer caches per token one latent ``c`` of ``rank`` values and one
rotary key ``k_rope`` every head shares, side by side in ONE plane
``[1, num_pages, rank + rope, page_size]`` (token-minor pages, as the
K/V planes of ops/attention.py); there is no second plane. With the
up-projection split a head, ``W_kvb = [W_UK_h | W_UV_h]``, the scores
of head ``h`` are ``(q_nope_h W_UK_h^T) . c_s + q_rope_h . k_rope_s``
and its output ``(sum_s p_s c_s) W_UV_h``: the *absorbed* form, in
which the 64 heads read the one 576-wide key a token and its first
``rank`` rows are the values. ``latent_paged_attention`` is that form
over the pages a block at a time (``ops/attention.py``'s block loop and
running softmax), for a decode step (T = 1, with the burst's latent
tail where given) and for a prefill chunk. Cached tokens are never
expanded to per-head keys and values (35 times the bytes). The
*materialised* form of a prefill chunk (up-project each gathered block
and contract heads of ``dn + dr`` and ``dv``: the same numbers, 3.4
times fewer operations a pair) read the same time a whole prefill step
on a v5e, 188.7 ms against 188.6 (PERF.md section 6, PR 41: the
softmax's passes over the block's scores take the time in either), so
the one form is all there is.

The Pallas form of the decode step is ops/mla_attention_pallas.py;
this module is the ground truth it is tested against, and what that
path still calls of it is ``absorb_queries`` before the kernel and
``up_project_values`` after it: since PR 45 the kernel folds the
burst's tail into its own running softmax and normalises, so
``latent_tail_state`` serves this module's ``latent_paged_attention``
alone (the prefill path and the tests' reference). Neither form is in
``ops/attention.py`` ``ATTENTION_IMPLS``: that registry lists the forms
that read K/V planes and must have an int8 (``QuantKV``) parity test,
and a latent plane has no quantized form (int8 pages are refused for
the family at start-up, ``engine/config.py`` ``_latent_cache_refusals``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from production_stack_tpu.ops.attention import (
    NEG_INF,
    block_pages,
    gathered_blocks,
)


def absorb_queries(q_nope: jnp.ndarray, q_rope: jnp.ndarray,
                   w_uk: jnp.ndarray) -> jnp.ndarray:
    """[..., n, dn] and [..., n, dr] -> [..., n, rank + dr]: each
    head's ``q_nope`` through its ``W_UK^T`` (``w_uk [n, dn, rank]``),
    beside its rotary part: the query the latent plane is read
    with."""
    # The product's sums are float32 on the matrix unit and the result
    # is rounded once, to the queries' dtype: no wider result is asked
    # for (a batch of heads with one is a form the CPU backend lacks).
    q_lat = jnp.einsum("...nd,ndr->...nr", q_nope, w_uk)
    return jnp.concatenate([q_lat, q_rope], axis=-1)


def latent_tail_state(q: jnp.ndarray, tail: jnp.ndarray,
                      q_positions: jnp.ndarray, kv_lens: jnp.ndarray,
                      scale: float, rank: int):
    """``tail_softmax_state`` for a latent tail: the softmax's running
    state of absorbed queries ``q [B, T, n, rank + dr]`` over the
    burst's un-flushed tokens ``tail [B, S, 1, rank + dr]`` (slot s at
    position ``kv_lens + s``). Returns the running maximum and sum
    ``[B, n, T]`` and the weighted latents ``[B, n, T, rank]``,
    float32."""
    lat = tail[:, :, 0]  # [B, S, W]
    scores = jnp.einsum("btnw,bsw->bnts", q, lat,
                        preferred_element_type=jnp.float32) * scale
    tail_pos = kv_lens[:, None] + jnp.arange(lat.shape[1])[None, :]
    mask = tail_pos[:, None, :] <= q_positions[:, :, None]  # [B, T, S]
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    m = scores.max(axis=-1)
    probs = jnp.exp(scores - m[..., None])
    return (m, probs.sum(axis=-1), jnp.einsum(
        "bnts,bsr->bntr", probs.astype(lat.dtype), lat[..., :rank],
        preferred_element_type=jnp.float32))


def latent_paged_attention(q: jnp.ndarray, plane: jnp.ndarray,
                           page_table: jnp.ndarray,
                           q_positions: jnp.ndarray,
                           kv_lens: jnp.ndarray, w_uk: jnp.ndarray,
                           w_uv: jnp.ndarray, scale: float,
                           tail: "jnp.ndarray | None" = None
                           ) -> jnp.ndarray:
    """Causal latent attention of queries against a row's cached
    latents.

    Args:
      q:           [B, T, n, dn + dr], the rotary part already turned
      plane:       [1, num_pages, rank + dr, page_size]
      page_table:  [B, max_pages]; q_positions [B, T]; kv_lens [B]
      w_uk:        [n, dn, rank]; w_uv [n, rank, dv]
      scale:       the scores' scale, ``(dn + dr) ** -0.5``
      tail:        optional [B, S, 1, rank + dr] latent tail of a
                   deferred-write burst (slot s at ``kv_lens + s``)

    Returns [B, T, n, dv] in q's dtype.
    """
    b, t, n, _ = q.shape
    dn, rank = w_uk.shape[1], w_uk.shape[2]
    page = plane.shape[-1]
    max_pages = page_table.shape[1]
    block = block_pages(max_pages, page)
    page_table = jnp.pad(page_table, ((0, 0), (0, -max_pages % block)))
    qa = absorb_queries(q[..., :dn], q[..., dn:], w_uk)

    def add_block(i, carry):
        m, denom, acc = carry
        table = jax.lax.dynamic_slice_in_dim(page_table, i * block,
                                             block, axis=1)
        lat = plane[0][table]  # [B, P, W, page], the plane's own order
        scores = jnp.einsum("btnw,bpwc->bntpc", qa, lat,
                            preferred_element_type=jnp.float32) * scale
        token_pos = ((i * block + jnp.arange(block))[:, None] * page
                     + jnp.arange(page)[None, :])  # [P, page]
        mask = ((token_pos[None, None] <= q_positions[:, :, None, None])
                & (token_pos[None] < kv_lens[:, None, None])[:, None])
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=(-2, -1)))
        keep = jnp.exp(m - m_new)
        probs = jnp.exp(scores - m_new[..., None, None])
        denom = denom * keep + probs.sum(axis=(-2, -1))
        pv = jnp.einsum("bntpc,bprc->bntr", probs.astype(lat.dtype),
                        lat[:, :, :rank],
                        preferred_element_type=jnp.float32)
        return m_new, denom, acc * keep[..., None] + pv

    stat = (b, n, t)
    if tail is not None:
        carry = latent_tail_state(qa, tail, q_positions, kv_lens, scale,
                                  rank)
    else:
        carry = (jnp.full(stat, NEG_INF, jnp.float32),
                 jnp.zeros(stat, jnp.float32),
                 jnp.zeros((*stat, rank), jnp.float32))
    if max_pages <= block:
        carry = add_block(0, carry)
    else:
        carry = jax.lax.fori_loop(
            0, gathered_blocks(jnp.max(kv_lens), max_pages, page),
            add_block, carry)
    _, denom, acc = carry
    o_lat = acc / jnp.maximum(denom, 1e-30)[..., None]  # [B, n, T, rank]
    return up_project_values(o_lat.astype(q.dtype), w_uv, "bntr")


def up_project_values(o_lat: jnp.ndarray, w_uv: jnp.ndarray,
                      order: str = "btnr") -> jnp.ndarray:
    """The weighted latents of each head through its ``W_UV``
    (``[n, rank, dv]``): ``[B, T, n, dv]`` in ``o_lat``'s dtype, from
    ``o_lat`` in the axis order named."""
    return jnp.einsum(f"{order},nrv->btnv", o_lat, w_uv)
