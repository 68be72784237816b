"""Sliding-window attention whose K/V is a ring a sequence, not pages.

A windowed layer's query at position ``i`` sees key ``j`` iff
``i - window < j <= i``: ``window`` keys with its own, for ever. Its
K/V therefore needs ``window`` places a sequence whatever the row's
length, and lives in the state pool's slots (engine/kv_cache.py), one
ring a slot, laid out as the paged planes are: the ring pool is
``[kv_heads, slots, head_dim, window]``, a plane whose pages are the
slots and whose page size is the window, so that a slot's ring is a
page whose table has one entry (``slots[:, None]``) and the paged
writers and kernels serve it as they serve pages.

Token ``p`` lives at place ``p mod window``; K goes in rotated, so the
softmax does not care about the ring's order. All the mask must know
is what each place holds (``ring_positions``): with ``n`` tokens in
the ring's row, place ``j`` holds the newest position ``p <= n - 1``
with ``p = j (mod window)``, or nothing yet while ``n <= j``.

One call attends the ring as it stood BEFORE the call's own tokens and
the call's tokens themselves (``k_new``/``v_new``: a prefill chunk, a
decode step's one token, or the tail of a deferred-write burst), in
one softmax; ``write_to_ring`` then puts the newest ``window`` of them
in their places.

``window_attention`` is the XLA form, the ground truth and what a
decode step runs: on the chip it took 117 us a call at 128 rows where
the paged decode kernel over one page a row took 186 (PERF.md section
6, PR 50), so that kernel has no window term. ``window_prefill_pallas``
hands a chunk's contract to the paged prefill kernel
(ops/prefill_attention_pallas.py) under its window term.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from production_stack_tpu.ops.attention import NEG_INF, write_to_pages


def ring_positions(ring_len: jnp.ndarray, window: int) -> jnp.ndarray:
    """[B, window] int32: the position each place of a row's ring
    holds when the row has ``ring_len [B]`` tokens: the newest ``p <=
    ring_len - 1`` with ``p = j (mod window)``; negative where the
    place holds nothing yet."""
    newest = ring_len[:, None] - 1
    place = jnp.arange(window, dtype=ring_len.dtype)[None, :]
    return newest - (newest - place) % window


def in_window(key_positions: jnp.ndarray, q_positions: jnp.ndarray,
              window: int) -> jnp.ndarray:
    """[B, T, S] bool: key ``s`` of row ``b`` (``key_positions [B,
    S]``, negative = no key) is in sight of query ``t``
    (``q_positions [B, T]``)."""
    key = key_positions[:, None, :]
    q = q_positions[:, :, None]
    return (key >= 0) & (key <= q) & (key > q - window)


def write_to_ring(ring: jnp.ndarray, new_kv: jnp.ndarray,
                  slots: jnp.ndarray, positions: jnp.ndarray,
                  valid: jnp.ndarray, row_len: jnp.ndarray) -> jnp.ndarray:
    """The newest ``window`` of a call's tokens into their places.

    ``ring [kv, slots, d, window]``, ``new_kv [B, S, kv, d]`` at
    ``positions [B, S]``; ``row_len [B]`` is the row's length after
    the call. A token older than the window's reach (``position <
    row_len - window``: the head of a chunk longer than the window)
    is not written, so no place is written twice; what is not valid
    goes to the trash slot 0."""
    window = ring.shape[-1]
    keep = valid & (positions >= row_len[:, None] - window)
    return write_to_pages(ring, new_kv, slots[:, None],
                          positions % window, keep)


def window_attention(q, k_ring, v_ring, slots, ring_len, q_positions,
                     k_new, v_new, new_positions, new_valid):
    """Windowed attention over a ring and the call's own tokens (XLA).

    Args:
      q:            [B, T, q_heads, d]
      k_ring/v_ring: [kv, slots, d, window] ring pools
      slots:        [B] each row's slot
      ring_len:     [B] tokens the row held before this call
      q_positions:  [B, T]
      k_new/v_new:  [B, S, kv, d] the call's own tokens (S = T for a
                    chunk or a step; a burst's tail otherwise)
      new_positions: [B, S]; new_valid [B, S] bool

    Returns (out [B, T, q_heads, d], keys [B, T] int32: how many ring
    places and own tokens each query had in sight).
    """
    b, t, num_q_heads, head_dim = q.shape
    num_kv_heads, _, _, window = k_ring.shape
    group = num_q_heads // num_kv_heads
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    qg = q.reshape(b, t, num_kv_heads, group, head_dim)
    rk, rv = k_ring[:, slots], v_ring[:, slots]     # [kv, B, d, W]
    ring_scores = jnp.einsum("btkgd,kbdw->bkgtw", qg, rk,
                             preferred_element_type=jnp.float32)
    new_scores = jnp.einsum("btkgd,bskd->bkgts", qg, k_new,
                            preferred_element_type=jnp.float32)
    mask = jnp.concatenate([
        in_window(ring_positions(ring_len, window), q_positions, window),
        in_window(jnp.where(new_valid, new_positions, -1), q_positions,
                  window)], axis=-1)                 # [B, T, W + S]
    scores = jnp.concatenate([ring_scores, new_scores], axis=-1) * scale
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = (jnp.einsum("bkgtw,kbdw->btkgd",
                      probs[..., :window].astype(rv.dtype), rv,
                      preferred_element_type=jnp.float32)
           + jnp.einsum("bkgts,bskd->btkgd",
                        probs[..., window:].astype(v_new.dtype), v_new,
                        preferred_element_type=jnp.float32))
    return (out.reshape(b, t, num_q_heads, head_dim).astype(q.dtype),
            jnp.sum(mask, axis=-1, dtype=jnp.int32))


def _in_position_order(ring_rows: jnp.ndarray, start: jnp.ndarray):
    """[kv, B, d, W] rings to position order: place ``(start + r) mod
    W`` of row ``b`` comes to index ``r``, so that index ``r`` holds
    position ``start - W + r`` (junk where that is negative)."""
    window = ring_rows.shape[-1]
    doubled = jnp.concatenate([ring_rows, ring_rows], axis=-1)
    rows = jax.vmap(
        lambda row, first: jax.lax.dynamic_slice_in_dim(
            row, first, window, axis=-1),
        in_axes=(1, 0), out_axes=1)
    return rows(doubled, start % window)


def window_prefill_pallas(q, k_ring, v_ring, slots, ring_len, k_new,
                          v_new, row_len, interpret: bool = False):
    """``window_attention`` for a prefill chunk (positions contiguous
    from ``ring_len``) through the paged prefill kernel: each row's
    ring, turned to position order, and its chunk become a plane of
    the step's own, ``1 + ceil(T / window)`` pages a row, in
    coordinates relative to ``ring_len - window``; the kernel's
    window term masks by position and by the first key that exists.

    q [B, T, q_heads, d]; k_new/v_new [B, T, kv, d]; ``row_len [B]``
    the row's length after the chunk. Returns out [B, T, q_heads, d].
    """
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    b, t = q.shape[:2]
    num_kv_heads, _, head_dim, window = k_ring.shape
    chunk_pages = -(-t // window)

    def plane(ring, new):
        held = _in_position_order(ring[:, slots], ring_len)
        new = jnp.pad(new.transpose(2, 0, 3, 1),       # [kv, B, d, T]
                      ((0, 0),) * 3 + ((0, chunk_pages * window - t),))
        new = new.reshape(num_kv_heads, b, head_dim, chunk_pages,
                          window).transpose(0, 1, 3, 2, 4)
        return jnp.concatenate([held[:, :, None], new], axis=2).reshape(
            num_kv_heads, b * (1 + chunk_pages), head_dim, window)

    table = jnp.arange(b * (1 + chunk_pages), dtype=jnp.int32).reshape(
        b, 1 + chunk_pages)
    rel = jnp.broadcast_to(
        window + jnp.arange(t, dtype=jnp.int32)[None, :], (b, t))
    return paged_prefill_attention(
        q, plane(k_ring, k_new), plane(v_ring, v_new), table, rel,
        (window + row_len - ring_len).astype(jnp.int32),
        window=window,
        first_key=jnp.maximum(window - ring_len, 0).astype(jnp.int32),
        interpret=interpret)
