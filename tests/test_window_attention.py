"""Sliding-window attention over a ring (ops/window_attention.py): the
XLA form and the paged prefill kernel under its window term (interpret
mode) against a dense softmax over the keys ``i - window < j <= i`` of
the whole sequence, through chunked prefill (a chunk longer than,
equal to and shorter than the window; rows that end inside a chunk; a
row shorter than the window) and a deferred burst whose tail crosses
the ring's edge, and the flush that wraps.

float32 on the CPU; ``CLOSE`` 2e-5: one softmax in another order of
sums (readings under 2e-6).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops import window_attention as wa
from production_stack_tpu.ops.attention import write_to_tail

CLOSE = 2e-5
W, KV, HEADS, D, ROWS, LONGEST = 128, 2, 4, 128, 3, 700
LENGTHS = (600, 300, 97)            # the last is shorter than the window
SLOTS = (3, 1, 4)                   # of five; slot 0 is the trash


def dense(q, keys, values, positions):
    """q [T, heads, d] at ``positions`` against the whole sequence's
    keys [N, kv, d]: a softmax over the window that ends at each."""
    out = np.zeros(q.shape, np.float32)
    group = q.shape[1] // keys.shape[1]
    for t, i in enumerate(positions):
        lo = max(0, i - W + 1)
        for h in range(q.shape[1]):
            scores = keys[lo:i + 1, h // group] @ q[t, h] / np.sqrt(D)
            p = np.exp(scores - scores.max())
            out[t, h] = (p / p.sum()) @ values[lo:i + 1, h // group]
    return out


@pytest.fixture(scope="module")
def sequences():
    rng = np.random.default_rng(0)
    draw = lambda heads: rng.standard_normal(  # noqa: E731
        (ROWS, LONGEST, heads, D)).astype(np.float32)
    return draw(HEADS), draw(KV), draw(KV)


def prefilled(sequences, chunk, check=None):
    """The rings after every row's prompt went through in chunks of
    ``chunk``; ``check(out_xla, out_pallas, keys, q, positions, n)`` is
    called a chunk."""
    queries, keys, values = sequences
    k_ring = jnp.zeros((KV, 5, D, W))
    v_ring = jnp.zeros((KV, 5, D, W))
    slots = jnp.asarray(SLOTS)
    start = np.zeros(ROWS, int)
    while (start < np.asarray(LENGTHS)).any():
        n = np.clip(np.asarray(LENGTHS) - start, 0, chunk)
        valid = np.arange(chunk)[None] < n[:, None]
        at = start[:, None] + np.arange(chunk)[None]
        positions = jnp.asarray(np.where(valid, at, 0), jnp.int32)
        take = lambda a: jnp.asarray(np.take_along_axis(  # noqa: E731
            a, np.clip(at, 0, LONGEST - 1)[:, :, None, None], 1))
        q, k, v = take(queries), take(keys), take(values)
        before = jnp.asarray(start, jnp.int32)
        after = jnp.asarray(start + n, jnp.int32)
        if check is not None:
            out, seen = wa.window_attention(
                q, k_ring, v_ring, slots, before, positions, k, v,
                positions, jnp.asarray(valid))
            kernel = wa.window_prefill_pallas(
                q, k_ring, v_ring, slots, before, k, v, after,
                interpret=True)
            check(out, kernel, seen, q, np.asarray(positions), n)
        k_ring = wa.write_to_ring(k_ring, k, slots, positions,
                                  jnp.asarray(valid), after)
        v_ring = wa.write_to_ring(v_ring, v, slots, positions,
                                  jnp.asarray(valid), after)
        start = start + n
    return k_ring, v_ring


@pytest.mark.parametrize("chunk", [256, 128, 48])
def test_a_chunk_reads_the_ring_the_chunk_before_wrote(sequences, chunk):
    _, keys, values = sequences
    worst = {"xla": 0.0, "kernel": 0.0}

    def check(out, kernel, seen, q, positions, n):
        for row in range(ROWS):
            if not n[row]:
                continue
            real = slice(0, n[row])
            want = dense(np.asarray(q[row, real]), keys[row], values[row],
                         positions[row, real])
            worst["xla"] = max(worst["xla"], np.abs(
                np.asarray(out[row, real]) - want).max())
            worst["kernel"] = max(worst["kernel"], np.abs(
                np.asarray(kernel[row, real]) - want).max())
            # A window's keys, or the row's while it is shorter.
            assert (np.asarray(seen[row, real])
                    == np.minimum(positions[row, real] + 1, W)).all()

    k_ring, _ = prefilled(sequences, chunk, check)
    assert worst["xla"] < CLOSE and worst["kernel"] < CLOSE
    # Token p lives at place p mod window; nobody's slots stay empty.
    for row, (length, slot) in enumerate(zip(LENGTHS, SLOTS)):
        for p in range(max(0, length - W), length):
            assert np.array_equal(np.asarray(k_ring[:, slot, :, p % W]),
                                  keys[row, p])
    assert float(jnp.abs(k_ring[:, 2]).max()) == 0.0


def test_a_burst_crosses_the_rings_edge_and_its_flush_wraps(sequences):
    """32 steps from lengths 600, 300 and 97: the second row's tail
    runs over place 127 to place 0, the third's passes the window's
    length. Every step a query sees a window's keys, fewer of them in
    the ring as the tail grows."""
    queries, keys, values = sequences
    k_ring, v_ring = prefilled(sequences, 128)
    slots, steps = jnp.asarray(SLOTS), 32
    held = jnp.asarray(LENGTHS, jnp.int32)
    k_tail = jnp.zeros((ROWS, steps, KV, D))
    v_tail = jnp.zeros((ROWS, steps, KV, D))
    rows, every = np.arange(ROWS), jnp.ones(ROWS, bool)
    tail_at = held[:, None] + jnp.arange(steps)[None]
    for s in range(steps):
        at = np.asarray(LENGTHS) + s
        q = jnp.asarray(queries[rows, at])[:, None]
        slot = jnp.full((ROWS,), s)
        k_tail = write_to_tail(k_tail, jnp.asarray(keys[rows, at])[:, None],
                               slot, every)
        v_tail = write_to_tail(
            v_tail, jnp.asarray(values[rows, at])[:, None], slot, every)
        positions = jnp.asarray(at, jnp.int32)[:, None]
        out, seen = wa.window_attention(
            q, k_ring, v_ring, slots, held, positions, k_tail, v_tail,
            tail_at, tail_at <= positions)
        for row in range(ROWS):
            want = dense(np.asarray(q[row]), keys[row], values[row],
                         [at[row]])
            assert np.abs(np.asarray(out[row]) - want).max() < CLOSE
            assert int(seen[row, 0]) == min(at[row] + 1, W)
    count = jnp.asarray([32, 20, 32])       # the second row stopped early
    k_ring = wa.write_to_ring(
        k_ring, k_tail, slots, tail_at,
        jnp.arange(steps)[None] < count[:, None], held + count)
    for row, (length, slot) in enumerate(zip(LENGTHS, SLOTS)):
        end = length + int(count[row])
        for p in range(end - W, end):
            assert np.array_equal(np.asarray(k_ring[:, slot, :, p % W]),
                                  keys[row, p])


def test_the_prefill_kernels_window_comes_with_its_first_key():
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    ring = jnp.zeros((KV, 5, D, W))
    lens = jnp.array([5, 9], jnp.int32)
    with pytest.raises(ValueError, match="go together"):
        paged_prefill_attention(
            jnp.zeros((2, 16, HEADS, D)), ring, ring,
            jnp.zeros((2, 2), jnp.int32), jnp.zeros((2, 16), jnp.int32),
            lens, window=W, interpret=True)
