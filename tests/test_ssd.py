"""The Mamba-2 (state-space dual) recurrence of ``ops/ssd.py`` and its
decode kernel over the pool (``ops/ssd_pallas.py``), against a plain
token loop written here from the published equations in the published
layout (``h [heads, d_head, d_state]``), so that the layout the ops
keep (``[d_state, heads * d_head]``) is tested and not assumed.

Tiny widths, float32, seeded, on the CPU. ``FLOAT32`` 2e-5: both sides
are float32 on one CPU and differ in the order of sums (a chunk's
matrix form against a token at a time); the readings are under 3e-6.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops import ssd
from production_stack_tpu.ops.ssd_pallas import ssd_decode

FLOAT32 = 2e-5
HEADS, D_HEAD, D_STATE = 4, 32, 16


def inputs(rng, rows, tokens):
    f32 = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)  # noqa
    return (f32(rows, tokens, HEADS, D_HEAD),
            jax.nn.softplus(f32(rows, tokens, HEADS) - 1.0),
            -jnp.exp(f32(HEADS)),
            f32(rows, tokens, D_STATE), f32(rows, tokens, D_STATE))


def token_loop(x, dt, a, b, c, h):
    """The recurrence as published, one sequence: x [T, H, P], dt
    [T, H], a [H], b, c [T, N], h [H, P, N]. Returns (y [T, H, P], h)."""
    ys = []
    for t in range(x.shape[0]):
        decay = np.exp(dt[t] * a)[:, None, None]
        h = decay * h + (dt[t][:, None] * x[t])[:, :, None] * b[t]
        ys.append(h @ c[t])
    return np.stack(ys), h


def published(h):
    """The ops' ``[N, H * P]`` as the published ``[H, P, N]``."""
    h = np.asarray(h)
    return h.reshape(h.shape[0], HEADS, D_HEAD).transpose(1, 2, 0)


def kept(h):
    """The published ``[H, P, N]`` as the ops keep it."""
    return jnp.asarray(np.asarray(h).transpose(2, 0, 1).reshape(
        D_STATE, HEADS * D_HEAD))


@pytest.mark.parametrize("tokens,chunk", [
    (1, 8), (7, 8), (8, 8), (19, 8), (24, 8), (33, 16), (20, 64)])
def test_the_chunked_form_equals_the_token_loop(tokens, chunk):
    """One chunk, a chunk's edge, several chunks and a ragged last one,
    from a state that is not zero."""
    rng = np.random.RandomState(tokens)
    x, dt, a, b, c = inputs(rng, 2, tokens)
    h0 = jnp.asarray(rng.randn(2, D_STATE, HEADS * D_HEAD), jnp.float32)
    got, state = ssd.ssd_chunked(x, dt, a, b, c, h0, chunk)
    for row in range(2):
        want, want_h = token_loop(*(np.asarray(v[row])
                                    for v in (x, dt)), np.asarray(a),
                                  np.asarray(b[row]), np.asarray(c[row]),
                                  published(h0[row]))
        assert np.abs(got[row] - want).max() < FLOAT32
        assert np.abs(published(state[row]) - want_h).max() < FLOAT32


@pytest.mark.parametrize("tokens", [1, 5, 12])
def test_the_step_form_equals_the_chunked_form(tokens):
    rng = np.random.RandomState(1)
    x, dt, a, b, c = inputs(rng, 3, tokens)
    h0 = jnp.asarray(rng.randn(3, D_STATE, HEADS * D_HEAD), jnp.float32)
    want, want_h = ssd.ssd_chunked(x, dt, a, b, c, h0, 4)
    h = h0
    for t in range(tokens):
        y, h = ssd.ssd_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], h)
        assert np.abs(y - want[:, t]).max() < FLOAT32
    assert np.abs(h - want_h).max() < FLOAT32


def test_the_state_is_carried_from_one_call_to_the_next():
    """A prompt in two prefill steps (13 + 11 tokens, neither a
    multiple of the chunk), tokens that are not real (dt 0) padded
    behind each, equals the prompt in one."""
    rng = np.random.RandomState(2)
    x, dt, a, b, c = inputs(rng, 2, 24)
    zero = jnp.zeros((2, D_STATE, HEADS * D_HEAD))
    want, want_h = ssd.ssd_chunked(x, dt, a, b, c, zero, 8)

    def padded(lo, hi):
        pad = lambda v: jnp.pad(  # noqa: E731
            v[:, lo:hi], ((0, 0), (0, 16 - (hi - lo)))
            + ((0, 0),) * (v.ndim - 2))
        # What a padded token carries is anything: only dt says it is
        # not real.
        junk = lambda v: pad(v).at[:, hi - lo:].set(7.0)  # noqa: E731
        return junk(x), pad(dt), junk(b), junk(c)

    first = padded(0, 13)
    y1, h = ssd.ssd_chunked(first[0], first[1], a, first[2], first[3],
                            zero, 8)
    second = padded(13, 24)
    y2, h = ssd.ssd_chunked(second[0], second[1], a, second[2],
                            second[3], h, 8)
    assert np.abs(y1[:, :13] - want[:, :13]).max() < FLOAT32
    assert np.abs(y2[:, :11] - want[:, 13:]).max() < FLOAT32
    assert np.abs(h - want_h).max() < FLOAT32


def test_keep_and_a_row_with_dt_zero():
    """``keep`` 0 starts a row from zero whatever ``h`` holds; a row
    whose ``dt`` is 0 writes back what it read, to the bit."""
    rng = np.random.RandomState(3)
    x, dt, a, b, c = (v[:, 0] if v.ndim > 1 else v
                      for v in inputs(rng, 3, 1))
    dt = dt.at[1].set(0.0)
    h0 = jnp.asarray(rng.randn(3, D_STATE, HEADS * D_HEAD), jnp.float32)
    keep = jnp.array([1.0, 1.0, 0.0])
    y, h = ssd.ssd_step(x, dt, a, b, c, h0, keep=keep)
    assert np.array_equal(h[1], h0[1])
    fresh_y, fresh_h = ssd.ssd_step(x, dt, a, b, c, jnp.zeros_like(h0))
    assert np.array_equal(h[2], fresh_h[2])
    assert np.array_equal(y[2], fresh_y[2])
    plain_y, plain_h = ssd.ssd_step(x, dt, a, b, c, h0)
    assert np.array_equal(h[0], plain_h[0])
    assert np.array_equal(y[0], plain_y[0])
    assert not np.array_equal(h[2], plain_h[2])
    # The published layout's outer product, for the fresh row.
    want = (np.asarray(dt[2])[:, None] * np.asarray(x[2]))[:, :, None] \
        * np.asarray(b[2])
    assert np.abs(published(h[2]) - want).max() < FLOAT32
    assert np.array_equal(np.asarray(kept(published(h[2]))),
                          np.asarray(h[2]))


def test_the_decode_kernel_over_the_pool_equals_gather_step_scatter():
    """The Pallas kernel (interpret mode) reads each row's h from its
    slot, advances it and writes it back in place; against the XLA
    step. Two padded rows share the trash slot 0 and leave it as it
    was, to the bit; a row that starts at position 0 starts from
    zero; nobody's slot is untouched. 2048 channels: two strips of the
    kernel's walk."""
    rng = np.random.RandomState(0)
    rows, heads, d_head, n = 5, 32, 64, 16
    f32 = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)  # noqa
    x, b, c = f32(rows, heads, d_head), f32(rows, n), f32(rows, n)
    padded = jnp.array([False, True, False, False, True])[:, None]
    dt = jnp.where(padded, 0.0, jax.nn.softplus(f32(rows, heads) - 1.0))
    a = -jnp.exp(f32(heads))
    pool = f32(8, n, heads * d_head)
    slots = jnp.array([3, 0, 5, 1, 0])
    keep = jnp.array([1.0, 1.0, 0.0, 1.0, 1.0])
    want_y, state = ssd.ssd_step(x, dt, a, b, c, pool[slots], keep=keep)
    got_y, got_pool = ssd_decode(x, dt, a, b, c, pool, slots, keep,
                                 interpret=True)
    assert np.abs(got_y - want_y).max() < FLOAT32
    assert np.abs(got_pool - pool.at[slots].set(state)).max() < FLOAT32
    for untouched in (0, 2, 4, 6, 7):
        assert np.array_equal(got_pool[untouched], pool[untouched])
    # A fresh row's result does not depend on what its slot held.
    dx = (dt[2][:, None] * x[2]).reshape(-1)
    assert np.array_equal(got_pool[5], b[2][:, None] * dx[None, :])


def test_the_decode_kernel_lowers_for_the_tpu_at_the_published_widths():
    """Mosaic's rules on tiling and block shapes run while lowering:
    128 rows over 137 slots, a row's h 4 194 304 B."""
    f32, i32 = jnp.float32, jnp.int32
    rows, heads, d_head, n, slots = 128, 128, 64, 128, 137
    shapes = (((rows, heads, d_head), f32), ((rows, heads), f32),
              ((heads,), f32), ((rows, n), f32), ((rows, n), f32),
              ((slots, n, heads * d_head), f32), ((rows,), i32),
              ((rows,), f32))
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes]
    text = jax.jit(ssd_decode).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
