"""The gated delta rule (Gated DeltaNet linear attention) and the short
causal convolution in front of it.

Per value head, with a state ``S`` [d_k, d_v] that starts at zero::

    S' = alpha_t * S_{t-1}
    S_t = S' + k_t (x) (beta_t * (v_t - S'^T k_t))
    o_t = S_t^T q_t

Two forms of the same recurrence. ``gated_delta_step`` advances one
token (a decode step): elementwise products and sums over ``S`` in
float32, no matrix unit, bound by reading and writing ``S``.
``gated_delta_chunked`` advances a block of tokens a chunk of 64 at a
time (a prefill chunk): inside a chunk the tokens' corrections solve
one unit-lower-triangular system (the WY form of the delta rule, as in
Yang et al., "Gated Delta Networks", 2024, and the published
``Qwen3NextGatedDeltaNet``), and only the chunk boundaries are
sequential. Both take and return the state, so a prompt's chunks and
a decode burst's steps carry it from one to the next.

A token that is not real (padding, a row that stopped) is made a
no-op: ``beta`` 0 (nothing is written) and log-decay 0 (nothing
fades). The state is float32 throughout, as the published recurrence
keeps it; every small product inside a chunk runs at the matrix
unit's highest precision, because at the default a float32 operand is
rounded to bfloat16 on a TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
CHUNK = 64


def causal_conv(x: jnp.ndarray, tail: jnp.ndarray, w: jnp.ndarray,
                num_valid: jnp.ndarray):
    """Depthwise causal convolution over a block with a carried tail.

    Args:
      x:    [B, T, C] this block's inputs
      tail: [B, K-1, C] the K-1 inputs before the block (zeros at the
            start of a sequence)
      w:    [K, C]; ``w[K-1]`` weighs the current token
      num_valid: [B] how many leading tokens of the block are real

    Returns (y [B, T, C] without activation, new tail [B, K-1, C]: the
    K-1 inputs before position ``num_valid``).
    """
    k = w.shape[0]
    t = x.shape[1]
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w32 = w.astype(jnp.float32)
    y = sum(xx[:, j:j + t].astype(jnp.float32) * w32[j]
            for j in range(k))
    new_tail = jax.vmap(
        lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, k - 1, 0)
    )(xx, num_valid)
    return y.astype(x.dtype), new_tail.astype(tail.dtype)


def causal_conv_step(x: jnp.ndarray, tail, w: jnp.ndarray,
                     live: jnp.ndarray):
    """``causal_conv`` for one token a row (a decode step), with the
    tail held as its K-1 rows, so that shifting it moves no row.

    Args:
      x:    [B, C] this step's inputs
      tail: K-1 arrays [B, C], the inputs before this one, oldest first
      w:    [K, C]; ``w[K-1]`` weighs the current token
      live: [B] bool, False for a row with no real token

    Returns (y [B, C] without activation, the new tail: shifted by one
    where the row is live, as it was where it is not). The products and
    the order of the sum are ``causal_conv``'s, so at T = 1 the two
    agree to the bit.
    """
    k = w.shape[0]
    rows = tuple(t.astype(x.dtype) for t in tail) + (x,)
    w32 = w.astype(jnp.float32)
    y = sum(rows[j].astype(jnp.float32) * w32[j] for j in range(k))
    new_tail = tuple(
        jnp.where(live[:, None], rows[j + 1], rows[j]).astype(tail[j].dtype)
        for j in range(k - 1))
    return y.astype(x.dtype), new_tail


def slot_causal_conv(x: jnp.ndarray, w: jnp.ndarray, fresh: jnp.ndarray,
                     valid: jnp.ndarray, slots: jnp.ndarray,
                     tail_pool: jnp.ndarray, conv_tail=None):
    """A recurrent layer's convolution over a block ``x [B, T, C]``
    whose tails are kept a sequence in ``tail_pool [slots, K-1, C]``.

    A row whose block starts its sequence (``fresh [B]``) starts from a
    zero tail whatever its slot holds; a row with no real token
    (``valid [B, T]``) leaves its tail as it was (its slot is the trash
    slot, or a sequence that stopped inside a burst).

    Without ``conv_tail`` every row's tail is gathered from the pool by
    ``slots [B]`` and scattered back: returns (y, the pool). With
    ``conv_tail`` (a deferred-write decode burst, T == 1: the rows' K-1
    held inputs, a ``[B, C]`` array each, oldest first, which the
    runner gathered before the burst and scatters back after it) those
    are read and shifted in one pass and the pool is neither read nor
    written: returns (y, the new held inputs).
    """
    live = valid[:, 0]
    if conv_tail is not None:
        y, conv_tail = causal_conv_step(
            x[:, 0], tuple(jnp.where(fresh[:, None], 0, row)
                           for row in conv_tail), w, live)
        return y[:, None], conv_tail
    held = tail_pool[slots]
    y, new_tail = causal_conv(
        x, jnp.where(fresh[:, None, None], 0, held), w,
        jnp.sum(valid, axis=1, dtype=jnp.int32))
    new_tail = jnp.where(live[:, None, None], new_tail, held)
    return y, tail_pool.at[slots].set(new_tail)


def l2_normalize(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_step(q, k, v, g, beta, state, keep=None):
    """One token a row.

    Args (float32): q, k [B, H, d_k] (normalised, q scaled);
      v [B, H, d_v]; g [B, H] log of the decay; beta [B, H];
      state [B, H, d_k, d_v]; keep [B], 0 for a row that starts from
      a zero state whatever ``state`` holds, else 1 (folded into the
      decay, so that it costs no pass over the state).
    Returns (o [B, H, d_v], new state).
    """
    decay = jnp.exp(g)
    if keep is not None:
        decay = decay * keep[:, None]
    state = state * decay[..., None, None]
    kv = jnp.sum(state * k[..., None], axis=-2)
    delta = (v - kv) * beta[..., None]
    state = state + k[..., None] * delta[..., None, :]
    o = jnp.sum(state * q[..., None], axis=-2)
    return o, state


def gated_delta_chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """A block of tokens a row, ``chunk`` at a time.

    Args (float32): q, k [B, T, H, d_k]; v [B, T, H, d_v];
      g, beta [B, T, H]; state [B, H, d_k, d_v].
    Returns (o [B, T, H, d_v], new state).
    """
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, t)
    pad = (-t) % c
    if pad:
        # Padded tokens are no-ops: beta 0 and log-decay 0.
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (t + pad) // c

    def blocks(a):  # [B, T, H, ...] -> [B, H, n, c, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = (blocks(a) for a in (q, k, v, g, beta))
    k_beta = k * beta[..., None]
    v_beta = v * beta[..., None]
    g = jnp.cumsum(g, axis=-1)                    # within a chunk
    lower = jnp.tril(jnp.ones((c, c), bool))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    # decay[i, j] = exp(g_i - g_j) for i >= j (never above 1).
    decay = jnp.where(lower,
                      jnp.exp(jnp.where(
                          lower, g[..., :, None] - g[..., None, :],
                          0.0)), 0.0)
    a = jnp.where(
        strict,
        jnp.einsum("bhnik,bhnjk->bhnij", k_beta, k,
                   precision=_HI) * decay, 0.0)
    # Token i's correction depends on those of the tokens before
    # it in the chunk: (I + A) U = rhs, unit lower triangular.
    rhs = jnp.concatenate(
        [v_beta, k_beta * jnp.exp(g)[..., None]], axis=-1)
    eye = jnp.eye(c, dtype=a.dtype)
    u = jax.scipy.linalg.solve_triangular(
        a + eye, rhs, lower=True, unit_diagonal=True)
    value, k_cumdecay = u[..., :dv], u[..., dv:]
    attn = jnp.where(
        lower,
        jnp.einsum("bhnik,bhnjk->bhnij", q, k, precision=_HI)
        * decay, 0.0)

    def step(s, xs):
        q_i, k_i, g_i, value_i, kcd_i, attn_i = xs
        v_new = value_i - jnp.einsum("bhck,bhkv->bhcv", kcd_i, s,
                                     precision=_HI)
        o_i = (jnp.einsum("bhck,bhkv->bhcv",
                          q_i * jnp.exp(g_i)[..., None], s,
                          precision=_HI)
               + jnp.einsum("bhij,bhjv->bhiv", attn_i, v_new,
                            precision=_HI))
        last = g_i[..., -1]
        s = (s * jnp.exp(last)[..., None, None]
             + jnp.einsum(
                 "bhck,bhcv->bhkv",
                 k_i * jnp.exp(last[..., None] - g_i)[..., None],
                 v_new, precision=_HI))
        return s, o_i

    xs = tuple(jnp.moveaxis(a_, 2, 0)
               for a_ in (q, k, g, value, k_cumdecay, attn))
    state, o = jax.lax.scan(step, state, xs)      # o [n,B,H,c,dv]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * c, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t], state
