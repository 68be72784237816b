"""The state-space dual recurrence of a Mamba-2 mixer (Dao & Gu,
"Transformers are SSMs", 2024; the published ``granitemoehybrid``
Mamba layer).

Per head ``p`` of ``heads`` with a state ``h [d_head, d_state]`` that
starts at zero, for each token in order::

    h_t = exp(dt_t[p] * A[p]) * h_{t-1} + (dt_t[p] * x_t[p, :]) (outer) B_t
    y_t[p, :] = h_t @ C_t

``dt`` (after its softplus) and ``A = -exp(A_log)`` are one number a
head: the decay is a SCALAR a head a token, where Mamba-1's is one a
channel and a state element (``ops/selective_scan.py``). ``B`` and
``C`` depend on the token and are shared by every head (one group).
The skip ``D * x_t``, the gate and the gated norm belong to the mixer
(``models/granitemoehybrid.py``).

The state's layout, here and in the pool: ``h [d_state, heads *
d_head]``, the ``d_state`` 128 state elements along the SUBLANES (16
whole tiles of 8) and the 8192 channels ``(head, d_head)`` along the
LANES (64 whole tiles of 128): a row's state is 16 x 64 whole float32
tiles, 4 194 304 B at the published widths, nothing padded. The
published layout ``[heads, d_head, d_state]`` fills the lanes as well,
but the step would then need ``dt * x`` as a column that broadcasts
along the lanes and ``y`` as a sum along them, one relayout and one
cross-lane reduction a head; with the channels along the lanes ``dt *
x`` and the decay are lane vectors that broadcast over the sublanes,
``B`` and ``C`` columns that broadcast over the lanes, and ``y`` is a
sum over sublanes, which is plain vector adds: the form
``ops/selective_scan_pallas.py`` already runs. A head's scalar is
repeated over its ``d_head`` lanes outside the kernel (``[B, 8192]``,
nothing beside the state).

Two forms of one recurrence. ``ssd_step`` advances one token a row (a
decode step): elementwise work in float32, bound by reading and
writing ``h``; ``ops/ssd_pallas.py`` is the same step in place in the
pool. ``ssd_chunked`` advances a block of tokens ``chunk`` at a time in
the matrix form that a scalar decay allows: with ``a_t = dt_t * A`` and
``s`` its running sum inside the chunk,

    Y = ((C B^T) * L) @ (dt * X) + exp(s) * (C @ h_in)
    L[i, j] = exp(s_i - s_j) for i >= j, else 0
    h_out = exp(s_last) * h_in + sum_j exp(s_last - s_j) * B_j (outer) (dt_j * x_j)

``C B^T`` is one ``[Q, Q]`` matrix a sequence, shared by the heads;
``L`` is one a head. Only the chunk boundaries are sequential. It is
the skeleton of ``ops/gated_delta.gated_delta_chunked`` without the
triangular solve: nothing a token writes depends on what the tokens
before it in the chunk wrote. Every exponent is at most 0. Both forms
take and return ``h``, so a prompt's chunks and a burst's steps carry
it from one to the next.

A token that is not real (padding, a row that stopped) is a no-op
where its ``dt`` is 0: ``exp(0) = 1`` fades nothing and ``0 * x``
writes nothing. The state is float32 throughout, and the chunk's
products run at the matrix unit's highest precision, because at the
default a float32 operand is rounded to bfloat16 on a TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def per_channel(a: jnp.ndarray, d_head: int) -> jnp.ndarray:
    """``[..., heads]`` -> ``[..., heads * d_head]``: a head's number on
    each of its channels."""
    return jnp.repeat(a, d_head, axis=-1)


def rows_of(pool: jnp.ndarray, slots: jnp.ndarray) -> jnp.ndarray:
    """``pool[slots]`` for a few rows of a pool whose rows are large:
    one slice a row, each a contiguous copy of the row's whole state.
    The gather XLA makes of ``pool[slots]`` moved a prefill step's
    eight 4 MB rows at 18 GB/s on a v5e (1.8 ms a layer, a fifth of
    the step: PERF.md section 6, PR 47)."""
    return jnp.stack([
        jax.lax.dynamic_index_in_dim(pool, slots[i], 0, keepdims=False)
        for i in range(slots.shape[0])])


def step_operands(x, dt, a, keep=None):
    """What a step needs of ``x``, ``dt`` and ``A`` a channel: the
    decay ``exp(dt * A)`` (times ``keep``) and ``dt * x``, both
    ``[B, heads * d_head]``."""
    rows, heads, d_head = x.shape
    decay = jnp.exp(dt * a)
    if keep is not None:
        decay = decay * keep[:, None]
    return (per_channel(decay, d_head),
            (dt[..., None] * x).reshape(rows, heads * d_head))


def ssd_step(x, dt, a, b, c, h, keep=None):
    """One token a row.

    Args (float32): x [B, heads, d_head]; dt [B, heads] (0 for a row
      that is not real, which then writes what it read); a [heads]
      (negative); b, c [B, d_state]; h [B, d_state, heads * d_head];
      keep [B], 0 for a row that starts from a zero state whatever
      ``h`` holds, else 1 (folded into the decay, so that it costs no
      pass over the state).
    Returns (y [B, heads, d_head] without the skip, new h).
    """
    decay, dx = step_operands(x, dt, a, keep)
    h = decay[:, None, :] * h + b[:, :, None] * dx[:, None, :]
    return jnp.sum(h * c[:, :, None], axis=1).reshape(x.shape), h


def ssd_chunked(x, dt, a, b, c, h, chunk: int):
    """A block of tokens a row, ``chunk`` at a time.

    Args (float32): x [B, T, heads, d_head]; dt [B, T, heads] (0 for a
      token that is not real); a [heads]; b, c [B, T, d_state];
      h [B, d_state, heads * d_head] (already zeroed for a row that
      starts afresh).
    Returns (y [B, T, heads, d_head] without the skip, new h).
    """
    rows, t, heads, d_head = x.shape
    q = min(chunk, t)
    pad = (-t) % q
    if pad:
        # Padded tokens are no-ops: dt 0.
        x, dt, b, c = (
            jnp.pad(arr, ((0, 0), (0, pad)) + ((0, 0),) * (arr.ndim - 2))
            for arr in (x, dt, b, c))
    n = (t + pad) // q

    def blocks(arr):  # [B, T, ...] -> [n, B, q, ...]
        return jnp.moveaxis(
            arr.reshape((rows, n, q) + arr.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((q, q), bool))

    def step(h, xs):
        x_i, dt_i, b_i, c_i = xs
        s = jnp.cumsum(dt_i * a, axis=1)                     # [B, q, H]
        s_t = jnp.moveaxis(s, 2, 1)                          # [B, H, q]
        # L[i, j] = exp(s_i - s_j) for i >= j: never above 1.
        decay = jnp.where(
            lower,
            jnp.exp(jnp.where(lower,
                              s_t[..., :, None] - s_t[..., None, :],
                              0.0)), 0.0)                    # [B, H, q, q]
        cb = jnp.einsum("bin,bjn->bij", c_i, b_i, precision=_HI)
        dx = dt_i[..., None] * x_i                           # [B, q, H, P]
        y = jnp.einsum("bhij,bjhp->bihp", cb[:, None] * decay, dx,
                       precision=_HI)
        carried = jnp.einsum("bin,bnd->bid", c_i, h, precision=_HI)
        y = y + (jnp.exp(s)[..., None]
                 * carried.reshape(rows, q, heads, d_head))
        last = s[:, -1]                                      # [B, H]
        faded = (jnp.exp(last[:, None] - s)[..., None] * dx
                 ).reshape(rows, q, heads * d_head)
        h = (per_channel(jnp.exp(last), d_head)[:, None, :] * h
             + jnp.einsum("bjn,bjd->bnd", b_i, faded, precision=_HI))
        return h, y

    h, y = jax.lax.scan(step, h, tuple(blocks(arr)
                                       for arr in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1).reshape(rows, n * q, heads, d_head)
    return y[:, :t], h
