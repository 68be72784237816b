"""The selective scan of a Mamba-1 mixer (Gu & Dao, "Mamba", 2023; the
published Jamba block).

Per channel ``d`` of ``d_inner`` with a state ``h[d, :]`` of ``d_state``
numbers that starts at zero, for each token in order::

    h_t = exp(delta_t[d] * A[d, :]) * h_{t-1} + (delta_t[d] * x_t[d]) * B_t
    y_t[d] = h_t[d, :] . C_t

``delta`` (after its softplus), ``B`` and ``C`` depend on the token,
``A = -exp(A_log)`` does not. The skip ``D * x_t`` and the gate belong
to the mixer (``models/jamba.py``).

The state is kept TRANSPOSED, ``h [d_state, d_inner]``, here and in the
pool: ``d_state`` is 16 and a TPU's lanes are 128 wide, so a minor
dimension of 16 would fill an eighth of every register and of every
tile of the pool in HBM; with the 5120 channels along the lanes a
row's state is 2 x 40 whole float32 tiles, ``delta`` and ``x`` are lane
vectors that broadcast over the sublanes, ``B`` and ``C`` columns that
broadcast over the lanes, and ``y`` is a sum over 16 sublanes. ``A``
comes transposed the same way (``a_t [d_state, d_inner]``).

Two forms of one recurrence. ``selective_scan_step`` advances one token
a row (a decode step): elementwise work in float32, bound by reading
and writing ``h``; ``ops/selective_scan_pallas.py`` is the same step in
place in the pool. ``selective_scan_block`` advances a block of tokens
(a prefill chunk) token by token, which is what the recurrence is: its
decay is per channel and per state element, so a block has no
matrix form as the delta rule's has. Both take and return ``h``, so a
prompt's chunks and a burst's steps carry it from one to the next.

A token that is not real (padding, a row that stopped) is a no-op where
its ``delta`` is 0: ``exp(0) = 1`` fades nothing and ``0 * x`` writes
nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Tokens a trip of the prefill loop: the loop's own cost a trip is of
# the order of a token's work at 8 rows.
UNROLL = 8


def selective_scan_step(delta, dx, b, c, a_t, h, keep=None):
    """One token a row.

    Args (float32): delta, dx [B, D] (``dx = delta * x``; both 0 for a
      row that is not real); b, c [B, N]; a_t [N, D]; h [B, N, D];
      keep [B], 0 for a row that starts from a zero state whatever
      ``h`` holds, else 1.
    Returns (y [B, D] without the skip, new h).
    """
    if keep is not None:
        h = h * keep[:, None, None]
    h = (jnp.exp(delta[:, None, :] * a_t[None]) * h
         + dx[:, None, :] * b[:, :, None])
    return jnp.sum(h * c[:, :, None], axis=1), h


def selective_scan_block(delta, dx, b, c, a_t, h):
    """A block of tokens a row, in order.

    Args (float32): delta, dx [B, T, D]; b, c [B, T, N]; a_t [N, D];
      h [B, N, D] (already zeroed for a row that starts afresh).
    Returns (y [B, T, D], new h).
    """
    def step(h, xs):
        y, h = selective_scan_step(*xs, a_t, h)
        return h, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (delta, dx, b, c))
    h, y = jax.lax.scan(step, h, xs, unroll=min(UNROLL, delta.shape[1]))
    return jnp.moveaxis(y, 0, 1), h
