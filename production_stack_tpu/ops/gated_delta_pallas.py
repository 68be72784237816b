"""The gated delta rule's decode step as one Pallas kernel over the
state pool: each row's ``S`` is read once from its slot, advanced one
token and written back to the same slot.

The XLA form (``ops/gated_delta.gated_delta_step`` between a gather
from the pool and a scatter back) moves the state some seven times a
step: the gather writes a copy, the rule reads it twice and writes
once, the scatter reads that and writes the pool. Here the pool is an
aliased operand, the slot of each row reaches the block index through
scalar prefetch, and a row's whole ``S`` (value heads x d_k x d_v
float32, 2 MB at the published widths) is one block in the fast
memory: two passes, which is what the recurrence needs.

Per head, with ``S`` as [d_k, d_v] (d_k on sublanes, d_v on lanes), the
step is elementwise work with two broadcasts and two sublane sums, no
matrix unit and no rounding below float32::

    S' = S * decay                     decay, beta: a lane vector a head
    kv = sum_k S'[k, :] * k[k]         k, q: a column (d_k on sublanes)
    S  = S' + k (x) (beta * (v - kv))
    o  = sum_k S[k, :] * q[k]

so q and k come transposed ([B, d_k, H]: a head is a lane column) and
decay and beta come broadcast over d_v ([B, H, d_v]).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(slots_ref, qt_ref, kt_ref, v_ref, decay_ref, beta_ref,
            s_ref, o_ref, s_out_ref, *, heads: int):
    del slots_ref  # consumed by the index maps
    for h in range(heads):
        s = s_ref[0, h] * decay_ref[0, h:h + 1, :]          # [dk, dv]
        k_col = kt_ref[0, :, h:h + 1]                        # [dk, 1]
        kv = jnp.sum(s * k_col, axis=0, keepdims=True)       # [1, dv]
        delta = beta_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - kv)
        s = s + k_col * delta
        s_out_ref[0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(
            s * qt_ref[0, :, h:h + 1], axis=0, keepdims=True)


def gated_delta_decode(q, k, v, decay, beta, s_pool, slots,
                       interpret: bool = False):
    """One token a row, in place in the pool.

    Args (float32): q, k [B, H, d_k] (normalised, q scaled);
      v [B, H, d_v]; decay [B, H] (exp of the log-decay, 0 for a row
      that starts from zero); beta [B, H] (0 for a row that is not
      real, whose decay is 1: it writes back what it read);
      s_pool [slots, H, d_k, d_v]; slots [B] int32, no two real rows
      alike (padded rows share the trash slot 0).
    Returns (o [B, H, d_v], the pool).
    """
    b, h, dk = q.shape
    dv = v.shape[-1]
    row = lambda i, slots_ref: (i, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, dk, h), row),
            pl.BlockSpec((1, dk, h), row),
            pl.BlockSpec((1, h, dv), row),
            pl.BlockSpec((1, h, dv), row),
            pl.BlockSpec((1, h, dv), row),
            pl.BlockSpec((1, h, dk, dv),
                         lambda i, slots_ref: (slots_ref[i], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, dv), row),
            pl.BlockSpec((1, h, dk, dv),
                         lambda i, slots_ref: (slots_ref[i], 0, 0, 0)),
        ],
    )
    wide = lambda a: jnp.broadcast_to(a[..., None], (b, h, dv))  # noqa: E731
    o, s_pool = pl.pallas_call(
        functools.partial(_kernel, heads=h),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype)],
        # Operands count the prefetched slots: the pool is the seventh.
        input_output_aliases={6: 1},
        interpret=interpret,
        name="gdn_decode_kernel",
    )(slots.astype(jnp.int32), jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
      v, wide(decay), wide(beta), s_pool)
    return o, s_pool
