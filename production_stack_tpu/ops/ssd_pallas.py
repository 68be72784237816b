"""The Mamba-2 decode step as one Pallas kernel over the state pool:
each row's ``h`` is read once from its slot, advanced one token and
written back to the same slot.

The XLA form (``ops/ssd.ssd_step`` between a gather from the pool and
a scatter back) moves the state several times a step, and a copy of
the pool (0.57e9 B a layer at the cell's 137 slots, 5.2e9 B over the
nine layers) does not fit beside it. Here the pool is an aliased
operand, the slot of each row reaches the block index through scalar
prefetch, and a row's whole ``h`` (``[d_state, heads * d_head]``
float32, 4 194 304 B at the published widths: 16 x 64 whole tiles, the
channels along the lanes, see ``ops/ssd.py``) is one block in the fast
memory, one 4 MB transfer in and one out: two passes, which is what
the recurrence needs. Elementwise work, no matrix unit and no rounding
below float32::

    h = decay * h + B * dx        decay, dx: lane vectors
    y = sum_n h[n, :] * C[n]      B, C: columns

``decay = exp(dt * A)`` a head, times 0 for a row that starts from a
zero state, and ``dx = dt * x`` are made outside (a head's scalar
repeated over its channels: ``[B, 8192]``, a five-hundredth of the
state) and come as two rows of one operand, ``B`` and ``C`` as two
columns of another. The block is walked ``LANES`` lanes at a time so
that what the kernel holds beside its blocks is a strip and not a
second state.

``VMEM_KEPT`` as in ``ops/selective_scan_pallas.py``: the kernel's
blocks need 16 MB (in and out, each double-buffered), over the default
scoped limit, and with most of the fast memory claimed the compiler
parks no other operand of the burst there around the call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.ops.ssd import step_operands

VMEM_KEPT = 100 * 2 ** 20
LANES = 1024


def _kernel(slots_ref, rows_ref, cols_ref, h_ref, y_ref, h_out_ref):
    del slots_ref  # consumed by the index maps
    b_col, c_col = cols_ref[0, :, 0:1], cols_ref[0, :, 1:2]  # [N, 1]
    d = h_ref.shape[-1]
    strip = min(LANES, d)
    for lo in range(0, d, strip):
        lanes = pl.ds(lo, strip)
        decay, dx = rows_ref[0, 0:1, lanes], rows_ref[0, 1:2, lanes]
        h = decay * h_ref[0, :, lanes] + b_col * dx           # [N, strip]
        h_out_ref[0, :, lanes] = h
        y_ref[0, :, lanes] = jnp.sum(h * c_col, axis=0, keepdims=True)


def ssd_decode(x, dt, a, b, c, h_pool, slots, keep,
               interpret: bool = False):
    """One token a row, in place in the pool.

    Args (float32): x [B, heads, d_head]; dt [B, heads] (0 for a row
      that is not real, which then writes back what it read); a
      [heads]; b, c [B, d_state]; h_pool [slots, d_state, heads *
      d_head]; slots [B] int32, no two real rows alike (padded rows
      share the trash slot 0); keep [B], 0 for a row that starts from
      a zero state, else 1.
    Returns (y [B, heads, d_head] without the skip, the pool).
    """
    rows, heads, d_head = x.shape
    n = b.shape[-1]
    d = heads * d_head
    decay, dx = step_operands(x, dt, a, keep)
    row = lambda i, slots_ref: (i, 0, 0)  # noqa: E731
    slot = lambda i, slots_ref: (slots_ref[i], 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, 2, d), row),
            pl.BlockSpec((1, n, 2), row),
            pl.BlockSpec((1, n, d), slot),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, d), row),
            pl.BlockSpec((1, n, d), slot),
        ],
    )
    y, h_pool = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct(h_pool.shape, h_pool.dtype)],
        # Operands count the prefetched vector: the pool is the fourth.
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_KEPT),
        interpret=interpret,
        name="ssd_decode_kernel",
    )(slots.astype(jnp.int32), jnp.stack([decay, dx], axis=1),
      jnp.stack([b, c], axis=-1), h_pool)
    return y.reshape(rows, heads, d_head), h_pool
