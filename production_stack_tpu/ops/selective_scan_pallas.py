"""The selective scan's decode step as one Pallas kernel over the state
pool: each row's ``h`` is read once from its slot, advanced one token
and written back to the same slot.

The XLA form (``ops/selective_scan.selective_scan_step`` between a
gather from the pool and a scatter back) moves the state several times
a step. Here the pool is an aliased operand, the slot of each row
reaches the block index through scalar prefetch, and a row's whole
``h`` (``[d_state, d_inner]`` float32, 327 680 B at the published
widths: 2 x 40 whole tiles, the channels along the lanes, see
``ops/selective_scan.py``) is one block in the fast memory: two passes,
which is what the recurrence needs. Elementwise work, no matrix unit
and no rounding below float32::

    h = exp(delta * A^T) * (keep * h) + dx * B     delta, dx: lane vectors
    y = sum_n h[n, :] * C[n]                       B, C: columns

so ``delta`` and ``dx`` come as two rows of one operand and ``B`` and
``C`` as two columns of another.

``VMEM_KEPT``: the kernel claims most of the chip's 128 MiB of fast
memory though its blocks need 2 MB. Left the default 16 MiB, the
compiler moved the whole pool (45 MB a layer at the cell's 137 slots)
into the rest of that memory before 11 of the burst's 26 calls and back
after them, in asynchronous copies that other operations waited for:
more bytes than the rows' blocks, and the trace then charged those
calls with less than the bytes they need (44 us where 101 us is the
least: PERF.md section 6, PR 34). With the memory claimed the pool
stays in HBM and the kernel's time is the time of its own traffic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_KEPT = 100 * 2 ** 20


def _kernel(slots_ref, keep_ref, rows_ref, cols_ref, a_ref, h_ref,
            y_ref, h_out_ref):
    del slots_ref  # consumed by the index maps
    keep = keep_ref[pl.program_id(0)].astype(jnp.float32)
    delta, dx = rows_ref[0, 0:1, :], rows_ref[0, 1:2, :]     # [1, D]
    b_col, c_col = cols_ref[0, :, 0:1], cols_ref[0, :, 1:2]  # [N, 1]
    h = (jnp.exp(delta * a_ref[...]) * (h_ref[0] * keep)
         + dx * b_col)                                        # [N, D]
    h_out_ref[0] = h
    y_ref[0] = jnp.sum(h * c_col, axis=0, keepdims=True)


def selective_scan_decode(delta, dx, b, c, a_t, h_pool, slots, keep,
                          interpret: bool = False):
    """One token a row, in place in the pool.

    Args (float32): delta, dx [B, D] (``dx = delta * x``; both 0 for a
      row that is not real, which then writes back what it read);
      b, c [B, N]; a_t [N, D]; h_pool [slots, N, D]; slots [B] int32,
      no two real rows alike (padded rows share the trash slot 0);
      keep [B], 0 for a row that starts from a zero state, else 1.
    Returns (y [B, D] without the skip, the pool).
    """
    rows, d = delta.shape
    n = b.shape[-1]
    row = lambda i, slots_ref, keep_ref: (i, 0, 0)  # noqa: E731
    slot = lambda i, slots_ref, keep_ref: (slots_ref[i], 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(rows,),
        in_specs=[
            pl.BlockSpec((1, 2, d), row),
            pl.BlockSpec((1, n, 2), row),
            pl.BlockSpec((n, d), lambda i, slots_ref, keep_ref: (0, 0)),
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
        # Operands count the two prefetched vectors: the pool is the
        # sixth.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_KEPT),
        interpret=interpret,
        name="ssm_decode_kernel",
    )(slots.astype(jnp.int32), keep.astype(jnp.int32),
      jnp.stack([delta, dx], axis=1), jnp.stack([b, c], axis=-1),
      a_t, h_pool)
    return y[:, 0], h_pool
