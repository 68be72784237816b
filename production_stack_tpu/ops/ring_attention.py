"""Ring attention: exact causal attention over a sequence-sharded ring.

Long-context strategy for this stack. The reference delegates sequence
length entirely to the engine (`maxModelLen`/chunked-prefill flags passed
through to vLLM, reference helm/templates/deployment-vllm-multi.yaml:69-79)
and has no sequence/context parallelism anywhere; here long context is a
first-class mesh axis (``sp``): every device holds a ``T/n`` slice of the
sequence, K/V blocks rotate around the ring with ``lax.ppermute`` over
ICI, and attention accumulates with an online (flash-style) softmax so the
full [T, T] score matrix never materializes. Compute on each hop overlaps
XLA's async collective-permute, so ICI latency hides behind the block
matmuls (the scaling-book ring-attention recipe).

This module is written to run *inside* ``shard_map`` — all collectives are
explicit (``ppermute`` / ``axis_index``) and everything else is local
block math that XLA tiles onto the MXU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = True) -> jnp.ndarray:
    """Exact attention with q/k/v sharded along the sequence dimension.

    Must be called inside ``shard_map`` with sequence dim mapped to mesh
    axis ``axis_name``. Grouped-query attention is supported (num q heads
    a multiple of num kv heads).

    Args:
      q: [B, T_local, num_q_heads, head_dim] local query shard.
      k: [B, T_local, num_kv_heads, head_dim] local key shard.
      v: [B, T_local, num_kv_heads, head_dim] local value shard.
      axis_name: mesh axis the sequence is sharded over.
      causal: apply a global causal mask (positions are global:
        shard i covers [i*T_local, (i+1)*T_local)).

    Returns [B, T_local, num_q_heads, head_dim], the local output shard.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))

    qg = q.astype(jnp.float32).reshape(b, t, hkv, group, d)
    q_pos = idx * t + jnp.arange(t)  # global positions of local queries

    perm = [(j, (j + 1) % n) for j in range(n)]

    def block(carry, step):
        k_blk, v_blk, m, l, o = carry
        src = (idx - step) % n  # which shard's K/V we hold this hop
        kv_pos = src * t + jnp.arange(t)

        # [B, kv, group, Tq, Tkv]
        scores = jnp.einsum(
            "btkgd,bskd->bkgts", qg, k_blk.astype(jnp.float32)
        ) * scale
        if causal:
            mask = kv_pos[None, :] <= q_pos[:, None]  # [Tq, Tkv]
            scores = jnp.where(mask[None, None, None], scores, NEG_INF)

        blk_max = jnp.max(scores, axis=-1)  # [B, kv, g, Tq]
        new_m = jnp.maximum(m, blk_max)
        # Guard: a fully-masked block keeps new_m finite via the old m;
        # on the very first hop the diagonal block is never fully masked.
        correction = jnp.exp(m - new_m)
        p = jnp.exp(scores - new_m[..., None])  # [B, kv, g, Tq, Tkv]
        new_l = l * correction + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgts,bskd->bkgtd", p,
                        v_blk.astype(jnp.float32))
        new_o = o * correction[..., None] + pv

        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_next, v_next, new_m, new_l, new_o), None

    m0 = jnp.full((b, hkv, group, t), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, t), jnp.float32)
    o0 = jnp.zeros((b, hkv, group, t, d), jnp.float32)
    (_, _, m, l, o), _ = jax.lax.scan(
        block, (k, v, m0, l0, o0), jnp.arange(n)
    )

    out = o / l[..., None]  # [B, kv, g, Tq, d]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(b, t, hq, d)
    return out.astype(q.dtype)


def ring_attention_sharded(q: jnp.ndarray, k: jnp.ndarray,
                           v: jnp.ndarray, mesh,
                           sp_axis: str = "sp",
                           causal: bool = True) -> jnp.ndarray:
    """Convenience wrapper: shard_map ``ring_attention`` over ``sp_axis``.

    q/k/v are global [B, T, H, D] arrays; T must divide evenly by the
    size of the ``sp`` axis. Batch/head dims stay replicated here — for
    combined dp x sp x tp, call ``ring_attention`` inside your own
    shard_map (see parallel/context.py).
    """
    from jax.sharding import PartitionSpec as P
    spec = P(None, sp_axis, None, None)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=sp_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
