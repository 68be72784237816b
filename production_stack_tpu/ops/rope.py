"""Rotary position embeddings (RoPE), the Llama flavor.

Implemented as a pure function of positions so it works identically for
packed prefill chunks and scattered decode batches (no precomputed cache
table needed; XLA fuses the sin/cos into the surrounding matmuls).
"""

import jax.numpy as jnp


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0,
               rotary_dim: "int | None" = None) -> jnp.ndarray:
    """Rotate q or k.

    Args:
      x: [..., seq, heads, head_dim]
      positions: [..., seq] absolute token positions
      theta: rope base frequency
      rotary_dim: how many leading dimensions of each head turn
        (partial rotary: the frequencies are those of a head of that
        size, the rest of the head passes unchanged); None = all
    """
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        turned = apply_rope(x[..., :rotary_dim], positions, theta)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    head_dim = x.shape[-1]
    half = head_dim // 2
    freq_exponents = jnp.arange(half, dtype=jnp.float32) / half
    timescale = theta ** freq_exponents  # [half]
    angles = positions[..., None].astype(jnp.float32) / timescale  # [...,seq,half]
    angles = angles[..., None, :]  # broadcast over heads: [..., seq, 1, half]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)
