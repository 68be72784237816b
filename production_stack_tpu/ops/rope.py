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


def apply_rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                           theta: float) -> jnp.ndarray:
    """``apply_rope`` for heads whose rotary pairs lie side by side,
    ``(x[2i], x[2i+1])`` turning by ``positions / theta^(2i/d)`` (the
    DeepSeek-family convention), where ``apply_rope`` pairs ``x[i]``
    with ``x[i + d/2]``. Same argument shapes."""
    d = x.shape[-1]
    timescale = theta ** (jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    angles = (positions[..., None].astype(jnp.float32)
              / timescale)[..., None, :]  # [..., seq, 1, d/2]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)
