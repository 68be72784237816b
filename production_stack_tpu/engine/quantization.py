"""Weight-only int8 quantization for serving.

Decode on TPU is HBM-bandwidth-bound on weight streaming; storing the
projection matrices as int8 with per-output-channel scales halves that
traffic (the weight-only-quantization recipe vLLM exposes via
--quantization; here it is a load-time transform, no calibration data
needed for symmetric weight-only).

Representation: a quantized weight is the pytree pair
``(w_int8 [L, in, out], scale [L, out] f32)``; the matmul helper
(engine/lora.py lora_matmul) computes ``(x @ w_int8) * scale`` — XLA
fuses the int8->bf16 convert and the scale into the dot's epilogue, so
only int8 bytes ever cross HBM. Activations stay bf16; the MXU result
is rescaled per channel.

Serving-path only: the dense encode/training forwards use the
unquantized layout (the Embedder refuses quantized params).

Weights are one of the two int8 serving knobs; the other is the KV
cache. ``--kv-cache-dtype int8`` (CacheConfig.kv_cache_dtype) stores
KV *pages* as int8 with per-slot per-head scales — quantized on the
page write path (ops/attention.write_to_pages), dequantized in-kernel
on the attention read path — and expands the page budget ~2x at the
same HBM bytes. The two compose freely: this module covers weight
streaming bandwidth, the KV knob covers cache capacity + decode read
bandwidth (ops/quant_kv.py, docs/kv_quantization.md).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import ModelConfig

QuantizedWeight = Tuple[jnp.ndarray, jnp.ndarray]

# Projection params quantized per architecture (layer-stacked rank-3
# [L, in, out]). Norms, embeddings and biases stay in full precision.
_TARGETS = {
    "llama": ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"),
    "mistral": ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"),
    "qwen2": ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"),
    "opt": ("wq", "wk", "wv", "wo", "fc1", "fc2"),
    "gpt2": ("wq", "wk", "wv", "wo", "fc1", "fc2"),
}


def quantize_weight(w: jnp.ndarray) -> QuantizedWeight:
    """Symmetric per-output-channel int8 over the contraction dim."""
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return q, scale.squeeze(-2)  # [L, in, out] -> scale [L, out]


def dequant_matmul(x: jnp.ndarray, qw: QuantizedWeight) -> jnp.ndarray:
    q, scale = qw
    out = x @ q.astype(x.dtype)
    return out * scale.astype(x.dtype)


def is_quantized(w) -> bool:
    return isinstance(w, tuple) and len(w) == 2


def quantize_params(params: Dict, config: ModelConfig) -> Dict:
    targets = _TARGETS.get(config.architecture)
    if targets is None:
        raise NotImplementedError(
            f"--quantization int8 is not supported for "
            f"architecture {config.architecture!r}"
        )
    out = dict(params)
    for name in targets:
        if name in out:
            out[name] = quantize_weight(out[name])
    return out


def has_quantized_leaves(params: Dict) -> bool:
    return any(is_quantized(v) for v in params.values())


def init_random_quantized(init_fn, config: ModelConfig,
                          seed: int) -> Dict:
    """Random-init an int8 model WITHOUT materializing it in full
    precision.

    ``init_fn`` followed by :func:`quantize_params` peaks at the full
    bf16 model plus f32 quantization copies on device — a 16 GB HBM
    chip cannot hold that for an 8B model even though the final int8
    footprint (~8 GB) fits comfortably (observed: RESOURCE_EXHAUSTED
    at the 8B bench config, builder-captured 2026-07-31). Random
    weights
    carry no information worth quantizing, so the projection targets
    are sampled directly as int8 (uniform) with a flat per-channel
    scale matching the init distribution's magnitude; only the
    non-target leaves (embeddings, norms, biases) are materialized in
    their full dtype. Peak device memory = the final serving
    footprint. Leaf names/shapes come from ``jax.eval_shape`` so
    every model family's init stays the single source of truth.
    """
    import numpy as np

    targets = _TARGETS.get(config.architecture)
    if targets is None:
        raise NotImplementedError(
            f"--quantization int8 is not supported for "
            f"architecture {config.architecture!r}"
        )
    import dataclasses
    import functools

    shapes = jax.eval_shape(functools.partial(init_fn, config),
                            jax.random.PRNGKey(seed & 0x7FFFFFFF))
    # Leaf *semantics* (ones for norm gains, zeros for biases, random
    # for dense) come from materializing the SAME init at a shrunken
    # geometry — the family's init stays the single source of truth;
    # no name heuristics to silently misclassify a new architecture's
    # leaves.
    probe_cfg = dataclasses.replace(
        config, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, vocab_size=256,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        max_position_embeddings=64)
    probe = init_fn(probe_cfg, jax.random.PRNGKey(0))
    kinds = {}
    for name, leaf in probe.items():
        a = np.asarray(jax.device_get(leaf), np.float32)
        kinds[name] = ("ones" if np.all(a == 1.0)
                       else "zeros" if np.all(a == 0.0)
                       else "dense")
    if set(kinds) != set(shapes):
        raise AssertionError(
            "init leaf set changed with geometry: "
            f"{sorted(set(kinds) ^ set(shapes))}")
    # np.random.Generator (PCG64): ~4x faster than RandomState at the
    # 8B leaf sizes (the init runs on the bench host and eats
    # chip-window minutes).
    rng = np.random.Generator(np.random.PCG64(seed & 0x7FFFFFFF))
    out: Dict = {}
    for name, sds in shapes.items():
        shape = sds.shape
        if name in targets:
            q = rng.integers(-127, 128, size=shape, dtype=np.int8)
            scale = np.full(shape[:-2] + (shape[-1],), 0.02 / 127.0,
                            np.float32)
            out[name] = (jnp.asarray(q), jnp.asarray(scale))
        elif kinds[name] == "ones":
            out[name] = jnp.ones(shape, sds.dtype)
        elif kinds[name] == "zeros":
            out[name] = jnp.zeros(shape, sds.dtype)
        else:
            host = 0.02 * rng.standard_normal(shape,
                                              dtype=np.float32)
            # Cast on host (ml_dtypes handles bf16) so only the
            # final-dtype bytes land on device — an on-device astype
            # would stage a transient f32 copy of each dense leaf.
            out[name] = jnp.asarray(host.astype(sds.dtype))
    return out
