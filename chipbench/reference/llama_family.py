"""A plain float32 forward pass of the Llama family of decoders
(Llama, Mistral, Qwen2), written from the published description and
independent of the program's ``models/llama.py``.

Pre-norm decoder: RMSNorm, rotary embeddings in the "rotate half"
convention (dimension i pairs with i + d/2, frequency theta^(-2i/d)),
grouped-query causal attention scaled by 1/sqrt(d) (query head h reads
key-value head h // (heads / kv_heads)), optional q/k/v bias (Qwen2),
SwiGLU feed-forward, a final RMSNorm and an output head that is its own
matrix or the transposed embedding (tied).  No cache, no kernels, no
batching: one sequence, one full forward, every matrix product under
``jax.default_matmul_precision("highest")`` so that a TPU too would
compute it in float32.

Weights come as ``Weights``: the caller fills it from whatever holds the
values; linear weights are [in, out].  For the benchmark
``program_model`` fills it from the program's own random init, which
is data here: nothing else of the program is used.

``reference/check.py`` finds this module by the family a configuration
names and uses ``program_model`` and ``log_probs`` of it, as of any
family's.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class Shape:
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rms_eps: float
    rope_theta: float


@dataclasses.dataclass
class Weights:
    embed: jnp.ndarray                  # [vocab, hidden]
    final_norm: jnp.ndarray             # [hidden]
    lm_head: Optional[jnp.ndarray]      # [hidden, vocab]; None = tied
    # layer(i) -> dict of float32 arrays: attn_norm, wq, wk, wv, wo,
    # mlp_norm, w_gate, w_up, w_down, and bq, bk, bv where biased.
    layer: Callable[[int], dict]


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rope(x, theta):
    """x: [T, heads, d], positions 0..T-1."""
    t, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


def attention(q, k, v):
    """q: [T, heads, d]; k, v: [T, kv_heads, d]; causal."""
    t, heads, d = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)


def program_model(hf_config: dict, bench: dict):
    """The server's random weights for this configuration (``bench``:
    its ``chipbench`` group), by the server's own init from
    ``weights_seed``, as the ``(Weights, Shape)`` that ``log_probs``
    takes; int8 leaves dequantised as value x scale."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.engine.quantization import (
        init_random_quantized,
    )
    from production_stack_tpu.models.registry import get_model

    config = ModelConfig.from_hf_config(hf_config)
    config.quantization = bench["quantization"]
    seed = bench["weights_seed"]
    init_fn, _ = get_model(config)
    if config.quantization == "int8":
        params = init_random_quantized(init_fn, config, seed)
    else:
        params = init_fn(config, jax.random.PRNGKey(seed))
    per_layer = [k for k, v in params.items()
                 if k not in ("embed", "final_norm", "lm_head")]

    def layer(i: int) -> dict:
        out = {}
        for name in per_layer:
            leaf = params[name]
            if isinstance(leaf, tuple):  # (int8 values, scale per column)
                q, scale = leaf
                out[name] = (q[i].astype(jnp.float32)
                             * scale[i].astype(jnp.float32)[None, :])
            else:
                out[name] = leaf[i].astype(jnp.float32)
        return out

    shape = Shape(
        num_layers=config.num_hidden_layers,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim, rms_eps=config.rms_norm_eps,
        rope_theta=config.rope_theta)
    return Weights(
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params.get("lm_head"), layer=layer), shape


def log_probs(model, tokens, positions):
    """Log-softmax over the vocabulary of the next token after each of
    ``positions`` (indices into ``tokens``): [len(positions), vocab].
    ``model`` is ``(Weights, Shape)``."""
    weights, shape = model
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = weights.embed[tokens].astype(jnp.float32)
        t = x.shape[0]
        for i in range(shape.num_layers):
            w = weights.layer(i)
            h = rms_norm(x, w["attn_norm"], shape.rms_eps)
            q, k, v = h @ w["wq"], h @ w["wk"], h @ w["wv"]
            if "bq" in w:
                q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
            q = rope(q.reshape(t, shape.num_heads, shape.head_dim),
                     shape.rope_theta)
            k = rope(k.reshape(t, shape.num_kv_heads, shape.head_dim),
                     shape.rope_theta)
            v = v.reshape(t, shape.num_kv_heads, shape.head_dim)
            x = x + attention(q, k, v).reshape(t, -1) @ w["wo"]
            h = rms_norm(x, w["mlp_norm"], shape.rms_eps)
            x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])
                     ) @ w["w_down"]
        x = rms_norm(x[jnp.asarray(positions)],
                     weights.final_norm.astype(jnp.float32), shape.rms_eps)
        head = (weights.embed.astype(jnp.float32).T
                if weights.lm_head is None
                else weights.lm_head.astype(jnp.float32))
        return jax.nn.log_softmax(x @ head, axis=-1)
