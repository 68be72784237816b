"""Pipeline parallelism: layer stages over a ``pp`` mesh axis.

The reference exposes no pipeline parallelism (SURVEY.md §2.6 — vLLM
TP only); this is the TPU-native extension for models deeper than one
slice's HBM: the layer-stacked parameters shard their leading L axis
across pp stages, and a GPipe-style microbatch schedule streams
activations stage-to-stage with ``ppermute`` hops over ICI/DCN.

Idiomatic-JAX shape: one ``shard_map`` block; inside it each stage
scans a static tick loop of length M + S - 1 (M microbatches, S
stages). At tick t, stage s processes microbatch t - s: stage 0 embeds
a fresh microbatch, inner stages run their local layer block on the
activation received last tick, the last stage collects final hidden
states. All stages execute every tick (bubble ticks compute on zeros —
the XLA-friendly trade for a static schedule).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models.llama import rms_norm
from production_stack_tpu.ops.rope import apply_rope

Params = Dict[str, jnp.ndarray]


def _layer_block(x, lp, config: ModelConfig, positions):
    """Apply one stage's stack of dense causal layers (same numerics
    as models.llama.encode's layer_step)."""
    nh, nkv, d = (config.num_attention_heads,
                  config.num_key_value_heads, config.head_dim)
    b, t, _ = x.shape
    causal = jnp.tril(jnp.ones((t, t), bool))

    def step(x, lp_i):
        a_in = rms_norm(x, lp_i["attn_norm"], config.rms_norm_eps)
        q = a_in @ lp_i["wq"]
        k = a_in @ lp_i["wk"]
        v = a_in @ lp_i["wv"]
        if config.attention_bias:
            q, k, v = (q + lp_i["bq"], k + lp_i["bk"], v + lp_i["bv"])
        q = apply_rope(q.reshape(b, t, nh, d), positions,
                       config.rope_theta)
        k = apply_rope(k.reshape(b, t, nkv, d), positions,
                       config.rope_theta)
        v = v.reshape(b, t, nkv, d)
        group = nh // nkv
        qg = q.reshape(b, t, nkv, group, d)
        scores = jnp.einsum(
            "btkgd,bskd->bkgts", qg.astype(jnp.float32),
            k.astype(jnp.float32),
        ) / jnp.sqrt(jnp.asarray(d, jnp.float32))
        scores = jnp.where(causal[None, None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum(
            "bkgts,bskd->btkgd", probs, v.astype(jnp.float32)
        ).reshape(b, t, nh * d).astype(x.dtype)
        x = x + attn @ lp_i["wo"]
        m_in = rms_norm(x, lp_i["mlp_norm"], config.rms_norm_eps)
        x = x + (jax.nn.silu(m_in @ lp_i["w_gate"])
                 * (m_in @ lp_i["w_up"])) @ lp_i["w_down"]
        return x, None

    x, _ = jax.lax.scan(step, x, lp)
    return x


def _layer_param_names(config: ModelConfig):
    names = ["attn_norm", "wq", "wk", "wv", "wo",
             "mlp_norm", "w_gate", "w_up", "w_down"]
    if config.attention_bias:
        names += ["bq", "bk", "bv"]
    return names


def pipeline_forward(params: Params, config: ModelConfig,
                     tokens: jnp.ndarray, mesh: Mesh,
                     pp_axis: str = "pp",
                     num_microbatches: Optional[int] = None
                     ) -> jnp.ndarray:
    """Dense causal forward with layers pipelined over ``pp_axis``.

    Args:
      params: llama-family stacked params (models/llama.py layout);
        layer count must divide by the pp-axis size.
      tokens: [B, T]; B must divide by num_microbatches.
      num_microbatches: defaults to the pp-axis size.

    Returns logits [B, T, vocab] (replicated).
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    S = axes[pp_axis]
    M = num_microbatches or S
    b, t = tokens.shape
    if b % M:
        raise ValueError(f"batch {b} must divide by microbatches {M}")
    L = config.num_hidden_layers
    if L % S:
        raise ValueError(f"layers {L} must divide by pp size {S}")
    mb = b // M
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (mb, t))

    layer_names = _layer_param_names(config)
    layer_params = {k: params[k] for k in layer_names}
    shared = {k: v for k, v in params.items() if k not in layer_names}

    layer_specs = {k: P(pp_axis) for k in layer_params}
    none_spec = P(*([None] * 0))

    if M % S:
        raise ValueError(
            f"microbatches {M} must divide by pp size {S} (outputs "
            "shard M over the stages)")
    mps = M // S  # microbatches homed per stage

    def stage_fn(layer_local, shared_p, tokens_all):
        stage = jax.lax.axis_index(pp_axis)
        ticks = M + S - 1
        # Microbatch views: [M, mb, T]
        mbs = tokens_all.reshape(M, mb, t)
        h = config.hidden_size
        dtype = shared_p["embed"].dtype
        shift = [(i, (i + 1) % S) for i in range(S)]

        # The tick loop is UNROLLED (M + S - 1 is small and static) so
        # every collective uses a static permutation. Finished
        # microbatch m is delivered straight from the last stage to its
        # home stage m // mps — one [mb,T,H] hop each — and outputs
        # stay SHARDED over pp (out_specs P(pp_axis)); no full-tensor
        # psum broadcast (round-1 review finding).
        recv = jnp.zeros((mb, t, h), dtype)
        collected = jnp.zeros((mps, mb, t, h), dtype)
        for t_idx in range(ticks):
            # Stage 0 feeds microbatch t_idx (clamped; bubble ticks
            # re-embed a stale microbatch and are ignored downstream).
            m_idx = min(t_idx, M - 1)
            embedded = shared_p["embed"][mbs[m_idx]]
            x = jnp.where(stage == 0, embedded.astype(dtype), recv)
            x = _layer_block(x, layer_local, config, positions)
            recv = jax.lax.ppermute(x, pp_axis, shift)
            m_done = t_idx - (S - 1)
            if m_done >= 0:
                home, slot = m_done // mps, m_done % mps
                if home == S - 1:
                    delivered = x  # already on the last stage
                else:
                    delivered = jax.lax.ppermute(
                        x, pp_axis, [(S - 1, home)])
                collected = jnp.where(
                    stage == home,
                    collected.at[slot].set(delivered),
                    collected,
                )
        x = rms_norm(collected.reshape(mps * mb, t, h),
                     shared_p["final_norm"], config.rms_norm_eps)
        head = shared_p.get("lm_head")
        if head is None:
            head = shared_p["embed"].T
        return (x @ head).astype(jnp.float32)

    fn = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(layer_specs, {k: none_spec for k in shared},
                  none_spec),
        out_specs=P(pp_axis),
        check_vma=False,
    )
    # Device s returns its mps home microbatches; the pp-sharded global
    # result is already in microbatch order (homes are contiguous
    # blocks), so a reshape recovers [B, T, vocab].
    return fn(layer_params, shared, tokens).reshape(b, t, -1)


def shard_params_pipeline(params: Params, config: ModelConfig,
                          mesh: Mesh, pp_axis: str = "pp") -> Params:
    """Place layer-stacked params with their L axis sharded across the
    pp stages (everything else replicated)."""
    from jax.sharding import NamedSharding
    layer_names = set(_layer_param_names(config))
    out = {}
    for k, v in params.items():
        spec = (P(pp_axis) if k in layer_names else P())
        out[k] = jax.device_put(v, NamedSharding(mesh, spec))
    return out
