"""Context parallelism: full-model forward with the sequence sharded
over the ``sp`` mesh axis.

The reference has no sequence/context parallelism (verified in SURVEY.md
§2.6 — nothing in repo); its long-context story is flag pass-through to
vLLM. Here a long prompt is a first-class distributed object: activations
are sharded [B, T/n] per device, attention runs as ring attention
(ops/ring_attention.py, K/V hops over ICI via ppermute), and everything
else (norms, projections, MLP) is purely local so XLA keeps the MXU busy
between hops. Combined with the ``dp`` axis for batch sharding this is
the dp x sp layout of the scaling-book recipe; ``tp`` composes by
sharding the head dimension of the same shard_map block.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models.llama import rms_norm as _rms_norm
from production_stack_tpu.ops.ring_attention import ring_attention
from production_stack_tpu.ops.rope import apply_rope

Params = Dict[str, jnp.ndarray]


def _local_forward(params: Params, tokens: jnp.ndarray,
                   config: ModelConfig, sp_axis: str) -> jnp.ndarray:
    """Per-device body: local activations, ring attention for mixing.

    tokens: [B_local, T_local] — this device's slice of the batch and
    sequence. Positions are global: sp shard i covers
    [i*T_local, (i+1)*T_local).
    """
    nh, nkv, d = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    b, t = tokens.shape
    idx = jax.lax.axis_index(sp_axis)
    positions = idx * t + jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))

    x = params["embed"][tokens]

    layer_params = {
        k: params[k] for k in (
            "attn_norm", "wq", "wk", "wv", "wo",
            "mlp_norm", "w_gate", "w_up", "w_down",
        )
    }

    def layer_step(x, lp):
        a_in = _rms_norm(x, lp["attn_norm"], config.rms_norm_eps)
        q = apply_rope((a_in @ lp["wq"]).reshape(b, t, nh, d),
                       positions, config.rope_theta)
        k = apply_rope((a_in @ lp["wk"]).reshape(b, t, nkv, d),
                       positions, config.rope_theta)
        v = (a_in @ lp["wv"]).reshape(b, t, nkv, d)
        attn = ring_attention(q, k, v, axis_name=sp_axis, causal=True)
        x = x + attn.reshape(b, t, nh * d) @ lp["wo"]
        m_in = _rms_norm(x, lp["mlp_norm"], config.rms_norm_eps)
        x = x + (jax.nn.silu(m_in @ lp["w_gate"])
                 * (m_in @ lp["w_up"])) @ lp["w_down"]
        return x, None

    x, _ = jax.lax.scan(layer_step, x, layer_params)
    x = _rms_norm(x, params["final_norm"], config.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return (x @ head).astype(jnp.float32)


def context_parallel_forward(params: Params, config: ModelConfig,
                             tokens: jnp.ndarray, mesh: Mesh,
                             sp_axis: str = "sp",
                             dp_axis: Optional[str] = "dp",
                             ) -> jnp.ndarray:
    """Dense causal forward (same numerics as ``llama.forward_train``)
    with sequence sharded over ``sp`` and batch over ``dp``.

    tokens: global [B, T]; T must divide by the sp-axis size, B by the
    dp-axis size (if present in the mesh). Returns global logits
    [B, T, vocab] (sharded the same way).
    """
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axis = dp_axis if (dp_axis and dp_axis in axes
                             and axes[dp_axis] > 1) else None
    tok_spec = P(batch_axis, sp_axis)
    out_spec = P(batch_axis, sp_axis, None)

    fn = jax.shard_map(
        partial(_local_forward, config=config, sp_axis=sp_axis),
        mesh=mesh,
        in_specs=(P(), tok_spec),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(params, tokens)
