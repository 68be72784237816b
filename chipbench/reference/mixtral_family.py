"""A plain float32 forward pass of the Mixtral family of decoders
(sparse mixture of experts), written from the published description
(Jiang et al., "Mixtral of Experts", 2024, section 2.1) and independent
of the program's ``models/mixtral.py``.

The decoder is the Llama family's (``llama_family.py``: RMSNorm, rotary
embeddings, grouped-query causal attention, a final RMSNorm and an
output head) with the feed-forward of each layer replaced by ``E``
SwiGLU experts and a router: a token's router logits are its
normalised hidden state times ``moe_gate`` [hidden, E]; the ``k``
largest pick its experts, a softmax over those ``k`` logits alone
weighs them, and the layer's output is the weighted sum of the chosen
experts' outputs.  Here each token's experts are gathered and run for
that token only (the program runs every expert on every token and
masks).  No cache, no kernels, no batching: one sequence, one full
forward, every matrix product under
``jax.default_matmul_precision("highest")``.

``reference/check.py`` finds this module by the family a configuration
names and uses ``program_model`` and ``log_probs`` of it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from chipbench.reference.llama_family import attention, rms_norm, rope


@dataclasses.dataclass
class Model:
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    experts_per_token: int
    rms_eps: float
    rope_theta: float
    embed: jnp.ndarray                  # [vocab, hidden]
    final_norm: jnp.ndarray             # [hidden]
    lm_head: Optional[jnp.ndarray]      # [hidden, vocab]; None = tied
    # layer(i) -> dict of float32 arrays: attn_norm, wq, wk, wv, wo,
    # mlp_norm, moe_gate [hidden, E], w_gate and w_up [E, hidden, ffn],
    # w_down [E, ffn, hidden].
    layer: Callable[[int], dict]


def experts(h, w, k: int):
    """h: [T, hidden], normalised.  Each token through its ``k`` experts."""
    logits = h @ w["moe_gate"]                              # [T, E]
    chosen = jnp.argsort(-logits, axis=-1)[:, :k]           # [T, k]
    weight = jax.nn.softmax(
        jnp.take_along_axis(logits, chosen, axis=-1), axis=-1)
    out = jnp.zeros_like(h)
    for slot in range(k):
        e = chosen[:, slot]
        gate = jnp.einsum("th,thf->tf", h, w["w_gate"][e])
        up = jnp.einsum("th,thf->tf", h, w["w_up"][e])
        down = jnp.einsum("tf,tfh->th", jax.nn.silu(gate) * up,
                          w["w_down"][e])
        out = out + weight[:, slot, None] * down
    return out


def program_model(hf_config: dict, bench: dict) -> Model:
    """The server's random weights for this configuration (``bench``:
    its ``chipbench`` group), by the server's own init from
    ``weights_seed``, which is data here: nothing else of the program
    is used."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models.registry import get_model

    if bench["quantization"] != "none":
        raise ValueError("the Mixtral family's reference takes weights "
                         "that are not quantized")
    config = ModelConfig.from_hf_config(hf_config)
    config.dtype = bench["dtype"]  # the server's --dtype: the init casts
    init_fn, _ = get_model(config)
    params = init_fn(config, jax.random.PRNGKey(bench["weights_seed"]))
    per_layer = [k for k in params
                 if k not in ("embed", "final_norm", "lm_head")]
    return Model(
        num_layers=config.num_hidden_layers,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim,
        experts_per_token=config.num_experts_per_tok,
        rms_eps=config.rms_norm_eps, rope_theta=config.rope_theta,
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params.get("lm_head"),
        layer=lambda i: {k: params[k][i].astype(jnp.float32)
                         for k in per_layer})


def log_probs(model: Model, tokens, positions):
    """Log-softmax over the vocabulary of the next token after each of
    ``positions`` (indices into ``tokens``): [len(positions), vocab]."""
    m = model
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        x = m.embed[tokens].astype(jnp.float32)
        t = x.shape[0]
        for i in range(m.num_layers):
            w = m.layer(i)
            h = rms_norm(x, w["attn_norm"], m.rms_eps)
            q = rope((h @ w["wq"]).reshape(t, m.num_heads, m.head_dim),
                     m.rope_theta)
            k = rope((h @ w["wk"]).reshape(t, m.num_kv_heads, m.head_dim),
                     m.rope_theta)
            v = (h @ w["wv"]).reshape(t, m.num_kv_heads, m.head_dim)
            x = x + attention(q, k, v).reshape(t, -1) @ w["wo"]
            x = x + experts(rms_norm(x, w["mlp_norm"], m.rms_eps), w,
                            m.experts_per_token)
        x = rms_norm(x[jnp.asarray(positions)],
                     m.final_norm.astype(jnp.float32), m.rms_eps)
        head = (m.embed.astype(jnp.float32).T if m.lm_head is None
                else m.lm_head.astype(jnp.float32))
        return jax.nn.log_softmax(x @ head, axis=-1)
