"""A plain float32 forward pass of the Granite-MoE-hybrid family of
decoders (IBM Granite-4.0-H), written from the layer equations (the
published ``granitemoehybrid`` modelling code and its ``config.json``)
and independent of the program's ``models/granitemoehybrid.py`` and
``ops/``.

``layer_types`` lists each layer as ``mamba`` or ``attention``. All
norms are plain, ``norm(x; w) = x / sqrt(mean(x^2) + eps) * w``.

- Model: ``h = E[ids] * embedding_multiplier``; the layers; logits
  ``(norm(h) E^T) / logits_scaling`` with the embedding ``E`` (the head
  is tied).
- Layer: ``h <- h + residual_multiplier * mix(norm(h))``,
  ``h <- h + residual_multiplier * (moe(u) + shared(u))`` with
  ``u = norm(h)``. Every layer has the experts and the shared expert.
- ``attention``: ``q = u W_q`` as heads of ``d``, ``k = u W_k``, ``v =
  u W_v`` as KV heads, no bias, NO positional encoding of any kind;
  causal softmax attention with the scores scaled by
  ``attention_multiplier`` (NOT ``d^-1/2``), query head ``h`` reading
  key-value head ``h // (heads / kv_heads)``; ``W_o``.
- ``mamba`` (a Mamba-2 mixer), ``heads`` heads of ``d_head`` channels,
  each channel with ``d_state`` numbers: ``z | xBC | dt = u W_in``
  (widths ``heads * d_head | heads * d_head + 2 * d_state | heads``);
  ``xBC = SiLU(conv(xBC) + b_conv)``, a depthwise causal convolution
  of width ``d_conv`` over zero history; ``x | B | C = xBC``; ``dt =
  softplus(dt + dt_bias)`` a head; ``A = -exp(A_log)`` a head; token by
  token from ``h_0 = 0``, per head ``p``: ``h = exp(dt[p] A[p]) h +
  (dt[p] x[p]) (outer) B`` (``h`` is ``[d_head, d_state]``), ``y[p] = h
  C + D[p] x[p]``; ``B`` and ``C`` are shared by all heads (one group);
  ``y = norm(y * SiLU(z); w_norm)`` over all channels; ``W_out``.
- Experts: ``l = u W_r`` over ALL published experts; the ``top_k``
  largest; ``gates = softmax`` over those ``top_k`` logits; ``y = sum
  g_e E_e(u)``, ``E(u) = W_down(SiLU(W_gate u) * W_up u)``. Expert by
  expert, the tokens that chose it go through it and no others (the
  choices are read on the host: the reference runs eagerly). Shared
  expert: the same form, added with NO gate on its output.

Departures from the published model: of the experts only
``[first_expert, first_expert + held)`` are given (one chip's share of
an expert-parallel deployment); a chosen expert that is not held adds
nothing, in the program alike, and the partial sum goes on. The state
here is ``[heads, d_head, d_state]`` as published, not the program's
layout; the experts' gate | up layout is the program's own and
``split_layer`` splits it. The weights are random (``program_model``
takes the program's init as data) and the tokenizer is the benchmark's
word-level one.

No cache, no kernels, no batching, no chunks: one sequence, one full
forward, the recurrence token by token and never in its matrix form,
every product under ``jax.default_matmul_precision("highest")``;
attention goes a block of queries at a time, the layers' weights are
made float32 one layer at a time, and the head is applied at the asked
positions alone. ``reference/check.py`` uses ``program_model`` and
``log_probs``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


@dataclasses.dataclass
class Model:
    layer_is_mamba: tuple
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rms_eps: float
    mamba_heads: int
    mamba_d_head: int
    d_state: int
    top_k: int
    first_expert: int
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    embed: jnp.ndarray                  # [vocab, hidden]
    final_norm: jnp.ndarray             # [hidden]
    # layer(i) -> dict of float32 arrays (see split_layer).
    layer: Callable[[int], dict]


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def causal_attention(q, k, v, scale):
    """q: [T, heads, d]; k, v: [T, kv_heads, d]. A block of queries at
    a time over the keys up to each."""
    t, heads, _ = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(t, lo + QUERY_BLOCK)
        scores = jnp.einsum("thd,shd->hts", q[lo:hi], k[:hi]) * scale
        causal = (jnp.arange(hi)[None, :]
                  <= jnp.arange(lo, hi)[:, None])
        scores = jnp.where(causal[None], scores, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd",
                              jax.nn.softmax(scores, -1), v[:hi]))
    return jnp.concatenate(out)


def attention(m: Model, w: dict, x):
    t = x.shape[0]
    q = (x @ w["w_q"]).reshape(t, m.num_heads, m.head_dim)
    k = (x @ w["w_k"]).reshape(t, m.num_kv_heads, m.head_dim)
    v = (x @ w["w_v"]).reshape(t, m.num_kv_heads, m.head_dim)
    return causal_attention(q, k, v, m.attention_multiplier
                            ).reshape(t, -1) @ w["w_o"]


def causal_conv(x, w):
    """x: [T, C]; w: [K, C], ``w[K-1]`` on the current token; zeros
    before the sequence."""
    kk, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((kk - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + t] * w[j] for j in range(kk))


def state_space(x, dt, a, b, c):
    """Token by token. x: [T, heads, d_head]; dt: [T, heads]; a:
    [heads]; b, c: [T, d_state]. Returns ``h_t C_t``: [T, heads,
    d_head]."""

    def step(h, token):
        x_t, dt_t, b_t, c_t = token
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t)
        return h, h @ c_t

    heads, d_head = x.shape[1:]
    _, y = jax.lax.scan(
        step, jnp.zeros((heads, d_head, b.shape[-1]), jnp.float32),
        (x, dt, b, c))
    return y


def output_gate(y, z):
    return y * jax.nn.silu(z)


def mamba_mixer(m: Model, w: dict, x):
    t = x.shape[0]
    d_inner = m.mamba_heads * m.mamba_d_head
    n = m.d_state
    zxd = x @ w["w_in"]
    z, xbc, dt = (zxd[:, :d_inner], zxd[:, d_inner:2 * d_inner + 2 * n],
                  zxd[:, 2 * d_inner + 2 * n:])
    xbc = jax.nn.silu(causal_conv(xbc, w["conv"]) + w["conv_bias"])
    xs = xbc[:, :d_inner].reshape(t, m.mamba_heads, m.mamba_d_head)
    b, c = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y = state_space(xs, dt, -jnp.exp(w["A_log"]), b, c)
    y = (y + w["D"][:, None] * xs).reshape(t, d_inner)
    return norm(output_gate(y, z), w["gated_norm"], m.rms_eps) @ w["w_out"]


def expert(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def choose(m: Model, logits):
    """(gates [T, k], ids [T, k]): the ``top_k`` largest logits and the
    softmax over those alone."""
    top, chosen = jax.lax.top_k(logits, m.top_k)
    return jax.nn.softmax(top, axis=-1), chosen


def shared_expert(w: dict, x):
    return expert(x, w["s_gate"], w["s_up"], w["s_down"])


def sparse_block(m: Model, w: dict, x):
    """x: [T, hidden], normalised: the held experts' part of the routed
    sum, and the shared expert whole."""
    gate, chosen = choose(m, x @ w["w_router"])
    held = w["e_gate"].shape[0]
    local = np.asarray(chosen) - m.first_expert
    routed = jnp.zeros_like(x)
    # Expert by expert, the tokens that chose it and no others; a
    # chosen expert that is held elsewhere adds nothing.
    for e in np.unique(local[(local >= 0) & (local < held)]):
        token, slot = np.nonzero(local == e)
        out = expert(x[token], w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        routed = routed.at[token].add(gate[token, slot][:, None] * out)
    return routed + shared_expert(w, x)


def forward_hidden(m: Model, tokens):
    """The final norm's input after every layer: [T, hidden]."""
    x = (m.embed[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
         * m.embedding_multiplier)
    for i, is_mamba in enumerate(m.layer_is_mamba):
        w = m.layer(i)
        u = norm(x, w["input_norm"], m.rms_eps)
        x = x + m.residual_multiplier * (
            mamba_mixer(m, w, u) if is_mamba else attention(m, w, u))
        u = norm(x, w["post_norm"], m.rms_eps)
        x = x + m.residual_multiplier * sparse_block(m, w, u)
    return x


def log_probs(model: Model, tokens, positions):
    """Log-softmax over the vocabulary of the next token after each of
    ``positions`` (indices into ``tokens``): [len(positions), vocab]."""
    m = model
    with jax.default_matmul_precision("highest"):
        x = forward_hidden(m, tokens)[jnp.asarray(positions)]
        x = norm(x, m.final_norm.astype(jnp.float32), m.rms_eps)
        logits = x @ m.embed.astype(jnp.float32).T / m.logits_scaling
        return jax.nn.log_softmax(logits, axis=-1)


def split_layer(config, params: dict, i: int) -> dict:
    """Layer ``i`` of the program's parameter stacks under this file's
    names, float32, ``z | xBC | dt`` left fused as published, gate | up
    split."""
    c = config
    mamba = tuple(kind == "mamba" for kind in c.layer_types)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    fe, fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
    gate_up = f32(params[f"w_gate_up_{i}"])
    shared = f32(params["shared_gate_up"][i])
    w = {"input_norm": f32(params["attn_norm"][i]),
         "post_norm": f32(params["ffn_norm"][i]),
         "w_router": f32(params["router"][i]),
         "e_gate": gate_up[..., :fe], "e_up": gate_up[..., fe:],
         "e_down": f32(params[f"w_down_{i}"]),
         "s_gate": shared[:, :fs], "s_up": shared[:, fs:],
         "s_down": f32(params["shared_down"][i])}
    j = mamba[:i].count(mamba[i])
    if mamba[i]:
        w.update({
            "w_in": f32(params["m_in"][j]),
            "conv": f32(params["m_conv"][j]),
            "conv_bias": f32(params["m_conv_b"][j]),
            "dt_bias": f32(params["m_dt_b"][j]),
            "A_log": f32(params["m_A_log"][j]),
            "D": f32(params["m_D"][j]),
            "gated_norm": f32(params["m_norm"][j]),
            "w_out": f32(params["m_out"][j]),
        })
    else:
        w.update({"w_q": f32(params["wq"][j]), "w_k": f32(params["wk"][j]),
                  "w_v": f32(params["wv"][j]), "w_o": f32(params["wo"][j])})
    return w


def model_of(config, params: dict) -> Model:
    """``Model`` of the program's configuration object (read as data)
    and parameter values."""
    c = config
    if "lm_head" in params:
        raise ValueError("the family's head is the embedding")
    return Model(
        layer_is_mamba=tuple(kind == "mamba" for kind in c.layer_types),
        num_heads=c.num_attention_heads,
        num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
        rms_eps=c.rms_norm_eps, mamba_heads=c.mamba_n_heads,
        mamba_d_head=c.mamba_d_head, d_state=c.mamba_d_state,
        top_k=c.num_experts_per_tok,
        first_expert=c.expert_parallel_rank * c.num_experts,
        embedding_multiplier=c.embedding_multiplier,
        attention_multiplier=c.attention_multiplier,
        residual_multiplier=c.residual_multiplier,
        logits_scaling=c.logits_scaling,
        embed=params["embed"], final_norm=params["final_norm"],
        layer=lambda i: split_layer(c, params, i))


def program_model(hf_config: dict, bench: dict) -> Model:
    """The server's random weights for this configuration (``bench``:
    its ``chipbench`` group), by the server's own init from
    ``weights_seed``, which is data here: nothing else of the program
    is used."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models.registry import get_model

    if bench["quantization"] != "none":
        raise ValueError("the Granite-MoE-hybrid family's reference "
                         "takes weights that are not quantized")
    config = ModelConfig.from_hf_config(hf_config)
    config.dtype = bench["dtype"]  # the server's --dtype: the init casts
    init_fn, _ = get_model(config)
    params = init_fn(config, jax.random.PRNGKey(bench["weights_seed"]))
    return model_of(config, params)
