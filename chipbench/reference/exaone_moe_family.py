"""A plain float32 forward pass of the EXAONE-MoE family of decoders
(LGAI-EXAONE/K-EXAONE-236B-A23B), written from the layer equations and
independent of the program's ``models/exaone_moe.py`` and ``ops/``.

Sizes: hidden ``H``; ``n`` query heads over ``kv`` key/value heads of
``d``; window ``W``. All norms are plain, ``norm(x; w) = x /
sqrt(mean(x^2) + eps) * w``; SwiGLU is ``(silu(x W_g) * (x W_u)) W_d``.

- Layer, on ``x``: ``x = x + norm_post_attn(A(x))``, ``x = x +
  norm_post_ffn(F(x))``: the norm is on each sublayer's OUTPUT and
  there is none on its input (transformers' ``Exaone4DecoderLayer``).
  Model: embedding, ``num_hidden_layers`` layers, a final norm, an
  untied head.
- ``A``: ``q = x W_q`` as ``n`` heads, ``k = x W_k``, ``v = x W_v`` as
  ``kv`` heads, no bias; ``q`` and ``k`` through a norm over the ``d``
  of each head (``q_norm``, ``k_norm``); on a ``sliding_attention``
  layer, and on no other, both then turn by a rotary embedding over
  all ``d`` dimensions, half-split pairs (``x[i]`` with ``x[i +
  d/2]``), base ``rope_theta`` (``modeling_exaone4.py``: ``if
  self.sliding_window is None or self.is_sliding``). Scores ``q . k /
  sqrt(d)`` under an explicit ``[T, T]`` mask: key ``j`` is visible to
  query ``i`` iff ``j <= i`` and, on a ``sliding_attention`` layer,
  ``j > i - W`` (``W`` keys with the query's own:
  ``masking_utils.sliding_window_overlay``); softmax; ``o = (softmax
  v) W_o``.
- ``F`` in the first ``first_k_dense_replace`` layers: SwiGLU of
  ``intermediate_size``. In the others: ``s = sigmoid(x W_r)`` over
  all routed experts; the ``num_experts_per_tok`` largest of ``s + b``
  are chosen (``b``: ``e_score_correction_bias``, for the choice
  alone; ``n_group`` 1: no group limit); weights
  ``routed_scaling_factor * s_i / (sum of the chosen s + 1e-20)``;
  ``F(x) = sum_i w_i E_i(x) + E_shared(x)``. Expert by expert, the
  tokens that chose it go through it and no others (the choices are
  read on the host: the reference runs eagerly).

Departures from the published model: of the routed experts only
``[first_expert, first_expert + held)`` are given; a chosen expert
that is not held adds nothing, in the program alike (one chip of an
expert-parallel group without its exchange). The checkpoint's
multi-token-prediction layer is not made: it changes no logit of the
main model. The weights are random (``program_model`` takes the
program's init as data; gate | up, which the program keeps side by
side, are split again) and the tokenizer is the benchmark's word-level
one.

No cache, no ring, no kernels, no batching, no chunks: one sequence,
one full forward, every product under
``jax.default_matmul_precision("highest")``; attention goes a block of
queries at a time under the same mask, the weights are made float32 one
layer at a time, and the head is applied at the asked positions alone.
``reference/check.py`` uses ``program_model`` and ``log_probs``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
ROUTER_EPS = 1e-20


@dataclasses.dataclass
class Model:
    num_layers: int
    num_dense_layers: int
    windowed: tuple                     # per layer: a window's layer
    window: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    rms_eps: float
    top_k: int
    routed_scale: float
    first_expert: int
    embed: jnp.ndarray                  # [vocab, hidden]
    final_norm: jnp.ndarray             # [hidden]
    lm_head: jnp.ndarray                # [hidden, vocab]
    layer: Callable[[int], dict]        # layer(i) -> float32 arrays


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: [T, heads, d], positions 0..T-1; dimension i pairs with
    i + d/2 and the pair turns by t / theta^(2i/d)
    (``rotate_half``)."""
    t, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def visible(lo: int, hi: int, window: int):
    """[hi - lo, hi] bool: key ``j < hi`` to query ``i`` in ``[lo,
    hi)``; ``window`` 0: causal alone."""
    i = jnp.arange(lo, hi)[:, None]
    j = jnp.arange(hi)[None, :]
    mask = j <= i
    return mask & (j > i - window) if window else mask


def masked_attention(q, k, v, window: int):
    """q: [T, n, d]; k, v: [T, kv, d]; a block of queries at a time."""
    t, n, d = q.shape
    group = n // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(t, lo + QUERY_BLOCK)
        scores = jnp.einsum("thd,shd->hts", q[lo:hi], k[:hi]) * d ** -0.5
        scores = jnp.where(visible(lo, hi, window)[None], scores,
                           -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd",
                              jax.nn.softmax(scores, -1), v[:hi]))
    return jnp.concatenate(out)


def attention(m: Model, w: dict, x, windowed: bool):
    t, n, kv, d = x.shape[0], m.num_heads, m.num_kv_heads, m.head_dim
    q = norm((x @ w["w_q"]).reshape(t, n, d), w["q_norm"], m.rms_eps)
    k = norm((x @ w["w_k"]).reshape(t, kv, d), w["k_norm"], m.rms_eps)
    v = (x @ w["w_v"]).reshape(t, kv, d)
    if windowed:
        q, k = rope(q, m.rope_theta), rope(k, m.rope_theta)
    o = masked_attention(q, k, v, m.window if windowed else 0)
    return o.reshape(t, n * d) @ w["w_o"]


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def choose(m: Model, w: dict, scores):
    """(weights [T, k], ids [T, k]): chosen by score + bias, weighed by
    the score alone over the chosen scores' sum, times the scale."""
    _, chosen = jax.lax.top_k(scores + w["router_bias"], m.top_k)
    kept = jnp.take_along_axis(scores, chosen, axis=-1)
    return (m.routed_scale * kept
            / (jnp.sum(kept, -1, keepdims=True) + ROUTER_EPS), chosen)


def expert_block(m: Model, w: dict, x):
    """x: [T, hidden]."""
    weight, chosen = choose(m, w, jax.nn.sigmoid(x @ w["w_router"]))
    held = w["e_gate"].shape[0]
    local = np.asarray(chosen) - m.first_expert
    routed = jnp.zeros_like(x)
    # Expert by expert, the tokens that chose it and no others.
    for e in np.unique(local[(local >= 0) & (local < held)]):
        token, slot = np.nonzero(local == e)
        out = swiglu(x[token], w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        routed = routed.at[token].add(weight[token, slot][:, None] * out)
    return routed + swiglu(x, w["s_gate"], w["s_up"], w["s_down"])


def layer_forward(m: Model, i: int, x):
    w = m.layer(i)
    x = x + norm(attention(m, w, x, m.windowed[i]), w["post_attn_norm"],
                 m.rms_eps)
    if i < m.num_dense_layers:
        y = swiglu(x, w["w_gate"], w["w_up"], w["w_down"])
    else:
        y = expert_block(m, w, x)
    return x + norm(y, w["post_ffn_norm"], m.rms_eps)


def forward_hidden(m: Model, tokens):
    """The final norm's input after every layer: [T, hidden]."""
    x = m.embed[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for i in range(m.num_layers):
        x = layer_forward(m, i, x)
    return x


def log_probs(model: Model, tokens, positions):
    """Log-softmax over the vocabulary of the next token after each of
    ``positions`` (indices into ``tokens``): [len(positions), vocab]."""
    with jax.default_matmul_precision("highest"):
        x = forward_hidden(model, tokens)[jnp.asarray(positions)]
        x = norm(x, model.final_norm.astype(jnp.float32), model.rms_eps)
        return jax.nn.log_softmax(
            x @ model.lm_head.astype(jnp.float32), axis=-1)


def split_layer(config, params: dict, i: int) -> dict:
    """Layer ``i`` of the program's stacks under this file's names,
    float32, gate | up split."""
    c = config
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    out = {"w_q": f32(params[f"wq_{i}"]), "w_k": f32(params[f"wk_{i}"]),
           "w_v": f32(params[f"wv_{i}"]), "w_o": f32(params[f"wo_{i}"]),
           "q_norm": f32(params["q_norm"][i]),
           "k_norm": f32(params["k_norm"][i]),
           "post_attn_norm": f32(params["post_attn_norm"][i]),
           "post_ffn_norm": f32(params["post_ffn_norm"][i])}
    if i < c.num_dense_layers:
        f = c.intermediate_size
        gate_up = f32(params["w_gate_up"][i])
        out.update({"w_gate": gate_up[:, :f], "w_up": gate_up[:, f:],
                    "w_down": f32(params["w_down"][i])})
        return out
    fe, fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
    gate_up = f32(params[f"e_w_gate_up_{i}"])
    shared = f32(params[f"shared_gate_up_{i}"])
    out.update({"w_router": f32(params[f"router_{i}"]),
                "router_bias": f32(params[f"router_bias_{i}"]),
                "e_gate": gate_up[..., :fe], "e_up": gate_up[..., fe:],
                "e_down": f32(params[f"e_w_down_{i}"]),
                "s_gate": shared[:, :fs], "s_up": shared[:, fs:],
                "s_down": f32(params[f"shared_down_{i}"])})
    return out


def model_of(config, params: dict) -> Model:
    """``Model`` of the program's configuration object (read as data)
    and parameter values."""
    c = config
    return Model(
        num_layers=c.num_hidden_layers,
        num_dense_layers=c.num_dense_layers,
        windowed=tuple(kind == "sliding_attention"
                       for kind in c.layer_types),
        window=c.sliding_window,
        num_heads=c.num_attention_heads,
        num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
        rope_theta=c.rope_theta, rms_eps=c.rms_norm_eps,
        top_k=c.num_experts_per_tok,
        routed_scale=c.routed_scaling_factor,
        first_expert=c.expert_parallel_rank * c.num_experts,
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params["lm_head"],
        layer=lambda i: split_layer(c, params, i))


def program_model(hf_config: dict, bench: dict) -> Model:
    """The server's random weights for this configuration (``bench``:
    its ``chipbench`` group), by the server's own init from
    ``weights_seed``, which is data here: nothing else of the program
    is used. The values stay in the server's dtype (they are its
    values) and a layer is made float32 when the forward comes to
    it."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models.registry import get_model

    if bench["quantization"] != "none":
        raise ValueError("the EXAONE-MoE family's reference takes "
                         "weights that are not quantized")
    config = ModelConfig.from_hf_config(hf_config)
    config.dtype = bench["dtype"]  # the server's --dtype: the init casts
    init_fn, _ = get_model(config)
    params = init_fn(config, jax.random.PRNGKey(bench["weights_seed"]))
    return model_of(config, params)
