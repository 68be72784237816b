"""A plain float32 forward pass of the Qwen3-Next family of hybrid
decoders, written from the layer equations (the published
``Qwen3NextForCausalLM`` and its ``config.json``) and independent of the
program's ``models/qwen3_next.py`` and ``ops/``.

48 layers in the published model, layer ``i`` gated full attention when
``(i + 1) % full_attention_interval == 0`` and Gated DeltaNet
otherwise. All norms in float32. ``norm(x; w) = x / sqrt(mean(x^2) +
eps) * (1 + w)`` (zero-centred weight): input norm, post-attention
norm, final norm, per-head ``q_norm`` and ``k_norm``.

- Block: ``x <- x + Mix(norm(x))``, ``x <- x + MoE(norm(x))``; logits
  ``norm(x) W_head``, head untied.
- Gated full attention: ``W_q`` gives per head a query and a gate;
  ``q <- q_norm(q)``, ``k <- k_norm(W_k x)``, ``v = W_v x``; rotary
  embedding (rotate-half) on the first ``partial_rotary_factor * d``
  dimensions; causal softmax attention scaled ``d^-1/2``, query head
  ``h`` reading key-value head ``h // (heads / kv_heads)``; output
  ``W_o (attn * sigmoid(gate))``.
- Gated DeltaNet: ``q, k, v`` pass a depthwise causal convolution of
  width 4 (zero history, no bias) and SiLU; ``beta = sigmoid(b)``,
  ``alpha = exp(-exp(A_log) softplus(a + dt_bias))`` per value head;
  ``q, k`` L2-normalised (eps 1e-6), ``q`` scaled ``d_k^-1/2``, key
  head ``j // (Hv / Hk)`` serving value head ``j``; token by token
  from ``S_0 = 0``: ``S' = alpha_t S``, ``S = S' + k_t (x) (beta_t
  (v_t - S'^T k_t))``, ``o_t = S^T q_t``; output ``W_out flatten(w *
  o_t / sqrt(mean(o_t^2) + eps) * SiLU(z_t))``, the norm per head over
  ``d_v`` with a weight ``w`` that is not zero-centred.
- Sparse block: ``p = softmax(W_r x)`` over all experts; the
  ``top_k`` largest, their weights divided by their sum where
  ``norm_topk_prob``; ``y = sum p_e E_e(x) + sigmoid(w_s . x)
  E_shared(x)``, ``E(x) = W_down(SiLU(W_gate x) * W_up x)``. Expert by
  expert, the tokens that chose it go through it and no others (the
  choices are read on the host: the reference runs eagerly).

Departures from the published model: the multi-token-prediction module
of the checkpoint is not part of the forward pass and is left out. Of
the experts only ``[first_expert, first_expert + held)`` are given (one
chip's share of an expert-parallel deployment); a chosen expert that is
not held adds nothing, in the program alike, and the partial sum goes
on. The fused projections' layout is the program's own (q | gate,
q | k | v, b | a, gate | up) and ``program_model`` splits them.

No cache, no kernels, no batching, no chunks: one sequence, one full
forward, every product under ``jax.default_matmul_precision("highest")``.
``reference/check.py`` uses ``program_model`` and ``log_probs``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Model:
    layer_is_linear: tuple
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    rms_eps: float
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    top_k: int
    norm_topk: bool
    first_expert: int
    embed: jnp.ndarray                  # [vocab, hidden]
    final_norm: jnp.ndarray             # [hidden]
    lm_head: Optional[jnp.ndarray]      # [hidden, vocab]; None = tied
    # layer(i) -> dict of float32 arrays (see program_model).
    layer: Callable[[int], dict]


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def partial_rope(x, theta, rotary_dim):
    """x: [T, heads, d], positions 0..T-1; the first ``rotary_dim``
    dimensions turn (rotate-half among themselves)."""
    t = x.shape[0]
    r = rotary_dim
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    turn = x[..., :r]
    rotated = jnp.concatenate([-turn[..., r // 2:], turn[..., :r // 2]], -1)
    return jnp.concatenate([turn * cos + rotated * sin, x[..., r:]], -1)


def causal_attention(q, k, v):
    """q: [T, heads, d]; k, v: [T, kv_heads, d]."""
    t, heads, d = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * d ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)


def gated_attention(m: Model, w: dict, x):
    t = x.shape[0]
    q = (x @ w["w_q"]).reshape(t, m.num_heads, m.head_dim)
    gate = (x @ w["w_q_gate"]).reshape(t, m.num_heads, m.head_dim)
    k = (x @ w["w_k"]).reshape(t, m.num_kv_heads, m.head_dim)
    v = (x @ w["w_v"]).reshape(t, m.num_kv_heads, m.head_dim)
    q = partial_rope(norm(q, w["q_norm"], m.rms_eps), m.rope_theta,
                     m.rotary_dim)
    k = partial_rope(norm(k, w["k_norm"], m.rms_eps), m.rope_theta,
                     m.rotary_dim)
    out = causal_attention(q, k, v) * jax.nn.sigmoid(gate)
    return out.reshape(t, -1) @ w["w_o"]


def causal_conv(x, w):
    """x: [T, C]; w: [K, C], ``w[K-1]`` on the current token; zeros
    before the sequence."""
    kk, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((kk - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + t] * w[j] for j in range(kk))


def l2norm(x, eps=1e-6):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def delta_rule(q, k, v, alpha, beta):
    """Token by token. q, k: [T, Hv, d_k]; v: [T, Hv, d_v];
    alpha, beta: [T, Hv]. Returns o [T, Hv, d_v]."""

    def step(s, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        s = a_t[:, None, None] * s
        read = jnp.einsum("hkv,hk->hv", s, k_t)
        s = s + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - read))
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    _, o = jax.lax.scan(step, s0, (q, k, v, alpha, beta))
    return o


def gated_delta_net(m: Model, w: dict, x):
    t = x.shape[0]
    hk, hv, dk, dv = m.key_heads, m.value_heads, m.key_dim, m.value_dim
    q = jax.nn.silu(causal_conv(x @ w["w_lin_q"], w["conv_q"]))
    k = jax.nn.silu(causal_conv(x @ w["w_lin_k"], w["conv_k"]))
    v = jax.nn.silu(causal_conv(x @ w["w_lin_v"], w["conv_v"]))
    z = (x @ w["w_lin_z"]).reshape(t, hv, dv)
    beta = jax.nn.sigmoid(x @ w["w_lin_b"])
    alpha = jnp.exp(-jnp.exp(w["A_log"])
                    * jax.nn.softplus(x @ w["w_lin_a"] + w["dt_bias"]))
    q = l2norm(q.reshape(t, hk, dk)) * dk ** -0.5
    k = l2norm(k.reshape(t, hk, dk))
    q = jnp.repeat(q, hv // hk, axis=1)
    k = jnp.repeat(k, hv // hk, axis=1)
    o = delta_rule(q, k, v.reshape(t, hv, dv), alpha, beta)
    o = (w["lin_norm"] * o
         * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + m.rms_eps)
         * jax.nn.silu(z))
    return o.reshape(t, hv * dv) @ w["w_lin_out"]


def expert(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def sparse_block(m: Model, w: dict, x):
    """x: [T, hidden], normalised."""
    p = jax.nn.softmax(x @ w["w_router"], axis=-1)           # all experts
    weight, chosen = jax.lax.top_k(p, m.top_k)
    if m.norm_topk:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    held = w["e_gate"].shape[0]
    local = np.asarray(chosen) - m.first_expert
    routed = jnp.zeros_like(x)
    # Expert by expert, the tokens that chose it and no others; a
    # chosen expert that is held elsewhere adds nothing.
    for e in np.unique(local[(local >= 0) & (local < held)]):
        token, slot = np.nonzero(local == e)
        out = expert(x[token], w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        routed = routed.at[token].add(weight[token, slot][:, None] * out)
    shared = expert(x, w["s_gate"], w["s_up"], w["s_down"])
    return (routed
            + jax.nn.sigmoid(x @ w["w_shared_gate"])[:, None] * shared)


def forward_hidden(m: Model, tokens):
    """The final-norm input after every layer: [T, hidden]."""
    x = m.embed[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for i, linear in enumerate(m.layer_is_linear):
        w = m.layer(i)
        h = norm(x, w["input_norm"], m.rms_eps)
        x = x + (gated_delta_net(m, w, h) if linear
                 else gated_attention(m, w, h))
        x = x + sparse_block(m, w, norm(x, w["post_norm"], m.rms_eps))
    return x


def log_probs(model: Model, tokens, positions):
    """Log-softmax over the vocabulary of the next token after each of
    ``positions`` (indices into ``tokens``): [len(positions), vocab]."""
    m = model
    with jax.default_matmul_precision("highest"):
        x = forward_hidden(m, tokens)[jnp.asarray(positions)]
        x = norm(x, m.final_norm.astype(jnp.float32), m.rms_eps)
        head = (m.embed.astype(jnp.float32).T if m.lm_head is None
                else m.lm_head.astype(jnp.float32))
        return jax.nn.log_softmax(x @ head, axis=-1)


def split_layer(config, params: dict, i: int) -> dict:
    """Layer ``i`` of the program's parameter stacks under this
    file's names, float32, the fused projections split."""
    c = config
    linear = c.layer_is_linear
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    f, fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
    gate_up, shared = f32(params[f"w_gate_up_{i}"]), f32(
        params["shared_gate_up"][i])
    w = {
        "input_norm": f32(params["attn_norm"][i]),
        "post_norm": f32(params["mlp_norm"][i]),
        "w_router": f32(params["router"][i]),
        "e_gate": gate_up[..., :f], "e_up": gate_up[..., f:],
        "e_down": f32(params[f"w_down_{i}"]),
        "s_gate": shared[:, :fs], "s_up": shared[:, fs:],
        "s_down": f32(params["shared_down"][i]),
        "w_shared_gate": f32(params["shared_gate"][i]),
    }
    if linear[i]:
        j = linear[:i].count(True)
        key = c.linear_num_key_heads * c.linear_key_head_dim
        hv = c.linear_num_value_heads
        qkv, conv = f32(params["gdn_qkv"][j]), f32(params["gdn_conv"][j])
        ba = f32(params["gdn_ba"][j])
        w.update({
            "w_lin_q": qkv[:, :key], "w_lin_k": qkv[:, key:2 * key],
            "w_lin_v": qkv[:, 2 * key:],
            "conv_q": conv[:, :key], "conv_k": conv[:, key:2 * key],
            "conv_v": conv[:, 2 * key:],
            "w_lin_z": f32(params["gdn_z"][j]),
            "w_lin_b": ba[:, :hv], "w_lin_a": ba[:, hv:],
            "A_log": f32(params["gdn_A_log"][j]),
            "dt_bias": f32(params["gdn_dt_bias"][j]),
            "lin_norm": f32(params["gdn_norm"][j]),
            "w_lin_out": f32(params["gdn_out"][j]),
        })
    else:
        j = linear[:i].count(False)
        qg = f32(params["wqg"][j])
        half = qg.shape[1] // 2
        w.update({
            "w_q": qg[:, :half], "w_q_gate": qg[:, half:],
            "w_k": f32(params["wk"][j]), "w_v": f32(params["wv"][j]),
            "w_o": f32(params["wo"][j]),
            "q_norm": f32(params["q_norm"][j]),
            "k_norm": f32(params["k_norm"][j]),
        })
    return w


def model_of(config, params: dict) -> Model:
    """``Model`` of the program's configuration object (read as data)
    and parameter values."""
    c = config
    return Model(
        layer_is_linear=tuple(c.layer_is_linear),
        num_heads=c.num_attention_heads,
        num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
        rotary_dim=int(c.head_dim * c.partial_rotary_factor),
        rope_theta=c.rope_theta, rms_eps=c.rms_norm_eps,
        key_heads=c.linear_num_key_heads,
        value_heads=c.linear_num_value_heads,
        key_dim=c.linear_key_head_dim, value_dim=c.linear_value_head_dim,
        top_k=c.num_experts_per_tok, norm_topk=c.norm_topk_prob,
        first_expert=c.expert_parallel_rank * c.num_experts,
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params.get("lm_head"),
        layer=lambda i: split_layer(c, params, i))


def program_model(hf_config: dict, bench: dict) -> Model:
    """The server's random weights for this configuration (``bench``:
    its ``chipbench`` group), by the server's own init from
    ``weights_seed``, which is data here: nothing else of the program
    is used."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models.registry import get_model

    if bench["quantization"] != "none":
        raise ValueError("the Qwen3-Next family's reference takes "
                         "weights that are not quantized")
    config = ModelConfig.from_hf_config(hf_config)
    config.dtype = bench["dtype"]  # the server's --dtype: the init casts
    init_fn, _ = get_model(config)
    params = init_fn(config, jax.random.PRNGKey(bench["weights_seed"]))
    return model_of(config, params)
