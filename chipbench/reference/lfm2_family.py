"""A plain float32 forward pass of the LFM2-MoE family of hybrid
decoders (LiquidAI LFM2-8B-A1B), written from the layer equations (the
published ``lfm2_moe`` modelling code and its ``config.json``) and
independent of the program's ``models/lfm2_moe.py`` and ``ops/``.

``layer_types`` lists each layer as ``conv`` or ``full_attention``; the
first ``num_dense_layers`` feed-forwards are dense, the others routed
experts. All norms are plain, ``norm(x; w) = x / sqrt(mean(x^2) + eps)
* w``: ``operator_norm`` before each operator, ``ffn_norm`` before each
feed-forward, ``embedding_norm`` after the last layer, and per head of
64 dimensions ``q_layernorm`` and ``k_layernorm``.

- Layer: ``h <- h + op(norm(h))``, ``h <- h + ffn(norm(h))``; logits
  ``norm(h) E^T`` with the embedding ``E`` (the head is tied).
- ``conv``: ``B | C | x = u W_in`` (hidden -> 3 x hidden, no bias);
  ``z = B * x``; ``c_t = sum_j w[j] z_{t-(K-1)+j}``, a depthwise causal
  convolution of ``K = conv_L_cache`` taps over zero history, no bias,
  NO activation; output ``(C * c) W_out``.
- ``full_attention``: ``q = u W_q`` as heads of ``d``, ``k = u W_k``,
  ``v = u W_v`` as KV heads, no bias; ``q <- q_layernorm(q)``, ``k <-
  k_layernorm(k)`` over each head's ``d``; a full rotary embedding
  (rotate-half, base ``rope_theta``) on q and k; causal softmax
  attention scaled ``d^-1/2``, query head ``h`` reading key-value head
  ``h // (heads / kv_heads)``; ``W_o``.
- Dense feed-forward: ``(SiLU(u W_gate) * (u W_up)) W_down``.
- Expert layer: ``s = sigmoid(u W_r)`` over ALL experts; the
  ``top_k`` experts with the largest ``s + expert_bias`` are chosen;
  their weights are the UNBIASED ``s`` of the chosen, divided by their
  sum + 1e-6 (the published code adds the 1e-6; its
  ``routed_scaling_factor`` is 1 and the program refuses another, as it
  refuses ``use_expert_bias`` or ``norm_topk_prob`` false, so
  ``use_expert_bias`` and ``norm_topk`` below are the tests' levers and
  no configuration's); ``y = sum w_e E_e(u)``,
  ``E(u) = W_down(SiLU(W_gate u) * W_up u)``. Expert by expert, the
  tokens that chose it go through it and no others (the choices are
  read on the host: the reference runs eagerly). No shared expert.

Departures from the published model: of the experts only
``[first_expert, first_expert + held)`` are given (one chip's share of
an expert-parallel deployment); a chosen expert that is not held adds
nothing, in the program alike, and the partial sum goes on. The experts'
gate | up layout is the program's own and ``split_layer`` splits it.
The weights are random (``program_model`` takes the program's init as
data) and the tokenizer is the benchmark's word-level one.

No cache, no kernels, no batching, no chunks: one sequence, one full
forward, every product under ``jax.default_matmul_precision("highest")``;
attention goes a block of queries at a time, the layers' weights are
made float32 one layer at a time, and the head is applied at the asked
positions alone. ``reference/check.py`` uses ``program_model`` and
``log_probs``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


@dataclasses.dataclass
class Model:
    layer_is_conv: tuple
    num_dense_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    rms_eps: float
    top_k: int
    norm_topk: bool
    use_expert_bias: bool
    first_expert: int
    embed: jnp.ndarray                  # [vocab, hidden]
    final_norm: jnp.ndarray             # [hidden]
    lm_head: Optional[jnp.ndarray]      # [hidden, vocab]; None = tied
    # layer(i) -> dict of float32 arrays (see split_layer).
    layer: Callable[[int], dict]


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: [T, heads, d], positions 0..T-1; every dimension turns
    (rotate-half)."""
    t, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


def causal_attention(q, k, v):
    """q: [T, heads, d]; k, v: [T, kv_heads, d]. A block of queries at
    a time over the keys up to each."""
    t, heads, d = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        hi = min(t, lo + QUERY_BLOCK)
        scores = jnp.einsum("thd,shd->hts", q[lo:hi], k[:hi]) * d ** -0.5
        causal = (jnp.arange(hi)[None, :]
                  <= jnp.arange(lo, hi)[:, None])
        scores = jnp.where(causal[None], scores, -jnp.inf)
        out.append(jnp.einsum("hts,shd->thd",
                              jax.nn.softmax(scores, -1), v[:hi]))
    return jnp.concatenate(out)


def q_layernorm(m: Model, w: dict, q):
    return norm(q, w["q_layernorm"], m.rms_eps)


def k_layernorm(m: Model, w: dict, k):
    return norm(k, w["k_layernorm"], m.rms_eps)


def attention(m: Model, w: dict, x):
    t = x.shape[0]
    q = (x @ w["w_q"]).reshape(t, m.num_heads, m.head_dim)
    k = (x @ w["w_k"]).reshape(t, m.num_kv_heads, m.head_dim)
    v = (x @ w["w_v"]).reshape(t, m.num_kv_heads, m.head_dim)
    q = rope(q_layernorm(m, w, q), m.rope_theta)
    k = rope(k_layernorm(m, w, k), m.rope_theta)
    return causal_attention(q, k, v).reshape(t, -1) @ w["w_o"]


def causal_conv(x, w):
    """x: [T, C]; w: [K, C], ``w[K-1]`` on the current token; zeros
    before the sequence."""
    kk, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((kk - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + t] * w[j] for j in range(kk))


def gate_in(b, x):
    return b * x


def gate_out(c, y):
    return c * y


def short_conv(w: dict, x):
    hidden = x.shape[1]
    bcx = x @ w["w_in"]
    b, c, xs = (bcx[:, :hidden], bcx[:, hidden:2 * hidden],
                bcx[:, 2 * hidden:])
    return gate_out(c, causal_conv(gate_in(b, xs), w["conv"])) @ w["w_out"]


def mlp(w: dict, x):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def expert(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def router_scores(logits):
    return jax.nn.sigmoid(logits)


def choose(m: Model, w: dict, scores):
    """(weights [T, k], ids [T, k]): chosen by score + bias, weighed by
    the score alone."""
    by = scores + w["expert_bias"] if m.use_expert_bias else scores
    _, chosen = jax.lax.top_k(by, m.top_k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    if m.norm_topk:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-6)
    return weight, chosen


def sparse_block(m: Model, w: dict, x):
    """x: [T, hidden], normalised."""
    weight, chosen = choose(m, w, router_scores(x @ w["w_router"]))
    held = w["e_gate"].shape[0]
    local = np.asarray(chosen) - m.first_expert
    routed = jnp.zeros_like(x)
    # Expert by expert, the tokens that chose it and no others; a
    # chosen expert that is held elsewhere adds nothing.
    for e in np.unique(local[(local >= 0) & (local < held)]):
        token, slot = np.nonzero(local == e)
        out = expert(x[token], w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        routed = routed.at[token].add(weight[token, slot][:, None] * out)
    return routed


def forward_hidden(m: Model, tokens):
    """The final norm's input after every layer: [T, hidden]."""
    x = m.embed[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for i, is_conv in enumerate(m.layer_is_conv):
        w = m.layer(i)
        u = norm(x, w["operator_norm"], m.rms_eps)
        x = x + (short_conv(w, u) if is_conv else attention(m, w, u))
        u = norm(x, w["ffn_norm"], m.rms_eps)
        x = x + (mlp(w, u) if i < m.num_dense_layers
                 else sparse_block(m, w, u))
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
    """Layer ``i`` of the program's parameter stacks under this file's
    names, float32, ``B | C | x`` left fused as published, the experts'
    gate | up split."""
    c = config
    conv = tuple(c.layer_is_linear)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    w = {"operator_norm": f32(params["op_norm"][i]),
         "ffn_norm": f32(params["ffn_norm"][i])}
    j = conv[:i].count(conv[i])
    if conv[i]:
        w.update({"w_in": f32(params["c_in"][j]),
                  "conv": f32(params["c_conv"][j]),
                  "w_out": f32(params["c_out"][j])})
    else:
        w.update({"w_q": f32(params["wq"][j]), "w_k": f32(params["wk"][j]),
                  "w_v": f32(params["wv"][j]), "w_o": f32(params["wo"][j]),
                  "q_layernorm": f32(params["q_norm"][j]),
                  "k_layernorm": f32(params["k_norm"][j])})
    if i < c.num_dense_layers:
        w.update({"w_gate": f32(params["w_gate"][i]),
                  "w_up": f32(params["w_up"][i]),
                  "w_down": f32(params["w_down"][i])})
    else:
        r = i - c.num_dense_layers
        f = c.moe_intermediate_size
        gate_up = f32(params[f"w_gate_up_{i}"])
        w.update({"w_router": f32(params["router"][r]),
                  "expert_bias": f32(params["expert_bias"][r]),
                  "e_gate": gate_up[..., :f], "e_up": gate_up[..., f:],
                  "e_down": f32(params[f"w_down_{i}"])})
    return w


def model_of(config, params: dict) -> Model:
    """``Model`` of the program's configuration object (read as data)
    and parameter values."""
    c = config
    return Model(
        layer_is_conv=tuple(kind == "conv" for kind in c.layer_types),
        num_dense_layers=c.num_dense_layers,
        num_heads=c.num_attention_heads,
        num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
        rope_theta=c.rope_theta, rms_eps=c.rms_norm_eps,
        top_k=c.num_experts_per_tok, norm_topk=True, use_expert_bias=True,
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
        raise ValueError("the LFM2-MoE family's reference takes weights "
                         "that are not quantized")
    config = ModelConfig.from_hf_config(hf_config)
    config.dtype = bench["dtype"]  # the server's --dtype: the init casts
    init_fn, _ = get_model(config)
    params = init_fn(config, jax.random.PRNGKey(bench["weights_seed"]))
    return model_of(config, params)
