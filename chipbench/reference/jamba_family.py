"""A plain float32 forward pass of the Jamba family of hybrid decoders
(dense: ``num_experts`` 1), written from the layer equations (the
published Jamba block, ``JambaForCausalLM`` and its ``config.json``)
and independent of the program's ``models/jamba.py`` and ``ops/``.

Layer ``i`` is attention when ``i % attn_layer_period ==
attn_layer_offset`` and a Mamba-1 mixer otherwise. All norms are plain,
``norm(x; w) = x / sqrt(mean(x^2) + eps) * w``: before each mixer,
before each MLP, the final one, and the mixer's three small ones.

- Block: ``x <- x + Mix(norm(x))``, ``x <- x + MLP(norm(x))``,
  ``MLP(u) = (SiLU(u W_gate) * (u W_up)) W_down``; logits
  ``norm(x) E^T`` with the embedding ``E`` where the head is tied.
- Attention: ``q = u W_q`` as heads of ``d``, ``k = u W_k``, ``v = u
  W_v`` as KV heads of ``d``, no bias, NO positional encoding of any
  kind; causal softmax attention scaled ``d^-1/2``, query head ``h``
  reading key-value head ``h // (heads / kv_heads)``; ``W_o``.
- Mamba mixer, ``d_inner`` channels with ``d_state`` numbers each:
  ``[xs, z] = u W_in``; ``xs = SiLU(conv(xs) + b_conv)``, a depthwise
  causal convolution of width ``d_conv`` over zero history;
  ``[dt, B, C] = xs W_x`` split ``dt_rank | d_state | d_state``;
  ``dt, B, C <- norm_dt(dt), norm_B(B), norm_C(C)`` (Jamba's own);
  ``delta = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; token by
  token from ``h_0 = 0``: ``h = exp(delta (x) A) * h + (delta * xs)
  (x) B``, ``y = h C + D * xs``; output ``(y * SiLU(z)) W_out``.

Departures from the published model: none in the forward pass. The
weights are random (``program_model`` takes the program's init as
data, and turns its ``A_log``, which the program keeps ``[d_state,
d_inner]``, back to the published ``[d_inner, d_state]``) and the
tokenizer is the benchmark's word-level one.

No cache, no kernels, no batching: one sequence, one full forward,
every product under ``jax.default_matmul_precision("highest")``. A long
prompt stays inside the host: the scan goes token by token and never
holds more than one token's ``[d_inner, d_state]`` beside the state,
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

QUERY_BLOCK = 512


@dataclasses.dataclass
class Model:
    layer_is_mamba: tuple
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rms_eps: float
    dt_rank: int
    d_state: int
    embed: jnp.ndarray                  # [vocab, hidden]
    final_norm: jnp.ndarray             # [hidden]
    lm_head: Optional[jnp.ndarray]      # [hidden, vocab]; None = tied
    # layer(i) -> dict of float32 arrays (see split_layer).
    layer: Callable[[int], dict]


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


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


def attention(m: Model, w: dict, x):
    t = x.shape[0]
    q = (x @ w["w_q"]).reshape(t, m.num_heads, m.head_dim)
    k = (x @ w["w_k"]).reshape(t, m.num_kv_heads, m.head_dim)
    v = (x @ w["w_v"]).reshape(t, m.num_kv_heads, m.head_dim)
    return causal_attention(q, k, v).reshape(t, -1) @ w["w_o"]


def causal_conv(x, w):
    """x: [T, C]; w: [K, C], ``w[K-1]`` on the current token; zeros
    before the sequence."""
    kk, t = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((kk - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + t] * w[j] for j in range(kk))


def selective_scan(xs, delta, a, b, c):
    """Token by token. xs, delta: [T, d_inner]; a: [d_inner, d_state];
    b, c: [T, d_state]. Returns ``h_t C_t``: [T, d_inner]."""

    def step(h, token):
        x_t, delta_t, b_t, c_t = token
        h = (jnp.exp(delta_t[:, None] * a) * h
             + (delta_t * x_t)[:, None] * b_t[None, :])
        return h, h @ c_t

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32),
                        (xs, delta, b, c))
    return y


def output_gate(y, z):
    return y * jax.nn.silu(z)


def mamba_mixer(m: Model, w: dict, x):
    d_inner = w["A_log"].shape[0]
    xz = x @ w["w_in"]
    xs, z = xz[:, :d_inner], xz[:, d_inner:]
    xs = jax.nn.silu(causal_conv(xs, w["conv"]) + w["conv_bias"])
    dbc = xs @ w["w_x"]
    r, n = m.dt_rank, m.d_state
    dt = norm(dbc[:, :r], w["dt_norm"], m.rms_eps)
    b = norm(dbc[:, r:r + n], w["b_norm"], m.rms_eps)
    c = norm(dbc[:, r + n:], w["c_norm"], m.rms_eps)
    delta = jax.nn.softplus(dt @ w["w_dt"] + w["dt_bias"])
    y = selective_scan(xs, delta, -jnp.exp(w["A_log"]), b, c)
    return output_gate(y + w["D"] * xs, z) @ w["w_out"]


def mlp(w: dict, x):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def forward_hidden(m: Model, tokens):
    """The final norm's input after every layer: [T, hidden]."""
    x = m.embed[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for i, is_mamba in enumerate(m.layer_is_mamba):
        w = m.layer(i)
        u = norm(x, w["input_norm"], m.rms_eps)
        x = x + (mamba_mixer(m, w, u) if is_mamba else attention(m, w, u))
        x = x + mlp(w, norm(x, w["pre_ff_norm"], m.rms_eps))
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


def split_layer(layer_is_mamba: tuple, params: dict, i: int) -> dict:
    """Layer ``i`` of the program's parameter stacks under this file's
    names, float32, ``x | z`` and ``dt | B | C`` left fused as
    published, ``A_log`` turned back to ``[d_inner, d_state]``."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    w = {"input_norm": f32(params["attn_norm"][i]),
         "pre_ff_norm": f32(params["mlp_norm"][i]),
         "w_gate": f32(params["w_gate"][i]),
         "w_up": f32(params["w_up"][i]),
         "w_down": f32(params["w_down"][i])}
    j = layer_is_mamba[:i].count(layer_is_mamba[i])
    if layer_is_mamba[i]:
        w.update({
            "w_in": f32(params["m_in"][j]),
            "conv": f32(params["m_conv"][j]),
            "conv_bias": f32(params["m_conv_b"][j]),
            "w_x": f32(params["m_x"][j]),
            "dt_norm": f32(params["m_dt_norm"][j]),
            "b_norm": f32(params["m_b_norm"][j]),
            "c_norm": f32(params["m_c_norm"][j]),
            "w_dt": f32(params["m_dt"][j]),
            "dt_bias": f32(params["m_dt_b"][j]),
            "A_log": f32(params["m_A_log"][j]).T,
            "D": f32(params["m_D"][j]),
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
    pattern = tuple(i % c.attn_layer_period != c.attn_layer_offset
                    for i in range(c.num_hidden_layers))
    return Model(
        layer_is_mamba=pattern, num_heads=c.num_attention_heads,
        num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
        rms_eps=c.rms_norm_eps, dt_rank=c.mamba_dt_rank,
        d_state=c.mamba_d_state,
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params.get("lm_head"),
        layer=lambda i: split_layer(pattern, params, i))


def program_model(hf_config: dict, bench: dict) -> Model:
    """The server's random weights for this configuration (``bench``:
    its ``chipbench`` group), by the server's own init from
    ``weights_seed``, which is data here: nothing else of the program
    is used."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models.registry import get_model

    if bench["quantization"] != "none":
        raise ValueError("the Jamba family's reference takes weights "
                         "that are not quantized")
    config = ModelConfig.from_hf_config(hf_config)
    config.dtype = bench["dtype"]  # the server's --dtype: the init casts
    init_fn, _ = get_model(config)
    params = init_fn(config, jax.random.PRNGKey(bench["weights_seed"]))
    return model_of(config, params)
