"""A plain float32 forward pass of the LongCat-Flash family of
decoders (the language model of meituan-longcat/LongCat-Flash-Omni),
written from the layer equations and independent of the program's
``models/longcat_flash.py`` and ``ops/``.

Sizes: hidden ``H``; ``n`` heads; a query/key head is ``dn``
(``qk_nope_head_dim``) + ``dr`` (``qk_rope_head_dim``) wide, a value
head ``dv``; the query's rank ``rq`` (``q_lora_rank``), the latent's
``r`` (``kv_lora_rank``). All norms are plain, ``norm(x; w) = x /
sqrt(mean(x^2) + eps) * w``.

- Layer, on ``h`` (``N1..N4`` norms, ``A1, A2`` attention sublayers,
  ``F1, F2`` dense SwiGLU feed-forwards of ``ffn_hidden_size``)::

      h1 = h + A1(N1(h));  u = N2(h1);  m = moe(u);  h2 = h1 + F1(u)
      h3 = h2 + A2(N3(h2));  out = h3 + F2(N4(h3)) + m

  The expert branch reads the first sublayer's normalised output and
  comes back only at the layer's end. Model: embedding, ``num_layers``
  layers, a final norm, an untied head.
- Attention sublayer (MLA), materialised: ``q = s_q * (norm(x W_qa)
  W_qb)`` as ``n`` heads ``[q_nope | q_rope]``, ``s_q = sqrt(H / rq)``
  where ``mla_scale_q_lora``; ``[c_raw | k_r] = x W_kva``; ``c = s_kv *
  norm(c_raw)``, ``s_kv = sqrt(H / r)`` where ``mla_scale_kv_lora``;
  ``[k_nope | v] = c W_kvb`` as ``n`` heads of ``dn + dv``; one rotary
  key ``rotary(k_r)`` shared by every head and ``rotary(q_rope)`` a
  head, the pairs interleaved (``x[2i], x[2i+1]``), base ``rope_theta``;
  scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(dn + dr)``,
  causal softmax, ``o = sum p v``, output ``concat(o) W_o``.
- ``moe(u)``: ``s = softmax(u W_r)`` over ALL the router's outputs, the
  routed experts and after them ``zero_expert_num`` zero-compute ones;
  the ``moe_topk`` largest of ``s + b`` are chosen (``b`` a learned
  bias, used for the choice alone); their weights are
  ``routed_scaling_factor * s``, NOT renormalised; a routed expert is
  ``E(u) = W_down(SiLU(W_gate u) * W_up u)``, a zero-compute expert the
  identity: ``sum_routed w_e E_e(u) + (sum_zero w_e) u``. Expert by
  expert, the tokens that chose it go through it and no others (the
  choices are read on the host: the reference runs eagerly). No shared
  expert.

Departures from the published model: of the routed experts only
``[first_expert, first_expert + held)`` are given (one chip's share of
an expert-parallel deployment); a chosen routed expert that is not held
adds nothing, in the program alike, and the partial sum goes on; the
identity term is whole (it holds no weights, so every rank has it).
``W_kvb`` is given as the program keeps it, a head's ``W_UK`` and
``W_UV`` apart, and ``split_sublayer`` puts them side by side again; so
with the feed-forwards' gate | up. The weights are random
(``program_model`` takes the program's init as data) and the tokenizer
is the benchmark's word-level one.

No cache, no kernels, no batching, no chunks, no absorbed form: one
sequence, one full forward, every product under
``jax.default_matmul_precision("highest")``; attention goes a block of
queries at a time, the weights are made float32 one sublayer, or one
layer's experts, at a time, and the head is applied at the asked
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
    num_layers: int
    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    q_scale: float
    kv_scale: float
    rope_theta: float
    rms_eps: float
    top_k: int
    routed_scale: float
    first_expert: int
    first_zero_expert: int
    embed: jnp.ndarray                  # [vocab, hidden]
    final_norm: jnp.ndarray             # [hidden]
    lm_head: jnp.ndarray                # [hidden, vocab]
    # sublayer(j) / branch(i) -> dict of float32 arrays.
    sublayer: Callable[[int], dict]
    branch: Callable[[int], dict]


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: [T, heads, d], positions 0..T-1; pair i is (x[2i], x[2i+1])
    and turns by t / theta^(2i/d)."""
    t, _, d = x.shape
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def key_rope(m: Model, k_r):
    return rope(k_r, m.rope_theta)


def latent_norm(m: Model, w: dict, c_raw):
    return norm(c_raw, w["kv_a_norm"], m.rms_eps)


def causal_attention(q, k, v, scale):
    """q, k: [T, heads, d]; v: [T, heads, dv]. A block of queries at a
    time over the keys up to each."""
    t = q.shape[0]
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
    """One MLA sublayer, materialised: x [T, H] normalised."""
    t, n = x.shape[0], m.num_heads
    dn, dr, dv = m.nope_dim, m.rope_dim, m.v_dim
    q = m.q_scale * (norm(x @ w["w_qa"], w["q_a_norm"], m.rms_eps)
                     @ w["w_qb"]).reshape(t, n, dn + dr)
    kv = x @ w["w_kva"]
    rank = kv.shape[1] - dr
    c = m.kv_scale * latent_norm(m, w, kv[:, :rank])
    k_rope = key_rope(m, kv[:, None, rank:])            # [T, 1, dr]
    up = (c @ w["w_kvb"]).reshape(t, n, dn + dv)
    k = jnp.concatenate(
        [up[..., :dn], jnp.broadcast_to(k_rope, (t, n, dr))], -1)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], m.rope_theta)], -1)
    o = causal_attention(q, k, up[..., dn:], (dn + dr) ** -0.5)
    return o.reshape(t, n * dv) @ w["w_o"]


def mlp(w: dict, x):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def expert(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def choose(m: Model, w: dict, scores):
    """(weights [T, k], ids [T, k]): chosen by score + bias, weighed by
    the scaled score alone, not renormalised."""
    _, chosen = jax.lax.top_k(scores + w["router_bias"], m.top_k)
    return (m.routed_scale * jnp.take_along_axis(scores, chosen, axis=-1),
            chosen)


def identity_term(m: Model, weight, chosen, x):
    """The zero-compute experts' part: each is the identity."""
    zero = jnp.where(chosen >= m.first_zero_expert, weight, 0.0)
    return jnp.sum(zero, -1, keepdims=True) * x


def moe(m: Model, w: dict, x):
    """x: [T, hidden], normalised."""
    weight, chosen = choose(m, w, jax.nn.softmax(x @ w["w_router"], -1))
    held = w["e_gate"].shape[0]
    local = np.asarray(chosen) - m.first_expert
    routed = jnp.zeros_like(x)
    # Expert by expert, the tokens that chose it and no others; a
    # chosen routed expert that is held elsewhere adds nothing.
    for e in np.unique(local[(local >= 0) & (local < held)]):
        token, slot = np.nonzero(local == e)
        out = expert(x[token], w["e_gate"][e], w["e_up"][e], w["e_down"][e])
        routed = routed.at[token].add(weight[token, slot][:, None] * out)
    return routed + identity_term(m, weight, chosen, x)


def layer_forward(m: Model, i: int, h):
    a1, a2 = m.sublayer(2 * i), None
    h1 = h + attention(m, a1, norm(h, a1["attn_norm"], m.rms_eps))
    u = norm(h1, a1["ffn_norm"], m.rms_eps)
    h2 = h1 + mlp(a1, u)
    del a1
    branch = moe(m, m.branch(i), u)
    a2 = m.sublayer(2 * i + 1)
    h3 = h2 + attention(m, a2, norm(h2, a2["attn_norm"], m.rms_eps))
    return h3 + mlp(a2, norm(h3, a2["ffn_norm"], m.rms_eps)) + branch


def forward_hidden(m: Model, tokens):
    """The final norm's input after every layer: [T, hidden]."""
    x = m.embed[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for i in range(m.num_layers):
        x = layer_forward(m, i, x)
    return x


def log_probs(model: Model, tokens, positions):
    """Log-softmax over the vocabulary of the next token after each of
    ``positions`` (indices into ``tokens``): [len(positions), vocab]."""
    m = model
    with jax.default_matmul_precision("highest"):
        x = forward_hidden(m, tokens)[jnp.asarray(positions)]
        x = norm(x, m.final_norm.astype(jnp.float32), m.rms_eps)
        return jax.nn.log_softmax(x @ m.lm_head.astype(jnp.float32),
                                  axis=-1)


def split_sublayer(config, params: dict, j: int) -> dict:
    """Sublayer ``j`` of the program's stacks under this file's names,
    float32: ``W_kvb`` put together again from a head's ``W_UK^T`` and
    ``W_UV`` (``[r, n * (dn + dv)]``, a head's key part then its value
    part), gate | up split."""
    c = config
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    f = c.intermediate_size
    w_uk, w_uv = f32(params["w_uk"][j]), f32(params["w_uv"][j])
    w_kvb = jnp.concatenate(
        [w_uk.transpose(2, 0, 1), w_uv.transpose(1, 0, 2)], -1)
    gate_up = f32(params["w_gate_up"][j])
    return {"attn_norm": f32(params["attn_norm"][j]),
            "ffn_norm": f32(params["ffn_norm"][j]),
            "w_qa": f32(params["q_a"][j]),
            "q_a_norm": f32(params["q_a_norm"][j]),
            "w_qb": f32(params["q_b"][j]),
            "w_kva": f32(params["kv_a"][j]),
            "kv_a_norm": f32(params["kv_a_norm"][j]),
            "w_kvb": w_kvb.reshape(w_kvb.shape[0], -1),
            "w_o": f32(params["wo"][j]),
            "w_gate": gate_up[:, :f], "w_up": gate_up[:, f:],
            "w_down": f32(params["w_down"][j])}


def split_branch(config, params: dict, i: int) -> dict:
    """Layer ``i``'s router and held experts, float32."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    f = config.moe_intermediate_size
    gate_up = f32(params[f"e_w_gate_up_{i}"])
    return {"w_router": f32(params["router"][i]),
            "router_bias": f32(params["router_bias"][i]),
            "e_gate": gate_up[..., :f], "e_up": gate_up[..., f:],
            "e_down": f32(params[f"e_w_down_{i}"])}


def model_of(config, params: dict) -> Model:
    """``Model`` of the program's configuration object (read as data)
    and parameter values."""
    c = config
    return Model(
        num_layers=c.num_hidden_layers, num_heads=c.num_attention_heads,
        nope_dim=c.qk_nope_head_dim, rope_dim=c.qk_rope_head_dim,
        v_dim=c.v_head_dim, q_scale=c.mla_q_scale, kv_scale=c.mla_kv_scale,
        rope_theta=c.rope_theta, rms_eps=c.rms_norm_eps,
        top_k=c.num_experts_per_tok, routed_scale=c.routed_scaling_factor,
        first_expert=c.expert_parallel_rank * c.num_experts,
        first_zero_expert=c.router_width - c.zero_expert_num,
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params["lm_head"],
        sublayer=lambda j: split_sublayer(c, params, j),
        branch=lambda i: split_branch(c, params, i))


def program_model(hf_config: dict, bench: dict) -> Model:
    """The server's random weights for this configuration (``bench``:
    its ``chipbench`` group), by the server's own init from
    ``weights_seed``, which is data here: nothing else of the program
    is used. The values stay in the server's dtype (they are its
    values) and a sublayer, or a layer's experts, is made float32 when
    the forward comes to it."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models.registry import get_model

    if bench["quantization"] != "none":
        raise ValueError("the LongCat-Flash family's reference takes "
                         "weights that are not quantized")
    config = ModelConfig.from_hf_config(hf_config)
    config.dtype = bench["dtype"]  # the server's --dtype: the init casts
    init_fn, _ = get_model(config)
    params = init_fn(config, jax.random.PRNGKey(bench["weights_seed"]))
    return model_of(config, params)
