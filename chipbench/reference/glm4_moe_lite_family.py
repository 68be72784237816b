"""A plain float32 forward pass of the GLM-4 MoE lite family of
decoders (zai-org/GLM-4.7-Flash) and of its multi-token-prediction
module, written from the layer equations and independent of the
program's ``models/glm4_moe_lite.py`` and ``ops/``.

Sizes: hidden ``H``; ``n`` heads; a query/key head is ``dn``
(``qk_nope_head_dim``) + ``dr`` (``qk_rope_head_dim``) wide, a value
head ``dv``; the query's rank ``rq`` (``q_lora_rank``), the latent's
``r`` (``kv_lora_rank``). All norms are plain, ``norm(x; w) = x /
sqrt(mean(x^2) + eps) * w``; SwiGLU is ``(silu(x W_g) * (x W_u)) W_d``.

- Layer, on ``h``: ``h' = h + MLA(N1(h))``, ``out = h' + F(N2(h'))``;
  ``F`` is the dense SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers and the expert block after them.
  Model: embedding, ``num_hidden_layers`` layers, a final norm, an
  untied head.
- MLA, materialised: ``q = norm(x W_qa) W_qb`` as ``n`` heads ``[q_nope
  | q_rope]``; ``[c_raw | k_r] = x W_kva``; ``c = norm(c_raw)``;
  ``[k_nope | v] = c W_kvb`` as ``n`` heads of ``dn + dv``; one rotary
  key ``rotary(k_r)`` shared by every head and ``rotary(q_rope)`` a
  head, the pairs interleaved (``x[2i], x[2i+1]``), base ``rope_theta``;
  scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(dn + dr)``,
  causal softmax, ``o = sum p v``, output ``concat(o) W_o``. No
  low-rank scales.
- Expert block on ``u``: ``s = sigmoid(u W_r)`` over all routed
  experts; the ``num_experts_per_tok`` largest of ``s + b`` are chosen
  (``b``: ``e_score_correction_bias``, for the choice alone; one
  group); weights ``routed_scaling_factor * s_i / (sum of the chosen s
  + 1e-20)``; ``F(u) = sum_i w_i E_i(u) + E_shared(u)``. Expert by
  expert, the tokens that chose it go through it and no others (the
  choices are read on the host: the reference runs eagerly).
- Prediction module (one layer, DeepSeek-V3's MTP): for position ``i``
  with the main model's last-layer hidden state ``h_i`` (before the
  final norm) and the next token ``t_{i+1}``: ``z_i =
  [enorm(emb(t_{i+1})) ; hnorm(h_i)] W_eh``, one decoder layer of the
  expert kind on ``z_0 .. z_{T-2}`` at rotary positions ``0 .. T-2``,
  attending over its own sequence, then ``lm_head(shared_head.norm(.))``:
  its distribution for token ``i + 2``. Embedding and head are the main
  model's. ``draft_log_probs`` is that.

Departures from the published model: of the routed experts only
``[first_expert, first_expert + held)`` are given (all of them in the
benchmark's configuration, where ``expert_parallel_size`` is 1); a
chosen expert that is not held adds nothing, in the program alike.
``W_kvb`` is given as the program keeps it, a head's ``W_UK`` and
``W_UV`` apart, and ``split_layer`` puts them side by side again; so
with gate | up. The weights are random (``program_model`` takes the
program's init as data) and the tokenizer is the benchmark's
word-level one.

No cache, no kernels, no batching, no chunks, no absorbed form, no
drafting: one sequence, one full forward, every product under
``jax.default_matmul_precision("highest")``; attention goes a block of
queries at a time, the weights are made float32 one layer at a time,
and the head is applied at the asked positions alone.
``reference/check.py`` uses ``program_model`` and ``log_probs``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
ROUTER_EPS = 1e-20


@dataclasses.dataclass
class Model:
    num_layers: int
    num_dense_layers: int
    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    rms_eps: float
    top_k: int
    routed_scale: float
    first_expert: int
    embed: jnp.ndarray                  # [vocab, hidden]
    final_norm: jnp.ndarray             # [hidden]
    lm_head: jnp.ndarray                # [hidden, vocab]
    # layer(i) -> dict of float32 arrays; i == num_layers is the
    # prediction module's layer.
    layer: Callable[[int], dict]
    # The module's own four weights, or None where it was not made.
    module: Optional[dict] = None


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
    q = (norm(x @ w["w_qa"], w["q_a_norm"], m.rms_eps)
         @ w["w_qb"]).reshape(t, n, dn + dr)
    kv = x @ w["w_kva"]
    rank = kv.shape[1] - dr
    c = norm(kv[:, :rank], w["kv_a_norm"], m.rms_eps)
    k_rope = rope(kv[:, None, rank:], m.rope_theta)      # [T, 1, dr]
    up = (c @ w["w_kvb"]).reshape(t, n, dn + dv)
    k = jnp.concatenate(
        [up[..., :dn], jnp.broadcast_to(k_rope, (t, n, dr))], -1)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], m.rope_theta)], -1)
    o = causal_attention(q, k, up[..., dn:], (dn + dr) ** -0.5)
    return o.reshape(t, n * dv) @ w["w_o"]


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
    """x: [T, hidden], normalised."""
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


def layer_forward(m: Model, i: int, h):
    w = m.layer(i)
    h = h + attention(m, w, norm(h, w["attn_norm"], m.rms_eps))
    u = norm(h, w["ffn_norm"], m.rms_eps)
    if i < m.num_dense_layers:
        return h + swiglu(u, w["w_gate"], w["w_up"], w["w_down"])
    return h + expert_block(m, w, u)


def forward_hidden(m: Model, tokens):
    """The final norm's input after every layer: [T, hidden]."""
    x = m.embed[jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    for i in range(m.num_layers):
        x = layer_forward(m, i, x)
    return x


def _head(m: Model, x, final_norm):
    x = norm(x, final_norm.astype(jnp.float32), m.rms_eps)
    return jax.nn.log_softmax(x @ m.lm_head.astype(jnp.float32), axis=-1)


def log_probs(model: Model, tokens, positions):
    """Log-softmax over the vocabulary of the next token after each of
    ``positions`` (indices into ``tokens``): [len(positions), vocab]."""
    with jax.default_matmul_precision("highest"):
        x = forward_hidden(model, tokens)[jnp.asarray(positions)]
        return _head(model, x, model.final_norm)


def module_hidden(m: Model, tokens, hidden):
    """The prediction layer's output for positions ``0 .. T-2`` of
    ``tokens``, from the main model's ``hidden [T, H]``: [T-1, H]."""
    w = m.module
    emb = m.embed[jnp.asarray(tokens[1:], jnp.int32)].astype(jnp.float32)
    z = jnp.concatenate([norm(emb, w["enorm"], m.rms_eps),
                         norm(hidden[:-1], w["hnorm"], m.rms_eps)],
                        -1) @ w["eh_proj"]
    return layer_forward(m, m.num_layers, z)


def draft_log_probs(model: Model, tokens, positions):
    """The prediction module's log-softmax for the token TWO after each
    of ``positions`` (indices ``i <= len(tokens) - 2``; the module reads
    ``tokens[i + 1]``): [len(positions), vocab]."""
    with jax.default_matmul_precision("highest"):
        x = module_hidden(model, tokens, forward_hidden(model, tokens))
        return _head(model, x[jnp.asarray(positions)],
                     model.module["head_norm"])


def split_layer(config, params: dict, i: int) -> dict:
    """Layer body ``i`` of the program's stacks under this file's
    names, float32: ``W_kvb`` put together again from a head's
    ``W_UK^T`` and ``W_UV`` (``[r, n * (dn + dv)]``, a head's key part
    then its value part), gate | up split."""
    c = config
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    w_uk, w_uv = f32(params["w_uk"][i]), f32(params["w_uv"][i])
    w_kvb = jnp.concatenate(
        [w_uk.transpose(2, 0, 1), w_uv.transpose(1, 0, 2)], -1)
    out = {"attn_norm": f32(params["attn_norm"][i]),
           "ffn_norm": f32(params["ffn_norm"][i]),
           "w_qa": f32(params["q_a"][i]),
           "q_a_norm": f32(params["q_a_norm"][i]),
           "w_qb": f32(params["q_b"][i]),
           "w_kva": f32(params["kv_a"][i]),
           "kv_a_norm": f32(params["kv_a_norm"][i]),
           "w_kvb": w_kvb.reshape(w_kvb.shape[0], -1),
           "w_o": f32(params["wo"][i])}
    if i < c.num_dense_layers:
        f = c.intermediate_size
        gate_up = f32(params["w_gate_up"][i])
        out.update({"w_gate": gate_up[:, :f], "w_up": gate_up[:, f:],
                    "w_down": f32(params["w_down"][i])})
        return out
    j = i - c.num_dense_layers
    fe, fs = c.moe_intermediate_size, c.shared_expert_intermediate_size
    gate_up = f32(params[f"e_w_gate_up_{i}"])
    shared = f32(params["shared_gate_up"][j])
    out.update({"w_router": f32(params["router"][j]),
                "router_bias": f32(params["router_bias"][j]),
                "e_gate": gate_up[..., :fe], "e_up": gate_up[..., fe:],
                "e_down": f32(params[f"e_w_down_{i}"]),
                "s_gate": shared[:, :fs], "s_up": shared[:, fs:],
                "s_down": f32(params["shared_down"][j])})
    return out


def model_of(config, params: dict) -> Model:
    """``Model`` of the program's configuration object (read as data)
    and parameter values."""
    c = config
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    module = None
    if c.num_nextn_predict_layers:
        module = {"enorm": f32(params["mtp_enorm"]),
                  "hnorm": f32(params["mtp_hnorm"]),
                  "eh_proj": f32(params["mtp_eh_proj"]),
                  "head_norm": f32(params["mtp_head_norm"])}
    return Model(
        num_layers=c.num_hidden_layers,
        num_dense_layers=c.num_dense_layers,
        num_heads=c.num_attention_heads,
        nope_dim=c.qk_nope_head_dim, rope_dim=c.qk_rope_head_dim,
        v_dim=c.v_head_dim, rope_theta=c.rope_theta,
        rms_eps=c.rms_norm_eps, top_k=c.num_experts_per_tok,
        routed_scale=c.routed_scaling_factor,
        first_expert=c.expert_parallel_rank * c.num_experts,
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params["lm_head"],
        layer=lambda i: split_layer(c, params, i), module=module)


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
        raise ValueError("the GLM-4 MoE lite family's reference takes "
                         "weights that are not quantized")
    config = ModelConfig.from_hf_config(hf_config)
    config.dtype = bench["dtype"]  # the server's --dtype: the init casts
    init_fn, _ = get_model(config)
    params = init_fn(config, jax.random.PRNGKey(bench["weights_seed"]))
    return model_of(config, params)
