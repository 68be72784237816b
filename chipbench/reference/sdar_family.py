"""A plain float32 forward pass and generation loop of the SDAR-MoE
family of block-diffusion decoders (JetLM SDAR-30B-A3B-Chat), written
from the layer equations (the published ``sdar_moe`` modelling code,
which follows Qwen3-MoE's layer, its ``config.json`` and the family's
``generate.py``) and independent of the program's
``models/sdar_moe.py``, ``ops/`` and engine.

- Layer: ``h <- h + attn(norm(h; w1))``, ``h <- h + experts(norm(h;
  w2))``; all norms plain, ``norm(x; w) = x / sqrt(mean(x^2) + eps) *
  w``; logits ``norm(h; w_f) W_head`` (untied).
- Attention: ``q = u W_q`` as heads of ``d``, ``k = u W_k``, ``v = u
  W_v`` as KV heads, no bias; ``q <- norm_d(q; q_norm)``, ``k <-
  norm_d(k; k_norm)`` over each head's ``d``; a full rotary embedding
  (rotate-half, base ``rope_theta``) at the true positions; softmax
  attention scaled ``d^-1/2``, query head ``h`` reading key-value head
  ``h // (heads / kv_heads)``; ``W_o``. SIGHT IS BY BLOCK: a query at
  ``t`` sees every key ``j <= B * floor(t / B) + B - 1``, its own
  block of ``B`` positions in both directions.
- Experts: ``p = softmax(u W_r)`` over ALL experts, the ``top_k``
  largest kept and (``norm_topk_prob``) divided by their sum; ``y =
  sum p_e E_e(u)``, ``E(u) = W_down(SiLU(W_gate u) * W_up u)``. Expert
  by expert, the tokens that chose it go through it and no others (the
  choices are read on the host: the reference runs eagerly). Every
  layer is such a layer; no shared expert.
- The head's row at ``t`` is the distribution of the token AT ``t``; a
  place whose token is not known yet enters as the embedding of
  ``mask_token_id`` (which place that is is a flag beside the ids).
- Generation (``generate``, the published ``block_diffusion_generate``
  for one greedy sequence): the prompt's whole blocks are context; then
  block by block, the block starts as the prompt's remainder followed
  by masked places; a denoising step forwards the sequence up to the
  block's end, takes ``x0 = argmax`` and its probability at every
  masked place and commits the places the strategy picks
  (``sequential``: the leftmost ``n_step``; ``low_confidence_static``:
  the ``n_step`` of highest confidence; ``low_confidence_dynamic``:
  all over the threshold if they are at least ``n_step``, else as
  static), ``n_step = B // S`` with the remainder on the first steps;
  a block whose places are all committed is done.

``log_probs`` serves ``reference/check.py``, which hands it prompt and
answers alone: under ``sequential`` the state in which a place was
committed follows from its position (the places to its left in its
block are the served tokens, the others masked), so every answer's row
is one forward of the sequence up to its block's end in that state.

Departures from the published model and loop: only masked places are
ever committed (the published top-k over confidences of ``-inf`` picks
committed places once fewer than ``n_step`` are left, and overwrites
them with a fresh draw); ties in confidence go to the leftmost place;
of the experts only ``[first_expert, first_expert + held)`` are given
(all of them in every configuration so far); the experts' gate | up
layout is the program's own and ``split_layer`` splits it; there is no
cache, so the published store pass has nothing to do here: a finished
block is simply part of the next forward's input. The weights are
random (``program_model`` takes the program's init as data) and the
tokenizer is the benchmark's word-level one. ``head_norms``,
``own_block``, ``causal_prefill``, ``head_shift``, ``norm_topk`` and
``stale_blocks`` are the tests' levers (each one a term left out or put
in) and no configuration's.

No cache, no kernels, no batching, no chunks: one sequence a forward,
every product under ``jax.default_matmul_precision("highest")``; the
layers' weights are made float32 one layer at a time and the head is
applied at the asked positions alone. ``log_probs`` puts the states it
needs into ONE forward as further copies of their blocks after the
sequence, under the same rule of sight (``in_sight``); ``generate``
runs the sequence up to the block's end once a denoising step.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

STRATEGIES = ("sequential", "low_confidence_static",
              "low_confidence_dynamic")


@dataclasses.dataclass
class Model:
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    rms_eps: float
    top_k: int
    norm_topk: bool
    first_expert: int
    block: int
    mask_id: int
    steps: int                          # denoising steps a block
    remasking: str
    threshold: float
    embed: jnp.ndarray                  # [vocab, hidden]
    final_norm: jnp.ndarray             # [hidden]
    lm_head: jnp.ndarray                # [hidden, vocab]
    layer: Callable[[int], dict]        # layer(i) -> float32 arrays
    # The tests' levers.
    head_norms: bool = True
    own_block: bool = True
    causal_prefill: bool = False
    head_shift: int = 0
    stale_blocks: bool = False


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta, positions):
    """x: [T, heads, d] at ``positions`` [T] (``rotate_half``)."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def in_sight(m: Model, positions, copies, prefilled: int):
    """[T, T] bool: element ``j`` as a key to element ``i`` as a query.
    An element is a position and the copy of its block it belongs to:
    copy 0 is the block as the sequence has it, a copy ``c > 0`` the
    same positions in another state (``log_probs``). A query sees the
    blocks before its own as the sequence has them, and its own block
    in its own copy, in both directions. ``prefilled``: the positions
    before it are the prompt's whole blocks (the ``causal_prefill``
    lever's range)."""
    pos_i, pos_j = positions[:, None], positions[None, :]
    blk_i, blk_j = pos_i // m.block, pos_j // m.block
    cp_i, cp_j = copies[:, None], copies[None, :]
    own = (cp_j == cp_i) & (blk_j == blk_i)
    if not m.own_block:
        own = own & (pos_j == pos_i)
    sight = ((cp_j == 0) & (blk_j < blk_i)) | own
    if m.causal_prefill:
        sight = jnp.where(pos_i < prefilled,
                          (cp_j == 0) & (pos_j <= pos_i), sight)
    return sight


def attention(m: Model, w: dict, x, positions, sight):
    t, n, kv, d = x.shape[0], m.num_heads, m.num_kv_heads, m.head_dim
    q = (x @ w["w_q"]).reshape(t, n, d)
    k = (x @ w["w_k"]).reshape(t, kv, d)
    v = (x @ w["w_v"]).reshape(t, kv, d)
    if m.head_norms:
        q = norm(q, w["q_norm"], m.rms_eps)
        k = norm(k, w["k_norm"], m.rms_eps)
    q = rope(q, m.rope_theta, positions)
    k = rope(k, m.rope_theta, positions)
    k, v = jnp.repeat(k, n // kv, axis=1), jnp.repeat(v, n // kv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * d ** -0.5
    scores = jnp.where(sight[None], scores, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v)
    return o.reshape(t, n * d) @ w["w_o"]


def choose(m: Model, probs):
    """(weights [T, k], ids [T, k]) of the router's softmax."""
    weight, chosen = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        weight = weight / jnp.sum(weight, -1, keepdims=True)
    return weight, chosen


def expert_block(m: Model, w: dict, x):
    """x: [T, hidden]."""
    weight, chosen = choose(m, jax.nn.softmax(x @ w["w_router"], -1))
    held = w["e_gate"].shape[0]
    local = np.asarray(chosen) - m.first_expert
    routed = jnp.zeros_like(x)
    # Expert by expert, the tokens that chose it and no others.
    for e in np.unique(local[(local >= 0) & (local < held)]):
        token, slot = np.nonzero(local == e)
        u = x[token]
        out = (jax.nn.silu(u @ w["e_gate"][e])
               * (u @ w["e_up"][e])) @ w["e_down"][e]
        routed = routed.at[token].add(weight[token, slot][:, None] * out)
    return routed


def forward_hidden(m: Model, tokens, masked, prefilled: int,
                   positions=None, copies=None):
    """The final norm's input after every layer, ``[T, hidden]``. A
    masked place enters as the mask's embedding whatever its id.
    ``positions`` (0..T-1 where not given) and ``copies`` (all 0):
    ``in_sight``'s."""
    t = len(tokens)
    positions = jnp.asarray(range(t) if positions is None else positions)
    copies = jnp.asarray([0] * t if copies is None else copies)
    sight = in_sight(m, positions, copies, prefilled)
    embed = m.embed.astype(jnp.float32)
    x = jnp.where(jnp.asarray(masked, bool)[:, None], embed[m.mask_id],
                  embed[jnp.asarray(tokens, jnp.int32)])
    for i in range(m.num_layers):
        w = m.layer(i)
        x = x + attention(m, w, norm(x, w["attn_norm"], m.rms_eps),
                          positions, sight)
        x = x + expert_block(m, w, norm(x, w["ffn_norm"], m.rms_eps))
    return x


def head(m: Model, x):
    x = norm(x, m.final_norm.astype(jnp.float32), m.rms_eps)
    return jax.nn.log_softmax(x @ m.lm_head.astype(jnp.float32), axis=-1)


def quotas(block: int, steps: int) -> list:
    """Places a denoising step commits: ``block // steps``, the
    remainder on the first steps."""
    return [block // steps + (s < block % steps) for s in range(steps)]


def sequential_state(m: Model, prompt_len: int, position: int):
    """Under ``sequential``: ``(start, known)`` of the state in which
    the place at ``position`` (an answer's) is committed: its block's
    first position, and how many of the block's places are known
    then."""
    b = m.block
    start = position - position % b
    prompt_blocks = prompt_len - prompt_len % b
    known = prompt_len - prompt_blocks if start == prompt_blocks else 0
    for quota in quotas(b, m.steps):
        if position - start < known + quota:
            return start, known
        known += quota
    raise ValueError(f"position {position} is never committed")


def last_denoising_state(m: Model, prompt_len: int, start: int) -> int:
    """Places of the block at ``start`` that are known in its LAST
    denoising step (the ``stale_blocks`` lever: what a cache holds if
    no store pass follows)."""
    return sequential_state(m, prompt_len, start + m.block - 1)[1]


def log_probs(model: Model, tokens, positions):
    """``reference/check.py``'s contract: row ``j`` is the log-softmax
    of the token AFTER ``positions[j]`` (indices into ``tokens``:
    prompt + answers, ``positions[0] + 1`` the prompt's length), under
    the model's own ``steps`` and the ``sequential`` rule: the
    distribution of ``tokens[positions[j] + 1]`` in the state in which
    that place was committed.

    One forward serves every row: the sequence as it stands (copy 0)
    and after it, for each state an answer was committed in, one more
    copy of that block's positions in that state (known places the
    served tokens, the others masked), which sees the blocks before it
    as they stand and itself (``in_sight``). Nothing sees a copy but
    the copy itself, so each is what a forward of the sequence up to
    that block in that state would give, at a fraction of the
    experts' work."""
    m = model
    b, prompt_len = m.block, positions[0] + 1
    prefilled = prompt_len - prompt_len % b
    end = positions[-1] + 2
    ids = list(tokens[:end]) + [0] * (-end % b)
    masked = [i >= end for i in range(len(ids))]
    if m.stale_blocks:
        for first in range(prefilled, len(ids), b):
            stale = last_denoising_state(m, prompt_len, first)
            masked[first + stale:first + b] = [True] * (b - stale)
    pos, copies = list(range(len(ids))), [0] * len(ids)
    states, rows = {}, []
    for p in positions:
        start, known = sequential_state(m, prompt_len, p + 1)
        if (start, known) not in states:
            states[start, known] = len(ids)
            ids += list(tokens[start:start + known]) + [0] * (b - known)
            masked += [False] * known + [True] * (b - known)
            pos += range(start, start + b)
            copies += [len(states)] * b
        rows.append(states[start, known] + p + 1 - start - m.head_shift)
    with jax.default_matmul_precision("highest"):
        x = forward_hidden(m, ids, masked, prefilled, pos, copies)
        return head(m, x[jnp.asarray(rows)])


def generate(model: Model, prompt, max_tokens: int, steps=None,
             strategy=None, threshold=None):
    """The published loop for one greedy sequence: ``(tokens,
    log_probs [len(tokens), vocab])``, each token's row the
    log-softmax of the step that committed it; cut to ``max_tokens``."""
    m = model
    b = m.block
    steps = m.steps if steps is None else steps
    strategy = m.remasking if strategy is None else strategy
    threshold = m.threshold if threshold is None else threshold
    known = list(prompt)
    prefilled = len(known) - len(known) % b
    rows = {}
    with jax.default_matmul_precision("highest"):
        while len(known) - len(prompt) < max_tokens:
            start = len(known) - len(known) % b
            block = known[start:] + [0] * (b - len(known) + start)
            masked = [i >= len(known) - start for i in range(b)]
            for quota in quotas(b, steps):
                if not any(masked):
                    break
                x = forward_hidden(m, known[:start] + block,
                                   [False] * start + masked, prefilled)
                lp = np.asarray(head(m, x[start:start + b]))
                x0, conf = lp.argmax(-1), np.exp(lp.max(-1))
                open_ = [i for i in range(b) if masked[i]]
                by_conf = sorted(open_, key=lambda i: (-conf[i], i))
                if strategy == "sequential":
                    pick = open_[:quota]
                elif strategy == "low_confidence_static":
                    pick = by_conf[:quota]
                else:
                    high = [i for i in open_ if conf[i] > threshold]
                    pick = high if len(high) >= quota else by_conf[:quota]
                for i in pick:
                    block[i], masked[i] = int(x0[i]), False
                    rows[start + i] = lp[i]
            known = known[:start] + block
    tokens = known[len(prompt):len(prompt) + max_tokens]
    return tokens, np.stack([rows[len(prompt) + j]
                             for j in range(len(tokens))])


def split_layer(config, params: dict, i: int) -> dict:
    """Layer ``i`` of the program's stacks under this file's names,
    float32, gate | up split."""
    fe = config.moe_intermediate_size
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    gate_up = f32(params[f"w_gate_up_{i}"])
    return {"w_q": f32(params["wq"][i]), "w_k": f32(params["wk"][i]),
            "w_v": f32(params["wv"][i]), "w_o": f32(params["wo"][i]),
            "q_norm": f32(params["q_norm"][i]),
            "k_norm": f32(params["k_norm"][i]),
            "attn_norm": f32(params["attn_norm"][i]),
            "ffn_norm": f32(params["ffn_norm"][i]),
            "w_router": f32(params["router"][i]),
            "e_gate": gate_up[..., :fe], "e_up": gate_up[..., fe:],
            "e_down": f32(params[f"w_down_{i}"])}


def model_of(config, params: dict, **levers) -> Model:
    """``Model`` of the program's configuration object (read as data)
    and parameter values."""
    c = config
    return Model(**{**dict(
        num_layers=c.num_hidden_layers,
        num_heads=c.num_attention_heads,
        num_kv_heads=c.num_key_value_heads, head_dim=c.head_dim,
        rope_theta=c.rope_theta, rms_eps=c.rms_norm_eps,
        top_k=c.num_experts_per_tok, norm_topk=c.norm_topk_prob,
        first_expert=c.expert_parallel_rank * c.num_experts,
        block=c.diffusion_block_length, mask_id=c.mask_token_id,
        steps=c.diffusion_steps, remasking=c.diffusion_remasking,
        threshold=c.diffusion_confidence_threshold,
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=(params["lm_head"] if "lm_head" in params
                 else params["embed"].T),
        layer=lambda i: split_layer(c, params, i)), **levers})


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
        raise ValueError("the SDAR family's reference takes weights "
                         "that are not quantized")
    config = ModelConfig.from_hf_config(hf_config)
    if config.diffusion_remasking != "sequential":
        raise ValueError(
            "log_probs reads a place's committing state off its "
            "position, which holds under 'sequential' alone; the "
            f"configuration says {config.diffusion_remasking!r}")
    config.dtype = bench["dtype"]  # the server's --dtype: the init casts
    init_fn, _ = get_model(config)
    params = init_fn(config, jax.random.PRNGKey(bench["weights_seed"]))
    return model_of(config, params)
