"""EXAONE-MoE decoders (LG AI Research's K-EXAONE-236B-A23B style).

Two kinds of attention layer in the order ``config.layer_types`` lists
(published: three ``sliding_attention`` to one ``full_attention``).
Both are grouped-query attention with an RMS norm over each head's
dimensions on q and on k. A ``sliding_attention`` layer then turns q
and k by a full rotary embedding (half-split pairs) and its query at
position ``i`` sees key ``j`` iff ``i - sliding_window < j <= i``; its
K/V is a ring of ``sliding_window`` places a sequence in the state
pool's slot, K and V one pool each (ops/window_attention.py), and
never pages. A ``full_attention`` layer has NO position term and sees
the whole row, over the paged cache.

Norms sit on each sublayer's OUTPUT and nowhere on its input::

    x = x + post_attn_norm(attention(x))
    x = x + post_ffn_norm(F(x))

``F`` is a dense SwiGLU of ``intermediate_size`` in the first
``num_dense_layers`` layers and the expert block of
``models/glm4_moe_lite.py`` after them (``expert_block``: a sigmoid an
expert over all published experts in float32, the ``top_k`` largest of
score + bias chosen, weights ``routed_scaling_factor * s_i / (sum of
the chosen s + 1e-20)``, the held experts' part of the sum beside a
shared expert added whole). The head is untied.

Same contract as ``models.qwen3_next.forward``: per-layer cache
tuples, of a windowed layer ``k_cache[i]`` the K ring pool and
``v_cache[i]`` the V ring pool, ``[kv_heads, slots, head_dim,
sliding_window]``; ``state_slots [B]`` says which slot each row's
sequence owns (slot 0 is the trash slot of padded rows). Nothing of a
ring is read that the row did not write: a place is in sight only
while the row's length says it holds one of the row's tokens. After
the layers ``k_cache`` carries the family's counters:
``count_step``'s six over the expert layers' decode steps, then
``swa_keys`` and ``swa_queries``: over the windowed layers' decode
steps, the ring places and own tokens the attention's own mask let
the real rows' queries see, and those queries. With ``kv_tail`` (a
deferred-write decode burst) every attention layer appends to its
tail and leaves its planes, rings among them, unwritten: the runner
flushes the tails once a burst, a windowed layer's to its ring's
places.

Parameters: the head norms and the two post-norms are stacks over all
layers and ``w_gate_up/w_down`` over the dense layers; every other
matrix is one array a layer, ``i`` the layer's index: the attention's
four (``wq_<i>`` ...), the router, its bias and the shared expert
(``router_<i>`` ...), the held experts (``e_w_gate_up_<i>``,
``e_w_down_<i>``). Sliced out of stacks inside the burst's loop the
attention's and the shared expert's matrices were copied every step,
2.8 ms of a 20.5 ms token-step on the chip (PERF.md section 6, PR 50).
Gate | up side by side is this program's own layout.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models.glm4_moe_lite import expert_block
from production_stack_tpu.models.llama import (
    hybrid_attention,
    hybrid_kernel_impl,
    rms_norm,
)
from production_stack_tpu.ops.attention import write_to_tail
from production_stack_tpu.ops.moe import count_step, swiglu
from production_stack_tpu.ops.rope import apply_rope
from production_stack_tpu.ops.window_attention import (
    window_attention,
    window_prefill_pallas,
    write_to_ring,
)

Params = Dict[str, jnp.ndarray]

LAYER = ("q_norm", "k_norm", "post_attn_norm", "post_ffn_norm")
ATTENTION = ("wq", "wk", "wv", "wo")  # one array a layer: <name>_<i>
DENSE = ("w_gate_up", "w_down")
# One array a layer, <name>_<i> (the experts e_<name>_<i>), ``i`` the
# layer's index.
ROUTED = ("router", "router_bias", "shared_gate_up", "shared_down")
EXPERTS = ("w_gate_up", "w_down")


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random parameters. What a zero or a one would switch off is
    drawn: every norm's weight (the head norms and the post-norms
    among them) 1 + N(0, 0.1), and ``router_bias`` N(0, 1e-2) in
    float32, the order of the gap between a token's eighth and ninth
    sigmoid scores at the published widths, so that it moves a visible
    share of the choices and leaves the scores a say
    (models/glm4_moe_lite.py)."""
    c = config
    h, d = c.hidden_size, c.head_dim
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    f, fe, fs = (c.intermediate_size, c.moe_intermediate_size,
                 c.shared_expert_intermediate_size)
    layers, nd = c.num_hidden_layers, c.num_dense_layers
    dtype = c.jax_dtype
    keys = iter(jax.random.split(key, 32 + 10 * layers))

    def dense(shape, scale=0.02, offset=0.0, to=dtype):
        # One leaf at a time: dispatched all at once, the float32
        # draws of every leaf are live together and the init alone
        # peaks at the device's limit (models/qwen3_next.py).
        return jax.block_until_ready(
            (offset + scale * jax.random.normal(next(keys), shape,
                                                jnp.float32)).astype(to))

    def near_one(shape):
        return dense(shape, scale=0.1, offset=1.0)

    params: Params = {
        "embed": dense((c.vocab_size, h)),
        "final_norm": near_one((h,)),
        "lm_head": dense((h, c.vocab_size)),
        "q_norm": near_one((layers, d)),
        "k_norm": near_one((layers, d)),
        "post_attn_norm": near_one((layers, h)),
        "post_ffn_norm": near_one((layers, h)),
        "w_gate_up": dense((nd, h, 2 * f)),
        "w_down": dense((nd, f, h)),
    }
    for i in range(layers):
        params[f"wq_{i}"] = dense((h, nh * d))
        params[f"wk_{i}"] = dense((h, nkv * d))
        params[f"wv_{i}"] = dense((h, nkv * d))
        params[f"wo_{i}"] = dense((nh * d, h))
    for i in range(nd, layers):
        params[f"router_{i}"] = dense((h, c.router_width))
        params[f"router_bias_{i}"] = dense((c.router_width,), scale=1e-2,
                                           to=jnp.float32)
        params[f"shared_gate_up_{i}"] = dense((h, 2 * fs))
        params[f"shared_down_{i}"] = dense((fs, h))
        params[f"e_w_gate_up_{i}"] = dense((c.num_experts, h, 2 * fe))
        params[f"e_w_down_{i}"] = dense((c.num_experts, fe, h))
    if c.tie_word_embeddings:
        del params["lm_head"]
    return params


def _windowed(config, q, k, v, k_ring, v_ring, slots, positions, kv_lens,
              valid, tails=None):
    """One windowed layer's attention over its ring and the call's own
    tokens: ``(attn, k entry, v entry, keys [B, T])``, ``keys`` (the
    places and own tokens the mask let each query see) None from the
    chunk's kernel form. Without ``tails`` (a prefill chunk or one
    eager step: positions contiguous a row from the ring's length on)
    the entries are the rings with the call's newest tokens written;
    with ``tails`` ``(k_tail, v_tail)`` (a deferred burst, T == 1,
    ``kv_lens`` the frozen count the rings hold) they are the updated
    tails."""
    t = q.shape[1]
    if tails is None:
        ring_len, k_new, v_new = positions[:, 0], k, v
        new_positions, new_valid = positions, valid
    else:
        ring_len = kv_lens
        slot, act = positions[:, 0] - kv_lens, valid[:, 0]
        k_new = write_to_tail(tails[0], k, slot, act)
        v_new = write_to_tail(tails[1], v, slot, act)
        new_positions = (kv_lens[:, None]
                         + jnp.arange(k_new.shape[1])[None, :])
        # Slots a row has not reached sit past its query's position.
        new_valid = new_positions <= positions[:, :1]
    # A decode step runs the XLA form, whose mask is also what counts
    # ``keys``; only a chunk has a kernel form (ops/window_attention.py).
    impl = config.attention_impl_prefill or config.attention_impl
    kernel = (t > 1 and impl.startswith("pallas")
              and not impl.startswith("pallas_ragged"))
    with jax.named_scope("swa_decode" if t == 1 else "swa_prefill"):
        if kernel:
            keys = None
            attn = window_prefill_pallas(
                q, k_ring, v_ring, slots, ring_len, k_new, v_new,
                kv_lens, interpret=impl == "pallas-interpret")
        else:
            attn, keys = window_attention(
                q, k_ring, v_ring, slots, ring_len, positions, k_new,
                v_new, new_positions, new_valid)
        if tails is not None:
            return attn, k_new, v_new, keys
        return (attn,
                write_to_ring(k_ring, k, slots, positions, valid, kv_lens),
                write_to_ring(v_ring, v, slots, positions, valid, kv_lens),
                keys)


def _attention(config, lp, x, windowed, positions, page_table, kv_lens,
               valid, slots, k_cache, v_cache, layer, kv_tail=None):
    """One attention sublayer: ``(out, k_cache, v_cache, keys)``,
    ``keys`` None for a full layer."""
    nh, nkv, d = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    b, t, _ = x.shape
    q = (x @ lp["wq"]).reshape(b, t, nh, d)
    k = (x @ lp["wk"]).reshape(b, t, nkv, d)
    v = (x @ lp["wv"]).reshape(b, t, nkv, d)
    q = rms_norm(q, lp["q_norm"], config.rms_norm_eps)
    k = rms_norm(k, lp["k_norm"], config.rms_norm_eps)
    keys = None
    if windowed:
        # The rotary is the windowed layers' alone.
        q = apply_rope(q, positions, config.rope_theta)
        k = apply_rope(k, positions, config.rope_theta)
        tails = (None if kv_tail is None
                 else (kv_tail[0][layer], kv_tail[1][layer]))
        attn, kc, vc, keys = _windowed(
            config, q, k, v, k_cache[layer], v_cache[layer], slots,
            positions, kv_lens, valid, tails)
        k_cache = k_cache[:layer] + (kc,) + k_cache[layer + 1:]
        v_cache = v_cache[:layer] + (vc,) + v_cache[layer + 1:]
    else:
        with jax.named_scope("full_attn"):
            attn, k_cache, v_cache = hybrid_attention(
                config, q, k, v, k_cache, v_cache, page_table, positions,
                kv_lens, valid, layer, kv_tail)
    return attn.reshape(b, t, nh * d) @ lp["wo"], k_cache, v_cache, keys


def forward(params: Params, config: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, page_table: jnp.ndarray,
            kv_lens: jnp.ndarray, valid: jnp.ndarray,
            k_cache, v_cache, lora=None, lora_ids=None,
            kv_tail=None, state_slots=None,
            ) -> Tuple[jnp.ndarray, tuple, tuple]:
    """Same contract as models.qwen3_next.forward: ``state_slots [B]``
    (None: every row the trash slot), per-layer caches and the counters
    after them; with ``kv_tail`` every layer's entries, planes and
    rings alike, are replaced by their updated tails in what comes
    back. No LoRA targets."""
    if lora is not None:
        raise NotImplementedError("exaone_moe has no LoRA targets")
    if not isinstance(k_cache, (list, tuple)):
        raise ValueError("exaone_moe keeps per-layer caches "
                         "(cache_layout='per_layer')")
    c = config
    b, t = tokens.shape
    if state_slots is None:
        state_slots = jnp.zeros((b,), jnp.int32)
    layers = c.num_hidden_layers
    stats = k_cache[layers]
    k_cache, v_cache = tuple(k_cache[:layers]), tuple(v_cache)
    impl = hybrid_kernel_impl(c)
    eps = c.rms_norm_eps
    real = jnp.sum(valid).astype(jnp.float32)

    x = params["embed"][tokens]
    for layer, windowed in enumerate(c.layer_is_linear):
        lp = {k: params[k][layer] for k in LAYER}
        lp.update({k: params[f"{k}_{layer}"] for k in ATTENTION})
        mixed, k_cache, v_cache, keys = _attention(
            c, lp, x, windowed, positions, page_table, kv_lens, valid,
            state_slots, k_cache, v_cache, layer, kv_tail)
        if windowed and t == 1:
            stats = stats.at[-2:].add(jnp.stack([
                jnp.sum(jnp.where(valid, keys, 0)).astype(jnp.float32),
                real]))
        x = x + rms_norm(mixed, lp["post_attn_norm"], eps)
        if layer < c.num_dense_layers:
            with jax.named_scope("dense_ffn"):
                y = swiglu(x, params["w_gate_up"][layer],
                           params["w_down"][layer])
        else:
            rp = {k: params[f"{k}_{layer}"] for k in ROUTED}
            rp.update({k: params[f"e_{k}_{layer}"] for k in EXPERTS})
            y, load = expert_block(c, rp, x, valid, impl)
            if t == 1:
                stats = count_step(stats, c.num_experts_per_tok, load,
                                   valid, c.router_width)
        x = x + rms_norm(y, lp["post_ffn_norm"], eps)

    x = rms_norm(x, params["final_norm"], eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = (x @ head).astype(jnp.float32)
    return logits, k_cache + (stats,), v_cache
