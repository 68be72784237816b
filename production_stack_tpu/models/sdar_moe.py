"""SDAR-MoE block-diffusion decoders (JetLM SDAR-30B-A3B-Chat style).

The layer is Qwen3-MoE's: grouped-query attention over the paged cache
with an RMS norm over each head's dimensions on q and on k before a
full rotary embedding, and in every layer a router over all published
experts (a softmax, the ``num_experts_per_tok`` largest kept and, with
``norm_topk_prob``, divided by their sum: ``ops/moe.py`` ``route``) and
the held experts' part of the top-k sum (``held_experts``). No shared
expert, no dense layer; norms are plain; embedding and head are untied
unless the configuration ties them.

What is not Qwen3-MoE's is what a position sees and what the head
says. Positions come in blocks of ``config.diffusion_block_length``:
a query at ``t`` sees every key up to the END of its block, ``key <= t
| (B - 1)``, its own block in both directions. The head's row at
``t`` is the distribution of the token AT ``t``, and a place whose
token is not known yet enters as the embedding of
``config.mask_token_id``: ``masked [B, T]`` says which places those
are (a flag beside the ids, never read off the id: a prompt may hold
the mask's id as a token). Generation is by denoising a block at a
time (engine/model_runner.py ``_decode_burst_block_impl``,
docs/block_diffusion.md).

Same contract as ``models.lfm2_moe.forward`` with per-layer cache
tuples and the family's counters after them (the expert layer's six,
then the burst's four: registry.py), in two forms:

- without ``kv_tail`` a prefill chunk of whole blocks: K/V go to the
  pages (a run, in place) and the chunk attends under sight by block
  (``models/llama.py`` ``cached_attention(block=B)``);
- with ``kv_tail`` one pass of the burst over one block a row (``T ==
  B``): K/V go to the block's tail slots, over whatever an earlier
  pass left there, and the block attends pages, finished blocks and
  itself (``block_attention``). A denoising pass and the store pass
  differ in the tokens they are given and in ``head``.

``head=False`` returns no logits (a prefill chunk and the store pass
sample nothing, and the head is 0.6e9 B of the 8.7e9 a pass reads);
``position_major`` returns them ``[T, B, vocab]``, each place a dense
plane, as the sampler reads them.

Parameters are stacks over the layers beside the experts, which are
one array a layer (``w_gate_up_<i>``, ``w_down_<i>``: a slice of a
stack handed to the grouped product's kernel is first copied out).
gate | up side by side is this program's own layout.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.models.llama import (
    block_attention,
    cached_attention,
    hybrid_kernel_impl,
    rms_norm,
)
from production_stack_tpu.ops.moe import count_step, held_experts, route
from production_stack_tpu.ops.rope import apply_rope

Params = Dict[str, jnp.ndarray]

LAYER = ("attn_norm", "ffn_norm", "wq", "wk", "wv", "wo", "q_norm",
         "k_norm", "router")
EXPERTS = ("w_gate_up", "w_down")   # one array a layer: <name>_<i>


def init_params(config: ModelConfig, key: jax.Array) -> Params:
    """Random parameters; every norm's weight (the two head norms
    among them) 1 + N(0, 0.1), so that leaving one out shows."""
    c = config
    h, d = c.hidden_size, c.head_dim
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    fe, layers = c.moe_intermediate_size, c.num_hidden_layers
    dtype = c.jax_dtype
    keys = iter(jax.random.split(key, 16 + 2 * layers))

    def dense(shape, scale=0.02):
        # One leaf at a time (models/lfm2_moe.py says why).
        return jax.block_until_ready(
            (scale * jax.random.normal(next(keys), shape, jnp.float32)
             ).astype(dtype))

    def near_one(shape):
        return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                              jnp.float32)).astype(dtype)

    params: Params = {
        "embed": dense((c.vocab_size, h)),
        "final_norm": near_one((h,)),
        "lm_head": dense((h, c.vocab_size)),
        "attn_norm": near_one((layers, h)),
        "ffn_norm": near_one((layers, h)),
        "wq": dense((layers, h, nh * d)),
        "wk": dense((layers, h, nkv * d)),
        "wv": dense((layers, h, nkv * d)),
        "wo": dense((layers, nh * d, h)),
        "q_norm": near_one((layers, d)),
        "k_norm": near_one((layers, d)),
        "router": dense((layers, h, c.router_width)),
    }
    for i in range(layers):
        params[f"w_gate_up_{i}"] = dense((c.num_experts, h, 2 * fe))
        params[f"w_down_{i}"] = dense((c.num_experts, fe, h))
    if c.tie_word_embeddings:
        del params["lm_head"]
    return params


def _attention(config, lp, x, positions, page_table, kv_lens, valid,
               k_cache, v_cache, layer, kv_tail):
    """Grouped-query attention, q and k normed a head, then rotary at
    each place's own position; sight by block."""
    nh, nkv, d = (config.num_attention_heads, config.num_key_value_heads,
                  config.head_dim)
    b, t, _ = x.shape
    q = (x @ lp["wq"]).reshape(b, t, nh, d)
    k = (x @ lp["wk"]).reshape(b, t, nkv, d)
    v = (x @ lp["wv"]).reshape(b, t, nkv, d)
    q = rms_norm(q, lp["q_norm"], config.rms_norm_eps)
    k = rms_norm(k, lp["k_norm"], config.rms_norm_eps)
    q = apply_rope(q, positions, config.rope_theta)
    k = apply_rope(k, positions, config.rope_theta)
    if kv_tail is None:
        attn, k_cache, v_cache = cached_attention(
            config, q, k, v, k_cache, v_cache, page_table, positions,
            kv_lens, valid, layer, block=config.diffusion_block_length)
    else:
        attn, kt, vt = block_attention(
            config, q, k, v, k_cache, v_cache, page_table, positions,
            kv_lens, valid, layer, kv_tail)
        k_cache = k_cache[:layer] + (kt,) + k_cache[layer + 1:]
        v_cache = v_cache[:layer] + (vt,) + v_cache[layer + 1:]
    return attn.reshape(b, t, nh * d) @ lp["wo"], k_cache, v_cache


def sparse_block(config: ModelConfig, lp, x, valid, moe_impl="xla"):
    """x [B, T, H] normalised -> (y [B, T, H], load [E]: real tokens
    that chose each held expert)."""
    b, t, h = x.shape
    flat = x.reshape(b * t, h)
    weights, ids = route(flat, lp["router"], config.num_experts_per_tok,
                         config.norm_topk_prob)
    y, load = held_experts(
        flat, weights, ids, lp["w_gate_up"], lp["w_down"],
        config.expert_parallel_rank * config.num_experts,
        valid=valid.reshape(b * t), impl=moe_impl,
        router_width=config.router_width)
    return y.reshape(b, t, h), load


def forward(params: Params, config: ModelConfig, tokens: jnp.ndarray,
            positions: jnp.ndarray, page_table: jnp.ndarray,
            kv_lens: jnp.ndarray, valid: jnp.ndarray,
            k_cache, v_cache, lora=None, lora_ids=None,
            kv_tail=None, masked=None, head: bool = True,
            position_major: bool = False,
            ) -> Tuple[jnp.ndarray, tuple, tuple]:
    """The module's text. Returns ``(logits or None, k_cache + (stats,),
    v_cache)``; with ``kv_tail`` the layers' planes are replaced by
    their updated tails in what comes back. No LoRA targets."""
    if lora is not None:
        raise NotImplementedError("sdar_moe has no LoRA targets")
    if not isinstance(k_cache, (list, tuple)):
        raise ValueError("sdar_moe keeps per-layer caches "
                         "(cache_layout='per_layer')")
    layers = config.num_hidden_layers
    stats = k_cache[layers]
    k_cache, v_cache = tuple(k_cache[:layers]), tuple(v_cache)
    impl = hybrid_kernel_impl(config)
    eps = config.rms_norm_eps

    x = params["embed"][tokens]
    if masked is not None:
        x = jnp.where(masked[..., None],
                      params["embed"][config.mask_token_id], x)
    for layer in range(layers):
        lp = {k: params[k][layer] for k in LAYER}
        lp.update({k: params[f"{k}_{layer}"] for k in EXPERTS})
        mixed, k_cache, v_cache = _attention(
            config, lp, rms_norm(x, lp["attn_norm"], eps), positions,
            page_table, kv_lens, valid, k_cache, v_cache, layer, kv_tail)
        x = x + mixed
        y, load = sparse_block(config, lp,
                               rms_norm(x, lp["ffn_norm"], eps), valid,
                               impl)
        if kv_tail is not None:
            # A pass of the burst: T real tokens a live row.
            stats = count_step(stats, config.num_experts_per_tok, load,
                               valid, config.router_width)
        x = x + y

    logits = None
    if head:
        x = rms_norm(x, params["final_norm"], eps)
        if position_major:
            x = jnp.swapaxes(x, 0, 1)
        w = params.get("lm_head")
        if w is None:
            w = params["embed"].T
        logits = (x @ w).astype(jnp.float32)
    return logits, tuple(k_cache) + (stats,), tuple(v_cache)
