"""The model families this engine serves, and what each declares.

``config.architecture`` names a ``Family``. Every family gives the
module of its ``init_params`` and ``forward`` and says whether its
forward takes ``kv_tail`` (the deferred-write decode burst). A *hybrid*
family, whose layers are not all attention over pages, declares the
rest here too, so that the engine's configuration, the cache builder
and the runner ask the family and name no model:

- ``recurrent_layers(config)``: per layer, True where the layer keeps a
  state of fixed size a sequence (in a slot of the state pool,
  ``engine/kv_cache.py``: a recurrence's state, a convolution's tail,
  a window's K/V) and no pages;
- ``state(config)``: one sequence's state in one such layer as one
  or two ``(shape, dtype name)`` entries (``"model"`` is the model's
  own dtype). The last is kept in the layer's ``v_cache`` entry; the
  one before it (a recurrence's own state, or a windowed attention
  layer's K where the last is its V: ``ring``) in its ``k_cache``
  entry, which is ``None`` for a family that declares one entry: no
  pool is made, read or written for it (``state_pools``);
- ``ring``: the two entries are no recurrence and no tail but a
  windowed attention layer's K and V, a ring of ``window`` places a
  sequence each (``[kv_heads, head_dim, window]``). Their pools are
  laid out as the paged planes are, the slots where a plane has its
  pages (``[kv_heads, slots, head_dim, window]``), so that the paged
  writers and kernels serve a slot as a page whose table has one entry
  (``ops/window_attention.py``). The forward takes ``kv_tail`` for
  these layers too: in a deferred-write burst a ring is read and not
  written, the layer's K/V rides a tail as a paged layer's does, and
  the runner flushes it to the ring's places once a burst;
- ``conv_tail``: the last of those is the tail of a short causal
  convolution, ``[K-1, channels]``, and the forward takes
  ``conv_tail``: in a deferred-write burst the runner gathers each
  row's tail from the pool once, carries it dense and scatters it
  back once (``ops/gated_delta.py`` ``causal_conv_step``);
- ``counters``: names of the float32 counters the forward keeps in one
  extra ``k_cache`` entry after the layers (none: no such entry);
- ``refusals``: in the family's own words, why it refuses the features
  that not every hybrid refuses for the same reason
  (``engine/config.py`` ``_recurrent_state_refusals`` words the rest).

A family whose pages do not hold K and V of ``num_key_value_heads``
heads of ``head_dim``, one entry a layer, declares what they hold:

- ``page_cache(config)``: a ``PageCache``: how many paged entries the
  model keeps (a layer may have more than one attention sublayer),
  the heads and the rows a token of one plane, and the planes an
  entry has: 2 is K and V, 1 is a latent that serves as both and is
  stored once, its second plane ``None`` and never made, read, written
  or counted. The cache builder, the burst's tails and flush, the
  bytes a token and the page budget follow it.

A family whose checkpoints carry a multi-token-prediction module says
so:

- ``draft_module``: the family's module has ``draft`` beside
  ``forward`` (``get_draft``), its ``forward`` takes ``return_hidden``
  and, with ``kv_tail``, two positions a row, and its ``page_cache``
  counts the module's entries after the layers'. Where the
  configuration has the module (``num_nextn_predict_layers``) the
  deferred burst drafts with it (``engine/model_runner.py``); the
  counters then end in ``drafts`` and ``accepted``.

A family that generates by diffusion over blocks says so:

- ``block(config)``: the block's length ``B`` in positions (a power of
  two). With it the engine knows the rest: a query sees its own block
  in both directions, so the forward takes whole blocks (``masked``
  beside the ids for the places not known yet, ``head=False`` for a
  pass that samples nothing); a prefill covers the prompt's whole
  blocks and yields NO token; a burst works a block a row at a time,
  denoising passes that commit some of its places and then one pass
  of its own that writes the block's final K/V (the store pass); the
  counters end in ``denoise_passes``, ``store_passes``, ``blocks``,
  ``committed`` and ``sorted_passes`` (engine/model_runner.py
  ``_decode_burst_block_impl``, docs/block_diffusion.md).

This module imports no model and nothing of the engine at load, so
``engine/config.py`` can ask it.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict, NamedTuple, Optional, Tuple


class PageCache(NamedTuple):
    entries: int   # paged cache entries of the whole model
    heads: int     # heads of one plane
    width: int     # rows a token a head
    planes: int    # 2: K and V; 1: one latent, stored once


@dataclasses.dataclass(frozen=True)
class Family:
    module: str
    deferred_kv: bool = False
    recurrent_layers: Optional[Callable] = None
    state: Optional[Callable] = None
    conv_tail: bool = False
    ring: bool = False
    counters: Tuple[str, ...] = ()
    refusals: Dict[str, str] = dataclasses.field(default_factory=dict)
    page_cache: Optional[Callable] = None
    draft_module: bool = False
    block: Optional[Callable] = None


def _qwen3_next_layers(c) -> tuple:
    """Gated full attention every ``full_attention_interval``-th
    layer, Gated DeltaNet otherwise."""
    n = c.full_attention_interval
    return tuple(bool(n) and (i + 1) % n != 0
                 for i in range(c.num_hidden_layers))


def _qwen3_next_state(c) -> tuple:
    """The delta rule's ``S`` (float32, as the published recurrence
    keeps it) and the tail of the convolution over q | k | v."""
    conv_channels = (2 * c.linear_num_key_heads * c.linear_key_head_dim
                     + c.linear_num_value_heads * c.linear_value_head_dim)
    return (((c.linear_num_value_heads, c.linear_key_head_dim,
              c.linear_value_head_dim), "float32"),
            ((c.linear_conv_kernel_dim - 1, conv_channels), "model"))


def _jamba_layers(c) -> tuple:
    """Attention where ``i % attn_layer_period == attn_layer_offset``,
    a Mamba mixer otherwise."""
    return tuple(i % c.attn_layer_period != c.attn_layer_offset
                 for i in range(c.num_hidden_layers))


def _jamba_state(c) -> tuple:
    """The selective scan's ``h`` (float32), kept transposed
    ``[d_state, d_inner]`` so that the channels lie along the lanes
    (``ops/selective_scan_pallas.py``), and the tail of the
    convolution over the ``d_inner`` channels."""
    return (((c.mamba_d_state, c.mamba_d_inner), "float32"),
            ((c.mamba_d_conv - 1, c.mamba_d_inner), "model"))


def _lfm2_moe_layers(c) -> tuple:
    """A gated short convolution where ``layer_types`` says ``conv``,
    attention where it says ``full_attention``: the published list,
    which no period reproduces."""
    return tuple(kind == "conv" for kind in c.layer_types)


def _lfm2_moe_state(c) -> tuple:
    """The tail of the convolution over the hidden channels, and
    nothing else: the layer has no recurrence of its own."""
    return (((c.conv_L_cache - 1, c.hidden_size), "model"),)


def _granitemoehybrid_layers(c) -> tuple:
    """A Mamba-2 mixer where ``layer_types`` says ``mamba``, attention
    where it says ``attention``: the published list."""
    return tuple(kind == "mamba" for kind in c.layer_types)


def _granitemoehybrid_state(c) -> tuple:
    """The Mamba-2 recurrence's ``h`` (float32), kept ``[d_state,
    heads * d_head]`` so that the channels lie along the lanes and the
    state elements along the sublanes (``ops/ssd.py`` says why), and
    the tail of the convolution over ``x | B | C``."""
    return (((c.mamba_d_state, c.mamba_d_inner), "float32"),
            ((c.mamba_d_conv - 1, c.mamba_d_inner + 2 * c.mamba_d_state),
             "model"))


def _exaone_moe_layers(c) -> tuple:
    """A window's ring where ``layer_types`` says
    ``sliding_attention``, pages where it says ``full_attention``:
    the published list."""
    return tuple(kind == "sliding_attention" for kind in c.layer_types)


def _exaone_moe_state(c) -> tuple:
    """A windowed layer's K ring and V ring: ``sliding_window``
    places a sequence, each what one page of that many tokens
    holds."""
    ring = ((c.num_key_value_heads, c.head_dim, c.sliding_window), "model")
    return (ring, ring)


def _longcat_flash_pages(c) -> PageCache:
    """Two latent-attention sublayers a layer, each with its own
    cache: per token the compressed latent (``kv_lora_rank``) and the
    one rotary key every head shares (``qk_rope_head_dim``), side by
    side in one plane of one head. The latent's rows are the values
    too, so there is no second plane."""
    return PageCache(entries=2 * c.num_hidden_layers, heads=1,
                     width=c.kv_lora_rank + c.qk_rope_head_dim, planes=1)


def _glm4_moe_lite_pages(c) -> PageCache:
    """One latent-attention sublayer a layer, and one more entry for
    each prediction layer the configuration keeps: the module's layer
    attends over its own latents, not the main model's."""
    return PageCache(
        entries=c.num_hidden_layers + c.num_nextn_predict_layers,
        heads=1, width=c.kv_lora_rank + c.qk_rope_head_dim, planes=1)


def _sdar_moe_pages(c) -> PageCache:
    """K and V of every layer, as a family that declares nothing has
    them; declared so that the cache is built here, with the family's
    counters after the layers."""
    return PageCache(entries=c.num_hidden_layers,
                     heads=c.num_key_value_heads, width=c.head_dim,
                     planes=2)


_EXPERT_COUNTERS = ("layer_steps", "choices", "held_choices", "max_load",
                    "experts_hit", "room_overflows")

_LLAMA = Family("llama", deferred_kv=True)

FAMILIES: Dict[str, Family] = {
    "llama": _LLAMA,
    "mistral": _LLAMA,
    "qwen2": _LLAMA,
    "opt": Family("opt"),
    "gpt2": Family("gpt2"),
    "mixtral": Family("mixtral"),
    "qwen3_next": Family(
        "qwen3_next", deferred_kv=True,
        recurrent_layers=_qwen3_next_layers, state=_qwen3_next_state,
        conv_tail=True,
        counters=_EXPERT_COUNTERS,
        refusals={
            "tensor parallelism": "the state pools and the expert "
                                  "layer have no sharding rules",
            "weight quantization": "the fused projections and experts "
                                   "have no quantized form",
        }),
    "jamba": Family(
        "jamba", deferred_kv=True,
        recurrent_layers=_jamba_layers, state=_jamba_state,
        conv_tail=True,
        refusals={
            "tensor parallelism": "the state pools and the Mamba "
                                  "mixer have no sharding rules",
            "weight quantization": "the Mamba mixer's projections "
                                   "have no quantized form",
        }),
    "lfm2_moe": Family(
        "lfm2_moe", deferred_kv=True,
        recurrent_layers=_lfm2_moe_layers, state=_lfm2_moe_state,
        conv_tail=True, counters=_EXPERT_COUNTERS,
        refusals={
            "tensor parallelism": "the convolution's tail pool and the "
                                  "expert layer have no sharding rules",
            "weight quantization": "the convolution's fused projection "
                                   "and the experts have no quantized "
                                   "form",
        }),
    "granitemoehybrid": Family(
        "granitemoehybrid", deferred_kv=True,
        recurrent_layers=_granitemoehybrid_layers,
        state=_granitemoehybrid_state,
        conv_tail=True, counters=_EXPERT_COUNTERS,
        refusals={
            "tensor parallelism": "the state pools, the Mamba-2 mixer "
                                  "and the expert layer have no "
                                  "sharding rules",
            "weight quantization": "the Mamba-2 mixer's projections "
                                   "and the experts have no quantized "
                                   "form",
        }),
    "exaone_moe": Family(
        "exaone_moe", deferred_kv=True,
        recurrent_layers=_exaone_moe_layers, state=_exaone_moe_state,
        ring=True,
        counters=_EXPERT_COUNTERS + ("swa_keys", "swa_queries"),
        refusals={
            "tensor parallelism": "the rings' pools and the expert "
                                  "layer have no sharding rules",
            "weight quantization": "the experts have no quantized "
                                   "form",
        }),
    "longcat_flash": Family(
        "longcat_flash", deferred_kv=True,
        page_cache=_longcat_flash_pages,
        counters=_EXPERT_COUNTERS + ("zero_choices",),
        refusals={
            "tensor parallelism": "the latent is one head shared by "
                                  "every query head, and the expert "
                                  "layer has no sharding rules",
            "weight quantization": "the low-rank projections and the "
                                   "experts have no quantized form",
        }),
    "glm4_moe_lite": Family(
        "glm4_moe_lite", deferred_kv=True,
        page_cache=_glm4_moe_lite_pages,
        counters=_EXPERT_COUNTERS + ("drafts", "accepted"),
        draft_module=True,
        refusals={
            "tensor parallelism": "the latent is one head shared by "
                                  "every query head, and the expert "
                                  "layer has no sharding rules",
            "weight quantization": "the low-rank projections and the "
                                   "experts have no quantized form",
        }),
    "sdar_moe": Family(
        "sdar_moe", deferred_kv=True,
        page_cache=_sdar_moe_pages,
        counters=_EXPERT_COUNTERS + ("denoise_passes", "store_passes",
                                     "blocks", "committed",
                                     "sorted_passes"),
        block=lambda c: c.diffusion_block_length,
        refusals={
            "tensor parallelism": "the expert layer has no sharding "
                                  "rules",
            "weight quantization": "the experts have no quantized "
                                   "form",
        }),
}


def family(architecture: str) -> Family:
    try:
        return FAMILIES[architecture]
    except KeyError:
        raise ValueError(f"Unknown architecture: {architecture}") from None


def get_model(config) -> Tuple[Callable, Callable]:
    """Returns (init_params, forward) for the configured architecture."""
    module = importlib.import_module(
        "production_stack_tpu.models." + family(config.architecture).module)
    return module.init_params, module.forward


def get_draft(config) -> Callable:
    """``draft`` of a family that declares a draft module."""
    fam = family(config.architecture)
    if not fam.draft_module:
        raise ValueError(f"{config.architecture} declares no draft module")
    return importlib.import_module(
        "production_stack_tpu.models." + fam.module).draft


def list_architectures():
    return list(FAMILIES)


def deferred_kv_architectures() -> tuple:
    """Architectures whose forward takes ``kv_tail``."""
    return tuple(a for a, f in FAMILIES.items() if f.deferred_kv)


def state_pools(config) -> tuple:
    """One sequence's state in one recurrent layer as ``(k entry, v
    entry)``, each ``(shape, dtype name)`` or ``None`` where the family
    keeps nothing there: the one reading of ``Family.state``'s one or
    two entries that the configuration, the cache builder and the
    runner share."""
    entries = tuple(family(config.architecture).state(config))
    if not 1 <= len(entries) <= 2:
        raise ValueError(
            f"{config.architecture} declares {len(entries)} state "
            "entries a recurrent layer; a family declares one (kept in "
            "v_cache) or two (k_cache, v_cache)")
    return (None,) * (2 - len(entries)) + entries


def page_cache(config) -> PageCache:
    """What the configuration's paged cache holds, as its family
    declares it, or K and V of every layer that is not recurrent."""
    declared = family(config.architecture).page_cache
    if declared is not None:
        return declared(config)
    return PageCache(entries=config.num_kv_layers,
                     heads=config.num_key_value_heads,
                     width=config.head_dim, planes=2)


def init_hybrid_cache(config, num_pages: int, page_size: int,
                      num_state_slots: int):
    """The per-entry cache tuples of a family that declares its cache
    here: page buffers for the paged entries (the second plane ``None``
    where the family declares one), the state pools it declares
    (``num_state_slots`` + the trash slot 0, the leading axis but of a
    ring's pool, whose slots lie where a plane's pages do; ``None``
    where it declares none) for the recurrent layers, and after the entries the family's
    counters, if it keeps any, as one more ``k_cache`` entry."""
    import jax.numpy as jnp

    fam = family(config.architecture)
    model_dtype = config.jax_dtype

    def pool(entry):
        if entry is None:
            return None
        shape, dtype = entry
        shape = tuple(shape)
        # A ring's pool is a paged plane whose pages are the slots.
        shape = (shape[:1] + (num_state_slots + 1,) + shape[1:]
                 if fam.ring else (num_state_slots + 1,) + shape)
        return jnp.zeros(
            shape, model_dtype if dtype == "model" else jnp.dtype(dtype))

    pages = page_cache(config)
    page_shape = (pages.heads, num_pages, pages.width, page_size)
    k_cache, v_cache = [], []
    for recurrent in config.cache_entry_is_state:
        if recurrent:
            k_entry, v_entry = state_pools(config)
            k_cache.append(pool(k_entry))
            v_cache.append(pool(v_entry))
        else:
            k_cache.append(jnp.zeros(page_shape, model_dtype))
            v_cache.append(jnp.zeros(page_shape, model_dtype)
                           if pages.planes == 2 else None)
    if fam.counters:
        k_cache.append(jnp.zeros((len(fam.counters),), jnp.float32))
    return tuple(k_cache), tuple(v_cache)
