"""The model families this engine serves, and what each declares.

``config.architecture`` names a ``Family``. Every family gives the
module of its ``init_params`` and ``forward`` and says whether its
forward takes ``kv_tail`` (the deferred-write decode burst). A *hybrid*
family, whose layers are not all attention over pages, declares the
rest here too, so that the engine's configuration, the cache builder
and the runner ask the family and name no model:

- ``recurrent_layers(config)``: per layer, True where the layer keeps a
  recurrent state a sequence (in a slot of the state pool,
  ``engine/kv_cache.py``) and no pages;
- ``state(config)``: one sequence's state in one such layer as two
  ``(shape, dtype name)`` entries, the first kept in the layer's
  ``k_cache`` entry and the second in its ``v_cache`` entry
  (``"model"`` is the model's own dtype);
- ``conv_tail``: the second of those is the tail of a short causal
  convolution, ``[K-1, channels]``, and the forward takes
  ``conv_tail``: in a deferred-write burst the runner gathers each
  row's tail from the pool once, carries it dense and scatters it
  back once (``ops/gated_delta.py`` ``causal_conv_step``);
- ``counters``: names of the float32 counters the forward keeps in one
  extra ``k_cache`` entry after the layers (none: no such entry);
- ``refusals``: in the family's own words, why it refuses the features
  that not every hybrid refuses for the same reason
  (``engine/config.py`` ``_recurrent_state_refusals`` words the rest).

This module imports no model and nothing of the engine at load, so
``engine/config.py`` can ask it.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class Family:
    module: str
    deferred_kv: bool = False
    recurrent_layers: Optional[Callable] = None
    state: Optional[Callable] = None
    conv_tail: bool = False
    counters: Tuple[str, ...] = ()
    refusals: Dict[str, str] = dataclasses.field(default_factory=dict)


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
        counters=("layer_steps", "choices", "held_choices", "max_load",
                  "experts_hit"),
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


def list_architectures():
    return list(FAMILIES)


def deferred_kv_architectures() -> tuple:
    """Architectures whose forward takes ``kv_tail``."""
    return tuple(a for a, f in FAMILIES.items() if f.deferred_kv)


def init_hybrid_cache(config, num_pages: int, page_size: int,
                      num_state_slots: int):
    """A hybrid family's per-layer cache tuples: page buffers for the
    attention layers, the two state pools (``num_state_slots`` + the
    trash slot 0) for the recurrent ones, and after the layers the
    family's counters, if it keeps any, as one more ``k_cache`` entry."""
    import jax.numpy as jnp

    fam = family(config.architecture)
    model_dtype = config.jax_dtype
    pools = [((num_state_slots + 1,) + shape,
              model_dtype if dtype == "model" else jnp.dtype(dtype))
             for shape, dtype in fam.state(config)]
    page_shape = (config.num_key_value_heads, num_pages, config.head_dim,
                  page_size)
    k_cache, v_cache = [], []
    for recurrent in fam.recurrent_layers(config):
        if recurrent:
            k_cache.append(jnp.zeros(*pools[0]))
            v_cache.append(jnp.zeros(*pools[1]))
        else:
            k_cache.append(jnp.zeros(page_shape, model_dtype))
            v_cache.append(jnp.zeros(page_shape, model_dtype))
    if fam.counters:
        k_cache.append(jnp.zeros((len(fam.counters),), jnp.float32))
    return tuple(k_cache), tuple(v_cache)
