"""HF checkpoint loading: safetensors/torch-bin -> stacked JAX pytrees.

Weight names follow the HF conventions for Llama
(model.layers.N.self_attn.q_proj.weight, ...) and OPT
(model.decoder.layers.N....). Per-layer tensors are stacked along a
leading L axis to match the scanned-layer model layout
(models/llama.py). Linear weights are transposed: HF stores [out, in],
our matmuls use [in, out].
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)


def _load_raw_tensors(model_dir: str) -> Dict[str, np.ndarray]:
    tensors: Dict[str, np.ndarray] = {}
    st_files = sorted(
        f for f in os.listdir(model_dir) if f.endswith(".safetensors")
    )
    if st_files:
        from safetensors.numpy import load_file
        for f in st_files:
            tensors.update(load_file(os.path.join(model_dir, f)))
        return tensors
    bin_files = sorted(
        f for f in os.listdir(model_dir)
        if f.endswith(".bin") and f.startswith("pytorch_model")
    )
    if bin_files:
        import torch
        for f in bin_files:
            state = torch.load(
                os.path.join(model_dir, f), map_location="cpu",
                weights_only=True,
            )
            for k, v in state.items():
                tensors[k] = v.float().numpy()
        return tensors
    raise FileNotFoundError(
        f"No safetensors/pytorch_model.bin found in {model_dir}"
    )


def load_model_config(model_dir: str,
                      name: str = "") -> ModelConfig:
    with open(os.path.join(model_dir, "config.json")) as f:
        hf = json.load(f)
    return ModelConfig.from_hf_config(hf, name=name or model_dir)


def _stack(tensors: Dict[str, np.ndarray], template: str, layers: int,
           transpose: bool = False) -> np.ndarray:
    parts = []
    for i in range(layers):
        t = tensors[template.format(i)]
        parts.append(t.T if transpose else t)
    return np.stack(parts)


def load_llama_weights(model_dir: str, config: ModelConfig,
                       dtype=None) -> Dict[str, jnp.ndarray]:
    raw = _load_raw_tensors(model_dir)
    raw = {k.removeprefix("model."): v for k, v in raw.items()}
    L = config.num_hidden_layers
    dtype = dtype or config.jax_dtype

    def lt(template, transpose=True):
        return jnp.asarray(
            _stack(raw, template, L, transpose=transpose), dtype
        )

    params = {
        "embed": jnp.asarray(raw["embed_tokens.weight"], dtype),
        "final_norm": jnp.asarray(raw["norm.weight"], dtype),
        "attn_norm": lt("layers.{}.input_layernorm.weight",
                        transpose=False),
        "wq": lt("layers.{}.self_attn.q_proj.weight"),
        "wk": lt("layers.{}.self_attn.k_proj.weight"),
        "wv": lt("layers.{}.self_attn.v_proj.weight"),
        "wo": lt("layers.{}.self_attn.o_proj.weight"),
        "mlp_norm": lt("layers.{}.post_attention_layernorm.weight",
                       transpose=False),
        "w_gate": lt("layers.{}.mlp.gate_proj.weight"),
        "w_up": lt("layers.{}.mlp.up_proj.weight"),
        "w_down": lt("layers.{}.mlp.down_proj.weight"),
    }
    if config.attention_bias:  # Qwen2-style q/k/v biases
        params["bq"] = lt("layers.{}.self_attn.q_proj.bias", False)
        params["bk"] = lt("layers.{}.self_attn.k_proj.bias", False)
        params["bv"] = lt("layers.{}.self_attn.v_proj.bias", False)
    if not config.tie_word_embeddings:
        head = raw.get("lm_head.weight")
        if head is None:
            config.tie_word_embeddings = True
        else:
            params["lm_head"] = jnp.asarray(head.T, dtype)
    return params


def load_gpt2_weights(model_dir: str, config: ModelConfig,
                      dtype=None) -> Dict[str, jnp.ndarray]:
    """HF GPT-2 checkpoints use Conv1D layout ([in, out], no transpose)
    and a fused qkv projection (``c_attn``), split here so the runtime
    shares the llama-family attention path."""
    raw = _load_raw_tensors(model_dir)
    raw = {k.removeprefix("transformer."): v for k, v in raw.items()}
    L = config.num_hidden_layers
    h = config.hidden_size
    dtype = dtype or config.jax_dtype

    def lt(template, transpose=False):
        return jnp.asarray(
            _stack(raw, template, L, transpose=transpose), dtype
        )

    qkv_w = _stack(raw, "h.{}.attn.c_attn.weight", L)   # [L, h, 3h]
    qkv_b = _stack(raw, "h.{}.attn.c_attn.bias", L)     # [L, 3h]
    return {
        "embed": jnp.asarray(raw["wte.weight"], dtype),
        "pos_embed": jnp.asarray(raw["wpe.weight"], dtype),
        "final_norm_w": jnp.asarray(raw["ln_f.weight"], dtype),
        "final_norm_b": jnp.asarray(raw["ln_f.bias"], dtype),
        "attn_norm_w": lt("h.{}.ln_1.weight"),
        "attn_norm_b": lt("h.{}.ln_1.bias"),
        "wq": jnp.asarray(qkv_w[:, :, 0 * h:1 * h], dtype),
        "bq": jnp.asarray(qkv_b[:, 0 * h:1 * h], dtype),
        "wk": jnp.asarray(qkv_w[:, :, 1 * h:2 * h], dtype),
        "bk": jnp.asarray(qkv_b[:, 1 * h:2 * h], dtype),
        "wv": jnp.asarray(qkv_w[:, :, 2 * h:3 * h], dtype),
        "bv": jnp.asarray(qkv_b[:, 2 * h:3 * h], dtype),
        "wo": lt("h.{}.attn.c_proj.weight"),
        "bo": lt("h.{}.attn.c_proj.bias"),
        "mlp_norm_w": lt("h.{}.ln_2.weight"),
        "mlp_norm_b": lt("h.{}.ln_2.bias"),
        "fc1": lt("h.{}.mlp.c_fc.weight"),
        "fc1_b": lt("h.{}.mlp.c_fc.bias"),
        "fc2": lt("h.{}.mlp.c_proj.weight"),
        "fc2_b": lt("h.{}.mlp.c_proj.bias"),
    }


def load_opt_weights(model_dir: str, config: ModelConfig,
                     dtype=None) -> Dict[str, jnp.ndarray]:
    raw = _load_raw_tensors(model_dir)
    raw = {
        k.removeprefix("model.").removeprefix("decoder."): v
        for k, v in raw.items()
    }
    L = config.num_hidden_layers
    dtype = dtype or config.jax_dtype

    def lt(template, transpose=True):
        return jnp.asarray(
            _stack(raw, template, L, transpose=transpose), dtype
        )

    return {
        "embed": jnp.asarray(raw["embed_tokens.weight"], dtype),
        "pos_embed": jnp.asarray(raw["embed_positions.weight"], dtype),
        "final_norm_w": jnp.asarray(raw["final_layer_norm.weight"], dtype),
        "final_norm_b": jnp.asarray(raw["final_layer_norm.bias"], dtype),
        "attn_norm_w": lt("layers.{}.self_attn_layer_norm.weight", False),
        "attn_norm_b": lt("layers.{}.self_attn_layer_norm.bias", False),
        "wq": lt("layers.{}.self_attn.q_proj.weight"),
        "bq": lt("layers.{}.self_attn.q_proj.bias", False),
        "wk": lt("layers.{}.self_attn.k_proj.weight"),
        "bk": lt("layers.{}.self_attn.k_proj.bias", False),
        "wv": lt("layers.{}.self_attn.v_proj.weight"),
        "bv": lt("layers.{}.self_attn.v_proj.bias", False),
        "wo": lt("layers.{}.self_attn.out_proj.weight"),
        "bo": lt("layers.{}.self_attn.out_proj.bias", False),
        "mlp_norm_w": lt("layers.{}.final_layer_norm.weight", False),
        "mlp_norm_b": lt("layers.{}.final_layer_norm.bias", False),
        "fc1": lt("layers.{}.fc1.weight"),
        "fc1_b": lt("layers.{}.fc1.bias", False),
        "fc2": lt("layers.{}.fc2.weight"),
        "fc2_b": lt("layers.{}.fc2.bias", False),
    }


def load_mixtral_weights(model_dir: str, config: ModelConfig,
                         dtype=None) -> Dict[str, jnp.ndarray]:
    """HF Mixtral: llama-style attention + per-expert SwiGLU weights
    (block_sparse_moe.experts.{e}.w1/w3/w2 = gate/up/down, all [out,
    in]) stacked to [L, E, in, out]."""
    raw = _load_raw_tensors(model_dir)
    raw = {k.removeprefix("model."): v for k, v in raw.items()}
    L = config.num_hidden_layers
    E = config.num_local_experts
    dtype = dtype or config.jax_dtype

    def lt(template, transpose=True):
        return jnp.asarray(
            _stack(raw, template, L, transpose=transpose), dtype
        )

    def experts(which):  # w1 | w2 | w3
        per_layer = []
        for i in range(L):
            per_expert = [
                raw[f"layers.{i}.block_sparse_moe.experts.{e}"
                    f".{which}.weight"].T
                for e in range(E)
            ]
            per_layer.append(np.stack(per_expert))
        return jnp.asarray(np.stack(per_layer), dtype)  # [L,E,in,out]

    params = {
        "embed": jnp.asarray(raw["embed_tokens.weight"], dtype),
        "final_norm": jnp.asarray(raw["norm.weight"], dtype),
        "attn_norm": lt("layers.{}.input_layernorm.weight", False),
        "wq": lt("layers.{}.self_attn.q_proj.weight"),
        "wk": lt("layers.{}.self_attn.k_proj.weight"),
        "wv": lt("layers.{}.self_attn.v_proj.weight"),
        "wo": lt("layers.{}.self_attn.o_proj.weight"),
        "mlp_norm": lt("layers.{}.post_attention_layernorm.weight",
                       False),
        "moe_gate": lt("layers.{}.block_sparse_moe.gate.weight"),
        "w_gate": experts("w1"),
        "w_up": experts("w3"),
        "w_down": experts("w2"),
    }
    head = raw.get("lm_head.weight")
    if head is None:
        config.tie_word_embeddings = True
    else:
        params["lm_head"] = jnp.asarray(head.T, dtype)
    return params


def load_weights(model_dir: str, config: ModelConfig,
                 dtype=None) -> Dict[str, jnp.ndarray]:
    if config.architecture == "opt":
        return load_opt_weights(model_dir, config, dtype)
    if config.architecture == "gpt2":
        return load_gpt2_weights(model_dir, config, dtype)
    if config.architecture == "mixtral":
        return load_mixtral_weights(model_dir, config, dtype)
    if config.architecture == "qwen3_next":
        raise NotImplementedError(
            "reading a Qwen3-Next checkpoint into this engine's fused "
            "layout is not written yet: serve the architecture with "
            "--random-weights")
    if config.architecture == "jamba":
        raise NotImplementedError(
            "reading a Jamba checkpoint into this engine's stacks "
            "(the Mamba mixers' and the attention layers' apart, A_log "
            "transposed) is not written yet: serve the architecture "
            "with --random-weights")
    if config.architecture == "lfm2_moe":
        raise NotImplementedError(
            "reading an LFM2-MoE checkpoint into this engine's stacks "
            "(the conv, attention, dense and expert layers' apart, "
            "gate | up fused) is not written yet: serve the "
            "architecture with --random-weights")
    if config.architecture == "longcat_flash":
        raise NotImplementedError(
            "reading a LongCat-Flash checkpoint into this engine's "
            "stacks (two sublayers a layer, kv_b split a head into "
            "w_uk and w_uv, gate | up fused, the held experts' block) "
            "is not written yet: serve the architecture with "
            "--random-weights")
    if config.architecture == "glm4_moe_lite":
        raise NotImplementedError(
            "reading a GLM-4 MoE lite checkpoint into this engine's "
            "stacks (kv_b split a head into w_uk and w_uv, gate | up "
            "fused, the prediction layer's body after the main "
            "layers') is not written yet: serve the architecture with "
            "--random-weights")
    if config.architecture == "granitemoehybrid":
        raise NotImplementedError(
            "reading a Granite-MoE-hybrid checkpoint into this engine's "
            "stacks (the Mamba-2 mixers' and the attention layers' "
            "apart, the held experts' block with gate | up fused) is "
            "not written yet: serve the architecture with "
            "--random-weights")
    if config.architecture == "exaone_moe":
        raise NotImplementedError(
            "reading an EXAONE-MoE checkpoint into this engine's "
            "stacks (gate | up fused, the held experts' block, the "
            "prediction layer left out) is not written yet: serve the "
            "architecture with --random-weights")
    if config.architecture == "sdar_moe":
        raise NotImplementedError(
            "reading an SDAR-MoE checkpoint into this engine's stacks "
            "(gate | up fused, the held experts' block one array a "
            "layer) is not written yet: serve the architecture with "
            "--random-weights")
    if config.architecture not in ("llama", "mistral", "qwen2"):
        raise NotImplementedError(
            f"no reader for a {config.architecture!r} checkpoint, and "
            "it is not read as a Llama's")
    return load_llama_weights(model_dir, config, dtype)
