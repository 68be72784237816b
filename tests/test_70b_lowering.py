"""BASELINE config 4 (Llama-3-70B tensor-parallel across a v5e-8
slice via ICI): the serving programs must LOWER with the intended
GSPMD shardings at the real 70B geometry.

A 70B checkpoint (140 GB bf16) cannot execute in CI or on the 16 GB
dev chip, but sharding validity is a compile-time property: this test
traces and lowers the engine's forward at full 70B shapes on the
8-device CPU mesh using jax.ShapeDtypeStruct inputs — no weight
materialization, no execution. What it proves: the head geometry
divides (nh=64, nkv=8 over tp=8 -> 8 q / 1 kv head per device), the
param/cache PartitionSpecs (parallel/mesh.py) are consistent at this
scale, and both the prefill-chunk and decode-step programs lower.
Reference workload: /root/reference helm values modelSpec with
tensorParallelSize (deployment-vllm-multi.yaml argv rendering).
"""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from production_stack_tpu.engine.config import ModelConfig


def llama3_70b_config() -> ModelConfig:
    return ModelConfig(
        name="llama-3-70b-class",
        architecture="llama",
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_hidden_layers=80,
        num_attention_heads=64,
        num_key_value_heads=8,
        head_dim=128,
        max_position_embeddings=8192,
        dtype="bfloat16",
    )


def test_70b_tp8_serving_programs_lower():
    from production_stack_tpu.models import llama
    from production_stack_tpu.parallel.mesh import (
        build_mesh,
        cache_spec,
        param_specs,
    )

    m = llama3_70b_config()
    mesh = build_mesh(tensor_parallel_size=8)
    specs = param_specs(m)
    # Guard against silent replicated fallback: the spec table must
    # actually cover the model's params with tp-sharded entries.
    init_shapes_names = set(jax.eval_shape(
        lambda key: llama.init_params(m, key),
        jax.random.PRNGKey(0)).keys())
    tp_specced = {k for k in init_shapes_names
                  if "tp" in tuple(specs.get(k, P()))}
    assert len(tp_specced) >= 5, (
        f"param_specs covers only {sorted(tp_specced)} with tp")

    # Abstract weights with their serving shardings (no allocation).
    init_shapes = jax.eval_shape(
        lambda key: llama.init_params(m, key), jax.random.PRNGKey(0))
    params = {
        k: jax.ShapeDtypeStruct(
            v.shape, v.dtype,
            sharding=NamedSharding(mesh, specs.get(k, P())))
        for k, v in init_shapes.items()
    }

    kv, d, ps, pages = m.num_key_value_heads, m.head_dim, 128, 64
    c_sharding = NamedSharding(mesh, cache_spec(mesh))
    cache = jax.ShapeDtypeStruct(
        (m.num_hidden_layers, kv, pages, d, ps), jnp.bfloat16,
        sharding=c_sharding)

    b, t_prefill, max_pages = 4, 512, 8
    repl = NamedSharding(mesh, P())

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)

    def run(tok_shape):
        bb, tt = tok_shape
        lowered = jax.jit(llama.forward, static_argnums=(1,)).lower(
            params, m,
            arg((bb, tt), jnp.int32),      # tokens
            arg((bb, tt), jnp.int32),      # positions
            arg((bb, max_pages), jnp.int32),  # page table
            arg((bb,), jnp.int32),         # kv_lens
            arg((bb, tt), jnp.bool_),      # valid
            cache, cache,
        )
        text = lowered.as_text()
        # A replicated fallback (e.g. a param-name drift making every
        # specs.get() miss) would still contain the word "sharding" —
        # require a non-replicated tp annotation in the module, in
        # either representation (Shardy '{"tp"}' / GSPMD 'devices=[').
        assert '{"tp"}' in text or "devices=[" in text, (
            "no non-replicated sharding annotation in lowered 70B "
            "program")
        return lowered

    # Prefill chunk and decode step both lower at 70B scale.
    run((b, t_prefill))
    run((b, 1))


def test_70b_head_geometry_divides():
    m = llama3_70b_config()
    for tp in (2, 4, 8):
        assert m.num_attention_heads % tp == 0
        assert m.num_key_value_heads % tp == 0
