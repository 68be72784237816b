"""Per-layer vs stacked KV cache layout parity.

CacheConfig.cache_layout='per_layer' is a tuple of L per-layer buffers instead of one stacked [L, ...] array. Numerics
must be identical — the layout changes buffer granularity (scatter
operands, donation aliasing), not math.
"""

import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams


def _run_engine(layout: str, family: str = "llama",
                decode_steps: int = 1):
    config = EngineConfig(
        model=tiny_model_config(family),
        cache=CacheConfig(page_size=16, num_pages=64,
                          cache_layout=layout),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=128,
                                  prefill_chunk_size=32,
                                  prefill_batch_size=2,
                                  decode_steps=decode_steps),
    )
    engine = LLMEngine(config)
    prompts = [list(range(3, 23)), list(range(40, 50))]
    seqs = []
    for p in prompts:
        sid = engine.add_request(
            p, SamplingParams(max_tokens=8, temperature=0.0,
                              ignore_eos=True))
        seqs.append(engine.sequences[sid])
    while engine.has_work():
        engine.step()
    return [s.output_token_ids for s in seqs]


@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_per_layer_matches_stacked_greedy(family):
    a = _run_engine("stacked", family)
    b = _run_engine("per_layer", family)
    assert a == b


def test_per_layer_matches_stacked_burst_decode():
    a = _run_engine("stacked", decode_steps=4)
    b = _run_engine("per_layer", decode_steps=4)
    assert a == b


def test_per_layer_offload_page_roundtrip():
    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64,
                          cache_layout="per_layer"),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=128,
                                  prefill_chunk_size=32,
                                  prefill_batch_size=2),
    )
    engine = LLMEngine(config)
    engine.add_request(list(range(3, 35)),
                       SamplingParams(max_tokens=4, temperature=0.0,
                                      ignore_eos=True))
    while engine.has_work():
        engine.step()
    runner = engine.runner
    k, v = runner.read_page(1)
    L = config.model.num_hidden_layers
    assert k.shape[0] == L and v.shape[0] == L
    # Round-trip: write back what was read, read again, identical.
    runner.write_page(1, k, v)
    k2, v2 = runner.read_page(1)
    np.testing.assert_array_equal(k, k2)
    np.testing.assert_array_equal(v, v2)

    # The serde page format matches the stacked layout's.
    config_s = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=128,
                                  prefill_chunk_size=32,
                                  prefill_batch_size=2),
    )
    engine_s = LLMEngine(config_s)
    ks, _ = engine_s.runner.read_page(1)
    assert ks.shape == k.shape

def test_auto_layout_resolves_per_layer():
    """The 'auto' default resolves to per_layer for plain configs."""
    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=128,
                                  prefill_chunk_size=32,
                                  prefill_batch_size=2),
    )
    assert config.cache.cache_layout == "auto"
    engine = LLMEngine(config)
    assert engine.runner.cache_layout == "per_layer"
    assert isinstance(engine.runner.k_cache, tuple)


def test_auto_layout_resolves_stacked_under_pp():
    """pp shards the stacked L axis, so 'auto' resolves to stacked
    there (explicit per_layer+pp stays a loud error)."""
    import jax

    from production_stack_tpu.engine.config import ParallelConfig
    from production_stack_tpu.parallel.mesh import build_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices for a pp mesh")
    parallel = ParallelConfig(pipeline_parallel_size=2)
    mesh = build_mesh(pipeline_parallel_size=2)
    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=128,
                                  prefill_chunk_size=32,
                                  prefill_batch_size=2),
        parallel=parallel,
    )
    engine = LLMEngine(config, mesh=mesh)
    assert engine.runner.cache_layout == "stacked"


def test_rejects_unknown_layout():
    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64,
                          cache_layout="bogus"),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=128),
    )
    with pytest.raises(ValueError, match="cache_layout"):
        LLMEngine(config)
