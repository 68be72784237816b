"""Weight-only int8 quantization: numerics bounds, generation sanity,
TP sharding of (weight, scale) pairs, LoRA composition."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.quantization import (
    dequant_matmul,
    quantize_params,
    quantize_weight,
)
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models import llama


def test_quantize_roundtrip_error_bound():
    rs = np.random.RandomState(0)
    w = jnp.asarray(rs.randn(2, 64, 96).astype(np.float32))
    q, scale = quantize_weight(w)
    assert q.dtype == jnp.int8
    assert scale.shape == (2, 96)
    deq = q.astype(jnp.float32) * scale[:, None, :]
    # Per-channel symmetric int8: error <= scale/2 per element.
    err = np.abs(np.asarray(deq - w))
    bound = np.asarray(scale)[:, None, :] * 0.5 + 1e-6
    assert np.all(err <= bound)


def test_dequant_matmul_close_to_dense():
    rs = np.random.RandomState(1)
    w = jnp.asarray(rs.randn(64, 96).astype(np.float32))
    x = jnp.asarray(rs.randn(4, 8, 64).astype(np.float32))
    q, scale = quantize_weight(w[None])
    got = dequant_matmul(x, (q[0], scale[0]))
    ref = x @ w
    rel = (np.abs(np.asarray(got - ref)).max()
           / np.abs(np.asarray(ref)).max())
    assert rel < 0.02


def _engine(quant, mesh=None, params=None):
    model = tiny_model_config("llama")
    model.quantization = quant
    config = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=128,
                                  prefill_chunk_size=32),
    )
    return LLMEngine(config, mesh=mesh, params=params)


def test_quantized_generation_tracks_full_precision():
    """Quantizing a given full-precision checkpoint (the real serving
    path — random int8 init draws its own weights by design, see
    quantization.init_random_quantized)."""
    prompt = list(range(3, 40))
    sp = dict(max_tokens=8, temperature=0.0, ignore_eos=True)
    params = llama.init_params(tiny_model_config("llama"),
                               jax.random.PRNGKey(0))
    full = _engine("none", params=params).generate(
        prompt, SamplingParams(**sp)).output_token_ids
    quant = _engine("int8", params=params).generate(
        prompt, SamplingParams(**sp)).output_token_ids
    assert len(quant) == 8
    # Random tiny weights amplify quantization noise; require the
    # greedy paths to agree on a prefix rather than every token.
    assert quant[0] == full[0]


def test_quantized_tp_sharding():
    from production_stack_tpu.parallel.mesh import build_mesh
    mesh = build_mesh(tensor_parallel_size=2)
    engine = _engine("int8", mesh=mesh)
    seq = engine.generate(
        list(range(5, 25)),
        SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True))
    assert len(seq.output_token_ids) == 4
    w, scale = engine.runner.params["wq"]
    assert w.dtype == jnp.int8


def test_quantization_rejects_mixtral():
    config = tiny_model_config("llama")
    config.architecture = "mixtral"
    params = {"wq": jnp.zeros((2, 8, 8))}
    with pytest.raises(NotImplementedError):
        quantize_params(params, config)


def test_quantized_params_reject_embedder():
    from production_stack_tpu.engine.embeddings import Embedder
    engine = _engine("int8")
    with pytest.raises(NotImplementedError, match="unquantized"):
        Embedder(engine.config.model, engine.runner.params,
                 max_len=128)


@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_direct_int8_random_init_shapes(family):
    """Random int8 init (quantization.init_random_quantized) produces
    the same pytree structure as quantize(init) without ever
    materializing the full-precision model (the 8B-on-16GB OOM
    fix). gpt2 exercises the bias/norm-bias
    leaves (semantics derived from the family init, not names)."""
    from production_stack_tpu.engine.quantization import (
        init_random_quantized,
        is_quantized,
    )
    from production_stack_tpu.models import gpt2 as gpt2_mod

    init_fns = {"llama": llama.init_params,
                "gpt2": gpt2_mod.init_params}
    model = tiny_model_config(family)
    init_fn = init_fns[family]
    ref = quantize_params(init_fn(model, jax.random.PRNGKey(0)), model)
    direct = init_random_quantized(init_fn, model, seed=0)
    assert set(direct) == set(ref)
    for name, leaf in ref.items():
        if is_quantized(leaf):
            assert is_quantized(direct[name])
            assert direct[name][0].shape == leaf[0].shape
            assert direct[name][0].dtype == jnp.int8
            assert direct[name][1].shape == leaf[1].shape
        else:
            assert direct[name].shape == leaf.shape
            assert direct[name].dtype == leaf.dtype
    # Norm gains must be ones (zeros would zero every activation);
    # biases must be zeros — exactly as the family init defines them.
    for name, leaf in ref.items():
        if is_quantized(leaf):
            continue
        a = np.asarray(leaf, np.float32)
        if np.all(a == 1.0):
            np.testing.assert_array_equal(
                np.asarray(direct[name], np.float32), 1.0)
        elif np.all(a == 0.0):
            np.testing.assert_array_equal(
                np.asarray(direct[name], np.float32), 0.0)
