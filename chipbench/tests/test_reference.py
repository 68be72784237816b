"""The float32 reference against the program's ``models/llama.py`` at
tiny widths on the CPU, for a biased, tied configuration (Qwen2's
shape) and an unbiased, untied one (Mistral's), and through the same
adapter the chip check uses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import llama_family

TINY = {"hidden_size": 64, "intermediate_size": 160,
        "num_hidden_layers": 3, "num_attention_heads": 8,
        "num_key_value_heads": 2, "vocab_size": 300,
        "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "max_position_embeddings": 256}


@pytest.mark.parametrize("arch,tied", [("Qwen2ForCausalLM", True),
                                       ("MistralForCausalLM", False)])
def test_reference_agrees_with_the_program_in_float32(arch, tied):
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models import llama

    hf = dict(TINY, architectures=[arch], tie_word_embeddings=tied)
    config = ModelConfig.from_hf_config(hf)
    config.dtype = "float32"
    params = llama.init_params(config, jax.random.PRNGKey(5))
    if config.attention_bias:
        # Random init leaves the biases at zero; the test must not.
        keys = jax.random.split(jax.random.PRNGKey(6), 3)
        for key, name in zip(keys, ("bq", "bk", "bv")):
            params[name] = 0.5 * jax.random.normal(
                key, params[name].shape, jnp.float32)
    assert ("bq" in params) == (arch == "Qwen2ForCausalLM")
    assert ("lm_head" in params) == (not tied)
    tokens = np.random.default_rng(0).integers(0, 300, 40)
    with jax.default_matmul_precision("highest"):
        logits = llama.forward_train(params, config, tokens[None])[0]
    want = np.asarray(jax.nn.log_softmax(logits, -1))

    per_layer = [k for k in params
                 if k not in ("embed", "final_norm", "lm_head")]
    weights = llama_family.Weights(
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params.get("lm_head"),
        layer=lambda i: {k: params[k][i] for k in per_layer})
    shape = llama_family.Shape(3, 8, 2, 8, 1e-6, 1e6)
    positions = [0, 7, 38, 39]
    got = np.asarray(llama_family.log_probs((weights, shape), tokens,
                                            positions))
    # float32 on both sides: rounding only.
    np.testing.assert_allclose(got, want[positions], atol=2e-5)
    # And it is a distribution.
    np.testing.assert_allclose(np.exp(got).sum(-1), 1.0, atol=1e-5)


def test_the_adapter_dequantises_int8_leaves_as_value_times_scale():
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.engine.quantization import (
        init_random_quantized,
    )
    from production_stack_tpu.models import llama

    hf = dict(TINY, architectures=["MistralForCausalLM"],
              tie_word_embeddings=False)
    weights, shape = llama_family.program_model(
        hf, {"quantization": "int8", "weights_seed": 3})
    config = ModelConfig.from_hf_config(hf)
    raw = init_random_quantized(llama.init_params, config, 3)
    q, scale = raw["w_gate"]
    assert q.dtype == jnp.int8
    layer = weights.layer(1)
    np.testing.assert_array_equal(
        np.asarray(layer["w_gate"]),
        np.asarray(q[1], np.float32) * np.asarray(scale[1])[None, :])
    assert layer["attn_norm"].dtype == jnp.float32
    assert (shape.num_layers, shape.num_heads, shape.num_kv_heads,
            shape.head_dim) == (3, 8, 2, 8)
    # The dequantised model runs and gives a distribution.
    got = llama_family.log_probs((weights, shape), [1, 2, 3, 4], [3])
    assert np.exp(np.asarray(got)).sum() == pytest.approx(1.0, abs=1e-5)


def _coarser(kind):
    """Stacked weight leaves of the program's float32 init, rounded as
    ``kind`` says, then cast to the bfloat16 the configuration states."""
    def fp8(w):
        return w.astype(jnp.float8_e4m3fn)

    def mantissa4(w):
        m, e = jnp.frexp(w)
        return jnp.ldexp(jnp.round(m * 16) / 16, e)

    def int8(w):
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127
        return jnp.round(w / scale) * scale

    return {"bfloat16": lambda w: w, "fp8": fp8, "mantissa4": mantissa4,
            "int8": int8}[kind]


@pytest.mark.parametrize("kind,fails", [
    ("bfloat16", False), ("fp8", True), ("mantissa4", True),
    ("int8", False)])
def test_the_tolerance_rule_fails_coarser_rounding(kind, fails):
    """The configuration's tolerance is 1.5 x the error the stated
    precision showed.  By that rule, at tiny widths: weights held in
    fp8 or with a 4-bit mantissa fail; int8 weight rounding does not,
    which is why the configuration's file does not claim it."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models import llama

    hf = dict(TINY, architectures=["Qwen2ForCausalLM"],
              tie_word_embeddings=True)
    config = ModelConfig.from_hf_config(hf)
    config.dtype = "float32"
    params = llama.init_params(config, jax.random.PRNGKey(5))
    tokens = np.random.default_rng(0).integers(0, 300, 200)
    with jax.default_matmul_precision("highest"):
        logits = llama.forward_train(params, config, tokens[None])[0]
    want = np.asarray(jax.nn.log_softmax(logits, -1))
    top = np.argsort(-want, -1)[:, :6]

    def error(rounding):
        served = {k: (rounding(v) if v.ndim > 2 else v).astype(jnp.bfloat16)
                  for k, v in params.items()}
        config.dtype = "bfloat16"
        got = llama.forward_train(served, config, tokens[None])[0]
        got = np.asarray(jax.nn.log_softmax(got.astype(jnp.float32), -1))
        diff = np.abs(np.take_along_axis(got, top, -1)
                      - np.take_along_axis(want, top, -1))
        return diff.mean(), diff.max()

    stated_mean, stated_worst = error(_coarser("bfloat16"))
    mean, worst = error(_coarser(kind))
    within = mean <= 1.5 * stated_mean and worst <= 1.5 * stated_worst
    assert within == (not fails), (kind, mean / stated_mean,
                                   worst / stated_worst)
