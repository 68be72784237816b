"""The door a second family comes through: a configuration names its
family, the harness finds the reference and the counts by that name,
and knows nothing else of an architecture."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import family, roofline, run as bench_run

REHEARSAL = os.path.join(bench_run.BENCH, "rehearsal", "configs")


def config(name):
    return bench_run.load_json(os.path.join(REHEARSAL, name + ".json"))


@pytest.mark.parametrize("group,says", [
    ({"name": "x"}, 'names no family: add "family": "<name>"'),
    ({"name": "x", "family": 3}, "names no family"),
    ({"name": "x", "family": "../llama_family"}, "names no family"),
    ({"name": "x", "family": "not_there"},
     "there is no chipbench/reference/not_there.py"),
])
def test_a_configuration_without_a_family_is_an_error(group, says):
    with pytest.raises(family.UnknownFamily) as raised:
        family.name_of({"chipbench": group})
    assert says in str(raised.value) and "'x'" in str(raised.value)
    with pytest.raises(family.UnknownFamily):
        roofline.decode_step_bytes({"chipbench": group}, 0)


def test_a_family_needs_its_counts_too(tmp_path, monkeypatch):
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "half.py").write_text("")
    monkeypatch.setattr(family, "BENCH", str(tmp_path))
    with pytest.raises(family.UnknownFamily) as raised:
        family.name_of({"chipbench": {"name": "x", "family": "half"}})
    assert "no chipbench/counts/half.py" in str(raised.value)


def test_the_run_ends_with_the_message(tmp_path, monkeypatch, capsys):
    """Before anything is started: no server, no reference child."""
    bad = config("tiny-qwen2")
    del bad["chipbench"]["family"]
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(bad))
    cell = bench_run.find_cell("rehearsal-open")
    monkeypatch.setattr(bench_run, "find_cell",
                        lambda name: dict(cell, config_file=str(path)))
    assert bench_run.main(["--workload", "rehearsal-open",
                           "--seconds", "2"]) == 1
    captured = capsys.readouterr()
    assert captured.out == ""
    assert 'names no family: add "family": "<name>"' in captured.err


def test_every_configuration_names_a_family_that_is_there():
    configs = [os.path.join(REHEARSAL, f) for f in os.listdir(REHEARSAL)]
    configs += [os.path.join(bench_run.BENCH, "configs", f)
                for f in os.listdir(os.path.join(bench_run.BENCH,
                                                 "configs"))]
    assert len(configs) >= 3
    for path in configs:
        cfg = bench_run.load_json(path)
        counts = family.module("counts", cfg)
        assert counts.decode_step_bytes(cfg, 0) > 0
        assert counts.prefill_flops(cfg, [(0, 4, True)]) > 0
        assert os.path.exists(os.path.join(
            bench_run.BENCH, "reference", family.name_of(cfg) + ".py"))


# ---- the Llama family through the door, against the path it replaced -------


def old_program_weights(llama_family, hf_config, quantization, seed):
    """``check.program_weights`` as it stood before the family was a
    name (PR 28's tree), kept here and not in the harness."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.engine.quantization import (
        init_random_quantized,
    )
    from production_stack_tpu.models.registry import get_model

    config = ModelConfig.from_hf_config(hf_config)
    config.quantization = quantization
    init_fn, _ = get_model(config)
    if quantization == "int8":
        params = init_random_quantized(init_fn, config, seed)
    else:
        params = init_fn(config, jax.random.PRNGKey(seed))
    per_layer = [k for k, v in params.items()
                 if k not in ("embed", "final_norm", "lm_head")]

    def layer(i):
        out = {}
        for name in per_layer:
            leaf = params[name]
            if isinstance(leaf, tuple):
                q, scale = leaf
                out[name] = (q[i].astype(jnp.float32)
                             * scale[i].astype(jnp.float32)[None, :])
            else:
                out[name] = leaf[i].astype(jnp.float32)
        return out

    shape = llama_family.Shape(
        num_layers=config.num_hidden_layers,
        num_heads=config.num_attention_heads,
        num_kv_heads=config.num_key_value_heads,
        head_dim=config.head_dim, rms_eps=config.rms_norm_eps,
        rope_theta=config.rope_theta)
    return llama_family.Weights(
        embed=params["embed"], final_norm=params["final_norm"],
        lm_head=params.get("lm_head"), layer=layer), shape


@pytest.mark.parametrize("name", ["tiny-qwen2", "tiny-mistral-int8"])
def test_the_door_gives_the_array_the_old_path_gave(name):
    cfg = config(name)
    reference = family.module("reference", cfg)
    bench = cfg.pop("chipbench")
    tokens = np.random.default_rng(3).integers(0, cfg["vocab_size"], 48)
    positions = [0, 17, 46, 47]
    got = np.asarray(reference.log_probs(
        reference.program_model(cfg, bench), tokens, positions))
    old = old_program_weights(reference, cfg, bench["quantization"],
                              bench["weights_seed"])
    # The old call, its four arguments as they were; the mathematics
    # has not changed by one operation, so the arrays are equal.
    weights, shape = old
    want = np.asarray(reference.log_probs((weights, shape), tokens,
                                          positions))
    np.testing.assert_array_equal(got, want)
    assert got.shape == (4, cfg["vocab_size"])
    np.testing.assert_allclose(np.exp(got).sum(-1), 1.0, atol=1e-5)
