"""The second family through the door: Mixtral's reference against the
program's ``models/mixtral.py`` served by the engine in float32 at tiny
widths on the CPU, the tolerance against a coarser rounding and a layer
left out, its counts by hand, and its rehearsal cell end to end.  Every
file of the family is new; none of the harness was edited for it."""

import dataclasses
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import family, roofline, run as bench_run
from chipbench.counts import mixtral_family as counts

CONFIG = os.path.join(bench_run.BENCH, "rehearsal", "configs",
                      "tiny-mixtral.json")


@pytest.fixture(scope="module")
def tiny():
    cfg = bench_run.load_json(CONFIG)
    reference = family.module("reference", cfg)
    assert reference.__name__ == "chipbench.reference.mixtral_family"
    hf = {k: v for k, v in cfg.items() if k != "chipbench"}
    return cfg, reference, reference.program_model(hf, cfg["chipbench"])


def served_log_probs(cfg, prompt, answers, top):
    """What the program says: the engine on the configuration's random
    weights, greedy, with the top log-probabilities of each answer."""
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig)
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sequence import SamplingParams
    import jax
    from production_stack_tpu.models.registry import get_model

    bench = cfg["chipbench"]
    config = ModelConfig.from_hf_config(
        {k: v for k, v in cfg.items() if k != "chipbench"})
    config.dtype = bench["dtype"]
    params = get_model(config)[0](
        config, jax.random.PRNGKey(bench["weights_seed"]))
    engine = LLMEngine(EngineConfig(
        model=config, cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64)), params=params)
    engine.add_request(list(prompt), SamplingParams(
        max_tokens=answers, temperature=0.0, ignore_eos=True,
        logprobs=True, top_logprobs=top))
    tokens, served = [], []
    while len(tokens) < answers:
        for out in engine.step():
            if out.new_token is not None:
                tokens.append(out.new_token)
                served.append(dict(out.logprobs[1]))
    return tokens, served


def test_reference_agrees_with_the_program_in_float32(tiny):
    cfg, reference, model = tiny
    assert model.experts_per_token == 2 and model.num_layers == 2
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 90)
    tokens, served = served_log_probs(cfg, prompt.tolist(), 8, 5)
    sequence = prompt.tolist() + tokens
    first = len(prompt) - 1
    got = np.asarray(reference.log_probs(
        model, sequence, list(range(first, first + 8))))
    np.testing.assert_allclose(np.exp(got).sum(-1), 1.0, atol=1e-5)
    diffs = [abs(value - got[j, tid]) for j, answer in enumerate(served)
             for tid, value in answer.items()]
    assert len(diffs) >= 40
    tolerance = cfg["chipbench"]["reference_tolerance"]
    # float32 on both sides: rounding only, well inside the tolerance
    # the rehearsal cell holds the server to.
    assert max(diffs) < tolerance["max_abs_logprob_diff"] / 2
    assert np.mean(diffs) < tolerance["mean_abs_logprob_diff"] / 2
    # Greedy: the served token is the reference's best.
    assert tokens == np.argmax(got, -1).tolist()


def _differences(reference, model, other):
    tokens = np.random.default_rng(0).integers(0, 512, 120)
    positions = list(range(60, 120))
    want = np.asarray(reference.log_probs(model, tokens, positions))
    got = np.asarray(reference.log_probs(other, tokens, positions))
    top = np.argsort(-want, -1)[:, :6]
    diff = np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1))
    return diff.max(), diff.mean()


@pytest.mark.parametrize("fault", ["bfloat16 weights", "a layer left out"])
def test_the_tolerance_fails_a_coarser_rounding_and_a_wrong_model(tiny,
                                                                  fault):
    """The control: the reference in the program's place, one step
    below the float32 the configuration states, or a layer short."""
    cfg, reference, model = tiny

    def rounded(w):
        return w.astype(jnp.bfloat16).astype(jnp.float32)

    if fault == "bfloat16 weights":
        other = dataclasses.replace(
            model, embed=rounded(model.embed),
            lm_head=rounded(model.lm_head),
            layer=lambda i: {k: rounded(v)
                             for k, v in model.layer(i).items()})
    else:
        other = dataclasses.replace(model, num_layers=model.num_layers - 1)
    worst, mean = _differences(reference, model, other)
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert (worst > 3 * tolerance["max_abs_logprob_diff"]
            or mean > 3 * tolerance["mean_abs_logprob_diff"]), (worst, mean)
    same = _differences(reference, model, dataclasses.replace(model))
    assert same == (0.0, 0.0)


# Mixtral-8x7B-v0.1's published widths (no cell: the second shape the
# counts are held to).
MIXTRAL_8X7B = {"hidden_size": 4096, "intermediate_size": 14336,
                "num_hidden_layers": 32, "num_attention_heads": 32,
                "num_key_value_heads": 8, "vocab_size": 32000,
                "num_local_experts": 8, "num_experts_per_tok": 2,
                "chipbench": {"family": "mixtral_family",
                              "quantization": "none"}}


def test_counts_by_hand():
    c = MIXTRAL_8X7B
    attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
    expert = 3 * 4096 * 14336
    assert counts.attention_params(c) == attention == 41_943_040
    assert counts.expert_params(c) == expert == 176_160_768
    assert counts.router_params(c) == 4096 * 8
    head = 4096 * 32000
    # A decode step reads all eight experts: the batch leaves none out.
    every = 2 * (32 * (attention + 4096 * 8 + 8 * expert) + head)
    assert every == 93_142_908_928  # 46.6 B parameters but the embedding
    assert counts.decode_step_bytes(c, 0) == every
    assert roofline.decode_step_bytes(c, 1000) == every + 1000 * 131_072
    # A chunk of 4 tokens: two experts a token, never eight.
    per_token = attention + 4096 * 8 + 2 * expert
    want = 2 * 32 * per_token * 4 + 4 * 32 * 32 * 128 * 10
    assert roofline.prefill_flops(c, [(0, 4, False)]) == want
    assert roofline.prefill_flops(c, [(0, 4, True)]) == want + 2 * head
    with pytest.raises(ValueError):
        counts.decode_step_bytes(
            dict(c, chipbench={"quantization": "int8"}), 0)


def test_the_rehearsal_cell_runs_to_a_correct_line():
    """``JAX_PLATFORMS=cpu python3 chipbench/run.py --workload
    rehearsal-moe --seconds 8``: the real server on ``models/mixtral.py``
    behind the real router, checked by this family's reference."""
    done = subprocess.run(
        [sys.executable, os.path.join(bench_run.BENCH, "run.py"),
         "--workload", "rehearsal-moe", "--seed", str(2**31 + 7),
         "--seconds", "8"],
        cwd=bench_run.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert (result["failed"], result["unfinished"]) == (0, 0)
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == {"output_tok_s", "setup_s"}
    report = bench_run.load_json(os.path.join(
        bench_run.STATE, "runs", "rehearsal-moe", "report.json"))
    assert report["reference"]["ok"] and report["reference"]["compared"] > 100
