"""The Qwen3-Next family through the door: its reference against the
program's ``models/qwen3_next.py`` served by the engine in float32 at
tiny widths on the CPU (a share of the experts, prompts of several
chunks), the tolerance against a coarser rounding and against each
term left out, its counts with the sums by hand at the published
widths, and its readers on a run directory made by hand.  Every file of
the family is new; none of the harness was edited for it."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import family, hybrid_slice, roofline, run as bench_run
from chipbench.counts import qwen3_next_family as counts
from chipbench.runfiles import RunFiles

TINY = os.path.join(bench_run.BENCH, "rehearsal", "configs",
                    "tiny-qwen3-next.json")
PUBLISHED = os.path.join(bench_run.BENCH, "configs",
                         "qwen3-next-80b-a3b-ep4.json")


@pytest.fixture(scope="module")
def hybrid():
    cfg = bench_run.load_json(TINY)
    reference = family.module("reference", cfg)
    assert reference.__name__ == "chipbench.reference.qwen3_next_family"
    hf = {k: v for k, v in cfg.items() if k != "chipbench"}
    return cfg, reference, reference.program_model(hf, cfg["chipbench"])


def hybrid_served_log_probs(cfg, prompt, answers, top):
    """What the program says: the engine on the configuration's random
    weights, greedy, prompts in chunks of 64 and bursts of 4."""
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig)
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sequence import SamplingParams

    bench = cfg["chipbench"]
    config = ModelConfig.from_hf_config(
        {k: v for k, v in cfg.items() if k != "chipbench"})
    config.dtype = bench["dtype"]
    config.attention_impl = "xla"
    engine = LLMEngine(EngineConfig(
        model=config, cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64, decode_steps=4),
        seed=bench["weights_seed"]))
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


def test_the_reference_agrees_with_the_program_in_float32(hybrid):
    cfg, reference, model = hybrid
    # Experts 4..7 of 16: a share, and not the first.
    assert (model.first_expert, model.top_k) == (4, 4)
    assert model.layer_is_linear == (True, True, False) * 2
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 150)
    tokens, served = hybrid_served_log_probs(cfg, prompt.tolist(), 8, 5)
    sequence = prompt.tolist() + tokens
    first = len(prompt) - 1
    got = np.asarray(reference.log_probs(
        model, sequence, list(range(first, first + 8))))
    np.testing.assert_allclose(np.exp(got).sum(-1), 1.0, atol=1e-5)
    diffs = [abs(value - got[j, tid]) for j, answer in enumerate(served)
             for tid, value in answer.items()]
    assert len(diffs) >= 40
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert max(diffs) < tolerance["max_abs_logprob_diff"] / 2
    assert np.mean(diffs) < tolerance["mean_abs_logprob_diff"] / 2
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


def _with_layers(model, change):
    return dataclasses.replace(
        model, layer=lambda i: change(dict(model.layer(i))))


def _zeroed(*names):
    def change(w):
        for name in names:
            if name in w:
                w[name] = jnp.zeros_like(w[name])
        return w
    return change


FAULTS = {
    "bfloat16 weights": lambda m: dataclasses.replace(
        _with_layers(m, lambda w: {
            k: v.astype(jnp.bfloat16).astype(jnp.float32)
            for k, v in w.items()}),
        embed=m.embed.astype(jnp.bfloat16).astype(jnp.float32),
        lm_head=m.lm_head.astype(jnp.bfloat16).astype(jnp.float32)),
    # sigmoid(0) = 1/2 on every head: the attention's gate says nothing.
    "the output gate left out": lambda m: _with_layers(
        m, _zeroed("w_q_gate")),
    # A_log -> -inf: alpha = exp(-0 * ...) = 1, the state never fades.
    "the decay left out": lambda m: _with_layers(
        m, lambda w: {k: (jnp.full_like(v, -jnp.inf) if k == "A_log" else v)
                      for k, v in w.items()}),
    "the shared expert's gate left out": lambda m: _with_layers(
        m, _zeroed("w_shared_gate")),
    "the top-k renormalisation left out": lambda m: dataclasses.replace(
        m, norm_topk=False),
    "the other shares' experts added": lambda m: dataclasses.replace(
        m, first_expert=0),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_tolerance_fails_a_coarser_rounding_and_each_term_left_out(
        hybrid, fault):
    """The control: the reference in the program's place, one step
    below the float32 the configuration states, or with one term of the
    mathematics made to say nothing."""
    cfg, reference, model = hybrid
    worst, mean = _differences(reference, model, FAULTS[fault](model))
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert (worst > 3 * tolerance["max_abs_logprob_diff"]
            or mean > 3 * tolerance["mean_abs_logprob_diff"]), (worst, mean)


def test_the_same_model_differs_by_nothing(hybrid):
    _, reference, model = hybrid
    assert _differences(reference, model,
                        dataclasses.replace(model)) == (0.0, 0.0)


def test_hybrid_counts_by_hand():
    """At the published widths, one chip's share (128 of 512 experts,
    8 of 48 layers, a quarter of the vocabulary)."""
    c = bench_run.load_json(PUBLISHED)
    assert counts.layer_is_linear(c) == [True, True, True, False] * 2
    assert (counts.num_linear(c), counts.num_full(c)) == (6, 2)
    # q + gate 16 heads x 2 x 256, k and v 2 heads x 256, o; two norms.
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 512
    assert counts.full_attention_params(c) == full == 27_263_488
    # q, k 2048 each, v, z 4096 each, b, a 32 each; conv 4 x 8192;
    # A_log, dt_bias 32 each; norm 128; out 4096 x 2048.
    linear = (2048 * (2 * 2048 + 2 * 4096 + 64) + 4 * 8192 + 64 + 128
              + 4096 * 2048)
    assert counts.linear_attention_params(c) == linear == 33_718_464
    expert = 3 * 2048 * 512
    assert counts.expert_params(c) == expert == 3_145_728
    assert counts.router_width(c) == 512
    shared = 2048 * 512 + expert + 2048 + 2 * 2048
    assert counts.sparse_shared_params(c) == shared == 4_200_448
    head = 2048 * 37_984
    dense = 6 * linear + 2 * full + 8 * shared + 2048 + head
    assert counts.dense_params(c) == dense == 368_234_624
    # With the held experts and the embedding: what the program's init
    # makes (3667 M parameters, 7.33 GB in bfloat16).
    assert dense + 8 * 128 * expert + head == 3_667_251_328
    kv = 2 * 2 * 2 * 256 * 2
    assert counts.kv_bytes_per_token(c) == kv == 4096
    assert counts.decode_step_bytes(c, 1000) == 2 * dense + 1000 * kv
    assert roofline.decode_step_bytes(c, 0) == 736_469_248
    state = 32 * 128 * 128
    assert counts.state_elements(c) == state == 524_288
    # 128 rows, 118 experts hit a layer, 100k tokens of context.
    step = (2 * dense + 100_000 * kv + 8 * 118 * expert * 2
            + 6 * 128 * 2 * state * 4)
    assert counts.hybrid_decode_step_bytes(c, 128, 118, 100_000) == step
    assert step == 736_469_248 + 409_600_000 + 5_939_134_464 + 3_221_225_472
    assert step == 10_306_429_184
    assert counts.gdn_decode(c, 128) == (7 * 128 * state,
                                         2 * 128 * state * 4)
    assert counts.gdn_prefill(c, [256, 100]) == (
        7 * 356 * state, 2 * 2 * state * 4 + 356 * (2 * 4096 + 2 * 4096) * 2)
    assert counts.moe_experts(c, 320, 118) == (
        2 * 320 * expert, 118 * expert * 2 + 320 * 2 * 2048 * 2)
    # A chunk of 4 tokens from position 10: a token's 10 choices fall
    # on held experts a quarter of the time.
    per_token = 6 * linear + 2 * full + 8 * (shared + 2.5 * expert)
    want = (2 * per_token * 4 + 6 * 7 * 4 * state
            + 4 * 2 * 16 * 256 * (4 * 10 + 10))
    assert roofline.prefill_flops(c, [(10, 4, False)]) == want
    assert roofline.prefill_flops(c, [(10, 4, True)]) == want + 2 * head
    with pytest.raises(ValueError):
        counts.decode_step_bytes(
            dict(c, chipbench={"quantization": "int8"}), 0)


def test_the_published_configuration_states_its_cut():
    c = bench_run.load_json(PUBLISHED)
    bench = c["chipbench"]
    assert bench["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                              "vocab_size": 151936}
    assert c["num_experts"] * c["expert_parallel_size"] == 512
    assert c["vocab_size"] * 4 == 151936
    assert {"state_dtype", "weights", "tokenizer"} <= set(bench["assumed"])
    flags = bench["server_flags"]
    assert (flags["max-num-seqs"], flags["decode-steps"]) == (128, 32)


# ---- the readers on a run made by hand -------------------------------------


def reader(name):
    import importlib
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


@pytest.fixture
def traced(tmp_path):
    config = bench_run.load_json(PUBLISHED)
    t0 = 1000.0
    burst = "jit(_decode_burst_impl)/jit(main)/while/body"
    step = "jit(_step_impl)/jit(main)"
    decode = {"kind": "decode", "window": 32, "host_ms": 10,
              "device_wait_ms": 1000, "decode_rows": 100,
              "moe_experts_hit": 110.0, "moe_tokens_per_expert_mean": 2.0,
              "moe_tokens_per_expert_max": 7.0, "state_slots_total": 136}
    files = {
        "cell.json": {"t0_unix": t0, "seconds": 20.0,
                      "slice_unix": [t0 + 8.0, t0 + 11.0],
                      "config_as_run": config,
                      "version": {"device_kind": "TPU v5 lite"}},
        "records.json": [{"phase": "window", "first": 1.0, "last": 15.0,
                          "prompt_tokens": 300, "tokens": 700}] * 100,
        "steps.json": [
            dict(decode, step=1, ts=t0 + 8.5, state_slots_used=101),
            dict(decode, step=2, ts=t0 + 9.5, state_slots_used=104),
            {"step": 3, "ts": t0 + 10.0, "kind": "prefill",
             "prefill_rows": 2, "host_ms": 5, "device_wait_ms": 60,
             "state_slots_used": 106, "state_slots_total": 136},
            dict(decode, step=4, ts=t0 + 15.0, state_slots_used=90,
                 moe_tokens_per_expert_max=9.0)],
        "trace_summary.json": {
            "window_s": 3.0, "busy_s": 2.9,
            "programs": {"_decode_burst_impl": {
                "count": 3, "seconds": 2.4, "whole_s": 0.96},
                "_step_impl": {"count": 1, "seconds": 0.06,
                               "whole_s": 0.06}},
            "scopes": {
                f"{burst}/gdn_decode/mul": {"seconds": 0.5, "count": 480},
                f"{burst}/gdn_decode/scatter": {"seconds": 0.3,
                                                "count": 480},
                f"{burst}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.9, "count": 1280},
                f"{step}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.02, "count": 16},
                f"{step}/gdn_prefill/while/body/dot_general": {
                    "seconds": 0.01, "count": 24}}},
    }
    for name, content in files.items():
        with open(tmp_path / name, "w") as f:
            json.dump(content, f)
    spans = [{"span": "engine_request", "request_id": f"r{i}", "events": [
        {"event": "prefill_chunk", "ts": t0 + 9.9, "start": 0,
         "tokens": 200 + 56 * i, "last": True}]} for i in range(2)]
    with open(tmp_path / "spans.jsonl", "w") as f:
        f.write("\n".join(map(json.dumps, spans)) + "\n")
    return RunFiles(str(tmp_path)), config


def test_the_kernels_shares_of_their_rooflines(traced):
    run, cfg = traced
    assert hybrid_slice.scope_seconds(run.trace, "gdn_decode",
                                      "_decode_burst") == (0.8, 960)
    # The expert layer's name in the prefill step is another program's.
    assert hybrid_slice.scope_seconds(run.trace, "moe_experts",
                                      "_decode_burst") == (0.9, 1280)
    assert hybrid_slice.scope_seconds(run.trace, "moe_experts",
                                      "_step_impl") == (0.02, 16)
    # 2.4 s of the burst at 0.96 s an execution: 2.5 bursts, 80 steps.
    steps = hybrid_slice.token_steps(run)
    assert steps == pytest.approx(80.0)
    # Two bursts stamped inside the slice, 100 rows each.
    state = 32 * 128 * 128
    moved = 2 * 100 * state * 4 * 80 * 6
    assert reader("gdn_decode_roofline").read(run) == pytest.approx(
        100 * moved / 819e9 / 0.8)
    expert = 3 * 2048 * 512
    moved = (110 * expert * 2 + 256 * 2 * 2048 * 2) * 80 * 8
    assert reader("moe_experts_roofline").read(run) == pytest.approx(
        100 * moved / 819e9 / 0.9)
    # Chunks of 200 and 256 tokens, one prefill record, one execution.
    flops, moved = counts.gdn_prefill(cfg, [200, 256])
    least = max(6 * flops / 197e12, 6 * moved / 819e9)
    assert reader("gdn_prefill_roofline").read(run) == pytest.approx(
        100 * least / 0.01)
    live = 100 * (300 + 700 * (9.5 - 1.0) / 14.0)
    moved = counts.hybrid_decode_step_bytes(cfg, 100, 110.0, live)
    assert reader("hybrid_decode_roofline").read(run) == pytest.approx(
        100 * moved / 819e9 / (0.96 / 32), rel=1e-3)
    assert reader("state_slots_peak").read(run) == pytest.approx(
        100 * 106 / 136)
    assert reader("moe_expert_load").read(run) == pytest.approx(
        (3.5 + 3.5 + 4.5) / 3)


def test_a_share_over_its_roofline_is_an_error_not_a_value(traced):
    run, _ = traced
    run.trace["scopes"] = {k: dict(v, seconds=v["seconds"] / 100)
                           for k, v in run.trace["scopes"].items()}
    with pytest.raises(ValueError, match="roofline"):
        reader("gdn_decode_roofline").read(run)


@pytest.mark.parametrize("name", [
    "gdn_decode_roofline", "gdn_prefill_roofline", "moe_experts_roofline",
    "hybrid_decode_roofline", "state_slots_peak", "moe_expert_load"])
def test_a_program_without_the_names_or_counters_gives_nothing(traced, name):
    """The parent commit's program: no scope of these names in its
    trace, no such field in its step records."""
    run, _ = traced
    run.trace["scopes"] = {"jit(_decode_burst_impl)/jit(main)/add":
                           {"seconds": 1.0, "count": 10}}
    for record in run.window_steps:
        for field in [k for k in record
                      if k.startswith(("moe_", "state_slots"))]:
            del record[field]
    assert reader(name).read(run) is None
