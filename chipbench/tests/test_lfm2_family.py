"""The LFM2-MoE family through the door: its reference against the
program's ``models/lfm2_moe.py`` served by the engine in float32 at tiny
widths on the CPU (prompts of several chunks, the deferred burst through
pages, slots and dense tails), the tolerance against a coarser rounding
and against each term left out or put in, its counts with the sums by
hand at the published widths, its readers on a run directory made by
hand, and its CPU rehearsal.  Every file of the family is new; none of
the harness was edited for it."""

import dataclasses
import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import family, hybrid_slice, roofline, run as bench_run
from chipbench.counts import lfm2_family as counts
from chipbench.runfiles import RunFiles

TINY = os.path.join(bench_run.BENCH, "rehearsal", "configs",
                    "tiny-lfm2.json")
PUBLISHED = os.path.join(bench_run.BENCH, "configs", "lfm2-8b-a1b-ep4.json")
CELL = "lfm2-8b-a1b-ep4.decode-closed"


@pytest.fixture(scope="module")
def lfm2():
    cfg = bench_run.load_json(TINY)
    assert family.name_of(cfg) == "lfm2_family"
    reference = family.module("reference", cfg)
    assert reference.__name__ == "chipbench.reference.lfm2_family"
    assert family.module("counts", cfg) is counts
    hf = {k: v for k, v in cfg.items() if k != "chipbench"}
    model = reference.program_model(hf, cfg["chipbench"])
    # Rank 1 of 2: experts 4..7 of 8.
    assert model.first_expert == 4 and model.layer(1)["e_gate"].shape[0] == 4
    return cfg, reference, model


def lfm2_served_log_probs(cfg, prompt, answers, top):
    """What the program says: the engine on the configuration's random
    weights, greedy, prompts in chunks of 64 and deferred bursts of 4."""
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
                                  prefill_chunk_size=64, decode_steps=4,
                                  deferred_kv_writes=True),
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


def test_the_lfm2_reference_agrees_with_the_program_in_float32(lfm2):
    """150 tokens in three chunks of 64, 64 and 22 (the tail carried
    twice), then nine answers over three deferred bursts."""
    cfg, reference, model = lfm2
    prompt = np.random.default_rng(1).integers(0, 512, 150).tolist()
    tokens, served = lfm2_served_log_probs(cfg, prompt, 9, 5)
    sequence = prompt + tokens
    got = np.asarray(reference.log_probs(
        model, sequence, list(range(149, 149 + 9))))
    diffs = [abs(lp - got[j, tid]) for j, top in enumerate(served)
             for tid, lp in top.items()]
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert len(diffs) >= 45
    assert max(diffs) < tolerance["max_abs_logprob_diff"] / 2
    assert np.mean(diffs) < tolerance["mean_abs_logprob_diff"] / 2
    assert tokens == np.argmax(got, -1).tolist()


def test_the_lfm2_reference_is_float32_at_the_highest_precision_and_alone():
    path = os.path.join(bench_run.BENCH, "reference", "lfm2_family.py")
    with open(path) as f:
        source = f.read()
    assert 'jax.default_matmul_precision("highest")' in source
    # Nothing of the program but the init's values, taken in
    # program_model alone.
    head, tail = source.split("def program_model")
    assert "production_stack_tpu" not in head.split('"""', 2)[2]
    assert tail.count("from production_stack_tpu") == 2
    assert "bfloat16" not in source.split('"""', 2)[2]


def test_a_long_lfm2_prompt_in_blocks_of_queries_is_the_same(lfm2,
                                                             monkeypatch):
    _, reference, model = lfm2
    tokens = np.random.default_rng(2).integers(0, 512, 90)
    whole = np.asarray(reference.log_probs(model, tokens, [50, 89]))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    blocks = np.asarray(reference.log_probs(model, tokens, [50, 89]))
    assert np.abs(blocks - whole).max() < 1e-5


# ---- the tolerance against a coarser rounding and each term -----------------


def _lfm2_differences(reference, model, other=None):
    tokens = np.random.default_rng(0).integers(0, 512, 120)
    positions = list(range(60, 120))
    want = np.asarray(reference.log_probs(model, tokens, positions))
    got = np.asarray(reference.log_probs(other or model, tokens, positions))
    top = np.argsort(-want, -1)[:, :6]
    diff = np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1))
    return diff.max(), diff.mean()


def _lfm2_with_layers(model, change):
    return dataclasses.replace(
        model, layer=lambda i: change(dict(model.layer(i))))


def _lfm2_rounded(dtype):
    def fault(m):
        cast = lambda a: jnp.asarray(a, jnp.float32).astype(  # noqa: E731
            dtype).astype(jnp.float32)
        return dataclasses.replace(
            _lfm2_with_layers(m, lambda w: {
                k: cast(v) if v.ndim >= 2 else v for k, v in w.items()}),
            embed=cast(m.embed))
    return fault


def _without_the_oldest_tap(w):
    if "conv" in w:
        w["conv"] = w["conv"].at[0].set(0.0)
    return w


# The model handed to the reference says the fault.
LFM2_MODEL_FAULTS = {
    "float8_e4m3 matrices": _lfm2_rounded(jnp.float8_e4m3fn),
    "a tap of the convolution left out": lambda m: _lfm2_with_layers(
        m, _without_the_oldest_tap),
    "expert_bias left out of the selection": lambda m: dataclasses.replace(
        m, use_expert_bias=False),
    "the top-k normalisation left out": lambda m: dataclasses.replace(
        m, norm_topk=False),
    "the other share's experts in place of its own":
        lambda m: dataclasses.replace(m, first_expert=0),
}


def _biased_weights(m, w, scores):
    """``choose`` with the bias leaking into the weights."""
    by = scores + w["expert_bias"]
    weight, chosen = jax.lax.top_k(by, m.top_k)
    return weight / (jnp.sum(weight, -1, keepdims=True) + 1e-6), chosen


# One function of the reference says the fault.
def _lfm2_equation_faults(reference):
    real_conv = reference.causal_conv
    return {
        "the B gate left out": ("gate_in", lambda b, x: x),
        "the C gate left out": ("gate_out", lambda c, y: y),
        "an activation after the convolution": (
            "causal_conv", lambda x, w: jax.nn.silu(real_conv(x, w))),
        "q_layernorm left out": ("q_layernorm", lambda m, w, q: q),
        "k_layernorm left out": ("k_layernorm", lambda m, w, k: k),
        "the rotary left out": ("rope", lambda x, theta: x),
        "the bias leaking into the weights": ("choose", _biased_weights),
        "softmax in place of sigmoid": (
            "router_scores", lambda logits: jax.nn.softmax(logits, -1)),
    }


LFM2_FAULTS = sorted(LFM2_MODEL_FAULTS) + [
    "the B gate left out", "the C gate left out",
    "an activation after the convolution", "q_layernorm left out",
    "k_layernorm left out", "the rotary left out",
    "the bias leaking into the weights", "softmax in place of sigmoid"]


@pytest.mark.parametrize("fault", LFM2_FAULTS)
def test_the_lfm2_tolerance_fails_float8_and_each_term_left_out_or_put_in(
        lfm2, fault, monkeypatch):
    """The control: the reference in the program's place, with its
    matrices rounded well below the float32 the configuration states,
    or with one term of the mathematics left out or put in."""
    cfg, reference, model = lfm2
    if fault in LFM2_MODEL_FAULTS:
        worst, mean = _lfm2_differences(reference, model,
                                        LFM2_MODEL_FAULTS[fault](model))
    else:
        tokens = np.random.default_rng(0).integers(0, 512, 120)
        positions = list(range(60, 120))
        want = np.asarray(reference.log_probs(model, tokens, positions))
        name, other = _lfm2_equation_faults(reference)[fault]
        monkeypatch.setattr(reference, name, other)
        got = np.asarray(reference.log_probs(model, tokens, positions))
        top = np.argsort(-want, -1)[:, :6]
        diff = np.abs(np.take_along_axis(got, top, -1)
                      - np.take_along_axis(want, top, -1))
        worst, mean = diff.max(), diff.mean()
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert (worst > 3 * tolerance["max_abs_logprob_diff"]
            or mean > 3 * tolerance["mean_abs_logprob_diff"]), (worst, mean)


def test_the_same_lfm2_differs_by_nothing(lfm2):
    _, reference, model = lfm2
    assert _lfm2_differences(reference, model) == (0.0, 0.0)


# ---- the counts, by hand ----------------------------------------------------


def test_lfm2_counts_by_hand():
    """At the published widths, one chip's share (8 of 32 experts, all
    24 layers, the whole vocabulary): the arithmetic of ISSUE 36."""
    c = bench_run.load_json(PUBLISHED)
    assert [i for i, conv in enumerate(counts.layer_is_conv(c))
            if not conv] == [2, 6, 10, 14, 18, 21]
    assert (counts.num_conv(c), counts.num_attention(c),
            counts.num_expert_layers(c)) == (18, 6, 22)
    assert (counts.head_dim(c), counts.router_width(c)) == (64, 32)
    # in_proj 2048 x 6144, three taps a channel, out_proj 2048 x 2048.
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert counts.conv_params(c) == conv == 16_783_360
    # q and o 2048 x 2048, k and v 2048 x 512, two norms of 64.
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert counts.attention_params(c) == attention == 10_485_888
    assert counts.dense_mlp_params(c) == 3 * 2048 * 7168 == 44_040_192
    expert = 3 * 2048 * 1792
    assert counts.expert_params(c) == expert == 11_010_048
    assert 22 * counts.router_params(c) == 22 * (2048 * 32 + 32) == 1_442_496
    assert counts.norm_params(c) == 49 * 2048 == 100_352
    head = 2048 * 65536
    assert counts.head_params(c) == head == 134_217_728
    dense = (18 * conv + 6 * attention + 2 * 44_040_192 + 1_442_496
             + 100_352 + head)
    assert counts.dense_params(c) == dense == 588_856_768
    total = dense + 22 * 8 * expert
    assert 22 * 8 * expert == 1_937_768_448
    assert counts.param_count(c) == total == 2_526_625_216
    # The published model whole: 32 experts a layer.
    assert dense + 22 * 32 * expert == 8_339_930_560
    # K/V: 6 layers x K,V x 8 heads x 64 x 2 B; a page of 128 tokens.
    assert counts.kv_bytes_per_token(c) == 12_288
    assert 128 * counts.kv_bytes_per_token(c) == 1_572_864
    # State: 18 layers x [2, 2048] in 2 bytes.
    assert counts.tail_elements(c) == 4096
    assert counts.state_bytes_per_sequence(c) == 147_456
    assert counts.decode_step_bytes(c, 1000) == 2 * dense + 1000 * 12_288
    assert roofline.decode_step_bytes(c, 0) == 1_177_713_536
    # A step at 256 rows, every held expert hit, 1300 tokens a row:
    # dense weights 1.18e9, experts 3.88e9, tails 0.08e9, K/V 4.09e9.
    step = counts.hybrid_decode_step_bytes(c, 256, 8, 256 * 1300)
    assert step == (1_177_713_536 + 22 * 8 * expert * 2
                    + 18 * 256 * 2 * 4096 * 2 + 256 * 1300 * 12_288)
    assert step == 9_218_194_304
    assert 11.2e-3 < step / 819e9 < 11.3e-3
    # One expert layer at 256 rows x 4 choices / 4 ranks, 8 hit.
    assert counts.moe_experts(c, 256, 8) == (
        2.0 * 256 * expert, 8 * expert * 2 + 256 * 2 * 2048 * 2)
    # One conv operator at 256 rows.
    assert counts.sconv_decode(c, 256) == (
        256 * (2.0 * 4 * 2048 * 2048 + 7 * 2048),
        conv * 2 + 256 * 2 * 4096 * 2 + 256 * 2 * 2048 * 2)
    assert counts.sconv_prefill(c, [128, 100], steps=1) == (
        228 * (2.0 * 4 * 2048 * 2048 + 7 * 2048),
        conv * 2 + 2 * 2 * 4096 * 2 + 228 * 2 * 2048 * 2)
    assert counts.sconv_prefill(c, [128], 3)[1] - counts.sconv_prefill(
        c, [128], 1)[1] == 2 * conv * 2
    # One attention layer at 256 rows over 332 800 live tokens.
    assert counts.attn_decode(c, 256, 332_800) == (
        4.0 * 2048 * 332_800, 2048 * 332_800 + 256 * 2 * 2048 * 2)
    per_token = (18 * conv + 6 * attention + 2 * 44_040_192 + 1_442_496
                 + 22 * 4 * 8 / 32 * expert)
    want = (2 * per_token * 4 + 4 * 6 * 32 * 64 * (4 * 10 + 10))
    assert roofline.prefill_flops(c, [(10, 4, False)]) == want
    assert roofline.prefill_flops(c, [(10, 4, True)]) == want + 2 * head
    with pytest.raises(ValueError):
        counts.decode_step_bytes(
            dict(c, chipbench={"quantization": "int8"}), 0)


def test_the_published_lfm2_is_the_catalogs_row_and_cuts_the_experts_alone():
    c = bench_run.load_json(PUBLISHED)
    bench = c["chipbench"]
    assert bench["reduced"] == ["num_experts"] and bench["chips"] == 1
    row = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048,
        intermediate_size=7168, max_position_embeddings=128000,
        model_type="lfm2_moe", moe_intermediate_size=1792, norm_eps=1e-05,
        norm_topk_prob=True, num_attention_heads=32, num_dense_layers=2,
        num_experts_per_tok=4, num_hidden_layers=24, num_key_value_heads=8,
        rope_theta=1000000, routed_scaling_factor=1, use_expert_bias=True,
        vocab_size=65536)
    assert {k: c[k] for k in row} == row
    assert "".join("c" if kind == "conv" else "A"
                   for kind in c["layer_types"]) == "ccAcccAcccAcccAcccAccAcc"
    assert set(c["layer_types"]) == {"conv", "full_attention"}
    assert (c["num_experts"], c["published"]["num_experts"],
            c["expert_parallel_size"], c["expert_parallel_rank"]) == (
                8, 32, 4, 0)
    assert {"architectures", "head_dim", "tie_word_embeddings",
            "state_dtype", "topk_normalisation", "weights",
            "tokenizer"} <= set(bench["assumed"])
    flags = bench["server_flags"]
    assert (flags["max-num-seqs"], flags["decode-steps"],
            flags["page-size"], flags["num-pages"]) == (256, 32, 128, 4096)
    assert "deferred-kv-writes" not in flags     # auto resolves it on
    cell = bench_run.find_cell(CELL)
    params = cell["traffic_params"]
    assert (params["clients"], params["ramp_s"], params["pool"],
            params["drain_limit_s"]) == (256, 30.0, 4096, 240)
    assert params["prompt_tokens"] == {"dist": "uniform", "min": 64,
                                       "max": 256}
    assert params["output_tokens"] == {"dist": "uniform", "min": 512,
                                       "max": 2048}
    assert cell["sampling"] == {"temperature": 0.7, "top_p": 1.0}
    # Every prefill bucket of the chunk the traffic can ask for is
    # warmed by name.
    from production_stack_tpu.engine.model_runner import prefill_buckets
    assert set(prefill_buckets(flags["prefill-chunk-size"])) <= set(
        cell["warm_prompt_tokens"])
    bench_run.validate(cell)


# ---- the readers on a run made by hand -------------------------------------


def lfm2_reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


NEW_READERS = ["lfm2_experts_roofline", "sconv_decode_roofline",
               "sconv_prefill_roofline", "lfm2_attn_decode_roofline"]


@pytest.fixture
def lfm2_traced(tmp_path):
    config = bench_run.load_json(PUBLISHED)
    t0 = 1000.0
    burst = "jit(_decode_burst_deferred_impl)/jit(main)/while/body"
    step = "jit(_step_impl)/jit(main)"
    decode = {"kind": "decode", "window": 32, "host_ms": 10,
              "device_wait_ms": 800, "decode_rows": 250,
              "state_slots_total": 272, "moe_experts_hit": 8.0,
              "moe_tokens_per_expert_mean": 31.0,
              "moe_tokens_per_expert_max": 44.0}
    files = {
        "cell.json": {"t0_unix": t0, "seconds": 20.0,
                      "slice_unix": [t0 + 8.0, t0 + 11.0],
                      "config_as_run": config,
                      "version": {"device_kind": "TPU v5 lite"}},
        "records.json": [{"phase": "window", "first": 1.0, "last": 15.0,
                          "prompt_tokens": 160, "tokens": 1200}] * 250,
        "steps.json": [
            dict(decode, step=1, ts=t0 + 8.5, state_slots_used=251),
            dict(decode, step=2, ts=t0 + 9.5, state_slots_used=254),
            {"step": 3, "ts": t0 + 10.0, "kind": "prefill",
             "prefill_rows": 2, "host_ms": 5, "device_wait_ms": 60,
             "state_slots_used": 256, "state_slots_total": 272},
            dict(decode, step=4, ts=t0 + 15.0, state_slots_used=240)],
        "trace_summary.json": {
            "window_s": 3.0, "busy_s": 2.9,
            "programs": {"_decode_burst_deferred_impl": {
                "count": 4, "seconds": 2.4, "whole_s": 0.8},
                "_step_impl": {"count": 1, "seconds": 0.12,
                               "whole_s": 0.12}},
            "scopes": {
                f"{burst}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.7, "count": 4224},
                f"{burst}/sconv_decode/dot_general": {
                    "seconds": 0.15, "count": 3456},
                f"{burst}/sconv_decode/mul": {"seconds": 0.05,
                                              "count": 3456},
                f"{burst}/qknorm_attn/gather": {"seconds": 1.0,
                                                "count": 1152},
                f"{step}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.02, "count": 44},
                f"{step}/sconv_prefill/dot_general": {
                    "seconds": 0.03, "count": 36},
                f"{step}/sconv_prefill/scatter": {"seconds": 0.01,
                                                  "count": 18}}},
    }
    for name, content in files.items():
        with open(tmp_path / name, "w") as f:
            json.dump(content, f)
    spans = [{"span": "engine_request", "request_id": f"r{i}", "events": [
        {"event": "prefill_chunk", "ts": t0 + 9.9, "start": 0,
         "tokens": 100 + 28 * i, "last": True}]} for i in range(2)]
    with open(tmp_path / "spans.jsonl", "w") as f:
        f.write("\n".join(map(json.dumps, spans)) + "\n")
    return RunFiles(str(tmp_path)), config


def test_the_new_layers_shares_of_their_rooflines(lfm2_traced):
    run, cfg = lfm2_traced
    assert hybrid_slice.scope_seconds(run.trace, "sconv_decode",
                                      "_decode_burst") == (0.2, 6912)
    # The expert layer's name in the prefill step is another program's.
    assert hybrid_slice.scope_seconds(run.trace, "moe_experts",
                                      "_decode_burst") == (0.7, 4224)
    assert hybrid_slice.scope_seconds(run.trace, "sconv_prefill",
                                      "_step_impl") == (0.04, 54)
    # 2.4 s of the burst at 0.8 s an execution: 3 bursts, 96 steps.
    assert hybrid_slice.token_steps(run) == pytest.approx(96.0)
    # Two bursts stamped inside the slice: 250 rows, 8 experts hit, 31
    # pairs a held expert; 22 expert layers, not 24.
    expert = 3 * 2048 * 1792
    moved = (8 * expert * 2 + 248 * 2 * 2048 * 2) * 96 * 22
    assert lfm2_reader("lfm2_experts_roofline").read(run) == pytest.approx(
        100 * moved / 819e9 / 0.7)
    flops, moved = counts.sconv_decode(cfg, 250)
    assert flops / 197e12 < moved / 819e9           # read-bound
    assert lfm2_reader("sconv_decode_roofline").read(run) == pytest.approx(
        100 * moved * 96 * 18 / 819e9 / 0.2)
    # Chunks of 100 and 128 tokens, one prefill record, one execution.
    flops, moved = counts.sconv_prefill(cfg, [100, 128], 1)
    least = max(18 * flops / 197e12, 18 * moved / 819e9)
    assert lfm2_reader("sconv_prefill_roofline").read(run) == pytest.approx(
        100 * least / 0.04)
    live = 250 * (160 + 1200 * (9.5 - 1.0) / 14.0)
    moved = (2048 * live + 250 * 2 * 2048 * 2) * 96 * 6
    assert lfm2_reader("lfm2_attn_decode_roofline").read(
        run) == pytest.approx(100 * moved / 819e9 / 1.0, rel=1e-3)
    # The two the other hybrid cell brought serve this family's counts
    # unchanged.
    whole = counts.hybrid_decode_step_bytes(cfg, 250, 8.0, live)
    assert lfm2_reader("hybrid_decode_roofline").read(run) == pytest.approx(
        100 * whole / 819e9 / (0.8 / 32), rel=1e-3)
    assert lfm2_reader("moe_expert_load").read(run) == pytest.approx(44 / 31)
    assert lfm2_reader("state_slots_peak").read(run) == pytest.approx(
        100 * 256 / 272)
    for name in NEW_READERS + ["hybrid_decode_roofline"]:
        assert 0 < lfm2_reader(name).read(run) < 100
    # moe_experts_roofline would count 24 expert layers here: 24 / 22
    # of the true share, which is why the cell does not list it.
    assert lfm2_reader("moe_experts_roofline").read(run) == pytest.approx(
        24 / 22 * lfm2_reader("lfm2_experts_roofline").read(run))
    assert "moe_experts_roofline" not in bench_run.find_cell(CELL)[
        "per_layer"]


def test_an_lfm2_share_over_its_roofline_is_an_error_not_a_value(lfm2_traced):
    run, _ = lfm2_traced
    run.trace["scopes"] = {k: dict(v, seconds=v["seconds"] / 100)
                           for k, v in run.trace["scopes"].items()}
    for name in ("lfm2_experts_roofline", "sconv_decode_roofline",
                 "lfm2_attn_decode_roofline"):
        with pytest.raises(ValueError, match="roofline"):
            lfm2_reader(name).read(run)


@pytest.mark.parametrize("name", NEW_READERS)
def test_an_lfm2_run_without_the_names_or_a_trace_gives_nothing(
        lfm2_traced, name, tmp_path):
    """A program with no scope of these names (the parent commit's) and
    a run that was not traced: nothing, and no error."""
    run, cfg = lfm2_traced
    run.trace["scopes"] = {"jit(_decode_burst_impl)/jit(main)/add":
                           {"seconds": 1.0, "count": 10}}
    run.trace["programs"] = {}
    assert lfm2_reader(name).read(run) is None
    os.remove(tmp_path / "trace_summary.json")
    assert lfm2_reader(name).read(RunFiles(str(tmp_path))) is None


def test_the_manifest_names_the_lfm2_cell_and_its_four_shares():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    config, = [c for c in manifest["configs"]
               if c["name"] == "lfm2-8b-a1b-ep4"]
    assert config["reduced"] == ["num_experts"]
    assert config["file"] == "chipbench/configs/lfm2-8b-a1b-ep4.json"
    entry, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert entry == {
        "name": CELL, "config": "lfm2-8b-a1b-ep4",
        "traffic": "decode-closed", "chips": 1,
        "why": bench_run.find_cell(CELL)["why"]}
    assert "a quarter of the deployment's" in entry["why"]
    mine = [m for m in manifest["per_layer"] if m["workloads"] == [CELL]]
    assert [m["name"] for m in mine] == NEW_READERS
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m["workloads"]}
    assert listed == set(bench_run.find_cell(CELL)["per_layer"])
    assert {"moe_expert_load", "hybrid_decode_roofline",
            "state_slots_peak"} <= listed
    # The cells and metrics the benchmark had are where they were.
    assert [w["name"] for w in manifest["workloads"]][:3] == [
        "qwen2.5-3b.decode-closed", "qwen3-next-80b-a3b-ep4.decode-closed",
        "jamba2-3b.decode-closed"]


# ---- the CPU rehearsal ------------------------------------------------------


def test_the_lfm2_rehearsal_runs_end_to_end_on_the_cpu():
    """The real server behind the real router at the tiny size, the
    reference check, the window, the traced side and the result line,
    as ``test_rehearsal.py`` runs the other families'."""
    cell = "rehearsal-lfm2"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(bench_run.BENCH, "run.py"),
         "--workload", cell, "--seed", str(2**31 + 7), "--seconds", "6",
         "--trace", "1"],
        cwd=bench_run.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert (result["failed"], result["unfinished"]) == (0, 0)
    assert result["attempted"] > 10
    assert result["device"]["platform"] == "cpu"
    wanted = bench_run.find_cell(cell)["per_layer"]
    assert set(result["metrics"]) <= set(wanted)
    assert result["metrics"]["window_compiles"]["value"] == 0
    assert result["metrics"]["state_slots_peak"]["value"] > 0
    assert result["metrics"]["moe_expert_load"]["value"] >= 1
    # Host threads stood in for the device: no device share from them.
    assert not set(result["metrics"]) & set(NEW_READERS + ["device_idle"])
    version = RunFiles(os.path.join(bench_run.STATE, "runs", cell)).cell[
        "version"]
    assert (version["kv_writes"], version["conv_tails"]) == (
        "deferred", "burst")
