"""The EXAONE-MoE family through the door: its reference against the
program's ``models/exaone_moe.py`` served by the engine in float32 at
tiny widths on the CPU (a prompt of five chunks of two windows, the
deferred burst through pages, rings and tails), the reference against
itself uncut, the tolerance against a coarser rounding and against
terms left out, its counts with the sums by hand at the published
widths, its two readers on a run directory made by hand, the manifest
asked by name, and its CPU rehearsal.  Every file of the family is
new; none of the harness was edited for it."""

import dataclasses
import importlib
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import family, hybrid_slice, run as bench_run
from chipbench.counts import exaone_moe_family as counts
from chipbench.runfiles import RunFiles

TINY = os.path.join(bench_run.BENCH, "rehearsal", "configs",
                    "tiny-exaone.json")
PUBLISHED = os.path.join(bench_run.BENCH, "configs",
                         "k-exaone-236b-a23b-ep16.json")
CONFIG = "k-exaone-236b-a23b-ep16"
CELL = CONFIG + ".decode-closed"
NEW_READERS = ["swa_decode_roofline", "swa_prefill_roofline"]
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "vocab_size"]


@pytest.fixture(scope="module")
def exaone():
    cfg = bench_run.load_json(TINY)
    assert family.name_of(cfg) == "exaone_moe_family"
    reference = family.module("reference", cfg)
    assert reference.__name__ == "chipbench.reference.exaone_moe_family"
    assert family.module("counts", cfg) is counts
    hf = {k: v for k, v in cfg.items() if k != "chipbench"}
    model = reference.program_model(hf, cfg["chipbench"])
    # Rank 0 of 2: experts 0..3 of 8; layer 0 is dense.
    assert model.first_expert == 0 and model.layer(1)["e_gate"].shape[0] == 4
    assert model.layer(1)["w_router"].shape == (64, 8)
    assert "w_router" not in model.layer(0)
    assert model.windowed == (True, True, False, True) and model.window == 16
    return cfg, reference, model


def exaone_served_log_probs(cfg, prompt, answers, top):
    """What the program says: the engine on the configuration's random
    weights, greedy, prompts in chunks of 32 and deferred bursts of 4."""
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
                                  prefill_chunk_size=32, decode_steps=4,
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


def test_the_exaone_reference_agrees_with_the_program_in_float32(exaone):
    """150 tokens in five chunks of 32 (two windows each: the mask
    inside a chunk, the ring between chunks), then nine answers over
    three deferred bursts that cross the ring's edge at 160."""
    cfg, reference, model = exaone
    prompt = np.random.default_rng(1).integers(0, 512, 150).tolist()
    tokens, served = exaone_served_log_probs(cfg, prompt, 13, 5)
    sequence = prompt + tokens
    got = np.asarray(reference.log_probs(
        model, sequence, list(range(149, 149 + 13))))
    diffs = [abs(lp - got[j, tid]) for j, top in enumerate(served)
             for tid, lp in top.items()]
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert len(diffs) >= 65
    assert max(diffs) < tolerance["max_abs_logprob_diff"] / 2
    assert np.mean(diffs) < tolerance["mean_abs_logprob_diff"] / 2
    assert tokens == np.argmax(got, -1).tolist()


def test_the_exaone_reference_is_float32_at_the_highest_precision_and_alone():
    path = os.path.join(bench_run.BENCH, "reference",
                        "exaone_moe_family.py")
    with open(path) as f:
        source = f.read()
    assert 'jax.default_matmul_precision("highest")' in source
    # Nothing of the program but the init's values, taken in
    # program_model alone; one explicit mask, no ring.
    head, tail = source.split("def program_model")
    assert "production_stack_tpu" not in head.split('"""', 2)[2]
    assert tail.count("from production_stack_tpu") == 2
    code = source.split('"""', 2)[2]
    assert "bfloat16" not in code and "% " not in code


def test_a_long_exaone_prompt_in_blocks_of_queries_is_the_same(
        exaone, monkeypatch):
    _, reference, model = exaone
    tokens = np.random.default_rng(2).integers(0, 512, 90)
    whole = np.asarray(reference.log_probs(model, tokens, [50, 89]))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    blocks = np.asarray(reference.log_probs(model, tokens, [50, 89]))
    assert np.abs(blocks - whole).max() < 1e-5


def test_the_two_ranks_parts_add_up_to_the_uncut_reference(exaone):
    """The reference against itself: with every expert held it gives,
    for one expert layer, what the two ranks' partial sums give
    together with the shared expert counted once."""
    cfg, reference, model = exaone
    hf = {k: v for k, v in cfg.items() if k != "chipbench"}
    whole = reference.program_model(
        dict(hf, num_experts=8, expert_parallel_size=1), cfg["chipbench"])
    layer = whole.layer(1)
    assert layer["e_gate"].shape[0] == 8
    x = jnp.asarray(np.random.default_rng(3).standard_normal((12, 64)),
                    jnp.float32)
    want = reference.expert_block(whole, layer, x)
    shared = reference.swiglu(x, layer["s_gate"], layer["s_up"],
                              layer["s_down"])
    total = jnp.zeros_like(x)
    for rank in range(2):
        held = slice(4 * rank, 4 * rank + 4)
        part = dataclasses.replace(whole, first_expert=4 * rank)
        w = dict(layer, e_gate=layer["e_gate"][held],
                 e_up=layer["e_up"][held], e_down=layer["e_down"][held])
        total = total + reference.expert_block(part, w, x) - shared
    assert float(jnp.abs(total + shared - want).max()) < 1e-5
    assert float(jnp.abs(total).max()) > 1e-3


# ---- the tolerance against a coarser rounding and terms left out -----------


def _exaone_differences(reference, model, other=None):
    tokens = np.random.default_rng(0).integers(0, 512, 120)
    positions = list(range(60, 120))
    want = np.asarray(reference.log_probs(model, tokens, positions))
    got = np.asarray(reference.log_probs(other or model, tokens, positions))
    top = np.argsort(-want, -1)[:, :6]
    diff = np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1))
    return diff.max(), diff.mean()


def _exaone_rounded(dtype):
    def fault(m):
        cast = lambda a: jnp.asarray(a, jnp.float32).astype(  # noqa: E731
            dtype).astype(jnp.float32)
        return dataclasses.replace(
            m, embed=cast(m.embed), lm_head=cast(m.lm_head),
            layer=lambda i: {k: cast(v) if v.ndim >= 2 else v
                             for k, v in m.layer(i).items()})
    return fault


def _exaone_without(name):
    def fault(m):
        def layer(i):
            w = dict(m.layer(i))
            if name in w:
                w[name] = jnp.zeros_like(w[name])
            return w
        return dataclasses.replace(m, layer=layer)
    return fault


# The model handed to the reference says the fault.
EXAONE_FAULTS = {
    "float8_e4m3 matrices": _exaone_rounded(jnp.float8_e4m3fn),
    "the window one key wider":
        lambda m: dataclasses.replace(m, window=m.window + 1),
    "the window one key narrower":
        lambda m: dataclasses.replace(m, window=m.window - 1),
    "every layer windowed":
        lambda m: dataclasses.replace(m, windowed=(True,) * m.num_layers),
    "no layer windowed":
        lambda m: dataclasses.replace(m, windowed=(False,) * m.num_layers),
    "the routed sum not scaled":
        lambda m: dataclasses.replace(m, routed_scale=1.0),
    "the shared expert left out": _exaone_without("s_down"),
    "the dense first layer left out": _exaone_without("w_down"),
}


@pytest.mark.parametrize("fault", sorted(EXAONE_FAULTS))
def test_the_exaone_tolerance_fails_float8_and_a_term_left_out(
        exaone, fault):
    """The control: the reference in the program's place, with its
    matrices rounded well below the float32 the configuration states,
    or with one term of the mathematics left out or put in wrongly.
    (``every layer windowed`` puts the rotary on the full layer with
    the window, ``no layer windowed`` takes both off; the norms' places,
    the head norms, the bias in the weights are read on the program's
    side: tests/test_exaone_moe.py.)"""
    cfg, reference, model = exaone
    worst, mean = _exaone_differences(reference, model,
                                      EXAONE_FAULTS[fault](model))
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert (worst > 3 * tolerance["max_abs_logprob_diff"]
            or mean > 3 * tolerance["mean_abs_logprob_diff"]), (worst, mean)


def test_the_same_exaone_differs_by_nothing(exaone):
    _, reference, model = exaone
    assert _exaone_differences(reference, model) == (0.0, 0.0)


# ---- the counts, by hand ----------------------------------------------------


def test_exaone_counts_by_hand():
    """The published widths, the cut's eight layers, 8 experts and
    19 200 vocabulary rows: every number of ISSUE 50's arithmetic."""
    c = bench_run.load_json(PUBLISHED)
    assert (counts.num_windowed(c), counts.num_full(c),
            counts.num_dense_layers(c), counts.num_expert_layers(c),
            counts.held_experts(c), counts.router_width(c)) == (
        6, 2, 1, 7, 8, 128)
    assert counts.attention_params(c) == (
        6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144 + 2 * 128
    ) == 113246464
    assert counts.expert_params(c) == 3 * 6144 * 2048 == 37748736
    assert counts.shared_params(c) == 37748736
    assert counts.router_params(c) == 6144 * 128 + 128 == 786560
    assert counts.expert_layer_shared_params(c) == (
        113246464 + 2 * 6144 + 37748736 + 786560) == 151794048
    assert counts.dense_layer_params(c) == (
        113246464 + 2 * 6144 + 3 * 6144 * 18432) == 452997376
    assert counts.head_params(c) == 19200 * 6144 == 117964800
    assert counts.param_count(c) == (
        452997376 + 7 * (151794048 + 8 * 37748736) + 2 * 117964800
        + 6144) == 3865420672
    assert counts.param_count(c) * 2 == 7730841344
    # Uncut: a layer whole, and the whole model.
    whole = dict(c, num_hidden_layers=48, num_experts=128,
                 expert_parallel_size=1, vocab_size=153600,
                 layer_types=["full_attention" if i % 4 == 3
                              else "sliding_attention" for i in range(48)])
    assert (counts.expert_layer_shared_params(whole)
            + 128 * counts.expert_params(whole)) == 4983632256
    assert counts.param_count(whole) == 236571156352
    # Two full layers of 8 KV heads of 128: 8192 B a token; a page of
    # 128 tokens 1 048 576 B. Six windowed layers' rings: 3 MB a row.
    assert counts.kv_bytes_per_token(c) == 2 * 2 * 8 * 128 * 2 == 8192
    assert counts.kv_bytes_per_token(c) * 128 == 1048576
    assert counts.ring_bytes(c) == 2 * 8 * 128 * 128 * 2 == 524288
    assert counts.state_bytes_per_sequence(c) == 6 * 524288 == 3145728
    assert 137 * counts.state_bytes_per_sequence(c) == 430964736
    # A decode step at 128 rows of 3 670 tokens: 11.7e9 B, 14.3 ms.
    floor = counts.decode_step_bytes(c, 0)
    assert floor == counts.dense_params(c) * 2 == (
        452997376 + 7 * 151794048 + 6144 + 117964800) * 2 == 3267053312
    live = 128 * 3670
    assert counts.decode_step_bytes(c, live) == floor + 8192 * live
    whole_step = counts.hybrid_decode_step_bytes(c, 128, 8, live)
    assert whole_step == (floor + 8192 * live + 7 * 8 * 37748736 * 2
                          + 6 * 128 * 524288) == 11745838848
    assert whole_step / 819e9 == pytest.approx(14.34e-3, rel=1e-3)
    rings, pages = 6 * 128 * 524288, 8192 * live
    assert rings / (rings + pages) == pytest.approx(0.095, abs=0.001)
    # Were all eight layers full: 75% of the K/V bytes would be theirs.
    assert 6 * 8192 // 2 * live / (8 * 8192 // 2 * live) == 0.75
    flops, moved = counts.swa_decode(c, 128)
    assert flops == 4 * 128 * 64 * 128 * 128
    assert moved == 128 * (524288 + 2 * 64 * 128 * 2)
    assert flops / 197e12 < moved / 819e9           # read-bound
    flops, moved = counts.swa_prefill(c, [256, 100])
    assert flops == 4 * 356 * 64 * 128 * 128
    assert moved == 2 * 2 * 524288 + 356 * (2 * 64 + 2 * 8) * 128 * 2
    assert flops / 197e12 < moved / 819e9           # read-bound too
    flops, moved = counts.moe_experts(c, 64, 7.9)
    assert flops == 2 * 64 * 37748736
    assert moved == 7.9 * 37748736 * 2 + 64 * 2 * 6144 * 2
    # Prefill: 2 a weight a token, the token's held choices at their
    # expected share 8 x 8 / 128; a window's keys in six layers, the
    # context so far in two.
    per_token = 452997376 + 7 * (151794048 + 0.5 * 37748736)
    assert counts.prefill_flops(c, [(0, 256, False)]) == pytest.approx(
        2 * per_token * 256 + 4 * 2 * 64 * 128 * (256 * 257 / 2)
        + 4 * 6 * 64 * 128 * (128 * 129 / 2 + 128 * 128))
    assert (counts.prefill_flops(c, [(1024, 256, False)])
            - counts.prefill_flops(c, [(512, 256, False)])
            ) == 4 * 2 * 64 * 128 * 256 * 512      # the full layers alone
    assert (counts.prefill_flops(c, [(128, 10, True)])
            - counts.prefill_flops(c, [(128, 10, False)])
            ) == 2 * 117964800
    with pytest.raises(ValueError, match="not quantized"):
        counts.decode_step_bytes(
            dict(c, chipbench={"quantization": "int8"}), 0)


def test_the_published_exaone_is_the_catalogs_row_and_cuts_what_it_says():
    c = bench_run.load_json(PUBLISHED)
    bench = c["chipbench"]
    assert bench["reduced"] == REDUCED
    assert set(bench["reduced_notes"]) == set(bench["reduced"])
    assert bench["chips"] == 1 and "EP-16" not in bench["source"]
    row = dict(
        first_k_dense_replace=1, head_dim=128, hidden_act="silu",
        hidden_size=6144, intermediate_size=18432,
        max_position_embeddings=262144, model_type="exaone_moe",
        moe_intermediate_size=2048, mtp_layer_types=["full_attention"],
        mtp_sliding_windows=[0], n_group=1, norm_topk_prob=True,
        num_attention_heads=64, num_experts_per_tok=8,
        num_key_value_heads=8, num_nextn_predict_layers=1,
        num_shared_experts=1, rms_norm_eps=1e-05,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
        routed_scaling_factor=2.5, scoring_func="sigmoid",
        sliding_window=128, sliding_window_pattern="LLLG",
        tie_word_embeddings=False, topk_group=1)
    assert {k: c[k] for k in row} == row
    assert "".join("L" if kind == "sliding_attention" else "G"
                   for kind in c["layer_types"]) == "LLLGLLLG"
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 7
    assert c["sliding_windows"] == [128, 128, 128, 0] * 2
    assert (c["num_hidden_layers"], c["published"]["num_hidden_layers"],
            c["num_experts"], c["published"]["num_experts"],
            c["vocab_size"], c["published"]["vocab_size"],
            c["expert_parallel_size"], c["expert_parallel_rank"]) == (
                8, 48, 8, 128, 19200, 153600, 16, 0)
    assert "four pipeline stages" in bench["deployment"]
    assert {"architectures", "post_norms", "head_norms", "rotary", "window",
            "router", "num_nextn_predict_layers", "weights",
            "tokenizer"} <= set(bench["assumed"])
    assert "modeling_exaone4.py" in bench["assumed"]["rotary"]
    assert "NOT made" in bench["assumed"]["num_nextn_predict_layers"]
    flags = bench["server_flags"]
    assert (flags["max-num-seqs"], flags["decode-steps"],
            flags["page-size"], flags["unified-step"]) == (
                128, 32, 128, "off")
    assert c["sliding_window"] % flags["page-size"] == 0
    assert "deferred-kv-writes" not in flags     # auto resolves it on
    cell = bench_run.find_cell(CELL)
    params = cell["traffic_params"]
    assert (params["clients"], params["ramp_s"], params["pool"],
            params["drain_limit_s"]) == (128, 45.0, 4096, 300)
    assert params["prompt_tokens"] == {"dist": "uniform", "min": 1024,
                                       "max": 4096}
    assert params["output_tokens"] == {"dist": "uniform", "min": 1024,
                                       "max": 3072}
    assert cell["sampling"] == {"temperature": 0.7, "top_p": 1.0}
    # The longest request fits the model length the server is given.
    assert flags["max-model-len"] > 4096 + 3072
    # Every prefill bucket of the chunk the traffic can ask for is
    # warmed by name, and the two-chunk path.
    from production_stack_tpu.engine.model_runner import prefill_buckets
    assert set(prefill_buckets(flags["prefill-chunk-size"])) <= set(
        cell["warm_prompt_tokens"])
    assert max(cell["warm_prompt_tokens"]) > flags["prefill-chunk-size"]
    bench_run.validate(cell)


# ---- the readers on a run made by hand -------------------------------------


def exaone_reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


@pytest.fixture
def exaone_traced(tmp_path):
    config = bench_run.load_json(PUBLISHED)
    t0 = 1000.0
    burst = "jit(_decode_burst_deferred_impl)/jit(main)/while/body"
    step = "jit(_step_impl)/jit(main)"
    decode = {"kind": "decode", "window": 32, "host_ms": 10,
              "device_wait_ms": 600, "decode_rows": 120,
              "state_slots_total": 136, "moe_experts_hit": 7.9,
              "moe_tokens_per_expert_mean": 7.5,
              "moe_tokens_per_expert_max": 15.0, "swa_keys_mean": 128.0}
    files = {
        "cell.json": {"t0_unix": t0, "seconds": 20.0,
                      "slice_unix": [t0 + 8.0, t0 + 11.0],
                      "config_as_run": config,
                      "version": {"device_kind": "TPU v5 lite"}},
        "records.json": [{"phase": "window", "first": 1.0, "last": 15.0,
                          "prompt_tokens": 2500, "tokens": 2000}] * 120,
        "steps.json": [
            dict(decode, step=1, ts=t0 + 8.5, state_slots_used=121),
            dict(decode, step=2, ts=t0 + 9.5, state_slots_used=124),
            {"step": 3, "ts": t0 + 10.0, "kind": "prefill",
             "prefill_rows": 2, "host_ms": 5, "device_wait_ms": 60,
             "state_slots_used": 126, "state_slots_total": 136},
            dict(decode, step=4, ts=t0 + 15.0, state_slots_used=110)],
        "trace_summary.json": {
            "window_s": 3.0, "busy_s": 2.9,
            "programs": {"_decode_burst_deferred_impl": {
                "count": 3, "seconds": 2.1, "whole_s": 0.7},
                "_step_impl": {"count": 1, "seconds": 0.06,
                               "whole_s": 0.06}},
            "scopes": {
                f"{burst}/swa_decode/paged_decode/pallas_call": {
                    "seconds": 0.25, "count": 576},
                f"{burst}/swa_decode/dot_general": {"seconds": 0.05,
                                                    "count": 1152},
                f"{burst}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.5, "count": 1344},
                f"{burst}/full_attn/paged_decode": {"seconds": 0.6,
                                                    "count": 192},
                f"{step}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.01, "count": 14},
                f"{step}/swa_prefill/pallas_call": {
                    "seconds": 0.0015, "count": 6},
                f"{step}/swa_prefill/gather": {"seconds": 0.0005,
                                               "count": 12}}},
    }
    for name, content in files.items():
        with open(tmp_path / name, "w") as f:
            json.dump(content, f)
    spans = [{"span": "engine_request", "request_id": f"r{i}", "events": [
        {"event": "prefill_chunk", "ts": t0 + 9.9, "start": 512,
         "tokens": 200 + 56 * i, "last": False}]} for i in range(2)]
    with open(tmp_path / "spans.jsonl", "w") as f:
        f.write("\n".join(map(json.dumps, spans)) + "\n")
    return RunFiles(str(tmp_path)), config


def test_the_two_new_shares_of_their_rooflines(exaone_traced):
    run, cfg = exaone_traced
    assert hybrid_slice.scope_seconds(run.trace, "swa_decode",
                                      "_decode_burst") == (
        pytest.approx(0.3), 1728)
    assert hybrid_slice.scope_seconds(run.trace, "swa_prefill",
                                      "_step_impl") == (
        pytest.approx(0.002), 18)
    # 2.1 s of the burst at 0.7 s an execution: 3 bursts, 96 steps.
    assert hybrid_slice.token_steps(run) == pytest.approx(96.0)
    # Two bursts stamped inside the slice, 120 rows; 6 windowed layers.
    moved = 120 * (524288 + 2 * 64 * 128 * 2) * 96 * 6
    assert exaone_reader("swa_decode_roofline").read(
        run) == pytest.approx(100 * moved / 819e9 / 0.3)
    # Chunks of 200 and 256 tokens, one prefill record, one execution.
    flops, moved = counts.swa_prefill(cfg, [200, 256])
    assert exaone_reader("swa_prefill_roofline").read(
        run) == pytest.approx(100 * 6 * moved / 819e9 / 0.002)
    # The readers the other cells brought serve this family's counts
    # unchanged: they ask the counts and name no family.
    live = 120 * (2500 + 2000 * (9.5 - 1.0) / 14.0)
    whole = counts.hybrid_decode_step_bytes(cfg, 120, 7.9, live)
    assert exaone_reader("hybrid_decode_roofline").read(
        run) == pytest.approx(100 * whole / 819e9 / (0.7 / 32), rel=1e-3)
    flops, moved = counts.moe_experts(cfg, 7.5 * 8, 7.9)
    assert exaone_reader("routed_experts_roofline").read(
        run) == pytest.approx(100 * moved * 96 * 7 / 819e9 / 0.5)
    assert exaone_reader("moe_expert_load").read(run) == pytest.approx(
        15 / 7.5)
    assert exaone_reader("state_slots_peak").read(run) == pytest.approx(
        100 * 126 / 136)
    for name in NEW_READERS + ["hybrid_decode_roofline",
                               "routed_experts_roofline"]:
        assert 0 < exaone_reader(name).read(run) < 100


def test_an_exaone_share_over_its_roofline_is_an_error_not_a_value(
        exaone_traced):
    run, _ = exaone_traced
    run.trace["scopes"] = {k: dict(v, seconds=v["seconds"] / 100)
                           for k, v in run.trace["scopes"].items()}
    for name in NEW_READERS:
        with pytest.raises(ValueError, match="roofline"):
            exaone_reader(name).read(run)


@pytest.mark.parametrize("name", NEW_READERS)
def test_an_exaone_run_without_the_names_or_a_trace_gives_nothing(
        exaone_traced, name, tmp_path):
    """A program with no scope of these names (the parent commit's), a
    family whose counts have no such function (another cell's), and a
    run that was not traced: nothing, and no error."""
    run, cfg = exaone_traced
    granite = bench_run.load_json(os.path.join(
        bench_run.BENCH, "configs", "granite-4.0-h-small-ep4.json"))
    run.cell["config_as_run"] = granite
    assert exaone_reader(name).read(run) is None
    run.cell["config_as_run"] = cfg
    run.trace["scopes"] = {"jit(_decode_burst_impl)/jit(main)/add":
                           {"seconds": 1.0, "count": 10}}
    run.trace["programs"] = {}
    assert exaone_reader(name).read(run) is None
    os.remove(tmp_path / "trace_summary.json")
    assert exaone_reader(name).read(RunFiles(str(tmp_path))) is None


def test_the_manifest_names_the_exaone_cell_and_its_two_shares():
    """By name and not by place: whatever later PRs append, this
    configuration, this cell and its metrics are found as they are."""
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    config, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == REDUCED
    assert config["file"] == f"chipbench/configs/{CONFIG}.json"
    assert config["source"] == bench_run.load_json(PUBLISHED)[
        "chipbench"]["source"]
    entry, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert entry == {
        "name": CELL, "config": CONFIG, "traffic": "decode-closed",
        "chips": 1, "why": bench_run.find_cell(CELL)["why"]}
    assert len(entry["why"]) <= 200 and "1/16 of EP-16's" in entry["why"]
    # The configuration's line is held to the same 200 characters as
    # the cell's (211 were refused before any run: CHANGES.md, PR 50).
    for line in (config["why"], config["source"]):
        assert 1 <= len(line) <= 200 and line.isprintable()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert (by_name[name]["unit"], by_name[name]["moves"],
                by_name[name]["source"], by_name[name]["layer"]) == (
            "%", "output_tok_s", "device_trace", "model + ops")
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m["workloads"]}
    assert listed == set(bench_run.find_cell(CELL)["per_layer"])
    assert len(listed) == 17
    assert {"moe_expert_load", "hybrid_decode_roofline",
            "routed_experts_roofline", "state_slots_peak"} <= listed
    # The other families' own shares are not this cell's.
    assert not {"ssd_decode_roofline", "ssm_decode_roofline",
                "gdn_decode_roofline", "mla_decode_roofline",
                "moe_experts_roofline", "decode_roofline"} & listed
    # The cells the benchmark had come before it, in their order, and
    # none takes four chips.
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) >= 7
    assert names[:7] == [
        "qwen2.5-3b.decode-closed", "qwen3-next-80b-a3b-ep4.decode-closed",
        "jamba2-3b.decode-closed", "lfm2-8b-a1b-ep4.decode-closed",
        "longcat-flash-omni-ep32.decode-closed",
        "glm-4.7-flash-pp8.decode-closed",
        "granite-4.0-h-small-ep4.decode-closed"]
    assert all(w["chips"] == 1 for w in manifest["workloads"])


# ---- the CPU rehearsal ------------------------------------------------------


def test_the_exaone_rehearsal_runs_end_to_end_on_the_cpu():
    """The real server behind the real router at the tiny size, the
    reference check, the window, the traced side and the result line,
    as ``test_rehearsal.py`` runs the other families'."""
    cell = "rehearsal-exaone"
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
    run = RunFiles(os.path.join(bench_run.STATE, "runs", cell))
    version = run.cell["version"]
    assert (version["family"], version["kv_writes"]) == ("exaone_moe",
                                                         "deferred")
    assert "conv_tails" not in version
    assert (version["sliding_window"], version["layer_types"]) == (
        16, ["sliding_attention", "sliding_attention", "full_attention",
             "sliding_attention"])
    # Three windowed layers' K and V rings [2, 16, 16], float32 here,
    # and one full layer's K and V a token.
    assert version["state_bytes_per_sequence"] == 3 * 2 * 2 * 16 * 16 * 4
    assert version["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    # A window's keys in sight on every decode record whose rows are
    # all past the window; never more.
    seen = [s["swa_keys_mean"] for s in run.window_steps
            if s.get("kind") == "decode" and "swa_keys_mean" in s]
    assert seen and max(seen) <= 16.0 and min(seen) > 8.0
