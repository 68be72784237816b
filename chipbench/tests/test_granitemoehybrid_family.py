"""The Granite-MoE-hybrid family through the door: its reference against
the program's ``models/granitemoehybrid.py`` served by the engine in
float32 at tiny widths on the CPU (a prompt of three chunks, the
deferred burst through pages, slots and dense tails), the tolerance
against a coarser rounding and against terms left out, its counts with
the sums by hand at the published widths, its two readers on a run
directory made by hand, the manifest asked by name, and its CPU
rehearsal.  Every file of the family is new; none of the harness was
edited for it."""

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
from chipbench.counts import granitemoehybrid_family as counts
from chipbench.runfiles import RunFiles

TINY = os.path.join(bench_run.BENCH, "rehearsal", "configs",
                    "tiny-granite.json")
PUBLISHED = os.path.join(bench_run.BENCH, "configs",
                         "granite-4.0-h-small-ep4.json")
CONFIG = "granite-4.0-h-small-ep4"
CELL = CONFIG + ".decode-closed"
NEW_READERS = ["ssd_decode_roofline", "ssd_prefill_roofline"]


@pytest.fixture(scope="module")
def granite():
    cfg = bench_run.load_json(TINY)
    assert family.name_of(cfg) == "granitemoehybrid_family"
    reference = family.module("reference", cfg)
    assert reference.__name__ == (
        "chipbench.reference.granitemoehybrid_family")
    assert family.module("counts", cfg) is counts
    hf = {k: v for k, v in cfg.items() if k != "chipbench"}
    model = reference.program_model(hf, cfg["chipbench"])
    # Rank 0 of 2: experts 0..3 of 8.
    assert model.first_expert == 0 and model.layer(1)["e_gate"].shape[0] == 4
    assert model.layer(1)["w_router"].shape == (64, 8)
    return cfg, reference, model


def granite_served_log_probs(cfg, prompt, answers, top):
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


def test_the_granite_reference_agrees_with_the_program_in_float32(granite):
    """150 tokens in three chunks of 64, 64 and 22 (h and the tail
    carried twice through the slot, each chunk eight Mamba chunks of 8
    or fewer), then nine answers over three deferred bursts."""
    cfg, reference, model = granite
    prompt = np.random.default_rng(1).integers(0, 512, 150).tolist()
    tokens, served = granite_served_log_probs(cfg, prompt, 9, 5)
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


def test_the_granite_reference_is_float32_at_the_highest_precision_and_alone():
    path = os.path.join(bench_run.BENCH, "reference",
                        "granitemoehybrid_family.py")
    with open(path) as f:
        source = f.read()
    assert 'jax.default_matmul_precision("highest")' in source
    # Nothing of the program but the init's values, taken in
    # program_model alone; the recurrence token by token.
    head, tail = source.split("def program_model")
    assert "production_stack_tpu" not in head.split('"""', 2)[2]
    assert tail.count("from production_stack_tpu") == 2
    assert "bfloat16" not in source.split('"""', 2)[2]
    assert "jax.lax.scan" in head and "cumsum" not in head


def test_a_long_granite_prompt_in_blocks_of_queries_is_the_same(
        granite, monkeypatch):
    _, reference, model = granite
    tokens = np.random.default_rng(2).integers(0, 512, 90)
    whole = np.asarray(reference.log_probs(model, tokens, [50, 89]))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    blocks = np.asarray(reference.log_probs(model, tokens, [50, 89]))
    assert np.abs(blocks - whole).max() < 1e-5


# ---- the tolerance against a coarser rounding and terms left out -----------


def _granite_differences(reference, model, other=None):
    tokens = np.random.default_rng(0).integers(0, 512, 120)
    positions = list(range(60, 120))
    want = np.asarray(reference.log_probs(model, tokens, positions))
    got = np.asarray(reference.log_probs(other or model, tokens, positions))
    top = np.argsort(-want, -1)[:, :6]
    diff = np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1))
    return diff.max(), diff.mean()


def _granite_rounded(dtype):
    def fault(m):
        cast = lambda a: jnp.asarray(a, jnp.float32).astype(  # noqa: E731
            dtype).astype(jnp.float32)
        return dataclasses.replace(
            m, embed=cast(m.embed),
            layer=lambda i: {k: cast(v) if v.ndim >= 2 else v
                             for k, v in m.layer(i).items()})
    return fault


def _granite_without(name):
    def fault(m):
        def layer(i):
            w = dict(m.layer(i))
            if name in w:
                w[name] = jnp.zeros_like(w[name])
            return w
        return dataclasses.replace(m, layer=layer)
    return fault


# The model handed to the reference says the fault.
GRANITE_FAULTS = {
    "float8_e4m3 matrices": _granite_rounded(jnp.float8_e4m3fn),
    "D left out": _granite_without("D"),
    "the convolution's bias left out": _granite_without("conv_bias"),
    "the embedding's multiplier left out":
        lambda m: dataclasses.replace(m, embedding_multiplier=1.0),
    "the residual's multiplier left out":
        lambda m: dataclasses.replace(m, residual_multiplier=1.0),
    "the logits not scaled":
        lambda m: dataclasses.replace(m, logits_scaling=1.0),
}


@pytest.mark.parametrize("fault", sorted(GRANITE_FAULTS))
def test_the_granite_tolerance_fails_float8_and_a_term_left_out(
        granite, fault):
    """The control: the reference in the program's place, with its
    matrices rounded well below the float32 the configuration states,
    or with one term of the mathematics left out. (The attention's
    scale, a rotary, the gate, the gated norm, the router's
    normalisation and a gate on the shared expert are read on the
    program's side, at matrices scaled up until each is heard:
    tests/test_granitemoehybrid.py.)"""
    cfg, reference, model = granite
    worst, mean = _granite_differences(reference, model,
                                       GRANITE_FAULTS[fault](model))
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert (worst > 3 * tolerance["max_abs_logprob_diff"]
            or mean > 3 * tolerance["mean_abs_logprob_diff"]), (worst, mean)


def test_the_same_granite_differs_by_nothing(granite):
    _, reference, model = granite
    assert _granite_differences(reference, model) == (0.0, 0.0)


# ---- the counts, by hand ----------------------------------------------------


def test_granite_counts_by_hand():
    """The published widths, the cut's ten layers and 18 experts: every
    number of ISSUE 47's arithmetic."""
    c = bench_run.load_json(PUBLISHED)
    assert (counts.num_mamba(c), counts.num_attention(c),
            counts.num_expert_layers(c), counts.held_experts(c),
            counts.router_width(c)) == (9, 1, 10, 18, 72)
    assert counts.d_inner(c) == 128 * 64 == 8192
    assert counts.conv_channels(c) == 8192 + 2 * 128 == 8448
    assert counts.expert_params(c) == 3 * 4096 * 768 == 9437184
    assert counts.shared_params(c) == 3 * 4096 * 1536 == 18874368
    assert counts.router_params(c) == 4096 * 72 == 294912
    # in 68 681 728, out 33 554 432, the rest small.
    assert 4096 * (8192 + 8448 + 128) == 68681728
    assert counts.mamba_params(c) == (68681728 + 4 * 8448 + 8448 + 3 * 128
                                      + 8192 + 8192 * 4096) == 102286976
    assert counts.attention_params(c) == (2 * 4096 * 4096
                                          + 2 * 4096 * 1024) == 41943040
    mamba_layer = 18 * 9437184 + 294912 + 18874368 + 8192 + 102286976
    attention_layer = 18 * 9437184 + 294912 + 18874368 + 8192 + 41943040
    assert (mamba_layer, attention_layer) == (291333760, 230989824)
    assert counts.head_params(c) == 100352 * 4096 == 411041792
    assert counts.param_count(c) == (9 * mamba_layer + attention_layer
                                     + 411041792 + 4096) == 3264039552
    assert counts.param_count(c) * 2 == 6528079104
    assert counts.dense_params(c) == 3264039552 - 10 * 18 * 9437184
    # One attention layer of 8 KV heads of 128: 4096 B a token.
    assert counts.kv_bytes_per_token(c) == 2 * 8 * 128 * 2 == 4096
    # h 4 194 304 B a row a layer, the tail 50 688: 38.2 MB a row.
    assert counts.state_elements(c) * 4 == 128 * 8192 * 4 == 4194304
    assert counts.state_bytes_per_sequence(c) == 9 * (
        4194304 + 3 * 8448 * 2) == 38204928
    assert 137 * counts.state_bytes_per_sequence(c) == 5234075136
    # A decode step at 128 rows: the state walk 9.66e9 B, beside
    # 3.13e9 B of weights outside the experts and 18 experts a layer.
    flops, moved = counts.ssd_decode(c, 128)
    assert moved == 2 * 128 * 4194304 == 1073741824
    assert flops == 5 * 128 * 128 * 8192
    assert flops / 197e12 < moved / 819e9           # read-bound
    floor = counts.decode_step_bytes(c, 0)
    assert floor == counts.dense_params(c) * 2 == 3130692864
    assert counts.decode_step_bytes(c, 1000) == floor + 4096 * 1000
    whole = counts.hybrid_decode_step_bytes(c, 128, 18, 250000)
    assert whole == (floor + 4096 * 250000 + 10 * 18 * 9437184 * 2
                     + 9 * 1073741824) == 17215755520
    assert 9 * 1073741824 / whole > 0.5             # half of the step
    flops, moved = counts.moe_experts(c, 320, 17.8)
    assert flops == 2 * 320 * 9437184
    assert moved == 17.8 * 9437184 * 2 + 320 * 2 * 4096 * 2
    # Two prompt chunks of 128 and 100 tokens in one layer.
    flops, moved = counts.ssd_prefill(c, [128, 100])
    assert flops == 5 * 228 * 128 * 8192
    assert moved == 2 * 2 * 4194304 + 228 * (2 * 8192 + 2 * 128 + 128) * 2
    assert flops / 197e12 < moved / 819e9           # read-bound
    # Prefill: 2 a weight a token, the token's held choices at their
    # expected share 10 x 18 / 72.
    per_token = (9 * 102286976 + 41943040
                 + 10 * (294912 + 18874368 + 8192 + 2.5 * 9437184))
    assert counts.prefill_flops(c, [(0, 128, False)]) == pytest.approx(
        2 * per_token * 128 + 9 * 5 * 128 * 128 * 8192
        + 4 * 32 * 128 * (128 * 129 / 2))
    assert (counts.prefill_flops(c, [(128, 10, True)])
            - counts.prefill_flops(c, [(128, 10, False)])
            ) == 2 * 411041792
    with pytest.raises(ValueError, match="not quantized"):
        counts.decode_step_bytes(
            dict(c, chipbench={"quantization": "int8"}), 0)


def test_the_published_granite_is_the_catalogs_row_and_cuts_what_it_says():
    c = bench_run.load_json(PUBLISHED)
    bench = c["chipbench"]
    assert bench["reduced"] == ["num_hidden_layers", "layer_types",
                                "num_local_experts"]
    assert set(bench["reduced_notes"]) == set(bench["reduced"])
    assert bench["chips"] == 1 and "EP-4" not in bench["source"]
    row = dict(
        attention_bias=False, attention_multiplier=0.0078125,
        embedding_multiplier=12, hidden_act="silu", hidden_size=4096,
        intermediate_size=768, logits_scaling=16, mamba_chunk_size=256,
        mamba_conv_bias=True, mamba_d_conv=4, mamba_d_head=64,
        mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
        mamba_n_heads=128, mamba_proj_bias=False,
        max_position_embeddings=131072, model_type="granitemoehybrid",
        normalization_function="rmsnorm", num_attention_heads=32,
        num_experts_per_tok=10, num_key_value_heads=8,
        position_embedding_type="nope", residual_multiplier=0.22,
        rms_norm_eps=1e-05, rope_scaling=None, rope_theta=10000,
        shared_intermediate_size=1536, tie_word_embeddings=True,
        vocab_size=100352)
    assert {k: c[k] for k in row} == row
    assert "".join(kind[0] for kind in c["layer_types"]) == "mmmmmammmm"
    assert (c["num_hidden_layers"], c["published"]["num_hidden_layers"],
            c["num_local_experts"], c["published"]["num_local_experts"],
            c["expert_parallel_size"], c["expert_parallel_rank"]) == (
                10, 40, 18, 72, 4, 0)
    assert "four pipeline stages" in bench["deployment"]
    assert {"architectures", "head_dim", "expert_width", "state_dtype",
            "weights", "tokenizer"} <= set(bench["assumed"])
    assert "float32" in bench["assumed"]["state_dtype"]
    flags = bench["server_flags"]
    assert (flags["max-num-seqs"], flags["decode-steps"],
            flags["page-size"], flags["unified-step"]) == (
                128, 32, 128, "off")
    assert "deferred-kv-writes" not in flags     # auto resolves it on
    cell = bench_run.find_cell(CELL)
    params = cell["traffic_params"]
    assert (params["clients"], params["ramp_s"], params["pool"]) == (
        128, 30.0, 4096)
    assert params["prompt_tokens"] == {"dist": "uniform", "min": 256,
                                       "max": 2048}
    assert params["output_tokens"] == {"dist": "uniform", "min": 512,
                                       "max": 2048}
    assert cell["sampling"] == {"temperature": 0.7, "top_p": 1.0}
    # The longest request fits the model length the server is given.
    assert flags["max-model-len"] > 2048 + 2048
    # Every prefill bucket of the chunk the traffic can ask for is
    # warmed by name.
    from production_stack_tpu.engine.model_runner import prefill_buckets
    assert set(prefill_buckets(flags["prefill-chunk-size"])) <= set(
        cell["warm_prompt_tokens"])
    bench_run.validate(cell)


# ---- the readers on a run made by hand -------------------------------------


def granite_reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


@pytest.fixture
def granite_traced(tmp_path):
    config = bench_run.load_json(PUBLISHED)
    t0 = 1000.0
    burst = "jit(_decode_burst_deferred_impl)/jit(main)/while/body"
    step = "jit(_step_impl)/jit(main)"
    decode = {"kind": "decode", "window": 32, "host_ms": 10,
              "device_wait_ms": 900, "decode_rows": 120,
              "state_slots_total": 136, "moe_experts_hit": 17.0,
              "moe_tokens_per_expert_mean": 16.5,
              "moe_tokens_per_expert_max": 29.0}
    files = {
        "cell.json": {"t0_unix": t0, "seconds": 20.0,
                      "slice_unix": [t0 + 8.0, t0 + 11.0],
                      "config_as_run": config,
                      "version": {"device_kind": "TPU v5 lite"}},
        "records.json": [{"phase": "window", "first": 1.0, "last": 15.0,
                          "prompt_tokens": 1100, "tokens": 1200}] * 120,
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
                "count": 3, "seconds": 2.7, "whole_s": 0.9},
                "_step_impl": {"count": 1, "seconds": 0.12,
                               "whole_s": 0.12}},
            "scopes": {
                f"{burst}/ssd_decode/ssd_decode_kernel/pallas_call": {
                    "seconds": 1.3, "count": 864},
                f"{burst}/ssd_decode/exp": {"seconds": 0.1, "count": 864},
                f"{burst}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.5, "count": 1920},
                f"{burst}/nope_attn/paged_decode": {"seconds": 0.1,
                                                    "count": 96},
                f"{step}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.02, "count": 20},
                f"{step}/ssd_prefill/dot_general": {
                    "seconds": 0.006, "count": 36},
                f"{step}/ssd_prefill/scatter": {"seconds": 0.002,
                                                "count": 9}}},
    }
    for name, content in files.items():
        with open(tmp_path / name, "w") as f:
            json.dump(content, f)
    spans = [{"span": "engine_request", "request_id": f"r{i}", "events": [
        {"event": "prefill_chunk", "ts": t0 + 9.9, "start": 128,
         "tokens": 100 + 28 * i, "last": True}]} for i in range(2)]
    with open(tmp_path / "spans.jsonl", "w") as f:
        f.write("\n".join(map(json.dumps, spans)) + "\n")
    return RunFiles(str(tmp_path)), config


def test_the_two_new_shares_of_their_rooflines(granite_traced):
    run, cfg = granite_traced
    assert hybrid_slice.scope_seconds(run.trace, "ssd_decode",
                                      "_decode_burst") == (
        pytest.approx(1.4), 1728)
    assert hybrid_slice.scope_seconds(run.trace, "ssd_prefill",
                                      "_step_impl") == (
        pytest.approx(0.008), 45)
    # 2.7 s of the burst at 0.9 s an execution: 3 bursts, 96 steps.
    assert hybrid_slice.token_steps(run) == pytest.approx(96.0)
    # Two bursts stamped inside the slice, 120 rows; 9 Mamba layers.
    moved = 2 * 120 * 4194304 * 96 * 9
    assert granite_reader("ssd_decode_roofline").read(
        run) == pytest.approx(100 * moved / 819e9 / 1.4)
    # Chunks of 100 and 128 tokens, one prefill record, one execution.
    flops, moved = counts.ssd_prefill(cfg, [100, 128])
    assert granite_reader("ssd_prefill_roofline").read(
        run) == pytest.approx(100 * 9 * moved / 819e9 / 0.008)
    # The readers the other cells brought serve this family's counts
    # unchanged: they ask the counts and name no family.
    live = 120 * (1100 + 1200 * (9.5 - 1.0) / 14.0)
    whole = counts.hybrid_decode_step_bytes(cfg, 120, 17.0, live)
    assert granite_reader("hybrid_decode_roofline").read(
        run) == pytest.approx(100 * whole / 819e9 / (0.9 / 32), rel=1e-3)
    flops, moved = counts.moe_experts(cfg, 16.5 * 18, 17.0)
    assert granite_reader("routed_experts_roofline").read(
        run) == pytest.approx(100 * moved * 96 * 10 / 819e9 / 0.5)
    assert granite_reader("moe_expert_load").read(run) == pytest.approx(
        29 / 16.5)
    assert granite_reader("state_slots_peak").read(run) == pytest.approx(
        100 * 126 / 136)
    for name in NEW_READERS + ["hybrid_decode_roofline",
                               "routed_experts_roofline"]:
        assert 0 < granite_reader(name).read(run) < 100


def test_a_granite_share_over_its_roofline_is_an_error_not_a_value(
        granite_traced):
    run, _ = granite_traced
    run.trace["scopes"] = {k: dict(v, seconds=v["seconds"] / 100)
                           for k, v in run.trace["scopes"].items()}
    for name in NEW_READERS:
        with pytest.raises(ValueError, match="roofline"):
            granite_reader(name).read(run)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_granite_run_without_the_names_or_a_trace_gives_nothing(
        granite_traced, name, tmp_path):
    """A program with no scope of these names (the parent commit's), a
    family whose counts have no such function (another cell's), and a
    run that was not traced: nothing, and no error."""
    run, cfg = granite_traced
    jamba = bench_run.load_json(os.path.join(
        bench_run.BENCH, "configs", "jamba2-3b.json"))
    run.cell["config_as_run"] = jamba
    assert granite_reader(name).read(run) is None
    run.cell["config_as_run"] = cfg
    run.trace["scopes"] = {"jit(_decode_burst_impl)/jit(main)/add":
                           {"seconds": 1.0, "count": 10}}
    run.trace["programs"] = {}
    assert granite_reader(name).read(run) is None
    os.remove(tmp_path / "trace_summary.json")
    assert granite_reader(name).read(RunFiles(str(tmp_path))) is None


def test_the_manifest_names_the_granite_cell_and_its_two_shares():
    """By name and not by place: whatever later PRs append, this
    configuration, this cell and its metrics are found as they are."""
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    config, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_local_experts"]
    assert config["file"] == f"chipbench/configs/{CONFIG}.json"
    assert config["source"] == bench_run.load_json(PUBLISHED)[
        "chipbench"]["source"]
    entry, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert entry == {
        "name": CELL, "config": CONFIG, "traffic": "decode-closed",
        "chips": 1, "why": bench_run.find_cell(CELL)["why"]}
    assert len(entry["why"]) <= 200 and "a quarter of" in entry["why"]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert (by_name[name]["unit"], by_name[name]["moves"],
                by_name[name]["source"], by_name[name]["layer"]) == (
            "%", "output_tok_s", "device_trace", "model + ops")
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m["workloads"]}
    assert listed == set(bench_run.find_cell(CELL)["per_layer"])
    assert {"moe_expert_load", "hybrid_decode_roofline",
            "routed_experts_roofline", "state_slots_peak"} <= listed
    # The other families' own shares are not this cell's.
    assert not {"ssm_decode_roofline", "gdn_decode_roofline",
                "moe_experts_roofline", "decode_roofline"} & listed
    # The cells the benchmark had come before it, in their order.
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) >= 6
    assert names[:6] == [
        "qwen2.5-3b.decode-closed", "qwen3-next-80b-a3b-ep4.decode-closed",
        "jamba2-3b.decode-closed", "lfm2-8b-a1b-ep4.decode-closed",
        "longcat-flash-omni-ep32.decode-closed",
        "glm-4.7-flash-pp8.decode-closed"]


# ---- the CPU rehearsal ------------------------------------------------------


def test_the_granite_rehearsal_runs_end_to_end_on_the_cpu():
    """The real server behind the real router at the tiny size, the
    reference check, the window, the traced side and the result line,
    as ``test_rehearsal.py`` runs the other families'."""
    cell = "rehearsal-granite"
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
    assert (version["family"], version["kv_writes"],
            version["conv_tails"]) == ("granitemoehybrid", "deferred",
                                       "burst")
    # Three Mamba layers' [16, 128] h and [3, 160] tail, float32 here.
    assert version["state_bytes_per_sequence"] == 3 * (16 * 128 + 3 * 160) * 4
