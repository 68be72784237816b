"""The Jamba family through the door: its reference against the
program's ``models/jamba.py`` served by the engine in float32 at tiny
widths on the CPU (prompts of several chunks, the deferred burst), the
tolerance against a coarser rounding and against each term left out,
its counts with the sums by hand at the published widths, and its
readers on a run directory made by hand.  Every file of the family is
new; none of the harness was edited for it."""

import dataclasses
import importlib
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import family, hybrid_slice, roofline, run as bench_run
from chipbench.counts import jamba_family as counts
from chipbench.runfiles import RunFiles

TINY = os.path.join(bench_run.BENCH, "rehearsal", "configs",
                    "tiny-jamba.json")
PUBLISHED = os.path.join(bench_run.BENCH, "configs", "jamba2-3b.json")
CELL = "jamba2-3b.decode-closed"


@pytest.fixture(scope="module")
def jamba():
    cfg = bench_run.load_json(TINY)
    assert family.name_of(cfg) == "jamba_family"
    reference = family.module("reference", cfg)
    assert reference.__name__ == "chipbench.reference.jamba_family"
    assert family.module("counts", cfg) is counts
    hf = {k: v for k, v in cfg.items() if k != "chipbench"}
    return cfg, reference, reference.program_model(hf, cfg["chipbench"])


def served_log_probs(cfg, prompt, answers, top):
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


def test_the_jamba_reference_agrees_with_the_program_in_float32(jamba):
    cfg, reference, model = jamba
    assert model.layer_is_mamba == (True, False, True, True)
    assert (model.num_heads, model.num_kv_heads) == (4, 1)
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], 150)
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
    assert max(diffs) < tolerance["max_abs_logprob_diff"] / 2
    assert np.mean(diffs) < tolerance["mean_abs_logprob_diff"] / 2
    assert tokens == np.argmax(got, -1).tolist()


def test_the_reference_is_float32_at_the_highest_precision_and_alone():
    path = os.path.join(bench_run.BENCH, "reference", "jamba_family.py")
    with open(path) as f:
        source = f.read()
    assert 'jax.default_matmul_precision("highest")' in source
    # Nothing of the program but the init's values, taken in
    # program_model alone.
    head, tail = source.split("def program_model")
    assert "production_stack_tpu" not in head.split('"""', 2)[2]
    assert tail.count("from production_stack_tpu") == 2
    assert "bfloat16" not in source.split('"""', 2)[2]


def test_a_long_prompt_in_blocks_of_queries_is_the_same(jamba, monkeypatch):
    _, reference, model = jamba
    tokens = np.random.default_rng(2).integers(0, 512, 90)
    whole = np.asarray(reference.log_probs(model, tokens, [50, 89]))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    blocks = np.asarray(reference.log_probs(model, tokens, [50, 89]))
    assert np.abs(blocks - whole).max() < 1e-5


def _differences(reference, model, other=None):
    tokens = np.random.default_rng(0).integers(0, 512, 120)
    positions = list(range(60, 120))
    want = np.asarray(reference.log_probs(model, tokens, positions))
    got = np.asarray(reference.log_probs(other or model, tokens, positions))
    top = np.argsort(-want, -1)[:, :6]
    diff = np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1))
    return diff.max(), diff.mean()


def _with_layers(model, change):
    return dataclasses.replace(
        model, layer=lambda i: change(dict(model.layer(i))))


def _zeroed(name):
    def change(w):
        if name in w:
            w[name] = jnp.zeros_like(w[name])
        return w
    return change


def _rounded(dtype):
    def fault(m):
        cast = lambda a: jnp.asarray(a, jnp.float32).astype(  # noqa: E731
            dtype).astype(jnp.float32)
        return dataclasses.replace(
            _with_layers(m, lambda w: {
                k: cast(v) if v.ndim == 2 else v for k, v in w.items()}),
            embed=cast(m.embed))
    return fault


# Weights changed: the model handed to the reference says the fault.
WEIGHT_FAULTS = {
    "float8_e4m3 matrices": _rounded(jnp.float8_e4m3fn),
    "D * xs left out": lambda m: _with_layers(m, _zeroed("D")),
    "the convolution's bias left out": lambda m: _with_layers(
        m, _zeroed("conv_bias")),
}


def _rope(x):
    """Rotate-half over the whole head, positions 0..T-1, theta 1e4."""
    t, _, d = x.shape
    inv_freq = 1e4 ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rotated * sin


# Equations changed: one function of the reference says the fault.
def _equation_faults(reference):
    real_norm, real_attn = reference.norm, reference.causal_attention
    return {
        "the three small norms left out": (
            "norm", lambda x, w, eps: (x if w.shape[-1] < 64
                                       else real_norm(x, w, eps))),
        "the silu(z) gate left out": ("output_gate", lambda y, z: y),
        "a rotary wrongly applied": (
            "causal_attention",
            lambda q, k, v: real_attn(_rope(q), _rope(k), v)),
    }


FAULTS = sorted(WEIGHT_FAULTS) + [
    "the three small norms left out", "the silu(z) gate left out",
    "a rotary wrongly applied"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_jamba_tolerance_fails_float8_and_each_term_left_out(
        jamba, fault, monkeypatch):
    """The control: the reference in the program's place, with its
    matrices rounded well below the float32 the configuration states,
    or with one term of the mathematics left out or put in."""
    cfg, reference, model = jamba
    if fault in WEIGHT_FAULTS:
        worst, mean = _differences(reference, model,
                                   WEIGHT_FAULTS[fault](model))
    else:
        want_tokens = np.random.default_rng(0).integers(0, 512, 120)
        positions = list(range(60, 120))
        want = np.asarray(reference.log_probs(model, want_tokens,
                                              positions))
        name, other = _equation_faults(reference)[fault]
        monkeypatch.setattr(reference, name, other)
        got = np.asarray(reference.log_probs(model, want_tokens,
                                             positions))
        top = np.argsort(-want, -1)[:, :6]
        diff = np.abs(np.take_along_axis(got, top, -1)
                      - np.take_along_axis(want, top, -1))
        worst, mean = diff.max(), diff.mean()
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert (worst > 3 * tolerance["max_abs_logprob_diff"]
            or mean > 3 * tolerance["mean_abs_logprob_diff"]), (worst, mean)


def test_the_same_jamba_differs_by_nothing(jamba):
    _, reference, model = jamba
    assert _differences(reference, model) == (0.0, 0.0)


# ---- the counts, by hand ----------------------------------------------------


def test_jamba_counts_by_hand():
    """At the published widths: the whole model, nothing reduced (the
    arithmetic of ISSUE 34's motivation)."""
    c = bench_run.load_json(PUBLISHED)
    assert [i for i, m in enumerate(counts.layer_is_mamba(c)) if not m] == [
        7, 21]
    assert (counts.num_mamba(c), counts.num_attention(c)) == (26, 2)
    assert (counts.d_inner(c), counts.head_dim(c)) == (5120, 128)
    # in_proj 2560 x 10240; conv 5120 x 4 + 5120; x_proj 5120 x 192;
    # three small norms 160 + 16 + 16; dt_proj 160 x 5120 + 5120;
    # A_log 5120 x 16; D 5120; out_proj 5120 x 2560.
    mamba = (2560 * 10240 + 5120 * 4 + 5120 + 5120 * 192 + 192
             + 160 * 5120 + 5120 + 5120 * 16 + 5120 + 5120 * 2560)
    assert counts.mamba_params(c) == mamba == 41_241_792
    # q and o 2560 x 2560 each, k and v 2560 x 128 each.
    attention = 2 * 2560 * 2560 + 2 * 2560 * 128
    assert counts.attention_params(c) == attention == 13_762_560
    shared = 3 * 2560 * 8192 + 2 * 2560
    assert counts.layer_shared_params(c) == shared == 62_919_680
    head = 2560 * 65536
    assert counts.head_params(c) == head == 167_772_160
    total = 26 * mamba + 2 * attention + 28 * shared + 2560 + head
    assert counts.total_params(c) == total == 3_029_337_472
    # Tied: a decode step reads the embedding once, as the head.
    assert counts.decode_params(c) == total
    assert counts.total_params(dict(c, tie_word_embeddings=False)) == (
        total + head)
    # One sequence's recurrent state: 26 x (327 680 B of h + 30 720 B
    # of tail); one token of K/V: 2 layers x 2 x 1 head x 128 x 2 B.
    assert counts.state_elements(c) == 5120 * 16
    assert counts.state_bytes_per_sequence(c) == 26 * (327_680 + 30_720)
    assert counts.state_bytes_per_sequence(c) == 9_318_400
    assert counts.kv_bytes_per_token(c) == 1024
    assert counts.decode_step_bytes(c, 1000) == 2 * total + 1000 * 1024
    assert roofline.decode_step_bytes(c, 0) == 6_058_674_944
    # 128 rows at 1250 tokens each: weights, state, K/V.
    step = counts.ssm_step_bytes(c, 128, 128 * 1250)
    assert step == 6_058_674_944 + 128 * 26 * 655_360 + 163_840_000
    assert step == 8_403_553_024
    assert counts.ssm_decode(c, 128) == (7 * 128 * 81920,
                                         2 * 128 * 81920 * 4)
    assert counts.ssm_prefill(c, [128, 100]) == (
        7 * 228 * 81920,
        2 * 2 * 81920 * 4 + 228 * (3 * 5120 + 2 * 16) * 2)
    per_token = 26 * mamba + 2 * attention + 28 * shared
    want = (2 * per_token * 4 + 26 * 7 * 4 * 81920
            + 4 * 2 * 20 * 128 * (4 * 10 + 10))
    assert roofline.prefill_flops(c, [(10, 4, False)]) == want
    assert roofline.prefill_flops(c, [(10, 4, True)]) == want + 2 * head
    with pytest.raises(ValueError):
        counts.decode_step_bytes(
            dict(c, chipbench={"quantization": "int8"}), 0)


def test_the_published_configuration_is_the_catalogs_row_and_cuts_nothing():
    c = bench_run.load_json(PUBLISHED)
    bench = c["chipbench"]
    assert bench["reduced"] == [] and bench["chips"] == 1
    row = dict(
        attn_layer_offset=7, attn_layer_period=14, expert_layer_offset=1,
        expert_layer_period=2, hidden_act="silu", hidden_size=2560,
        intermediate_size=8192, mamba_conv_bias=True, mamba_d_conv=4,
        mamba_d_state=16, mamba_dt_rank=160, mamba_expand=2,
        mamba_proj_bias=False, max_position_embeddings=262144,
        model_type="jamba", num_attention_heads=20, num_experts=1,
        num_experts_per_tok=1, num_hidden_layers=28, num_key_value_heads=1,
        num_logits_to_keep=1, rms_norm_eps=1e-06, sliding_window=None,
        tie_word_embeddings=True, use_mamba_kernels=True, vocab_size=65536)
    assert {k: c[k] for k in row} == row
    assert {"head_dim", "layer_order", "positions", "state_dtype",
            "weights", "tokenizer"} <= set(bench["assumed"])
    flags = bench["server_flags"]
    assert (flags["max-num-seqs"], flags["decode-steps"],
            flags["page-size"]) == (128, 32, 128)
    assert "deferred-kv-writes" not in flags     # auto resolves it on
    cell = bench_run.find_cell(CELL)
    params = cell["traffic_params"]
    assert (params["clients"], params["ramp_s"], params["pool"],
            params["drain_limit_s"]) == (128, 10.0, 2048, 240)
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


def reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


@pytest.fixture
def jamba_traced(tmp_path):
    config = bench_run.load_json(PUBLISHED)
    t0 = 1000.0
    burst = "jit(_decode_burst_deferred_impl)/jit(main)/while/body"
    step = "jit(_step_impl)/jit(main)"
    decode = {"kind": "decode", "window": 32, "host_ms": 10,
              "device_wait_ms": 400, "decode_rows": 120,
              "state_slots_total": 136}
    files = {
        "cell.json": {"t0_unix": t0, "seconds": 20.0,
                      "slice_unix": [t0 + 8.0, t0 + 11.0],
                      "config_as_run": config,
                      "version": {"device_kind": "TPU v5 lite"}},
        "records.json": [{"phase": "window", "first": 1.0, "last": 15.0,
                          "prompt_tokens": 160, "tokens": 1200}] * 120,
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
                "count": 6, "seconds": 2.4, "whole_s": 0.48},
                "_step_impl": {"count": 1, "seconds": 0.09,
                               "whole_s": 0.09}},
            "scopes": {
                f"{burst}/ssm_decode/ssm_decode_kernel/pallas_call": {
                    "seconds": 0.9, "count": 4160},
                f"{burst}/mqa_attn/dot_general": {"seconds": 0.1,
                                                  "count": 320},
                f"{step}/ssm_prefill/while/body/mul": {
                    "seconds": 0.04, "count": 3328},
                f"{step}/ssm_prefill/scatter": {"seconds": 0.01,
                                                "count": 26}}},
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


def test_the_scans_shares_of_their_rooflines(jamba_traced):
    run, cfg = jamba_traced
    assert hybrid_slice.scope_seconds(run.trace, "ssm_decode",
                                      "_decode_burst") == (0.9, 4160)
    assert hybrid_slice.scope_seconds(run.trace, "ssm_prefill",
                                      "_step_impl") == (0.05, 3354)
    # 2.4 s of the burst at 0.48 s an execution: 5 bursts, 160 steps.
    assert hybrid_slice.token_steps(run) == pytest.approx(160.0)
    # Two bursts stamped inside the slice, 120 rows each; 26 layers.
    moved = 2 * 120 * 81920 * 4 * 160 * 26
    assert reader("ssm_decode_roofline").read(run) == pytest.approx(
        100 * moved / 819e9 / 0.9)
    # Chunks of 100 and 128 tokens, one prefill record, one execution.
    flops, moved = counts.ssm_prefill(cfg, [100, 128])
    least = max(26 * flops / 197e12, 26 * moved / 819e9)
    assert reader("ssm_prefill_roofline").read(run) == pytest.approx(
        100 * least / 0.05)
    live = 120 * (160 + 1200 * (9.5 - 1.0) / 14.0)
    moved = counts.ssm_step_bytes(cfg, 120, live)
    assert reader("ssm_step_roofline").read(run) == pytest.approx(
        100 * moved / 819e9 / (0.48 / 32), rel=1e-3)
    assert reader("state_slots_peak").read(run) == pytest.approx(
        100 * 126 / 136)
    for name in ("ssm_decode_roofline", "ssm_prefill_roofline",
                 "ssm_step_roofline"):
        assert 0 < reader(name).read(run) < 100


def test_a_scan_share_over_its_roofline_is_an_error_not_a_value(jamba_traced):
    run, _ = jamba_traced
    run.trace["scopes"] = {k: dict(v, seconds=v["seconds"] / 100)
                           for k, v in run.trace["scopes"].items()}
    with pytest.raises(ValueError, match="roofline"):
        reader("ssm_decode_roofline").read(run)


@pytest.mark.parametrize("name", [
    "ssm_decode_roofline", "ssm_prefill_roofline", "ssm_step_roofline"])
def test_a_run_without_the_names_or_a_trace_gives_nothing(jamba_traced, name,
                                                          tmp_path):
    """The parent commit's program (no scope of these names, another
    family's counts) and a run that was not traced."""
    run, cfg = jamba_traced
    run.trace["scopes"] = {"jit(_decode_burst_impl)/jit(main)/add":
                           {"seconds": 1.0, "count": 10}}
    run.trace["programs"] = {}
    assert reader(name).read(run) is None
    os.remove(tmp_path / "trace_summary.json")
    assert reader(name).read(RunFiles(str(tmp_path))) is None


def test_another_familys_cell_gives_the_step_share_nothing(jamba_traced):
    """``ssm_step_roofline`` on a cell whose family counts no
    selective scan: nothing, and no error."""
    run, _ = jamba_traced
    other = bench_run.load_json(os.path.join(
        bench_run.BENCH, "configs", "qwen2.5-3b.json"))
    run.cell["config_as_run"] = other
    assert reader("ssm_step_roofline").read(run) is None


def test_the_manifest_names_the_cell_and_its_three_shares():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["configs"][-1]["name"] == "jamba2-3b"
    assert manifest["configs"][-1]["reduced"] == []
    assert manifest["workloads"][-1] == {
        "name": CELL, "config": "jamba2-3b", "traffic": "decode-closed",
        "chips": 1, "why": bench_run.find_cell(CELL)["why"]}
    mine = [m for m in manifest["per_layer"] if m["workloads"] == [CELL]]
    assert [m["name"] for m in mine] == [
        "ssm_decode_roofline", "ssm_prefill_roofline", "ssm_step_roofline"]
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m["workloads"]}
    assert listed == set(bench_run.find_cell(CELL)["per_layer"])
