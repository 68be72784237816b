"""The SDAR-MoE family through the door: its reference against the
program's ``models/sdar_moe.py`` served by the engine in float32 at tiny
widths on the CPU (a prompt in chunks of whole blocks that yields no
token, then blocks denoised over pages and tails and stored), the
tolerance against a coarser rounding, its counts with the sums by hand
at the published widths and against what the program's init makes, the
configuration against the catalog's row, its four readers (and the ones
it shares) on a run directory made by hand, the manifest's rules for
its entries, and its CPU rehearsal.  Every file of the family is new;
none of the harness was edited for it."""

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
from chipbench.counts import sdar_family as sdar_counts
from chipbench.runfiles import RunFiles

SDAR_TINY = os.path.join(bench_run.BENCH, "rehearsal", "configs",
                         "tiny-sdar.json")
SDAR_PUBLISHED = os.path.join(bench_run.BENCH, "configs",
                              "sdar-30b-a3b-pp8.json")
SDAR_CONFIG = "sdar-30b-a3b-pp8"
SDAR_CELL = SDAR_CONFIG + ".decode-closed"
SDAR_READERS = ["diffusion_tokens_per_pass", "diffusion_store_share",
                "block_attention_roofline", "unmask_roofline"]
SDAR_SHARED = ["host_share", "rows_per_step", "kv_pages_peak",
               "decode_step_ms", "window_compiles", "device_idle",
               "hbm_peak", "loop_host_ms", "dispatch_prep_ms", "emit_ms",
               "host_idle", "moe_expert_load", "routed_experts_roofline",
               "hybrid_decode_roofline"]


@pytest.fixture(scope="module")
def sdar():
    cfg = bench_run.load_json(SDAR_TINY)
    assert family.name_of(cfg) == "sdar_family"
    reference = family.module("reference", cfg)
    assert reference.__name__ == "chipbench.reference.sdar_family"
    assert family.module("counts", cfg) is sdar_counts
    hf = {k: v for k, v in cfg.items() if k != "chipbench"}
    model = reference.program_model(hf, cfg["chipbench"])
    assert (model.num_layers, model.block, model.steps, model.remasking,
            model.mask_id) == (2, 4, 2, "sequential", 511)
    assert model.layer(1)["e_gate"].shape[0] == 8
    return cfg, reference, model


def sdar_served(cfg, prompt, answers, top):
    """What the program says: the engine on the configuration's random
    weights, the prompt in chunks of 32, bursts of two blocks."""
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
                                  prefill_chunk_size=32, decode_steps=6,
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


@pytest.mark.parametrize("length", [70, 71, 73])
def test_the_sdar_reference_agrees_with_the_program_in_float32(sdar,
                                                               length):
    """The harness's own comparison: prompt and answers alone go to
    ``log_probs``; a prompt of three chunks with every kind of
    remainder, eight answers over three blocks."""
    cfg, reference, model = sdar
    prompt = np.random.default_rng(length).integers(0, 512, length)
    tokens, served = sdar_served(cfg, prompt, 8, 5)
    want = np.asarray(reference.log_probs(
        model, list(prompt) + tokens, list(range(length - 1, length + 7))))
    assert tokens == [int(t) for t in want.argmax(-1)]
    diffs = [abs(lp - want[j, tid]) for j, top in enumerate(served)
             for tid, lp in top.items()]
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert max(diffs) < tolerance["max_abs_logprob_diff"] / 3
    assert np.mean(diffs) < tolerance["mean_abs_logprob_diff"] / 3


def test_the_sdar_reference_is_float32_materialised_and_alone():
    path = os.path.join(bench_run.BENCH, "reference", "sdar_family.py")
    with open(path) as f:
        source = f.read()
    code = source.split('"""', 2)[2]
    assert code.count('jax.default_matmul_precision("highest")') == 2
    head, tail = code.split("def program_model")
    assert "production_stack_tpu" not in head
    assert tail.count("from production_stack_tpu") == 2
    assert "bfloat16" not in code
    for word in ("page", "tail", "pallas", "kv_cache", "unmask_block"):
        assert word not in code.lower(), word
    for said in ("Departures from the published model and loop",
                 "only masked places are\never committed",
                 "ties in confidence go to the leftmost"):
        assert said in source, said


# ---- the tolerance against a coarser rounding ---------------------------------


def _sdar_differences(reference, model, other=None):
    tokens = [int(t) for t in
              np.random.default_rng(0).integers(0, 512, 90)]
    positions = list(range(69, 89))
    want = np.asarray(reference.log_probs(model, tokens, positions))
    got = np.asarray(reference.log_probs(other or model, tokens, positions))
    top = np.argsort(-want, -1)[:, :6]
    diff = np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1))
    return [diff.max(), diff.mean()]


def test_the_sdar_tolerance_fails_float8(sdar):
    """The control: the reference in the program's place with its
    matrices rounded to float8_e4m3, well below the float32 the tiny
    configuration states, leaves the limits by over three times. (Each
    term of the equations left out or put in: tests/test_sdar_moe.py
    and tests/test_sdar_moe_engine.py, against the program itself.)"""
    cfg, reference, model = sdar
    cast = lambda a: jnp.asarray(a, jnp.float32).astype(  # noqa: E731
        jnp.float8_e4m3fn).astype(jnp.float32)
    layer = model.layer
    coarse = dataclasses.replace(
        model, embed=cast(model.embed), lm_head=cast(model.lm_head),
        layer=lambda i: {k: cast(v) if v.ndim >= 2 else v
                         for k, v in layer(i).items()})
    worst, mean = _sdar_differences(reference, model, coarse)
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert worst > 3 * tolerance["max_abs_logprob_diff"]
    assert mean > 3 * tolerance["mean_abs_logprob_diff"]
    assert _sdar_differences(reference, model) == [0.0, 0.0]


# ---- the counts, by hand -----------------------------------------------------


def test_sdar_counts_by_hand():
    cfg = bench_run.load_json(SDAR_PUBLISHED)
    c = sdar_counts
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert attention == 18_874_368
    assert c.attention_params(cfg) == attention + 256
    assert c.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    assert c.router_params(cfg) == 262_144
    assert c.head_params(cfg) == 151_936 * 2048 == 311_164_928
    layer = attention + 256 + 4096 + 262_144 + 128 * 4_718_592
    assert layer == 623_120_640
    assert c.param_count(cfg) == 6 * layer + 2 * 311_164_928 + 2048 \
        == 4_361_055_744
    assert c.param_count({**cfg, "num_hidden_layers": 48}) \
        == 30_532_122_624
    assert c.kv_bytes_per_token(cfg) == 6 * 2 * 4 * 128 * 2 == 12_288
    assert 128 * c.kv_bytes_per_token(cfg) == 1_572_864   # a page
    assert (c.block(cfg), c.denoise_share(cfg)) == (4, 2 / 3)
    assert (c.num_expert_layers(cfg), c.held_experts(cfg)) == (6, 128)
    # One pass at 256 rows and 123k live tokens: the floor, then 128
    # experts hit in each of six layers.
    live = 123_000.0
    floor = ((6 * (attention + 256 + 262_144) + 13 * 2048
              + 2 / 3 * 311_164_928) * 2 + 12_288 * live)
    assert c.decode_step_bytes(cfg, live) == pytest.approx(floor)
    whole = c.hybrid_decode_step_bytes(cfg, 256, 128.0, live)
    assert whole == pytest.approx(floor + 6 * 128 * 4_718_592 * 2)
    assert 9.0e9 < whole < 9.6e9
    flops, moved = c.moe_experts(cfg, 1024 * 8, 128.0)
    assert flops == 2.0 * 8192 * 4_718_592
    assert moved == 128 * 4_718_592 * 2 + 8192 * 2 * 2048 * 2
    # 64 tokens an expert: the experts are still read-bound on a v5e
    # (ridge 240), by a factor of under four.
    assert 3.0 < (moved / 819e9) / (flops / 197e12) < 4.0
    flops, moved = c.block_attention(cfg, 256, live)
    assert flops == 4.0 * 4096 * 4 * (live + 1024)
    assert moved == 2048 * (live + 1024) + 256 * 4 * 2 * 4096 * 2
    assert moved / 819e9 > flops / 197e12                 # read-bound
    flops, moved = c.unmask(cfg, 256)
    assert moved == 256 * 4 * 151_936 * 4 == 622_329_856
    assert flops == 4.0 * 256 * 4 * 151_936
    # A prefill chunk of 128 at position 128, under sight by block.
    per_token = 6 * (attention + 256 + 262_144 + 8 * 4_718_592)
    attended = 128 * 128 + sum((i | 3) + 1 for i in range(128))
    assert attended == 128 * 128 + 128 * 132 // 2
    assert c.prefill_flops(cfg, [(128, 128, True)]) == pytest.approx(
        2.0 * per_token * 128 + 4.0 * 6 * 4096 * attended)
    with pytest.raises(ValueError, match="not quantized"):
        c.decode_step_bytes(
            {**cfg, "chipbench": {**cfg["chipbench"],
                                  "quantization": "int8"}}, 0)


def test_the_sdar_count_is_what_the_programs_init_makes():
    import jax
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models import sdar_moe
    for path in (SDAR_TINY, SDAR_PUBLISHED):
        cfg = bench_run.load_json(path)
        config = ModelConfig.from_hf_config(
            {k: v for k, v in cfg.items() if k != "chipbench"})
        shapes = jax.eval_shape(
            lambda key: sdar_moe.init_params(config, key),
            jax.random.PRNGKey(0))
        made = sum(int(np.prod(s.shape)) for s in shapes.values())
        assert made == sdar_counts.param_count(cfg), path
        pages = config.page_cache
        assert (pages.entries * pages.planes * pages.heads * pages.width
                * 2 == sdar_counts.kv_bytes_per_token(cfg))


def test_the_published_sdar_is_the_catalogs_row_cut_in_one_key():
    c = bench_run.load_json(SDAR_PUBLISHED)
    bench = c["chipbench"]
    assert bench["reduced"] == ["num_hidden_layers"]
    assert bench["chips"] == 1
    assert bench["family"] == "sdar_family"
    row = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128,
        hidden_act="silu", hidden_size=2048, intermediate_size=6144,
        max_position_embeddings=32768, max_window_layers=48,
        mlp_only_layers=[], model_type="sdar_moe",
        moe_intermediate_size=768, norm_topk_prob=True,
        num_attention_heads=32, num_experts=128, num_experts_per_tok=8,
        num_key_value_heads=4, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=1000000, sliding_window=None,
        tie_word_embeddings=False, use_sliding_window=False,
        vocab_size=151936)
    assert {k: c[k] for k in row} == row
    own = {"diffusion_block_length": 4, "mask_token_id": 151669,
           "diffusion_steps": 2, "diffusion_remasking": "sequential",
           "diffusion_confidence_threshold": 0.9}
    assert {k: c[k] for k in own} == own
    assert set(c) == set(row) | set(own) | {
        "architectures", "num_hidden_layers", "published", "chipbench"}
    assert c["num_hidden_layers"] == 6
    assert c["published"] == {"num_hidden_layers": 48}
    assert c["architectures"] == ["SDARMoeForCausalLM"]
    assert {"architectures", "diffusion_block_length", "mask_token_id",
            "head_row", "diffusion_steps", "diffusion_remasking",
            "head_norms", "weights", "tokenizer"} <= set(bench["assumed"])
    flags = bench["server_flags"]
    assert (flags["max-num-seqs"], flags["decode-steps"],
            flags["page-size"], flags["max-model-len"],
            flags["prefill-chunk-size"], flags["prefill-batch-size"],
            flags["unified-step"]) == (256, 30, 128, 2048, 128, 16, "off")
    # 256 rows at the traffic's longest (256 + 1024) fit the pages.
    assert flags["num-pages"] * flags["page-size"] >= 256 * 1280
    # No flag names the mechanism: the configuration's own keys do.
    assert not {"deferred-kv-writes", "draft-module"} & set(flags)
    cell = bench_run.find_cell(SDAR_CELL)
    assert cell["traffic_kind"] == "closed_clients"
    assert cell["traffic_params"] == {
        "clients": 256, "ramp_s": 30.0, "pool": 4096,
        "prompt_tokens": {"dist": "uniform", "min": 64, "max": 256},
        "output_tokens": {"dist": "uniform", "min": 256, "max": 1024},
        "drain_limit_s": 300}
    assert cell["sampling"] == {
        "temperature": 0.7, "top_p": 1.0,
        "remasking_strategy": "low_confidence_static",
        "denoising_steps": 2}
    assert cell["warm_prompt_tokens"] == [16, 32, 64, 128, 256]
    assert cell["end_to_end"] == ["output_tok_s", "setup_s"]
    assert cell["per_layer"] == SDAR_SHARED + SDAR_READERS
    assert 256 + 1024 <= flags["max-model-len"]
    from production_stack_tpu.engine.model_runner import prefill_buckets
    assert set(prefill_buckets(flags["prefill-chunk-size"])) <= set(
        cell["warm_prompt_tokens"])
    bench_run.validate(cell)
    tolerance = bench["reference_tolerance"]
    assert 0 < tolerance["mean_abs_logprob_diff"] \
        < tolerance["max_abs_logprob_diff"] < 2


# ---- the readers on a run made by hand ---------------------------------------


def sdar_reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


@pytest.fixture
def sdar_traced(tmp_path):
    config = bench_run.load_json(SDAR_PUBLISHED)
    t0 = 1000.0
    burst = "jit(_decode_burst_block_impl)/jit(main)/while/body"
    decode = {"kind": "decode", "window": 30, "host_ms": 10,
              "device_wait_ms": 440, "decode_rows": 250,
              "moe_experts_hit": 128.0, "moe_tokens_per_expert_mean": 62.5,
              "moe_tokens_per_expert_max": 90.0,
              "denoise_passes": 20, "store_passes": 10, "blocks": 2500,
              "committed": 9800}
    files = {
        "cell.json": {"t0_unix": t0, "seconds": 20.0,
                      "slice_unix": [t0 + 8.0, t0 + 11.0],
                      "config_as_run": config,
                      "version": {"device_kind": "TPU v5 lite"}},
        "records.json": [{"phase": "window", "first": 1.0, "last": 15.0,
                          "prompt_tokens": 160, "tokens": 640}] * 250,
        "steps.json": [
            dict(decode, step=1, ts=t0 + 8.5),
            dict(decode, step=2, ts=t0 + 9.5),
            {"step": 3, "ts": t0 + 10.0, "kind": "prefill",
             "prefill_rows": 2, "host_ms": 5, "device_wait_ms": 60},
            dict(decode, step=4, ts=t0 + 15.0, committed=9000,
                 decode_rows=240)],
        "trace_summary.json": {
            "window_s": 3.0, "busy_s": 2.9,
            "programs": {"_decode_burst_block_impl": {
                "count": 6, "seconds": 2.7, "whole_s": 0.45},
                "_step_impl": {"count": 1, "seconds": 0.06,
                               "whole_s": 0.06}},
            "scopes": {
                f"{burst}/while/body/moe_experts/gmm/pallas_call": {
                    "seconds": 1.2, "count": 2160},
                f"{burst}/cond/moe_experts/gmm/pallas_call": {
                    "seconds": 0.6, "count": 1080},
                f"{burst}/while/body/block_attention/pallas_call": {
                    "seconds": 0.4, "count": 720},
                f"{burst}/cond/block_attention/pallas_call": {
                    "seconds": 0.2, "count": 360},
                f"{burst}/while/body/unmask_block/reduce": {
                    "seconds": 0.24, "count": 960},
                "jit(_step_impl)/jit(main)/moe_experts/gmm/pallas_call": {
                    "seconds": 0.03, "count": 12}}},
    }
    for name, content in files.items():
        with open(tmp_path / name, "w") as f:
            json.dump(content, f)
    return RunFiles(str(tmp_path)), config


def test_the_sdar_readers_on_a_run_made_by_hand(sdar_traced):
    run, cfg = sdar_traced
    c = sdar_counts
    # Every burst record of the window, by rows x passes.
    assert sdar_reader("diffusion_tokens_per_pass").read(run) == \
        pytest.approx((2 * 9800 + 9000) / (2 * 250 * 30 + 240 * 30))
    assert sdar_reader("diffusion_store_share").read(run) == \
        pytest.approx(100 * 30 / 90)
    # The names hold the denoising passes' calls and the store passes'.
    assert hybrid_slice.scope_seconds(run.trace, "block_attention",
                                      "_decode_burst") == (
        pytest.approx(0.6), 1080)
    # 2.7 s of the burst at 0.45 s an execution: 6 bursts, 180 passes.
    assert hybrid_slice.token_steps(run) == pytest.approx(180.0)
    live = 250 * (160 + 640 * (9.5 - 1.0) / 14.0)
    flops, moved = c.block_attention(cfg, 250, live)
    assert sdar_reader("block_attention_roofline").read(run) == \
        pytest.approx(100 * moved * 180 * 6 / 819e9 / 0.6, rel=1e-3)
    flops, moved = c.unmask(cfg, 250)
    assert sdar_reader("unmask_roofline").read(run) == pytest.approx(
        100 * moved * 180 * (20 / 30) / 819e9 / 0.24, rel=1e-3)
    # The shared readers take this family's counts: a pass's.
    flops, moved = c.moe_experts(cfg, 62.5 * 128, 128.0)
    assert sdar_reader("routed_experts_roofline").read(run) == \
        pytest.approx(100 * moved * 180 * 6 / 819e9 / 1.8, rel=1e-3)
    whole = c.hybrid_decode_step_bytes(cfg, 250, 128.0, live)
    assert sdar_reader("hybrid_decode_roofline").read(run) == \
        pytest.approx(100 * whole / 819e9 / (0.45 / 30), rel=1e-3)
    assert sdar_reader("moe_expert_load").read(run) == pytest.approx(
        90 / 62.5)
    assert sdar_reader("decode_step_ms").read(run) == pytest.approx(15.0)
    for name in ("block_attention_roofline", "unmask_roofline",
                 "routed_experts_roofline", "hybrid_decode_roofline"):
        assert 0 < sdar_reader(name).read(run) < 100, name


@pytest.mark.parametrize("name", SDAR_READERS)
def test_a_run_without_the_sdar_counters_or_names_gives_nothing(
        sdar_traced, name, tmp_path):
    """A program with no such counter or scope (the parent commit's,
    any other family) and a run that was not traced: nothing, and no
    error."""
    run, _ = sdar_traced
    for step in run.window_steps:
        for key in ("denoise_passes", "store_passes", "blocks",
                    "committed"):
            step.pop(key, None)
    run.trace["scopes"] = {
        "jit(_decode_burst_deferred_impl)/jit(main)/qknorm_attn/add":
            {"seconds": 1.0, "count": 10}}
    assert sdar_reader(name).read(run) is None
    os.remove(tmp_path / "trace_summary.json")
    os.remove(tmp_path / "steps.json")
    assert sdar_reader(name).read(RunFiles(str(tmp_path))) is None


def test_a_sdar_share_over_its_roofline_is_an_error_not_a_value(
        sdar_traced):
    run, _ = sdar_traced
    run.trace["scopes"] = {k: dict(v, seconds=v["seconds"] / 100)
                           for k, v in run.trace["scopes"].items()}
    for name in ("block_attention_roofline", "unmask_roofline"):
        with pytest.raises(ValueError, match="roofline"):
            sdar_reader(name).read(run)


def test_the_manifest_names_the_sdar_cell_and_its_four_readers():
    """By name and not by place: whatever later PRs append, this
    configuration, this cell and its metrics are found as they are."""
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    config, = [c for c in manifest["configs"] if c["name"] == SDAR_CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == f"chipbench/configs/{SDAR_CONFIG}.json"
    assert config["source"] == bench_run.load_json(SDAR_PUBLISHED)[
        "chipbench"]["source"]
    entry, = [w for w in manifest["workloads"] if w["name"] == SDAR_CELL]
    assert entry == {
        "name": SDAR_CELL, "config": SDAR_CONFIG,
        "traffic": "decode-closed", "chips": 1,
        "why": bench_run.find_cell(SDAR_CELL)["why"]}
    for line in (entry["why"], config["why"], config["source"]):
        assert 1 <= len(line) <= 200 and line.isprintable()
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    wanted = {"diffusion_tokens_per_pass": ("tokens", "higher",
                                            "program_counter"),
              "diffusion_store_share": ("%", "lower", "program_counter"),
              "block_attention_roofline": ("%", "higher", "device_trace"),
              "unmask_roofline": ("%", "higher", "device_trace")}
    for name, (unit, better, source) in wanted.items():
        m = by_name[name]
        assert m["workloads"] == [SDAR_CELL]
        assert (m["unit"], m["better"], m["source"], m["moves"],
                m["layer"]) == (unit, better, source, "output_tok_s",
                                "model + ops")
        reader = sdar_reader(name)
        assert (reader.UNIT, reader.MOVES, reader.SOURCE,
                reader.LAYER) == (unit, "output_tok_s", source,
                                  "model + ops")
    listed = {m["name"] for m in manifest["per_layer"]
              if SDAR_CELL in m["workloads"]}
    assert listed == set(bench_run.find_cell(SDAR_CELL)["per_layer"])
    assert len(listed) == 18
    # The other families' own shares are not this cell's.
    assert not {"ssd_decode_roofline", "mla_decode_roofline",
                "swa_decode_roofline", "mtp_accept_rate",
                "state_slots_peak", "decode_roofline"} & listed
    # What the benchmark had comes before it, in its order; every new
    # entry is at the end of its list and no cell takes four chips.
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(SDAR_CELL) >= 8
    assert names[:8] == [
        "qwen2.5-3b.decode-closed", "qwen3-next-80b-a3b-ep4.decode-closed",
        "jamba2-3b.decode-closed", "lfm2-8b-a1b-ep4.decode-closed",
        "longcat-flash-omni-ep32.decode-closed",
        "glm-4.7-flash-pp8.decode-closed",
        "granite-4.0-h-small-ep4.decode-closed",
        "k-exaone-236b-a23b-ep16.decode-closed"]
    assert [m["name"] for m in manifest["per_layer"]].index(
        "diffusion_tokens_per_pass") >= 34
    assert all(m["workloads"][-1] == SDAR_CELL
               for m in manifest["per_layer"]
               if SDAR_CELL in m["workloads"])
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    assert manifest["run_seconds"] == 45


# ---- the CPU rehearsal ---------------------------------------------------------


def test_the_sdar_rehearsal_runs_end_to_end_on_the_cpu():
    """The real server behind the real router at the tiny size, the
    request fields through the router, the reference check, the window,
    the traced side and the result line."""
    cell = "rehearsal-sdar"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(bench_run.BENCH, "run.py"),
         "--workload", cell, "--seed", str(2**31 + 11), "--seconds", "6",
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
    assert result["metrics"]["kv_pages_peak"]["value"] > 0
    # A counter is read whatever the device; a device share is not.
    assert 0.5 < result["metrics"]["diffusion_tokens_per_pass"][
        "value"] <= 4 / 3
    assert 25 < result["metrics"]["diffusion_store_share"]["value"] < 34
    assert "block_attention_roofline" not in result["metrics"]
    assert "unmask_roofline" not in result["metrics"]
    run = RunFiles(os.path.join(bench_run.STATE, "runs", cell))
    version = run.cell["version"]
    assert (version["family"], version["kv"], version["kv_writes"]) == (
        "sdar_moe", "pair", "deferred")
    assert version["block_diffusion"]["burst_blocks"] == 2
    assert version["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    bursts = [s for s in run.window_steps if s.get("kind") == "decode"]
    assert bursts and all(
        s["window"] == s["denoise_passes"] + s["store_passes"] <= 6
        and s["committed"] > 0 for s in bursts)
