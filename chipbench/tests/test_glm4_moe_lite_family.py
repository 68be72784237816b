"""The GLM-4 MoE lite family through the door: its reference against
the program's ``models/glm4_moe_lite.py`` served by the engine in
float32 at tiny widths on the CPU with the prediction module drafting
(prompts of several chunks over the latent pages, bursts of verify
iterations through pages and tails), the tolerance against a coarser
rounding, its counts with the sums by hand at the published widths and
against what the program's init makes, the configuration against the
catalog's row, its two readers (and the five it shares) on a run
directory made by hand, and its CPU rehearsal.  Every file of the
family is new; none of the harness was edited for it."""

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
from chipbench.counts import glm4_moe_lite_family as glm_counts
from chipbench.runfiles import RunFiles

GLM_TINY = os.path.join(bench_run.BENCH, "rehearsal", "configs",
                        "tiny-glm4.json")
GLM_PUBLISHED = os.path.join(bench_run.BENCH, "configs",
                             "glm-4.7-flash-pp8.json")
GLM_CONFIG = "glm-4.7-flash-pp8"
GLM_CELL = GLM_CONFIG + ".decode-closed"
GLM_READERS = ["mtp_accept_rate", "mtp_draft_roofline"]


@pytest.fixture(scope="module")
def glm():
    cfg = bench_run.load_json(GLM_TINY)
    assert family.name_of(cfg) == "glm4_moe_lite_family"
    reference = family.module("reference", cfg)
    assert reference.__name__ == "chipbench.reference.glm4_moe_lite_family"
    assert family.module("counts", cfg) is glm_counts
    hf = {k: v for k, v in cfg.items() if k != "chipbench"}
    model = reference.program_model(hf, cfg["chipbench"])
    assert (model.num_layers, model.num_dense_layers) == (3, 1)
    assert model.module is not None
    assert model.layer(3)["e_gate"].shape[0] == 8     # the module's layer
    return cfg, reference, model


def glm_served(cfg, prompt, answers, top, temperature=0.0):
    """What the program says: the engine on the configuration's random
    weights, prompts in chunks of 64, bursts of 4 iterations with the
    module drafting."""
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
                                  deferred_kv_writes=True,
                                  draft_module=True),
        seed=bench["weights_seed"]))
    engine.add_request(list(prompt), SamplingParams(
        max_tokens=answers, temperature=temperature, ignore_eos=True,
        logprobs=True, top_logprobs=top))
    tokens, served = [], []
    while len(tokens) < answers:
        for out in engine.step():
            if out.new_token is not None:
                tokens.append(out.new_token)
                served.append(dict(out.logprobs[1]))
    return tokens, served, engine.metrics


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_the_glm_reference_agrees_with_the_program_in_float32(
        glm, temperature):
    """150 tokens in three chunks of 64, 64 and 22, then twelve answers
    over bursts that draft: greedy (as the harness asks; drafts are
    accepted at chance) and at temperature 1, where most iterations
    commit an accepted draft and the token after it."""
    cfg, reference, model = glm
    prompt = np.random.default_rng(1).integers(0, 512, 150).tolist()
    tokens, served, metrics = glm_served(cfg, prompt, 12, 5, temperature)
    sequence = prompt + tokens
    got = np.asarray(reference.log_probs(
        model, sequence, list(range(149, 149 + 12))))
    diffs = [abs(lp - got[j, tid]) for j, top in enumerate(served)
             for tid, lp in top.items()]
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert len(diffs) >= 60
    assert max(diffs) < tolerance["max_abs_logprob_diff"] / 2
    assert np.mean(diffs) < tolerance["mean_abs_logprob_diff"] / 2
    assert metrics.spec_draft_tokens_total > 0
    if temperature:
        assert metrics.spec_accepted_tokens_total >= 3
    else:
        assert tokens == np.argmax(got, -1).tolist()


def test_the_glm_reference_is_float32_materialised_and_alone():
    path = os.path.join(bench_run.BENCH, "reference",
                        "glm4_moe_lite_family.py")
    with open(path) as f:
        source = f.read()
    code = source.split('"""', 2)[2]
    assert code.count('jax.default_matmul_precision("highest")') == 2
    head, tail = code.split("def program_model")
    assert "production_stack_tpu" not in head
    assert tail.count("from production_stack_tpu") == 2
    assert "bfloat16" not in code
    assert 'c @ w["w_kvb"]' in code
    for word in ("page", "absorb", "tail", "pallas", "spec_verify"):
        assert word not in code.lower(), word


# ---- the tolerance against a coarser rounding ---------------------------------


def _glm_differences(reference, model, other=None):
    tokens = np.random.default_rng(0).integers(0, 512, 120)
    positions = list(range(60, 119))
    out = []
    for fn in (reference.log_probs, reference.draft_log_probs):
        want = np.asarray(fn(model, tokens, positions))
        got = np.asarray(fn(other or model, tokens, positions))
        top = np.argsort(-want, -1)[:, :6]
        diff = np.abs(np.take_along_axis(got, top, -1)
                      - np.take_along_axis(want, top, -1))
        out += [diff.max(), diff.mean()]
    return out


def glm_rounded(model, dtype):
    """``model`` with every matrix rounded to ``dtype`` and back: the
    control one precision down."""
    cast = lambda a: jnp.asarray(a, jnp.float32).astype(  # noqa: E731
        dtype).astype(jnp.float32)
    layer = model.layer
    return dataclasses.replace(
        model,
        layer=lambda i: {k: cast(v) if v.ndim >= 2 else v
                         for k, v in layer(i).items()},
        module={k: cast(v) if v.ndim >= 2 else v
                for k, v in model.module.items()},
        embed=cast(model.embed), lm_head=cast(model.lm_head))


def test_the_glm_tolerance_fails_float8(glm):
    """The control: the reference in the program's place with its
    matrices rounded to float8_e4m3, well below the float32 the tiny
    configuration states: the main model's numbers and the module's
    both leave the limits by over three times. (Each term of the
    equations left out or put in: tests/test_glm4_moe_lite.py, against
    the program itself.)"""
    cfg, reference, model = glm
    worst, mean, q_worst, q_mean = _glm_differences(
        reference, model, glm_rounded(model, jnp.float8_e4m3fn))
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert worst > 3 * tolerance["max_abs_logprob_diff"]
    assert mean > 3 * tolerance["mean_abs_logprob_diff"]
    assert q_worst > 3 * tolerance["max_abs_logprob_diff"]
    assert _glm_differences(reference, model) == [0.0] * 4


# ---- the counts, by hand -----------------------------------------------------


def test_glm_counts_by_hand():
    cfg = bench_run.load_json(GLM_PUBLISHED)
    c = glm_counts
    assert c.drafting(cfg) and c.positions_per_row(cfg) == 2
    assert (c.num_sublayers(cfg), c.num_expert_layers(cfg),
            c.held_experts(cfg)) == (8, 7, 64)
    assert c.latent_width(cfg) == 576 and c.q_head_dim(cfg) == 256
    assert c.mla_params(cfg) == 21_759_232
    assert c.expert_params(cfg) == 9_437_184
    assert c.outside_experts_params(cfg) == 31_331_648
    assert c.expert_layer_params(cfg) == 635_311_424
    assert c.dense_layer_params(cfg) == 84_677_888
    assert c.module_params(cfg) == 643_706_176
    assert c.head_params(cfg) == 317_194_240
    assert c.param_count(cfg) == 5_174_643_136
    assert c.kv_bytes_per_token(cfg) == 9216
    assert c.kv_bytes_per_token(cfg) * 128 == 1_179_648      # a page
    # An iteration with nothing live: every weight outside the routed
    # experts once, the head twice, the embedding not at all.
    floor = (84_677_888 + 6 * 31_331_648 + 2048 + 317_194_240
             + 31_331_648 + 2 * 2048 * 2048 + 3 * 2048 + 317_194_240)
    assert c.decode_step_bytes(cfg, 0) == 2 * floor
    assert c.decode_step_bytes(cfg, 1000) == 2 * floor + 9216 * 1000
    # 160 rows, 40 experts hit a layer, 280k live tokens.
    whole = c.hybrid_decode_step_bytes(cfg, 160, 40, 280_000)
    assert whole == (2 * floor + 9216 * 280_000
                     + 7 * 40 * 9_437_184 * 2
                     + 160 * 2 * 2048 * 2 * 2 * 2 * 8)
    assert 9e9 < whole < 14e9
    flops, moved = c.moe_experts(cfg, 1280, 40)
    assert flops == 2.0 * 1280 * 9_437_184
    assert moved == 40 * 9_437_184 * 2 + 1280 * 2 * 2048 * 2
    # Two positions a row over ONE read of the live latent.
    flops, moved = c.mla_decode(cfg, 160, 280_000)
    assert flops == 2 * (160 * 2.0 * 20 * 512 * 448
                         + 280_000 * 2.0 * 20 * (576 + 512))
    assert moved == (280_000 * 576 * 2 + 512 * 20 * 448 * 2
                     + 2 * 160 * (20 * 256 + 576 + 20 * 256) * 2)
    assert flops / moved < 240                  # memory-bound on a v5e
    flops, moved = c.mla_prefill(cfg, [(0, 100), (128, 128)], 1)
    pairs = 100 * 101 / 2 + 128 * 128 + 128 * 129 / 2
    assert flops == 228 * 2.0 * 20 * 512 * 448 + pairs * 2.0 * 20 * 1088
    assert moved == (356 * 576 * 2 + 512 * 20 * 448 * 2
                     + 228 * (5120 + 576 + 5120) * 2)
    # The module: its layer outside the routed experts, eh_proj and the
    # norms, the head once more, the experts hit, its entry's latent,
    # and a row's logits out.
    flops, moved = c.mtp_draft(cfg, 160, 216, 30, 280_000)
    assert moved == ((31_331_648 + 2 * 2048 * 2048 + 3 * 2048
                      + 317_194_240 + 30 * 9_437_184) * 2
                     + 280_000 * 576 * 2 + 160 * 154880 * 4)
    assert flops == (2.0 * 216 * (31_331_648 - 2 * 2048 + 2 * 2048 * 2048
                                  + 4 * 9_437_184)
                     + 2.0 * 160 * 317_194_240
                     + 216 / 160 * 280_000 * 2.0 * 20 * 1088)
    # Switched off by the cell's flags: one position, seven entries, no
    # module and one read of the head.
    off = json.loads(json.dumps(cfg))
    off["chipbench"]["server_flags"]["draft-module"] = "off"
    assert not c.drafting(off) and c.num_sublayers(off) == 7
    assert c.param_count(off) == 5_174_643_136 - 643_706_176
    assert c.decode_step_bytes(off, 0) == 2 * (
        84_677_888 + 6 * 31_331_648 + 2048 + 317_194_240)
    # A prefill chunk: every layer and the module's, the head once.
    sparse = 31_331_648 + 4 * 9_437_184
    per_token = 84_677_888 + 6 * sparse + sparse + 2 * 2048 * 2048
    assert c.prefill_flops(cfg, [(0, 100, True)]) == (
        2.0 * per_token * 100
        + 2.0 * 8 * 20 * (256 + 256) * (100 * 101 / 2)
        + 2.0 * 317_194_240)


def test_the_glm_count_is_what_the_programs_init_makes():
    import jax
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models import glm4_moe_lite
    for path in (GLM_TINY, GLM_PUBLISHED):
        cfg = bench_run.load_json(path)
        config = ModelConfig.from_hf_config(
            {k: v for k, v in cfg.items() if k != "chipbench"})
        shapes = jax.eval_shape(
            lambda key: glm4_moe_lite.init_params(config, key),
            jax.random.PRNGKey(0))
        made = sum(int(np.prod(s.shape)) for s in shapes.values())
        assert made == glm_counts.param_count(cfg), path
        assert (config.page_cache.entries * config.page_cache.width * 2
                == glm_counts.kv_bytes_per_token(cfg))


def test_the_published_glm_is_the_catalogs_row_cut_in_one_key():
    c = bench_run.load_json(GLM_PUBLISHED)
    bench = c["chipbench"]
    assert bench["reduced"] == ["num_hidden_layers"]
    assert bench["chips"] == 1
    assert bench["family"] == "glm4_moe_lite_family"
    row = dict(
        attention_bias=False, hidden_act="silu", hidden_size=2048,
        intermediate_size=10240, max_position_embeddings=202752,
        model_type="glm4_moe_lite", moe_intermediate_size=1536,
        topk_method="noaux_tc", norm_topk_prob=True,
        num_attention_heads=20, n_group=1, topk_group=1,
        n_routed_experts=64, n_shared_experts=1, routed_scaling_factor=1.8,
        num_experts_per_tok=4, first_k_dense_replace=1,
        num_key_value_heads=20, num_nextn_predict_layers=1,
        partial_rotary_factor=1, rms_norm_eps=1e-05, rope_scaling=None,
        rope_theta=1000000, tie_word_embeddings=False, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, vocab_size=154880)
    assert {k: c[k] for k in row} == row
    assert set(c) == set(row) | {"architectures", "num_hidden_layers",
                                 "published", "chipbench"}
    assert c["num_hidden_layers"] == 7
    assert c["published"] == {"num_hidden_layers": 47}
    assert c["architectures"] == ["Glm4MoeLiteForCausalLM"]
    assert {"architectures", "rotary_pairs", "mla", "router",
            "prediction_module", "weights", "tokenizer",
            "acceptance"} <= set(bench["assumed"])
    flags = bench["server_flags"]
    assert (flags["max-num-seqs"], flags["decode-steps"],
            flags["page-size"], flags["num-pages"],
            flags["max-model-len"], flags["prefill-chunk-size"],
            flags["prefill-batch-size"], flags["unified-step"]) == (
        160, 32, 128, 3328, 4352, 128, 16, "off")
    # Drafting is on by the configuration's own key: no flag names it.
    assert "draft-module" not in flags
    assert "deferred-kv-writes" not in flags
    # The traffic is the other latent cell's to the letter.
    cell = bench_run.find_cell(GLM_CELL)
    other = bench_run.find_cell("longcat-flash-omni-ep32.decode-closed")
    for key in ("traffic", "traffic_kind", "traffic_params", "sampling",
                "warm_prompt_tokens", "end_to_end"):
        assert cell[key] == other[key], key
    assert cell["per_layer"] == other["per_layer"] + GLM_READERS
    assert 1024 + 3072 <= flags["max-model-len"]
    from production_stack_tpu.engine.model_runner import prefill_buckets
    assert set(prefill_buckets(flags["prefill-chunk-size"])) <= set(
        cell["warm_prompt_tokens"])
    bench_run.validate(cell)


# ---- the readers on a run made by hand ---------------------------------------


def glm_reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


@pytest.fixture
def glm_traced(tmp_path):
    config = bench_run.load_json(GLM_PUBLISHED)
    t0 = 1000.0
    burst = "jit(_decode_burst_draft_impl)/jit(main)/while/body"
    step = "jit(_step_impl)/jit(main)"
    decode = {"kind": "decode", "window": 32, "host_ms": 10,
              "device_wait_ms": 900, "decode_rows": 150,
              "moe_experts_hit": 40.0, "moe_tokens_per_expert_mean": 16.0,
              "moe_tokens_per_expert_max": 30.0,
              "drafts": 4650, "accepted": 1395}
    files = {
        "cell.json": {"t0_unix": t0, "seconds": 20.0,
                      "slice_unix": [t0 + 8.0, t0 + 11.0],
                      "config_as_run": config,
                      "version": {"device_kind": "TPU v5 lite"}},
        "records.json": [{"phase": "window", "first": 1.0, "last": 15.0,
                          "prompt_tokens": 640, "tokens": 2000}] * 150,
        "steps.json": [
            dict(decode, step=1, ts=t0 + 8.5),
            dict(decode, step=2, ts=t0 + 9.5),
            {"step": 3, "ts": t0 + 10.0, "kind": "prefill",
             "prefill_rows": 2, "host_ms": 5, "device_wait_ms": 60},
            dict(decode, step=4, ts=t0 + 15.0, drafts=4000, accepted=2000)],
        "trace_summary.json": {
            "window_s": 3.0, "busy_s": 2.9,
            "programs": {"_decode_burst_draft_impl": {
                "count": 3, "seconds": 2.7, "whole_s": 0.9},
                "_step_impl": {"count": 1, "seconds": 0.12,
                               "whole_s": 0.12}},
            "scopes": {
                f"{burst}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.7, "count": 672},
                f"{burst}/mla_decode/pallas_call": {
                    "seconds": 0.5, "count": 672},
                f"{burst}/mtp_draft/mla_decode/pallas_call": {
                    "seconds": 0.07, "count": 96},
                f"{burst}/mtp_draft/moe_experts/gmm/pallas_call": {
                    "seconds": 0.1, "count": 96},
                f"{burst}/mtp_draft/dot_general": {
                    "seconds": 0.13, "count": 288},
                f"{burst}/mtp_verify/reduce": {
                    "seconds": 0.2, "count": 960},
                f"{step}/mtp_draft/mla_prefill/dot_general": {
                    "seconds": 0.002, "count": 4},
                f"{step}/mla_prefill/dot_general": {
                    "seconds": 0.014, "count": 28}}},
    }
    for name, content in files.items():
        with open(tmp_path / name, "w") as f:
            json.dump(content, f)
    spans = [{"span": "engine_request", "request_id": f"r{i}", "events": [
        {"event": "prefill_chunk", "ts": t0 + 9.9, "start": 128 * i,
         "tokens": 100 + 28 * i, "last": True}]} for i in range(2)]
    with open(tmp_path / "spans.jsonl", "w") as f:
        f.write("\n".join(map(json.dumps, spans)) + "\n")
    return RunFiles(str(tmp_path)), config


def test_the_glm_readers_on_a_run_made_by_hand(glm_traced):
    run, cfg = glm_traced
    c = glm_counts
    # Every burst record of the window: (2 x 1395 + 2000) of (2 x 4650
    # + 4000) drafts.
    assert glm_reader("mtp_accept_rate").read(run) == pytest.approx(
        100 * 4790 / 13300)
    # The module's name holds its own attention and experts; the burst
    # prefix keeps the prefill step's module out.
    assert hybrid_slice.scope_seconds(run.trace, "mtp_draft",
                                      "_decode_burst") == (
        pytest.approx(0.3), 480)
    # 2.7 s of the burst at 0.9 s an execution: 3 bursts, 96 iterations.
    assert hybrid_slice.token_steps(run) == pytest.approx(96.0)
    live = 150 * (640 + 2000 * (9.5 - 1.0) / 14.0)
    flops, moved = c.mtp_draft(cfg, 150, 150 * 1.3, 40.0, live)
    least = max(flops / 197e12, moved / 819e9)
    assert moved / 819e9 > flops / 197e12            # read-bound
    assert glm_reader("mtp_draft_roofline").read(run) == pytest.approx(
        100 * least * 96 / 0.3, rel=1e-3)
    # The five shared readers take this family's counts: an iteration's.
    flops, moved = c.mla_decode(cfg, 150, live)
    assert glm_reader("mla_decode_roofline").read(run) == pytest.approx(
        100 * moved * 96 * 8 / 819e9 / 0.57, rel=1e-3)
    flops, moved = c.moe_experts(cfg, 16.0 * 64, 40.0)
    assert glm_reader("routed_experts_roofline").read(run) == \
        pytest.approx(100 * moved * 96 * 7 / 819e9 / 0.8, rel=1e-3)
    whole = c.hybrid_decode_step_bytes(cfg, 150, 40.0, live)
    assert glm_reader("hybrid_decode_roofline").read(run) == pytest.approx(
        100 * whole / 819e9 / (0.9 / 32), rel=1e-3)
    flops, moved = c.mla_prefill(cfg, [(0, 100), (128, 128)], 1)
    assert glm_reader("mla_prefill_roofline").read(run) == pytest.approx(
        100 * max(8 * flops / 197e12, 8 * moved / 819e9) / 0.016)
    assert glm_reader("moe_expert_load").read(run) == pytest.approx(30 / 16)
    for name in ("mtp_draft_roofline", "mla_decode_roofline",
                 "routed_experts_roofline", "hybrid_decode_roofline",
                 "mla_prefill_roofline"):
        assert 0 < glm_reader(name).read(run) < 100, name


@pytest.mark.parametrize("name", GLM_READERS)
def test_a_run_without_the_counters_or_the_name_gives_nothing(
        glm_traced, name, tmp_path):
    """A program with no such counter or scope (the parent commit's, a
    family that does not draft, drafting switched off) and a run that
    was not traced: nothing, and no error."""
    run, _ = glm_traced
    for step in run.window_steps:
        step.pop("drafts", None)
        step.pop("accepted", None)
    run.trace["scopes"] = {
        "jit(_decode_burst_deferred_impl)/jit(main)/mla_decode/add":
            {"seconds": 1.0, "count": 10}}
    assert glm_reader(name).read(run) is None
    # What a traced run alone writes: the trace and the step records.
    os.remove(tmp_path / "trace_summary.json")
    os.remove(tmp_path / "steps.json")
    assert glm_reader(name).read(RunFiles(str(tmp_path))) is None


def test_a_module_share_over_its_roofline_is_an_error_not_a_value(
        glm_traced):
    run, _ = glm_traced
    run.trace["scopes"] = {k: dict(v, seconds=v["seconds"] / 100)
                           for k, v in run.trace["scopes"].items()}
    with pytest.raises(ValueError, match="roofline"):
        glm_reader("mtp_draft_roofline").read(run)


def test_the_manifest_names_the_glm_cell_and_its_two_readers():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    config, = [c for c in manifest["configs"] if c["name"] == GLM_CONFIG]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["file"] == f"chipbench/configs/{GLM_CONFIG}.json"
    assert config["source"] == bench_run.load_json(GLM_PUBLISHED)[
        "chipbench"]["source"]
    assert len(config["why"]) <= 200
    entry, = [w for w in manifest["workloads"] if w["name"] == GLM_CELL]
    assert entry == {
        "name": GLM_CELL, "config": GLM_CONFIG, "traffic": "decode-closed",
        "chips": 1, "why": bench_run.find_cell(GLM_CELL)["why"]}
    assert len(entry["why"]) <= 200
    for said in ("an iteration of 1+a tokens", "0.3-0.4", "0.85-0.9"):
        assert said in entry["why"]
    mine = [m for m in manifest["per_layer"]
            if m["workloads"] == [GLM_CELL]]
    assert [m["name"] for m in mine] == GLM_READERS
    assert all(m["unit"] == "%" and m["better"] == "higher"
               and m["moves"] == "output_tok_s" for m in mine)
    listed = {m["name"] for m in manifest["per_layer"]
              if GLM_CELL in m["workloads"]}
    assert listed == set(bench_run.find_cell(GLM_CELL)["per_layer"])
    assert len(listed) == 18
    # Six configurations, six cells, all one chip, thirty readers; what
    # the benchmark had is where it was.
    assert len(manifest["configs"]) == len(manifest["workloads"]) == 6
    assert all(w["chips"] == 1 for w in manifest["workloads"])
    assert len(manifest["per_layer"]) == 30
    assert [w["name"] for w in manifest["workloads"]][:5] == [
        "qwen2.5-3b.decode-closed", "qwen3-next-80b-a3b-ep4.decode-closed",
        "jamba2-3b.decode-closed", "lfm2-8b-a1b-ep4.decode-closed",
        "longcat-flash-omni-ep32.decode-closed"]


# ---- the CPU rehearsal ---------------------------------------------------------


def test_the_glm_rehearsal_runs_end_to_end_on_the_cpu():
    """The real server behind the real router at the tiny size with the
    module drafting by the configuration's own key, the reference
    check, the window, the traced side and the result line."""
    cell = "rehearsal-glm4"
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
    assert 0 < result["metrics"]["mtp_accept_rate"]["value"] <= 100
    assert "mtp_draft_roofline" not in result["metrics"]
    run = RunFiles(os.path.join(bench_run.STATE, "runs", cell))
    version = run.cell["version"]
    assert (version["family"], version["kv"], version["kv_writes"]) == (
        "glm4_moe_lite", "latent", "deferred")
    assert version["drafts"] == {"by": "module", "layers": 1,
                                 "tokens_per_iteration": 2}
    assert version["kv_bytes_per_token"] == 4 * (24 + 8) * 4
    bursts = [s for s in run.window_steps if s.get("kind") == "decode"]
    assert bursts and all(s["drafts"] >= s["accepted"] >= 0
                          for s in bursts)
