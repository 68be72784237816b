"""The LongCat-Flash family through the door: its reference against the
program's ``models/longcat_flash.py`` served by the engine in float32 at
tiny widths on the CPU (prompts of several chunks over the latent
pages, the deferred burst through pages and latent tails), the
tolerance against a coarser rounding and against the other share's
experts, its counts against the bytes of real arrays and with the sums
by hand at the published widths, its three readers on a run directory
made by hand and on a recorded run of the cell (``longcat_v5e_run/``),
and its CPU rehearsal.  Every file of the family is new;
none of the harness was edited for it."""

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
from chipbench.counts import longcat_family as counts
from chipbench.runfiles import RunFiles

TINY = os.path.join(bench_run.BENCH, "rehearsal", "configs",
                    "tiny-longcat.json")
PUBLISHED = os.path.join(bench_run.BENCH, "configs",
                         "longcat-flash-omni-ep32.json")
CONFIG = "longcat-flash-omni-ep32"
CELL = CONFIG + ".decode-closed"


@pytest.fixture(scope="module")
def longcat():
    cfg = bench_run.load_json(TINY)
    assert family.name_of(cfg) == "longcat_family"
    reference = family.module("reference", cfg)
    assert reference.__name__ == "chipbench.reference.longcat_family"
    assert family.module("counts", cfg) is counts
    hf = {k: v for k, v in cfg.items() if k != "chipbench"}
    model = reference.program_model(hf, cfg["chipbench"])
    # Rank 1 of 2: routed experts 4..7 of 8; the zero experts from 8.
    assert (model.first_expert, model.first_zero_expert) == (4, 8)
    assert model.branch(1)["e_gate"].shape[0] == 4
    return cfg, reference, model


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


def test_the_longcat_reference_agrees_with_the_program_in_float32(longcat):
    """150 tokens in three chunks of 64, 64 and 22 (the later ones read
    the earlier latents back from the pages), then nine answers over
    three deferred bursts."""
    cfg, reference, model = longcat
    prompt = np.random.default_rng(1).integers(0, 512, 150).tolist()
    tokens, served = served_log_probs(cfg, prompt, 9, 5)
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


def test_the_longcat_reference_is_float32_materialised_and_alone():
    path = os.path.join(bench_run.BENCH, "reference", "longcat_family.py")
    with open(path) as f:
        source = f.read()
    code = source.split('"""', 2)[2]
    assert 'jax.default_matmul_precision("highest")' in code
    # Nothing of the program but the init's values, taken in
    # program_model alone.
    head, tail = code.split("def program_model")
    assert "production_stack_tpu" not in head
    assert tail.count("from production_stack_tpu") == 2
    assert "bfloat16" not in code
    # Materialised attention: per-head keys and values from W_kvb, no
    # cache, no absorbed product.
    assert 'c @ w["w_kvb"]' in code
    for word in ("page", "absorb", "tail", "pallas"):
        assert word not in code.lower(), word


def test_a_long_longcat_prompt_in_blocks_of_queries_is_the_same(
        longcat, monkeypatch):
    _, reference, model = longcat
    tokens = np.random.default_rng(2).integers(0, 512, 90)
    whole = np.asarray(reference.log_probs(model, tokens, [50, 89]))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    blocks = np.asarray(reference.log_probs(model, tokens, [50, 89]))
    assert np.abs(blocks - whole).max() < 1e-5


# ---- the tolerance against a coarser rounding -------------------------------


def _differences(reference, model, other=None):
    tokens = np.random.default_rng(0).integers(0, 512, 120)
    positions = list(range(60, 120))
    want = np.asarray(reference.log_probs(model, tokens, positions))
    got = np.asarray(reference.log_probs(other or model, tokens, positions))
    top = np.argsort(-want, -1)[:, :6]
    diff = np.abs(np.take_along_axis(got, top, -1)
                  - np.take_along_axis(want, top, -1))
    return diff.max(), diff.mean()


def rounded(model, dtype):
    """``model`` with every matrix rounded to ``dtype`` and back: the
    control one precision down."""
    cast = lambda a: jnp.asarray(a, jnp.float32).astype(  # noqa: E731
        dtype).astype(jnp.float32)

    def each(make):
        return lambda i: {k: cast(v) if v.ndim >= 2 else v
                          for k, v in make(i).items()}
    return dataclasses.replace(
        model, sublayer=each(model.sublayer), branch=each(model.branch),
        embed=cast(model.embed), lm_head=cast(model.lm_head))


LONGCAT_FAULTS = {
    "float8_e4m3 matrices": lambda m: rounded(m, jnp.float8_e4m3fn),
    "the other share's experts in place of its own":
        lambda m: dataclasses.replace(m, first_expert=0),
    "the zero-compute experts read as routed ones held elsewhere":
        lambda m: dataclasses.replace(m, first_zero_expert=10**6),
}


@pytest.mark.parametrize("fault", sorted(LONGCAT_FAULTS))
def test_the_longcat_tolerance_fails_float8_and_a_wrong_share(longcat,
                                                              fault):
    """The control: the reference in the program's place, with its
    matrices rounded well below the float32 the configuration states,
    or given another share of the experts. (Each term of the equations
    left out or put in: tests/test_longcat_flash.py, against the
    program itself.)"""
    cfg, reference, model = longcat
    worst, mean = _differences(reference, model,
                               LONGCAT_FAULTS[fault](model))
    tolerance = cfg["chipbench"]["reference_tolerance"]
    assert (worst > 3 * tolerance["max_abs_logprob_diff"]
            or mean > 3 * tolerance["mean_abs_logprob_diff"]), (worst, mean)


def test_the_same_longcat_differs_by_nothing(longcat):
    _, reference, model = longcat
    assert _differences(reference, model) == (0.0, 0.0)


# ---- the counts, by hand and against real arrays -----------------------------


def test_longcat_counts_by_hand():
    """At the published widths, one chip's share (4 of 28 layers, 16 of
    512 routed experts a layer, 16384 of 131072 vocabulary rows): the
    arithmetic of ISSUE 41."""
    c = bench_run.load_json(PUBLISHED)
    assert (counts.num_sublayers(c), counts.num_expert_layers(c),
            counts.held_experts(c)) == (8, 4, 16)
    assert (counts.latent_width(c), counts.q_head_dim(c),
            counts.router_width(c)) == (576, 192, 768)
    # q_a 6144 x 1536 and its norm, q_b 1536 x 12288, kv_a 6144 x 576
    # and its norm, kv_b 512 x 16384, o 8192 x 6144.
    mla = (6144 * 1536 + 1536 + 1536 * 12288 + 6144 * 576 + 512
           + 512 * 16384 + 8192 * 6144)
    assert counts.kvb_params(c) == 512 * 16384 == 8_388_608
    assert counts.mla_params(c) == mla == 90_572_800
    assert counts.dense_mlp_params(c) == 3 * 6144 * 12288 == 226_492_416
    expert = 3 * 6144 * 2048
    assert counts.expert_params(c) == expert == 37_748_736
    assert counts.router_params(c) == 6144 * 768 + 768 == 4_719_360
    assert counts.norm_params(c) == 17 * 6144
    head = 6144 * 16384
    assert counts.head_params(c) == head == 100_663_296
    layer = 2 * mla + 2 * 226_492_416 + 4_719_360 + 4 * 6144
    assert layer == 638_873_600 + 768
    dense = 4 * layer + 6144 + head
    assert counts.dense_params(c) == dense
    total = dense + head + 4 * 16 * expert
    assert counts.param_count(c) == total == 5_172_749_312
    assert 2 * total == 10_345_498_624
    # The published model whole: 28 layers of 512 experts, 131072 rows.
    assert 28 * (layer + 512 * expert) + 2 * 131072 * 6144 + 6144 == (
        560_664_980_480)
    # The latent: 8 sublayers x (512 + 64) x 2 B; a page of 128 tokens.
    assert counts.kv_bytes_per_token(c) == 9216
    assert 128 * counts.kv_bytes_per_token(c) == 1_179_648
    assert 3328 * 1_179_648 == 3_925_868_544
    # Materialised K and V for 64 heads of 192 + 128 would be 35 times.
    assert 8 * 64 * (192 + 128) * 2 == 327_680
    assert counts.decode_step_bytes(c, 1000) == 2 * dense + 1000 * 9216
    assert roofline.decode_step_bytes(c, 0) == 2 * dense == 5_312_333_824
    # A step at 160 rows, 14.75 of 16 experts hit, 1750 tokens a row.
    step = counts.hybrid_decode_step_bytes(c, 160, 14.75, 280_000)
    assert step == (2 * dense + 280_000 * 9216 + 4 * 14.75 * expert * 2
                    + 160 * 6144 * 2 * 2 * 20)
    assert 12.3e9 < step < 12.6e9
    assert 15.0e-3 < step / 819e9 < 15.4e-3
    # One expert branch at 160 rows x 12 choices x 16 / 768 held.
    assert counts.moe_experts(c, 40, 14.75) == (
        2.0 * 40 * expert, 14.75 * expert * 2 + 40 * 2 * 6144 * 2)
    # One sublayer's attention at 160 rows over 280 000 live tokens.
    flops, moved = counts.mla_decode(c, 160, 280_000)
    assert flops == (160 * 2.0 * 64 * 512 * 256
                     + 280_000 * 2.0 * 64 * (576 + 512))
    assert moved == (280_000 * 1152 + 8_388_608 * 2
                     + 160 * (64 * 192 + 576 + 64 * 128) * 2)
    # 64 x (576 + 512) x 2 = 139 264 operations against 1152 B a token:
    # 121 a byte, under the ridge of 240: memory bounds it.
    assert 2 * 64 * (576 + 512) == 139_264 and 120 < 139_264 / 1152 < 121
    assert flops / 197e12 < moved / 819e9
    # A chunk of 128 after 512 cached tokens, absorbed: 3.4 times the
    # operations a pair of per-head keys and values.
    pairs = 128 * 512 + 128 * 129 / 2
    assert counts.mla_prefill(c, [(512, 128)], 1) == (
        128 * 2.0 * 64 * 512 * 256 + pairs * 2.0 * 64 * 1088,
        640 * 1152 + 8_388_608 * 2 + 128 * (64 * 192 + 576 + 64 * 128) * 2)
    assert 3.3 < 1088 / 320 < 3.5
    assert counts.mla_prefill(c, [(0, 8)], 3)[1] - counts.mla_prefill(
        c, [(0, 8)], 1)[1] == 2 * 8_388_608 * 2
    per_token = (8 * (mla + 226_492_416)
                 + 4 * (4_719_360 + 12 * 16 / 768 * expert))
    want = 2 * per_token * 4 + 2 * 8 * 64 * 320 * (4 * 10 + 10)
    assert roofline.prefill_flops(c, [(10, 4, False)]) == want
    assert roofline.prefill_flops(c, [(10, 4, True)]) == want + 2 * head
    with pytest.raises(ValueError):
        counts.decode_step_bytes(
            dict(c, chipbench={"quantization": "int8"}), 0)


def test_each_longcat_count_is_the_bytes_of_the_programs_own_arrays():
    """The counts against the arrays the program makes at the published
    widths (shapes alone, nothing allocated): the parameters, a
    sublayer's matrices, an expert, and the latent planes."""
    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.models import longcat_flash
    from production_stack_tpu.models.registry import init_hybrid_cache

    c = bench_run.load_json(PUBLISHED)
    config = ModelConfig.from_hf_config(
        {k: v for k, v in c.items() if k != "chipbench"})
    shapes = jax.eval_shape(
        lambda key: longcat_flash.init_params(config, key),
        jax.random.PRNGKey(0))
    size = lambda a: int(np.prod(a.shape))  # noqa: E731
    nbytes = lambda a: size(a) * a.dtype.itemsize  # noqa: E731
    assert sum(map(size, jax.tree.leaves(shapes))) == counts.param_count(c)
    sub = counts.num_sublayers(c)
    assert sum(size(shapes[k]) for k in (
        "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "w_uk", "w_uv",
        "wo")) == sub * counts.mla_params(c)
    assert size(shapes["w_uk"]) + size(shapes["w_uv"]) == (
        sub * counts.kvb_params(c))
    assert nbytes(shapes["w_gate_up"]) + nbytes(shapes["w_down"]) == (
        sub * counts.dense_mlp_params(c) * counts.WEIGHT_BYTES)
    assert (nbytes(shapes["e_w_gate_up_0"]) + nbytes(shapes["e_w_down_0"])
            == counts.held_experts(c) * counts.expert_params(c)
            * counts.WEIGHT_BYTES)
    assert size(shapes["router"]) + size(shapes["router_bias"]) == (
        4 * counts.router_params(c))
    assert nbytes(shapes["lm_head"]) == counts.head_params(c) * 2
    flags = c["chipbench"]["server_flags"]
    k_cache, v_cache = jax.eval_shape(lambda: init_hybrid_cache(
        config, flags["num-pages"], flags["page-size"], 0))
    planes = k_cache[:-1]
    assert all(v is None for v in v_cache)
    assert sum(map(nbytes, planes)) == (
        flags["num-pages"] * flags["page-size"]
        * counts.kv_bytes_per_token(c))
    assert planes[0].shape[2] == counts.latent_width(c)


def test_the_published_longcat_is_the_catalogs_row_cut_in_three_keys():
    c = bench_run.load_json(PUBLISHED)
    bench = c["chipbench"]
    assert bench["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert bench["chips"] == 1 and bench["family"] == "longcat_family"
    row = dict(
        attention_bias=False, hidden_size=6144, ffn_hidden_size=12288,
        expert_ffn_hidden_size=2048, num_attention_heads=64,
        kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64,
        v_head_dim=128, qk_nope_head_dim=128, mla_scale_q_lora=True,
        mla_scale_kv_lora=True, routed_scaling_factor=6,
        max_position_embeddings=131072, rms_norm_eps=1e-05,
        rope_theta=10000000, attention_method="MLA", zero_expert_num=256,
        zero_expert_type="identity", moe_topk=12)
    assert {k: c[k] for k in row} == row
    assert (c["num_layers"], c["n_routed_experts"], c["vocab_size"]) == (
        4, 16, 16384)
    assert c["published"] == {"num_layers": 28, "n_routed_experts": 512,
                              "vocab_size": 131072}
    assert (c["expert_parallel_size"], c["expert_parallel_rank"]) == (32, 0)
    assert c["architectures"] == ["LongcatFlashForCausalLM"]
    assert {"architectures", "hidden_act", "tie_word_embeddings",
            "router", "rotary_pairs", "lora_scales", "weights",
            "tokenizer"} <= set(bench["assumed"])
    flags = bench["server_flags"]
    assert (flags["max-num-seqs"], flags["decode-steps"],
            flags["page-size"]) == (160, 32, 128)
    assert "deferred-kv-writes" not in flags     # auto resolves it on
    cell = bench_run.find_cell(CELL)
    params = cell["traffic_params"]
    assert (params["clients"], params["ramp_s"], params["pool"],
            params["drain_limit_s"]) == (160, 30.0, 4096, 300)
    assert params["prompt_tokens"] == {"dist": "uniform", "min": 256,
                                       "max": 1024}
    assert params["output_tokens"] == {"dist": "uniform", "min": 1024,
                                       "max": 3072}
    assert cell["sampling"] == {"temperature": 0.7, "top_p": 1.0}
    # The longest request fits the model length and the table.
    assert 1024 + 3072 <= flags["max-model-len"]
    # Every prefill bucket of the chunk is warmed by name, and the
    # multi-chunk path.
    from production_stack_tpu.engine.model_runner import prefill_buckets
    assert set(prefill_buckets(flags["prefill-chunk-size"])) <= set(
        cell["warm_prompt_tokens"])
    assert max(cell["warm_prompt_tokens"]) > flags["prefill-chunk-size"]
    bench_run.validate(cell)


# ---- the readers on a run made by hand -------------------------------------


def reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


NEW_READERS = ["mla_decode_roofline", "mla_prefill_roofline",
               "routed_experts_roofline"]


@pytest.fixture
def longcat_traced(tmp_path):
    config = bench_run.load_json(PUBLISHED)
    t0 = 1000.0
    burst = "jit(_decode_burst_deferred_impl)/jit(main)/while/body"
    step = "jit(_step_impl)/jit(main)"
    decode = {"kind": "decode", "window": 32, "host_ms": 10,
              "device_wait_ms": 800, "decode_rows": 158,
              "moe_experts_hit": 14.5, "moe_tokens_per_expert_mean": 2.5,
              "moe_tokens_per_expert_max": 7.0,
              "moe_zero_choice_share": 0.33}
    files = {
        "cell.json": {"t0_unix": t0, "seconds": 20.0,
                      "slice_unix": [t0 + 8.0, t0 + 11.0],
                      "config_as_run": config,
                      "version": {"device_kind": "TPU v5 lite"}},
        "records.json": [{"phase": "window", "first": 1.0, "last": 15.0,
                          "prompt_tokens": 640, "tokens": 2000}] * 158,
        "steps.json": [
            dict(decode, step=1, ts=t0 + 8.5),
            dict(decode, step=2, ts=t0 + 9.5),
            {"step": 3, "ts": t0 + 10.0, "kind": "prefill",
             "prefill_rows": 2, "host_ms": 5, "device_wait_ms": 60},
            dict(decode, step=4, ts=t0 + 15.0)],
        "trace_summary.json": {
            "window_s": 3.0, "busy_s": 2.9,
            "programs": {"_decode_burst_deferred_impl": {
                "count": 4, "seconds": 2.4, "whole_s": 0.8},
                "_step_impl": {"count": 1, "seconds": 0.12,
                               "whole_s": 0.12}},
            "scopes": {
                f"{burst}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.6, "count": 768},
                f"{burst}/mla_decode/pallas_call": {
                    "seconds": 0.5, "count": 768},
                f"{burst}/mla_decode/dot_general": {
                    "seconds": 0.1, "count": 1536},
                f"{burst}/dense_ffn/dot_general": {
                    "seconds": 0.5, "count": 1536},
                f"{step}/moe_experts/gmm/pallas_call": {
                    "seconds": 0.02, "count": 8},
                f"{step}/mla_prefill/dot_general": {
                    "seconds": 0.008, "count": 32},
                f"{step}/mla_prefill/scatter": {"seconds": 0.002,
                                                "count": 8}}},
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


def test_the_longcat_layers_shares_of_their_rooflines(longcat_traced):
    run, cfg = longcat_traced
    assert hybrid_slice.scope_seconds(run.trace, "mla_decode",
                                      "_decode_burst") == (0.6, 2304)
    # The expert layer's name in the prefill step is another program's.
    assert hybrid_slice.scope_seconds(run.trace, "moe_experts",
                                      "_decode_burst") == (0.6, 768)
    assert hybrid_slice.scope_seconds(run.trace, "mla_prefill",
                                      "_step_impl") == (0.01, 40)
    # 2.4 s of the burst at 0.8 s an execution: 3 bursts, 96 steps.
    assert hybrid_slice.token_steps(run) == pytest.approx(96.0)
    # Two bursts stamped inside the slice: 14.5 experts hit, 2.5 pairs
    # a held expert x 16 held; 4 expert layers.
    expert = 3 * 6144 * 2048
    moved = (14.5 * expert * 2 + 40 * 2 * 6144 * 2) * 96 * 4
    assert reader("routed_experts_roofline").read(run) == pytest.approx(
        100 * moved / 819e9 / 0.6)
    live = 158 * (640 + 2000 * (9.5 - 1.0) / 14.0)
    flops, moved = counts.mla_decode(cfg, 158, live)
    assert flops / 197e12 < moved / 819e9           # read-bound
    assert reader("mla_decode_roofline").read(run) == pytest.approx(
        100 * moved * 96 * 8 / 819e9 / 0.6, rel=1e-3)
    # Chunks of 100 tokens at 0 and 128 at 128, one prefill record, one
    # execution.
    flops, moved = counts.mla_prefill(cfg, [(0, 100), (128, 128)], 1)
    least = max(8 * flops / 197e12, 8 * moved / 819e9)
    assert reader("mla_prefill_roofline").read(run) == pytest.approx(
        100 * least / 0.01)
    # The two the hybrid cells brought serve this family's counts
    # unchanged.
    whole = counts.hybrid_decode_step_bytes(cfg, 158, 14.5, live)
    assert reader("hybrid_decode_roofline").read(run) == pytest.approx(
        100 * whole / 819e9 / (0.8 / 32), rel=1e-3)
    assert reader("moe_expert_load").read(run) == pytest.approx(7 / 2.5)
    for name in NEW_READERS + ["hybrid_decode_roofline"]:
        assert 0 < reader(name).read(run) < 100, name
    # The older reader asks the configuration for keys it lacks.
    with pytest.raises(KeyError):
        reader("moe_experts_roofline").read(run)


def test_the_one_expert_reader_serves_the_lfm2_family_once_it_can_count():
    """``routed_experts_roofline`` asks a family's counts for its expert
    layers and held experts; a family whose counts lack
    ``held_experts`` (the older two) gives nothing, and no error."""
    from chipbench.counts import lfm2_family
    assert not hasattr(lfm2_family, "held_experts")
    assert counts.held_experts({"n_routed_experts": 16}) == 16


def test_a_longcat_share_over_its_roofline_is_an_error_not_a_value(
        longcat_traced):
    run, _ = longcat_traced
    run.trace["scopes"] = {k: dict(v, seconds=v["seconds"] / 100)
                           for k, v in run.trace["scopes"].items()}
    for name in NEW_READERS:
        with pytest.raises(ValueError, match="roofline"):
            reader(name).read(run)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_longcat_run_without_the_names_or_a_trace_gives_nothing(
        longcat_traced, name, tmp_path):
    """A program with no scope of these names (the parent commit's) and
    a run that was not traced: nothing, and no error."""
    run, cfg = longcat_traced
    run.trace["scopes"] = {"jit(_decode_burst_impl)/jit(main)/add":
                           {"seconds": 1.0, "count": 10}}
    run.trace["programs"] = {}
    assert reader(name).read(run) is None
    os.remove(tmp_path / "trace_summary.json")
    assert reader(name).read(RunFiles(str(tmp_path))) is None


def test_the_manifest_names_the_longcat_cell_and_its_three_shares():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    config, = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["file"] == f"chipbench/configs/{CONFIG}.json"
    assert config["source"] == bench_run.load_json(PUBLISHED)[
        "chipbench"]["source"]
    assert len(config["why"]) <= 200
    entry, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert entry == {
        "name": CELL, "config": CONFIG, "traffic": "decode-closed",
        "chips": 1, "why": bench_run.find_cell(CELL)["why"]}
    assert len(entry["why"]) <= 200
    assert "a 32nd of the deployment's" in entry["why"]
    mine = [m for m in manifest["per_layer"] if m["workloads"] == [CELL]]
    assert [m["name"] for m in mine] == NEW_READERS
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m["workloads"]}
    assert listed == set(bench_run.find_cell(CELL)["per_layer"])
    assert len(listed) == 16
    assert {"moe_expert_load", "hybrid_decode_roofline"} <= listed
    assert "state_slots_peak" not in listed      # pages alone
    # The cells and metrics the benchmark had are where they were.
    assert [w["name"] for w in manifest["workloads"]][:4] == [
        "qwen2.5-3b.decode-closed", "qwen3-next-80b-a3b-ep4.decode-closed",
        "jamba2-3b.decode-closed", "lfm2-8b-a1b-ep4.decode-closed"]


# ---- the CPU rehearsal ------------------------------------------------------


def test_the_longcat_rehearsal_runs_end_to_end_on_the_cpu():
    """The real server behind the real router at the tiny size, the
    reference check, the window, the traced side and the result line,
    as ``test_rehearsal.py`` runs the other families'."""
    cell = "rehearsal-longcat"
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
    assert result["metrics"]["kv_pages_peak"]["value"] > 0
    assert result["metrics"]["moe_expert_load"]["value"] >= 1
    # Host threads stood in for the device: no device share from them.
    assert not set(result["metrics"]) & set(NEW_READERS + ["device_idle"])
    run = RunFiles(os.path.join(bench_run.STATE, "runs", cell))
    version = run.cell["version"]
    assert (version["family"], version["kv"], version["kv_writes"]) == (
        "longcat_flash", "latent", "deferred")
    assert version["kv_bytes_per_token"] == 4 * (24 + 8) * 4
    shares = [s["moe_zero_choice_share"] for s in run.window_steps
              if s.get("kind") == "decode"]
    assert shares and all(0 < share < 1 for share in shares)


# ---- the readers on a recorded run of the cell -------------------------------

RECORDED_RUN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "longcat_v5e_run")


def test_the_readers_on_a_recorded_run_give_the_values_its_line_printed():
    """A traced run of the cell on a v5e (PR 41's final tree, seed
    3400000123), cut to what the readers read (``cell.json``, the
    window's step records, the client's timelines, ``reduce.py``'s
    programs and scopes, the slice's ``prefill_chunk`` events) with the
    per-layer values of its result line beside them: the three new
    readers and the two reused ones give those values again, from the
    names the program really wrote."""
    run = RunFiles(RECORDED_RUN)
    with open(os.path.join(RECORDED_RUN, "result_metrics.json")) as f:
        printed = json.load(f)
    assert run.cell["version"]["device_kind"] == "TPU v5 lite"
    assert run.cell["version"]["kv"] == "latent"
    for name in NEW_READERS + ["hybrid_decode_roofline", "moe_expert_load"]:
        assert reader(name).read(run) == pytest.approx(
            printed[name]["value"], rel=1e-9), name
        assert 0 < printed[name]["value"] < 100
    burst = "jit(_decode_burst_deferred_impl)"
    stacks = list(run.trace["scopes"])
    for scope, program in (("mla_decode", burst), ("moe_experts", burst),
                           ("dense_ffn", burst),
                           ("mla_prefill", "jit(_step_impl)")):
        assert any(s.startswith(program) and f"/{scope}/" in s
                   for s in stacks), scope
    # The decode step's latent attention is the Pallas kernel's.
    assert any("mla_decode" in s and "latent_paged_decode_attention" in s
               for s in stacks)
    assert not any(s.startswith(burst) and "/mla_prefill/" in s
                   for s in stacks)
    # Eight sublayers' calls a token-step, four expert branches.
    seconds, events = hybrid_slice.scope_seconds(run.trace, "mla_decode",
                                                 "_decode_burst")
    assert 0.2 < seconds / run.trace["programs"][
        "_decode_burst_deferred_impl"]["seconds"] < 0.4
