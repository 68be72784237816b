"""LongCat-Flash through the engine: the scheduler, the page allocator,
the latent planes the family declares (two a layer, one plane each, no
second plane), the eager and the deferred decode burst with its latent
tails, preemption and recompute, the bytes a token and the page budget,
and what start-up refuses (the model and its ops:
tests/test_longcat_flash.py).

Tiny widths, float32, seeded, on the CPU. The oracle is the plain
reference of the family (chipbench/reference/longcat_family.py).
``FLOAT32`` 2e-5 on log-probabilities: both sides float32 on one CPU
with the same weights, differing in the order of sums; the readings
are under 2e-6.
"""

import dataclasses

import numpy as np
import pytest

from chipbench.reference import longcat_family as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    OffloadConfig,
    ParallelConfig,
    SchedulerConfig,
    tiny_longcat_flash_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models import registry

FLOAT32 = 2e-5


def model_config(**over):
    config = tiny_longcat_flash_config()
    config.attention_impl = "xla"
    return dataclasses.replace(config, **over)


def engine_config(model=None, **scheduler):
    sched = dict(max_num_seqs=4, max_model_len=256, prefill_chunk_size=32,
                 prefill_batch_size=2, decode_steps=4)
    sched.update(scheduler)
    return EngineConfig(
        model=model or model_config(),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(**sched))


def prompt_of(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, 512, size=n)]


def finish(engine, seqs):
    while any(s.state.name not in ("FINISHED", "ABORTED") for s in seqs):
        engine.step()


def greedy(engine, prompts, max_tokens=9):
    ids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True))
        for p in prompts]
    seqs = [engine.sequences[i] for i in ids]
    finish(engine, seqs)
    return seqs


@pytest.mark.parametrize("form", ["eager", "deferred",
                                  "deferred pallas-interpret",
                                  "deferred rank 1 of 2"])
def test_engine_prefill_chunks_and_bursts_agree_with_the_reference(form):
    """Through the scheduler, the allocator and the decode burst: six
    prompts over four rows, prompts of up to three chunks (a later
    chunk reads the earlier ones' latents back from the pages), bursts
    of four steps through the pages and (deferred) the latent tails;
    the top log-probabilities of every answer agree, logits and not
    tokens alone. ``pallas-interpret`` is what ``auto`` resolves on the
    chip: the latent decode kernel beside the burst's tail. ``rank 1
    of 2`` holds the upper half of the routed experts: the reference is
    given the same share."""
    over = {}
    if "pallas" in form:
        over = dict(attention_impl="pallas-interpret")
    if "rank" in form:
        over = dict(num_experts=4, expert_parallel_size=2,
                    expert_parallel_rank=1)
    engine = LLMEngine(engine_config(
        model_config(**over),
        deferred_kv_writes=form.startswith("deferred")))
    prompts = [prompt_of(n, seed=n) for n in (70, 20, 45, 33, 64, 12)]
    ids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=9, ignore_eos=True, logprobs=True,
        top_logprobs=5)) for p in prompts]
    seqs = [engine.sequences[i] for i in ids]
    served = {i: [] for i in ids}
    while any(s.state.name not in ("FINISHED", "ABORTED") for s in seqs):
        for out in engine.step():
            if out.new_token is not None:
                served[out.seq_id].append(out.logprobs)
    ref = reference.model_of(engine.config.model, engine.runner.params)
    worst = 0.0
    for prompt, seq in zip(prompts, seqs):
        tokens = prompt + seq.output_token_ids
        want = np.asarray(reference.log_probs(
            ref, tokens, list(range(len(prompt) - 1, len(tokens) - 1))))
        assert seq.output_token_ids == [int(t) for t in want.argmax(-1)]
        for j, entry in enumerate(served[seq.seq_id]):
            assert len(entry[1]) == 5
            for tid, lp in entry[1]:
                worst = max(worst, abs(lp - want[j, tid]))
    assert worst < (2e-4 if "pallas" in form else FLOAT32)
    stats = engine.stats()
    assert stats["engine_state_slots_total"] == 0     # pages alone
    assert 0 < stats["engine_moe_zero_choice_share"] < 1
    # Four latent planes and the counters; no second plane anywhere.
    assert [e.shape for e in engine.runner.k_cache] == [
        (1, 64, 32, 16)] * 4 + [(7,)]
    assert engine.runner.v_cache == (None,) * 4


def test_a_preempted_request_frees_its_latent_pages_and_is_recomputed():
    prompts = [prompt_of(40, seed=7), prompt_of(37, seed=8),
               prompt_of(52, seed=9)]
    alone = [greedy(LLMEngine(engine_config()), [p])[0].output_token_ids
             for p in prompts]
    engine = LLMEngine(engine_config(deferred_kv_writes=True))
    ids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=9, ignore_eos=True)) for p in prompts]
    seqs = [engine.sequences[i] for i in ids]
    while len(seqs[0].output_token_ids) < 3:
        engine.step()
    free = engine.cache_manager.num_free_pages
    held = len(seqs[0].pages)
    assert held >= 3
    engine.scheduler._preempt(seqs[0])
    assert not seqs[0].pages
    assert engine.cache_manager.num_free_pages == free + held
    finish(engine, seqs)
    assert seqs[0].all_token_ids[40:] == alone[0]
    assert [s.output_token_ids for s in seqs[1:]] == alone[1:]


@pytest.mark.parametrize("deferred", [False, True])
def test_a_row_that_stops_inside_a_burst_stops_there(deferred):
    def tokens(steps):
        engine = LLMEngine(engine_config(
            decode_steps=steps, deferred_kv_writes=deferred and steps > 1))
        ids = [engine.add_request(prompt_of(n, seed=n), SamplingParams(
            temperature=0.0, max_tokens=m, ignore_eos=True))
            for n, m in ((20, 3), (25, 9))]
        seqs = [engine.sequences[i] for i in ids]
        finish(engine, seqs)
        return [s.output_token_ids for s in seqs]

    assert tokens(4) == tokens(1)


def test_the_expert_counters_and_the_zero_choices():
    """The family's five counters a burst as the expert cells have
    them, and one more: the choices that fell on zero-compute experts.
    Every choice is a held one or a zero one when one rank holds all
    the routed experts."""
    engine = LLMEngine(engine_config(deferred_kv_writes=True))
    read, seen = engine.runner.read_moe_stats, []

    def record():
        seen.append(read())
        return seen[-1]

    engine.runner.read_moe_stats = record
    greedy(engine, [prompt_of(20, seed=1), prompt_of(11, seed=2)],
           max_tokens=9)
    bursts = [s for s in seen if s]
    assert len(bursts) == 2                 # 1 from prefill + 4 + 4
    for stats in bursts:
        assert stats["layer_steps"] == 4 * 2        # steps x layers
        assert stats["choices"] == 2 * 4 * 2 * 3    # top-3, two rows
        assert (stats["held_choices"] + stats["zero_choices"]
                == stats["choices"])
        assert 0 < stats["zero_choices"] < stats["choices"]
    assert engine.runner.read_moe_stats() is None          # zeroed
    stats = engine.stats()
    assert (stats["engine_moe_held_choice_share"]
            + stats["engine_moe_zero_choice_share"]) == pytest.approx(1.0)
    assert engine.metrics.moe_last["moe_experts_hit"] > 0


def test_a_prefix_hit_reuses_latent_pages():
    """A page is a page: the allocator and the prefix cache are
    untouched, and a second request with the same prompt skips the
    whole pages of it."""
    engine = LLMEngine(engine_config())
    assert engine.config.cache.enable_prefix_caching
    prompt = prompt_of(70, seed=11)
    first = greedy(engine, [prompt])[0]
    second = greedy(engine, [prompt])[0]
    assert engine.cache_manager.prefix_hit_tokens == 64
    assert second.output_token_ids == first.output_token_ids


def test_the_bytes_a_token_and_the_page_budget_are_the_arrays_own():
    """``kv_bytes_per_token`` against the bytes of the arrays the
    runner made: one latent of 24 + 8 float32 values a token in each of
    four sublayers, stored once; the ledger, the step gauge and the
    cache arrays agree, and nothing counts a second plane."""
    engine = LLMEngine(engine_config())
    config = engine.config
    assert config.cache.cache_layout == "per_layer"
    per_token = config.cache.kv_bytes_per_token(config.model)
    assert per_token == 4 * (24 + 8) * 4
    planes = engine.runner.k_cache[:-1]
    assert sum(p.nbytes for p in planes) == 64 * 16 * per_token
    assert engine.runner.observatory.hbm_bytes()["kv_pages"] == sum(
        p.nbytes for p in planes)
    assert engine.stats()["engine_kv_bytes_per_decode_step"] == 4 * per_token
    assert "recurrent_state" not in engine.runner.observatory.hbm_bytes()
    # A K/V family's count is what it was: two planes a layer.
    from production_stack_tpu.engine.config import tiny_model_config
    llama = tiny_model_config("llama")
    assert llama.page_cache == (llama.num_hidden_layers,
                                llama.num_key_value_heads, llama.head_dim, 2)
    assert CacheConfig().kv_bytes_per_token(llama) == (
        2 * llama.num_hidden_layers * llama.num_key_value_heads
        * llama.head_dim * np.dtype(llama.jax_dtype).itemsize)


def test_at_the_cells_sizes_a_page_is_1179648_bytes():
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs",
        "longcat-flash-omni-ep32.json")
    with open(path) as f:
        hf = json.load(f)
    flags = hf.pop("chipbench")["server_flags"]
    config = ModelConfig.from_hf_config(hf)
    cache = CacheConfig(page_size=flags["page-size"],
                        num_pages=flags["num-pages"])
    assert cache.kv_bytes_per_token(config) == 9216
    import jax
    k_cache, v_cache = jax.eval_shape(lambda: registry.init_hybrid_cache(
        config, cache.num_pages, cache.page_size, 0))
    assert [e.shape for e in k_cache[:-1]] == [
        (1, flags["num-pages"], 576, 128)] * 8
    assert k_cache[-1].shape == (7,) and v_cache == (None,) * 8
    nbytes = sum(int(np.prod(e.shape)) * e.dtype.itemsize
                 for e in k_cache[:-1])
    assert nbytes == flags["num-pages"] * 1179648


def test_start_up_refuses_in_one_message_what_is_true_of_this_family():
    with pytest.raises(ValueError) as refusal:
        EngineConfig(
            model=model_config(quantization="int8"),
            cache=CacheConfig(kv_cache_dtype="int8",
                              cache_layout="stacked"),
            parallel=ParallelConfig(tensor_parallel_size=2),
            offload=OffloadConfig(enable=True),
            engine_role="prefill",
            checkpoint_interval_tokens=64,
            scheduler=SchedulerConfig(unified_step=True))
    message = str(refusal.value)
    assert message.startswith(
        "longcat_flash caches one latent a token a sublayer in the place "
        "of a (K, V) pair; refused: ")
    for feature in ("int8 KV pages", "disaggregated prefill/decode",
                    "KV offload", "mid-stream checkpoint descriptors",
                    "tensor parallelism", "weight quantization",
                    "the unified ragged step", "cache_layout='stacked'"):
        assert feature in message
    # Its own words.
    assert "the latent is one head shared by every query head" in message
    assert "the low-rank projections and the experts" in message
    assert "recurrent" not in message
    for parallel, feature in (
            (ParallelConfig(pipeline_parallel_size=2),
             "pipeline-parallel serving"),
            (ParallelConfig(context_parallel_size=2),
             "context-parallel prefill")):
        with pytest.raises(ValueError, match=feature):
            EngineConfig(model=model_config(), parallel=parallel)
    with pytest.raises(ValueError, match="speculative decoding"):
        EngineConfig(model=model_config(),
                     scheduler=SchedulerConfig(speculative_k=2))


LONGCAT_FLASH = dict(
    attention_bias=False, vocab_size=131072, hidden_size=6144,
    ffn_hidden_size=12288, expert_ffn_hidden_size=2048, num_layers=28,
    num_attention_heads=64, kv_lora_rank=512, q_lora_rank=1536,
    qk_rope_head_dim=64, v_head_dim=128, qk_nope_head_dim=128,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6,
    n_routed_experts=512, max_position_embeddings=131072,
    rms_norm_eps=1e-05, rope_theta=10000000, attention_method="MLA",
    zero_expert_num=256, zero_expert_type="identity", moe_topk=12,
    model_type="longcat_flash")


def test_the_published_config_is_read_as_the_family():
    config = ModelConfig.from_hf_config(LONGCAT_FLASH)
    assert config.architecture == "longcat_flash"
    assert (config.num_hidden_layers, config.intermediate_size) == (28, 12288)
    assert (config.head_dim, config.num_key_value_heads) == (192, 1)
    assert (config.mla_q_scale, config.routed_scaling_factor) == (2.0, 6.0)
    assert config.router_width == 512 + 256 and not config.tie_word_embeddings
    assert config.page_cache == (56, 1, 576, 1)
    assert config.has_latent_cache and not config.has_recurrent_state
    assert config.cache_entry_is_state == (False,) * 56
    # The class name names it too; a chip's share counts the held.
    share = ModelConfig.from_hf_config(dict(
        LONGCAT_FLASH, architectures=["LongcatFlashForCausalLM"],
        n_routed_experts=16, expert_parallel_size=32,
        expert_parallel_rank=3))
    assert (share.architecture, share.router_width) == (
        "longcat_flash", 16 * 32 + 256)
    assert share.expert_parallel_rank * share.num_experts == 48
    off = ModelConfig.from_hf_config(dict(
        LONGCAT_FLASH, mla_scale_q_lora=False, mla_scale_kv_lora=False))
    assert (off.mla_q_scale, off.mla_kv_scale) == (1.0, 1.0)


@pytest.mark.parametrize("change,word", [
    (dict(attention_method="MHA"), "attention_method 'MHA'"),
    (dict(q_lora_rank=None), "q_lora_rank unset"),
    (dict(zero_expert_type="copy"), "zero_expert_type 'copy'"),
    (dict(attention_bias=True), "attention_bias true"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(norm_topk_prob=True), "norm_topk_prob true"),
    (dict(hidden_act="gelu"), "hidden_act 'gelu'"),
    (dict(expert_parallel_size=32, expert_parallel_rank=32),
     "expert_parallel_rank 32 is not one of"),
])
def test_a_longcat_this_engine_does_not_serve_is_refused_in_words(
        change, word):
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(dict(LONGCAT_FLASH, **change))


def test_a_longcat_checkpoint_is_refused(tmp_path):
    from production_stack_tpu.engine.weights import load_weights
    with pytest.raises(NotImplementedError, match="--random-weights"):
        load_weights(str(tmp_path), model_config())


def test_the_family_declares_its_pages_and_the_others_are_what_they_were():
    fam = registry.family("longcat_flash")
    assert fam.deferred_kv and not fam.conv_tail
    assert fam.recurrent_layers is None and fam.state is None
    assert fam.counters == registry.family("qwen3_next").counters + (
        "zero_choices",)
    assert set(fam.refusals) == {"tensor parallelism",
                                 "weight quantization"}
    config = model_config()
    assert registry.page_cache(config) == registry.PageCache(
        entries=4, heads=1, width=32, planes=1)
    k_cache, v_cache = registry.init_hybrid_cache(config, 8, 16, 0)
    assert [a.shape for a in k_cache] == [(1, 8, 32, 16)] * 4 + [(7,)]
    assert v_cache == (None,) * 4
    # The hybrids' caches, shape for shape.
    import test_lfm2_moe_engine
    lfm2 = test_lfm2_moe_engine.model_config()
    assert registry.page_cache(lfm2) == (2, 2, 16, 2)
    assert lfm2.cache_entry_is_state == lfm2.layer_is_linear
    k_cache, v_cache = registry.init_hybrid_cache(lfm2, 8, 16, 3)
    assert [None if a is None else a.shape for a in k_cache] == [
        None, (2, 8, 16, 16), None, None, (2, 8, 16, 16), (6,)]
    assert [a.shape for a in v_cache] == [
        (4, 2, 64), (2, 8, 16, 16), (4, 2, 64), (4, 2, 64),
        (2, 8, 16, 16)]


def test_the_runner_and_the_engine_name_no_model():
    import inspect

    from production_stack_tpu.engine import model_runner
    source = inspect.getsource(model_runner)
    assert "longcat" not in source
    assert "longcat_flash" in registry.list_architectures()
    assert "longcat_flash" in registry.deferred_kv_architectures()
