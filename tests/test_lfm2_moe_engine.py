"""LFM2-MoE through the engine: the scheduler, the cache manager that
owns pages and state slots, a slot pool that holds the convolution's
tail and nothing else, the eager and the deferred decode burst, and
what start-up refuses (the model and its ops: tests/test_lfm2_moe.py;
the bursts' carried tails: tests/test_conv_tails_burst.py, where this
family is one of the hybrids).

Tiny widths, float32, seeded, on the CPU. The oracle is the plain
reference of the family (chipbench/reference/lfm2_family.py).
``FLOAT32`` 2e-5 on log-probabilities: both sides float32 on one CPU
with the same weights, differing in the order of sums; the readings
are under 2e-6.
"""

import dataclasses

import numpy as np
import pytest

from chipbench.reference import lfm2_family as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    OffloadConfig,
    ParallelConfig,
    SchedulerConfig,
    tiny_lfm2_moe_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models import registry

FLOAT32 = 2e-5


def model_config(**over):
    config = tiny_lfm2_moe_config()
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
                                  "deferred pallas-interpret-decode",
                                  "deferred rank 1 of 2"])
def test_engine_prefill_chunks_and_bursts_agree_with_the_reference(form):
    """Through the scheduler, the cache manager and the decode burst:
    six prompts over four rows (two wait for a row and take a slot
    another left full), prompts of up to three chunks, bursts of four
    steps through pages, slots and (deferred) dense tails; the top
    log-probabilities of every answer agree. ``rank 1 of 2`` holds the
    upper half of the experts: the reference is given the same share."""
    over = {}
    if "pallas" in form:
        # ``-decode`` is what ``auto`` resolves on the chip: the
        # Pallas kernels, the paged decode kernel beside the burst's
        # tail among them. The other is its fallback where the decode
        # probe fails: the Pallas kernels beside XLA decode attention.
        over = dict(attention_impl="pallas-interpret")
        if not form.endswith("-decode"):
            over["attention_impl_decode"] = "xla"
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
    most = 0
    while any(s.state.name not in ("FINISHED", "ABORTED") for s in seqs):
        for out in engine.step():
            if out.new_token is not None:
                served[out.seq_id].append(out.logprobs)
        most = max(most, engine.cache_manager.num_used_state_slots)
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
    assert stats["engine_state_slots_total"] == 6   # 4 rows + 2 prefill
    assert 4 <= most <= 6
    assert stats["engine_state_slots_used"] == 0
    # Five layers and the counters; a conv layer owns no k entry.
    assert [e is None for e in engine.runner.k_cache] == [
        True, False, True, True, False, False]


def test_a_recycled_slot_needs_no_clearing_and_a_recompute_starts_afresh():
    prompts = [prompt_of(40, seed=7), prompt_of(37, seed=8),
               prompt_of(52, seed=9)]
    alone = [greedy(LLMEngine(engine_config()), [p])[0].output_token_ids
             for p in prompts]
    # One row, so every request takes the slot the last one left full.
    engine = LLMEngine(engine_config(max_num_seqs=1, prefill_batch_size=1))
    assert engine.cache_manager.num_state_slots == 2
    assert [s.output_token_ids for s in greedy(engine, prompts)] == alone
    # Preempted in the middle of decoding: pages and slot go back, the
    # sequence is recomputed from position 0 into whatever slot it is
    # given.
    engine = LLMEngine(engine_config())
    ids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=9, ignore_eos=True)) for p in prompts]
    seqs = [engine.sequences[i] for i in ids]
    while len(seqs[0].output_token_ids) < 3:
        engine.step()
    assert seqs[0].state_slot
    engine.scheduler._preempt(seqs[0])
    assert seqs[0].state_slot is None and not seqs[0].pages
    finish(engine, seqs)
    assert seqs[0].all_token_ids[40:] == alone[0]
    assert [s.output_token_ids for s in seqs[1:]] == alone[1:]
    assert engine.cache_manager.num_used_state_slots == 0


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


def test_the_expert_counters_count_the_expert_layers_alone():
    """``layer_steps`` counts expert layers: four of the five here (the
    first feed-forward is dense), 22 of 24 at the published depth."""
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
        assert stats["layer_steps"] == 4 * 4        # steps x layers
        assert stats["choices"] == 2 * 4 * 4 * 2    # top-2, two rows
        assert stats["held_choices"] == stats["choices"]   # all held
        assert 1 <= stats["experts_hit"] / stats["layer_steps"] <= 4
    assert engine.runner.read_moe_stats() is None          # zeroed
    assert engine.stats()["engine_moe_held_choice_share"] == 1.0


def test_a_prefix_hit_is_declined_and_counted():
    engine = LLMEngine(engine_config())
    assert engine.config.cache.enable_prefix_caching
    prompt = prompt_of(70, seed=11)
    first = greedy(engine, [prompt])[0]
    declined = engine.cache_manager.prefix_declined_tokens
    second = greedy(engine, [prompt])[0]
    assert engine.cache_manager.prefix_declined_tokens - declined == 64
    assert engine.cache_manager.prefix_hit_tokens == 0
    assert second.output_token_ids == first.output_token_ids


def test_start_up_refuses_in_one_message_what_is_true_of_this_family():
    with pytest.raises(ValueError) as refusal:
        EngineConfig(
            model=model_config(quantization="int8"),
            parallel=ParallelConfig(tensor_parallel_size=2),
            offload=OffloadConfig(enable=True),
            scheduler=SchedulerConfig(speculative_k=2, unified_step=True))
    message = str(refusal.value)
    assert message.startswith(
        "lfm2_moe keeps a recurrent state beside its pages; refused: ")
    for feature in ("KV offload", "speculative decoding",
                    "the unified ragged step", "tensor parallelism",
                    "weight quantization"):
        assert feature in message
    # Its own words: a tail pool and experts, no mixer and no delta rule.
    assert "convolution's tail pool and the expert layer" in message
    assert "convolution's fused projection and the experts" in message
    assert "Mamba" not in message and "state pools" not in message


LFM2_8B_A1B = dict(
    model_type="lfm2_moe", conv_L_cache=3, conv_bias=False,
    hidden_size=2048, intermediate_size=7168,
    layer_types=["conv" if c == "c" else "full_attention"
                 for c in "ccAcccAcccAcccAcccAccAcc"],
    max_position_embeddings=128000, moe_intermediate_size=1792,
    norm_eps=1e-5, norm_topk_prob=True, num_attention_heads=32,
    num_dense_layers=2, num_experts=32, num_experts_per_tok=4,
    num_hidden_layers=24, num_key_value_heads=8, rope_theta=1000000,
    routed_scaling_factor=1, use_expert_bias=True, vocab_size=65536)


def test_the_published_config_is_read_as_the_family():
    config = ModelConfig.from_hf_config(LFM2_8B_A1B)
    assert config.architecture == "lfm2_moe"
    # The list, not a period: the last attention layer is at 21.
    assert [i for i, c in enumerate(config.layer_is_linear) if not c] == [
        2, 6, 10, 14, 18, 21]
    assert (config.head_dim, config.rms_norm_eps) == (64, 1e-5)
    assert config.tie_word_embeddings
    assert (config.router_width, config.num_experts) == (32, 32)
    assert config.recurrent_state_shapes() == (None, (2, 2048))
    assert config.recurrent_state_bytes() == 147456
    # The class name names it too, and a chip's share is read as the
    # other hybrid's is: the key counts the experts held.
    share = ModelConfig.from_hf_config(dict(
        LFM2_8B_A1B, architectures=["Lfm2MoeForCausalLM"], num_experts=8,
        expert_parallel_size=4, expert_parallel_rank=3))
    assert (share.architecture, share.router_width) == ("lfm2_moe", 32)
    assert share.expert_parallel_rank * share.num_experts == 24


@pytest.mark.parametrize("change,word", [
    (dict(layer_types=["conv", "sliding_attention"] + ["conv"] * 22),
     r"layer_types entries \['sliding_attention'\]"),
    (dict(layer_types=["conv"] * 23), "layer_types lists 23 layers"),
    (dict(conv_bias=True), "conv_bias true"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(use_expert_bias=False), "use_expert_bias false"),
    (dict(norm_topk_prob=False), "norm_topk_prob false"),
    (dict(routed_scaling_factor=2.5), "routed_scaling_factor 2.5"),
    (dict(expert_parallel_size=4, expert_parallel_rank=4),
     "expert_parallel_rank 4 is not one of"),
])
def test_an_lfm2_this_engine_does_not_serve_is_refused_in_words(
        change, word):
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(dict(LFM2_8B_A1B, **change))


def test_an_lfm2_checkpoint_is_refused(tmp_path):
    from production_stack_tpu.engine.weights import load_weights
    with pytest.raises(NotImplementedError, match="--random-weights"):
        load_weights(str(tmp_path), model_config())


def test_a_family_that_declares_the_tail_alone_owns_no_other_pool():
    """One state entry: the tail in ``v_cache``, ``None`` in
    ``k_cache``; the families that declare two keep their pools as
    they were, shape for shape and dtype for dtype."""
    fam = registry.family("lfm2_moe")
    assert fam.conv_tail and fam.deferred_kv and len(fam.counters) == 6
    assert fam.counters == registry.family("qwen3_next").counters
    assert set(fam.refusals) == {"tensor parallelism",
                                 "weight quantization"}
    config = model_config()
    assert registry.state_pools(config) == (None, ((2, 64), "model"))
    k_cache, v_cache = registry.init_hybrid_cache(config, 8, 16, 3)
    assert [None if a is None else a.shape for a in k_cache] == [
        None, (2, 8, 16, 16), None, None, (2, 8, 16, 16), (6,)]
    assert [a.shape for a in v_cache] == [
        (4, 2, 64), (2, 8, 16, 16), (4, 2, 64), (4, 2, 64),
        (2, 8, 16, 16)]
    assert {a.dtype for a in v_cache} == {np.dtype("float32")}
    bf16 = registry.init_hybrid_cache(
        dataclasses.replace(config, dtype="bfloat16"), 8, 16, 3)
    assert bf16[1][0].dtype == "bfloat16" and bf16[0][-1].dtype == "float32"

    import test_jamba_engine
    import test_qwen3_next_engine
    k_cache, v_cache = registry.init_hybrid_cache(
        test_jamba_engine.model_config(), 8, 16, 3)
    assert [(a.shape, str(a.dtype)) for a in k_cache] == [
        ((4, 8, 128), "float32"), ((1, 8, 16, 16), "float32"),
        ((4, 8, 128), "float32"), ((4, 8, 128), "float32")]
    assert [(a.shape, str(a.dtype)) for a in v_cache] == [
        ((4, 3, 128), "float32"), ((1, 8, 16, 16), "float32"),
        ((4, 3, 128), "float32"), ((4, 3, 128), "float32")]
    hybrid = dataclasses.replace(test_qwen3_next_engine.model_config(),
                                 dtype="bfloat16")
    k_cache, v_cache = registry.init_hybrid_cache(hybrid, 8, 16, 3)
    assert (k_cache[0].shape, str(k_cache[0].dtype)) == (
        (4, 4, 16, 16), "float32")
    assert (v_cache[0].shape, str(v_cache[0].dtype)) == (
        (4, 3, 128), "bfloat16")
    assert k_cache[2].shape == (2, 8, 32, 16) and k_cache[-1].shape == (6,)
    assert hybrid.recurrent_state_shapes() == ((4, 16, 16), (3, 128))
    # A family may not declare three.
    three = dataclasses.replace(fam, state=lambda c: (((1,), "model"),) * 3)
    registry.FAMILIES["three"] = three
    try:
        with pytest.raises(ValueError, match="declares 3 state entries"):
            registry.state_pools(dataclasses.replace(
                config, architecture="three"))
    finally:
        del registry.FAMILIES["three"]


def test_the_runner_and_the_engine_name_no_model():
    import inspect

    from production_stack_tpu.engine import model_runner
    source = inspect.getsource(model_runner)
    for module in ("models.lfm2_moe", "models.jamba", "models.qwen3_next"):
        assert module not in source
    assert "lfm2_moe" in registry.list_architectures()
    assert "lfm2_moe" in registry.deferred_kv_architectures()


def test_the_memory_ledger_counts_the_tails_alone():
    engine = LLMEngine(engine_config())
    # Three conv layers' [2, 64] float32 tails; six slots and the trash.
    assert engine.config.model.recurrent_state_bytes() == 3 * 2 * 64 * 4
    assert engine.runner.observatory.hbm_bytes()[
        "recurrent_state"] == 7 * 3 * 2 * 64 * 4
