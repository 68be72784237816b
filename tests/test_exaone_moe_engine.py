"""EXAONE-MoE through the engine: the scheduler, the cache manager that
owns pages and state slots, the windowed layers' rings in the slot pool
beside the full layers' pages, the eager and the deferred decode burst,
and what start-up refuses (the model and its terms:
tests/test_exaone_moe.py; the window's three forms alone:
tests/test_window_attention.py).

Tiny widths (a window of 16, one tiny page), float32, seeded, on the
CPU. The oracle is the plain reference of the family
(chipbench/reference/exaone_moe_family.py). ``FLOAT32`` 2e-5 on
log-probabilities: both sides float32 on one CPU with the same
weights, differing in the order of sums; the readings are under 2e-6.
``INTERPRET`` 2e-4 where a Pallas kernel in interpret mode sums in
another order.
"""

import dataclasses

import numpy as np
import pytest
from test_exaone_moe import FLOAT32, INTERPRET, model_config, prompt_of

from chipbench.reference import exaone_moe_family as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    OffloadConfig,
    ParallelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models import registry


def engine_config(model=None, **scheduler):
    sched = dict(max_num_seqs=4, max_model_len=256, prefill_chunk_size=32,
                 prefill_batch_size=2, decode_steps=4)
    sched.update(scheduler)
    return EngineConfig(
        model=model or model_config(),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(**sched))


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


@pytest.mark.parametrize("form", [
    "eager", "deferred", "deferred bursts longer than the window",
    "deferred pallas-interpret", "deferred rank 1 of 2"])
def test_engine_prefill_chunks_and_bursts_agree_with_the_reference(form):
    """Through the scheduler, the cache manager and the decode burst:
    six prompts over four rows (two wait for a row and take a slot
    whose rings another left full), prompts of up to three chunks of 32
    (two windows: the mask inside a chunk and the ring between chunks),
    21 answers each in bursts of four steps (of 24: a tail longer than
    the window) through pages, rings and (deferred) tails whose flush
    wraps; logits and not tokens: the top log-probabilities of every
    answer agree. ``rank 1 of 2`` holds the upper half of the experts:
    the reference is given the same share."""
    over, steps = {}, 4
    if "pallas" in form:
        over = dict(attention_impl="pallas-interpret")
    if "rank" in form:
        over = dict(num_experts=4, expert_parallel_size=2,
                    expert_parallel_rank=1)
    if "longer" in form:
        steps = 24
    engine = LLMEngine(engine_config(
        model_config(**over), decode_steps=steps,
        deferred_kv_writes=form.startswith("deferred")))
    prompts = [prompt_of(n, seed=n) for n in (70, 20, 45, 33, 64, 12)]
    ids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=21, ignore_eos=True, logprobs=True,
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
    assert worst < (INTERPRET if "pallas" in form else FLOAT32)
    stats = engine.stats()
    assert stats["engine_state_slots_total"] == 6   # 4 rows + 2 prefill
    assert 4 <= most <= 6
    assert stats["engine_state_slots_used"] == 0


def test_a_recycled_slot_needs_no_clearing_and_a_recompute_starts_afresh():
    prompts = [prompt_of(40, seed=7), prompt_of(37, seed=8),
               prompt_of(52, seed=9)]
    alone = [greedy(LLMEngine(engine_config()), [p])[0].output_token_ids
             for p in prompts]
    # One row, so every request takes the slot the last one left full:
    # a place is in sight only while the row's own length says so.
    engine = LLMEngine(engine_config(max_num_seqs=1, prefill_batch_size=1))
    assert engine.cache_manager.num_state_slots == 2
    assert [s.output_token_ids for s in greedy(engine, prompts)] == alone
    # Preempted in the middle of decoding: pages and slot go back, the
    # rings are lost with the slot, and the sequence is recomputed from
    # position 0 into whatever slot it is given.
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


def test_pages_count_the_full_layer_alone_and_slots_the_rings():
    """A row of 70 + 9 tokens holds five pages of 16 whatever the
    three windowed layers keep: their K/V is the slot's, 16 places a
    layer for ever. The page budget and the bytes a token follow."""
    engine = LLMEngine(engine_config())
    config = engine.config
    assert config.model.num_kv_layers == 1
    # K and V of one full layer: 2 x 2 heads x 16 x 4 B.
    assert config.cache.kv_bytes_per_token(config.model) == 256
    # Three windowed layers x (K ring + V ring) of 2 x 16 x 16 x 4 B.
    assert config.model.recurrent_state_bytes() == 3 * 2 * 2048
    ids = [engine.add_request(prompt_of(70, seed=3), SamplingParams(
        temperature=0.0, max_tokens=9, ignore_eos=True))]
    seq, most, slots = engine.sequences[ids[0]], 0, 0
    while seq.state.name not in ("FINISHED", "ABORTED"):
        engine.step()
        most = max(most, len(seq.pages))
        slots = max(slots, engine.cache_manager.num_used_state_slots)
    assert most == 5 and slots == 1         # ceil(79 / 16) pages, one slot
    ledger = engine.runner.observatory.hbm_bytes()
    assert ledger["recurrent_state"] == 7 * 3 * 2 * 2048   # 6 + the trash


def test_the_counters_count_the_expert_layers_and_the_windows_keys():
    """``layer_steps`` counts the three expert layers (layer 0 is
    dense); ``swa_keys`` over ``swa_queries`` is the window, 16, on
    rows longer than it, at every step of a burst: the ring's places
    go out of sight one a step as the tail grows."""
    engine = LLMEngine(engine_config(deferred_kv_writes=True))
    read, seen = engine.runner.read_moe_stats, []

    def record():
        seen.append(read())
        return seen[-1]

    engine.runner.read_moe_stats = record
    greedy(engine, [prompt_of(20, seed=1), prompt_of(17, seed=2)],
           max_tokens=9)
    bursts = [s for s in seen if s]
    assert len(bursts) == 2                 # 1 from prefill + 4 + 4
    for stats in bursts:
        assert stats["layer_steps"] == 4 * 3        # steps x expert layers
        assert stats["choices"] == 2 * 4 * 3 * 3    # top-3, two rows
        assert stats["held_choices"] == stats["choices"]   # all held
        assert stats["swa_queries"] == 2 * 4 * 3    # rows x steps x layers
        assert stats["swa_keys"] == 16 * stats["swa_queries"]
    assert engine.runner.read_moe_stats() is None          # zeroed
    note = engine.metrics.on_moe_stats(bursts[0])
    assert note["swa_keys_mean"] == 16.0
    assert "swa_keys_mean" not in engine.metrics.moe_last


def test_start_up_refuses_in_one_message_what_is_true_of_this_family():
    with pytest.raises(ValueError) as refusal:
        EngineConfig(
            model=model_config(quantization="int8"),
            parallel=ParallelConfig(tensor_parallel_size=2),
            offload=OffloadConfig(enable=True),
            scheduler=SchedulerConfig(speculative_k=2, unified_step=True))
    message = str(refusal.value)
    assert message.startswith(
        "exaone_moe keeps a recurrent state beside its pages; refused: ")
    for feature in ("KV offload", "speculative decoding",
                    "the unified ragged step", "tensor parallelism",
                    "weight quantization"):
        assert feature in message
    # Its own words.
    assert "the rings' pools and the expert layer" in message
    assert "the experts have no quantized form" in message
    # A window that is no whole number of pages.
    with pytest.raises(ValueError, match="sliding_window 16 is not a "
                                         "whole number of pages of 32"):
        EngineConfig(model=model_config(),
                     cache=CacheConfig(page_size=32, num_pages=16))


def test_the_family_declares_its_rings_and_the_engine_names_no_model():
    import inspect

    from production_stack_tpu.engine import engine as engine_module
    from production_stack_tpu.engine import model_runner, scheduler
    fam = registry.family("exaone_moe")
    assert fam.ring and fam.deferred_kv and not fam.conv_tail
    assert fam.counters == registry.family("lfm2_moe").counters + (
        "swa_keys", "swa_queries")
    assert set(fam.refusals) == {"tensor parallelism",
                                 "weight quantization"}
    config = model_config()
    assert registry.state_pools(config) == (
        ((2, 16, 16), "model"), ((2, 16, 16), "model"))
    k_cache, v_cache = registry.init_hybrid_cache(
        dataclasses.replace(config, dtype="bfloat16"), 8, 16, 3)
    # A ring's pool is a plane whose pages are the slots (3 + the
    # trash); the full layer's are the pages.
    ring, pages = ((2, 4, 16, 16), "bfloat16"), ((2, 8, 16, 16), "bfloat16")
    assert [(a.shape, str(a.dtype)) for a in k_cache] == [
        ring, ring, pages, ring, ((8,), "float32")]
    assert [(a.shape, str(a.dtype)) for a in v_cache] == [
        ring, ring, pages, ring]
    for module in (model_runner, scheduler, engine_module):
        assert "exaone" not in inspect.getsource(module).lower()
    assert "exaone_moe" in registry.deferred_kv_architectures()
