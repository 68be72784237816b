"""Granite-MoE-hybrid through the engine: the scheduler, the cache
manager that owns pages and state slots, a state pool of Mamba-2 ``h``
beside the convolution's tails, the eager and the deferred decode
burst, and what start-up refuses (the model and its terms:
tests/test_granitemoehybrid.py; the recurrence: tests/test_ssd.py).

Tiny widths, float32, seeded, on the CPU. The oracle is the plain
reference of the family
(chipbench/reference/granitemoehybrid_family.py). ``FLOAT32`` 2e-5 on
log-probabilities: both sides float32 on one CPU with the same
weights, differing in the order of sums; the readings are under 2e-6.
``INTERPRET`` 2e-4 where a Pallas kernel in interpret mode sums in
another order.
"""

import dataclasses

import numpy as np
import pytest
from test_granitemoehybrid import (
    FLOAT32,
    INTERPRET,
    model_config,
    prompt_of,
)

from chipbench.reference import granitemoehybrid_family as reference
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


@pytest.mark.parametrize("form", ["eager", "deferred",
                                  "deferred pallas-interpret-decode",
                                  "deferred rank 1 of 2"])
def test_engine_prefill_chunks_and_bursts_agree_with_the_reference(form):
    """Through the scheduler, the cache manager and the decode burst:
    six prompts over four rows (two wait for a row and take a slot
    another left full), prompts of up to three chunks of 32 (four Mamba
    chunks of 8 each), bursts of four steps through pages, slots and
    (deferred) dense tails; logits and not tokens: the top
    log-probabilities of every answer agree. ``rank 1 of 2`` holds the
    upper half of the experts: the reference is given the same share."""
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


def test_the_expert_counters_count_every_layer():
    """``layer_steps`` counts expert layers: all four here, all ten of
    the cell's."""
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
        assert stats["choices"] == 2 * 4 * 4 * 3    # top-3, two rows
        assert stats["held_choices"] == stats["choices"]   # all held
        assert 1 <= stats["experts_hit"] / stats["layer_steps"] <= 6
    assert engine.runner.read_moe_stats() is None          # zeroed
    assert engine.stats()["engine_moe_held_choice_share"] == 1.0


def test_start_up_refuses_in_one_message_what_is_true_of_this_family():
    with pytest.raises(ValueError) as refusal:
        EngineConfig(
            model=model_config(quantization="int8"),
            parallel=ParallelConfig(tensor_parallel_size=2),
            offload=OffloadConfig(enable=True),
            scheduler=SchedulerConfig(speculative_k=2, unified_step=True))
    message = str(refusal.value)
    assert message.startswith(
        "granitemoehybrid keeps a recurrent state beside its pages; "
        "refused: ")
    for feature in ("KV offload", "speculative decoding",
                    "the unified ragged step", "tensor parallelism",
                    "weight quantization"):
        assert feature in message
    # Its own words.
    assert "the Mamba-2 mixer and the expert layer" in message
    assert "Mamba-2 mixer's projections and the experts" in message


def test_the_family_declares_its_state_and_the_engine_names_no_model():
    import inspect

    from production_stack_tpu.engine import engine as engine_module
    from production_stack_tpu.engine import model_runner, scheduler
    fam = registry.family("granitemoehybrid")
    assert fam.conv_tail and fam.deferred_kv
    assert fam.counters == registry.family("qwen3_next").counters
    assert set(fam.refusals) == {"tensor parallelism",
                                 "weight quantization"}
    config = model_config()
    assert registry.state_pools(config) == (
        ((16, 128), "float32"), ((3, 160), "model"))
    k_cache, v_cache = registry.init_hybrid_cache(
        dataclasses.replace(config, dtype="bfloat16"), 8, 16, 3)
    assert [(a.shape, str(a.dtype)) for a in k_cache] == [
        ((4, 16, 128), "float32"), ((4, 16, 128), "float32"),
        ((2, 8, 16, 16), "bfloat16"), ((4, 16, 128), "float32"),
        ((6,), "float32")]
    assert [(a.shape, str(a.dtype)) for a in v_cache] == [
        ((4, 3, 160), "bfloat16"), ((4, 3, 160), "bfloat16"),
        ((2, 8, 16, 16), "bfloat16"), ((4, 3, 160), "bfloat16")]
    for module in (model_runner, scheduler, engine_module):
        assert "granite" not in inspect.getsource(module).lower()
    assert "granitemoehybrid" in registry.deferred_kv_architectures()


def test_the_memory_ledger_counts_the_pool_at_its_size():
    """Three Mamba layers' [16, 128] float32 h and [3, 160] float32
    tails (the tiny model's dtype); six slots and the trash."""
    engine = LLMEngine(engine_config())
    per_sequence = 3 * (16 * 128 * 4 + 3 * 160 * 4)
    assert engine.config.model.recurrent_state_bytes() == per_sequence
    ledger = engine.runner.observatory.hbm_bytes()
    assert ledger["recurrent_state"] == 7 * per_sequence
