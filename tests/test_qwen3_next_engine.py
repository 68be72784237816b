"""Qwen3-Next through the engine: the scheduler, the cache manager
that owns pages and recurrent-state slots, the decode burst, and what
start-up refuses (the model and its ops: tests/test_qwen3_next.py).

Tiny widths, float32, seeded, on the CPU. The oracle is the plain
reference of the family (chipbench/reference/qwen3_next_family.py),
which imports nothing of the program's models or ops and is given the
program's parameter values.

Tolerances, each with its reason:

- ``FLOAT32`` 2e-5 on log-probabilities and logits: both sides are
  float32 on one CPU with the same weights; they differ in the order
  of sums (chunkwise scan against token-by-token recurrence, grouped
  product against expert-by-expert). The readings are under 2e-6.
- ``INTERPRET`` 2e-4 between the Pallas kernels in interpret mode and
  the XLA attention at ``head_dim`` 256: the kernels keep an online
  softmax in float32 with another order of sums (what
  tests/test_pallas_attention.py allows them).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import qwen3_next_family as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    LoRAConfig,
    OffloadConfig,
    ParallelConfig,
    SchedulerConfig,
    tiny_qwen3_next_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_cache import (
    OutOfPagesError,
    PagedCacheManager,
)
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models import qwen3_next
from production_stack_tpu.ops import gated_delta, moe
from production_stack_tpu.ops.rope import apply_rope

FLOAT32 = 2e-5
INTERPRET = 2e-4


def model_config(**over):
    config = tiny_qwen3_next_config()
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


# ---- the engine against the reference -------------------------------------


def test_engine_prefill_chunks_and_bursts_agree_with_the_reference():
    """Through the scheduler, the cache manager and the decode burst:
    five prompts over four rows, prompts of up to three chunks, bursts
    of four steps; the top log-probabilities of every answer agree."""
    engine = LLMEngine(engine_config())
    prompts = [prompt_of(n, seed=n) for n in (70, 20, 45, 33)]
    ids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=9, ignore_eos=True, logprobs=True,
        top_logprobs=5)) for p in prompts]
    seqs = [engine.sequences[i] for i in ids]
    served = {i: [] for i in ids}
    while any(s.state.name not in ("FINISHED", "ABORTED") for s in seqs):
        for out in engine.step():
            if out.new_token is not None:
                served[out.seq_id].append(out.logprobs)
    model = reference.model_of(engine.config.model, engine.runner.params)
    worst = 0.0
    for prompt, seq in zip(prompts, seqs):
        tokens = prompt + seq.output_token_ids
        want = np.asarray(reference.log_probs(
            model, tokens, list(range(len(prompt) - 1, len(tokens) - 1))))
        assert seq.output_token_ids == [int(t) for t in want.argmax(-1)]
        for j, entry in enumerate(served[seq.seq_id]):
            assert len(entry[1]) == 5
            for tid, lp in entry[1]:
                worst = max(worst, abs(lp - want[j, tid]))
    assert worst < FLOAT32
    stats = engine.stats()
    assert stats["engine_state_slots_total"] == 6   # 4 rows + 2 prefill
    assert stats["engine_state_slots_used"] == 0
    assert stats["engine_moe_held_choice_share"] == 1.0   # all held


# ---- the state pool --------------------------------------------------------


def test_state_slots_go_out_and_come_back_with_the_pages():
    manager = PagedCacheManager(CacheConfig(page_size=16, num_pages=8,
                                            num_state_slots=2))
    first, second = (manager.allocate_state_slot() for _ in range(2))
    assert {first, second} == {1, 2}            # slot 0 is the trash slot
    with pytest.raises(OutOfPagesError, match="state slots"):
        manager.allocate_state_slot()
    pages = manager.allocate_pages(2)
    manager.free_sequence(pages, first)
    assert manager.num_used_state_slots == 1
    assert manager.allocate_state_slot() == first
    # A model whose state is all pages has no pool and asks for none.
    assert PagedCacheManager(CacheConfig()).allocate_state_slot() is None


def test_a_slot_is_reset_on_reuse_and_after_a_recompute():
    prompts = [prompt_of(40, seed=7), prompt_of(37, seed=8),
               prompt_of(52, seed=9)]
    alone = [greedy(LLMEngine(engine_config()), [p])[0].output_token_ids
             for p in prompts]
    # One row, so every request takes the slot the last one left full.
    engine = LLMEngine(engine_config(max_num_seqs=1, prefill_batch_size=1))
    assert engine.cache_manager.num_state_slots == 2
    reused = [s.output_token_ids for s in greedy(engine, prompts)]
    assert reused == alone
    # Preempted in the middle of decoding: the pages and the slot go
    # back, the sequence is recomputed from position 0 into whatever
    # slot it is given, and goes on as if nothing had happened.
    engine = LLMEngine(engine_config())
    ids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=9, ignore_eos=True)) for p in prompts]
    seqs = [engine.sequences[i] for i in ids]
    while len(seqs[0].output_token_ids) < 3:
        engine.step()
    held = seqs[0].state_slot
    assert held
    engine.scheduler._preempt(seqs[0])
    assert seqs[0].state_slot is None and not seqs[0].pages
    assert engine.scheduler.num_preemptions == 1
    finish(engine, seqs)
    assert seqs[0].all_token_ids[40:] == alone[0]
    assert [s.output_token_ids for s in seqs[1:]] == alone[1:]
    assert engine.cache_manager.num_used_state_slots == 0


def test_a_prefix_hit_is_not_taken():
    engine = LLMEngine(engine_config())
    assert engine.config.cache.enable_prefix_caching
    prompt = prompt_of(70, seed=11)
    first = greedy(engine, [prompt])[0]
    declined = engine.cache_manager.prefix_declined_tokens
    second = greedy(engine, [prompt])[0]
    # The pages of the first request were there to hit (4 full pages
    # of 16); the state after them was not, so nothing was skipped.
    assert engine.cache_manager.prefix_declined_tokens - declined == 64
    assert engine.cache_manager.prefix_hit_tokens == 0
    assert second.output_token_ids == first.output_token_ids
    assert engine.stats()["engine_prefix_declined_tokens_total"] >= 64
    # A llama engine takes the same hit.
    from production_stack_tpu.engine.config import tiny_model_config
    llama = LLMEngine(EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=32)))
    greedy(llama, [prompt])
    greedy(llama, [prompt])
    assert llama.cache_manager.prefix_hit_tokens == 64
    assert llama.cache_manager.prefix_declined_tokens == 0


REFUSED = {
    "KV offload": dict(offload=OffloadConfig(enable=True)),
    "disaggregated prefill/decode": dict(engine_role="decode"),
    "mid-stream checkpoint descriptors": dict(
        checkpoint_interval_tokens=64),
    "speculative decoding": dict(
        scheduler=SchedulerConfig(speculative_k=2)),
    "pipeline-parallel serving": dict(
        parallel=ParallelConfig(pipeline_parallel_size=2)),
    "context-parallel prefill": dict(
        parallel=ParallelConfig(context_parallel_size=2)),
    "tensor parallelism": dict(
        parallel=ParallelConfig(tensor_parallel_size=2)),
    "the unified ragged step": dict(
        scheduler=SchedulerConfig(unified_step=True)),
    "LoRA": dict(lora=LoRAConfig(enable=True)),
    "int8 KV pages": dict(cache=CacheConfig(kv_cache_dtype="int8")),
    "weight quantization": dict(
        model=model_config(quantization="int8")),
    "cache_layout='stacked'": dict(
        cache=CacheConfig(cache_layout="stacked")),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_what_cannot_carry_the_state_is_refused_at_start_up(feature):
    fields = dict(model=model_config())
    fields.update(REFUSED[feature])
    with pytest.raises(ValueError) as refusal:
        EngineConfig(**fields)
    message = str(refusal.value)
    assert "keeps a recurrent state beside its pages" in message
    assert feature in message
    # The same configuration serves a model whose state is all pages.
    from production_stack_tpu.engine.config import tiny_model_config
    if feature not in ("weight quantization",):
        fields["model"] = tiny_model_config("llama")
        try:
            EngineConfig(**fields)
        except ValueError as other:
            assert "recurrent state" not in str(other)


def test_the_server_resolves_the_ragged_step_off_and_reads_the_config():
    import argparse

    from production_stack_tpu.engine.config import ModelConfig
    from production_stack_tpu.engine.server import _resolve_unified_step
    args = argparse.Namespace(
        unified_step="auto", pipeline_parallel_size=1,
        context_parallel_size=1, distributed=False, engine_role="both")
    assert _resolve_unified_step(args) is True
    assert _resolve_unified_step(args, model_config()) is False
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs",
        "qwen3-next-80b-a3b-ep4.json")
    with open(path) as f:
        hf = json.load(f)
    config = ModelConfig.from_hf_config(hf)
    assert config.architecture == "qwen3_next"
    assert config.layer_is_linear == (True, True, True, False) * 2
    assert (config.num_experts, config.router_width) == (128, 512)
    assert config.num_experts == hf["published"]["num_experts"] // 4
    assert config.recurrent_state_bytes() == 6 * (
        32 * 128 * 128 * 4 + 3 * 8192 * 2)
    with pytest.raises(ValueError, match="expert_parallel_rank"):
        ModelConfig.from_hf_config(dict(hf, expert_parallel_rank=4))
    with pytest.raises(ValueError, match="mlp_only_layers"):
        ModelConfig.from_hf_config(dict(hf, mlp_only_layers=[0]))
    from production_stack_tpu.engine.weights import load_weights
    with pytest.raises(NotImplementedError, match="--random-weights"):
        load_weights("/nonexistent", config)
