"""Jamba through the engine: the scheduler, the cache manager that owns
pages and recurrent-state slots, the eager and the deferred decode
burst, and what start-up refuses (the model and its ops:
tests/test_jamba.py).

Tiny widths, float32, seeded, on the CPU. The oracle is the plain
reference of the family (chipbench/reference/jamba_family.py).
``FLOAT32`` 2e-5 on log-probabilities: both sides float32 on one CPU
with the same weights, differing in the order of sums; the readings
are under 2e-6.
"""

import dataclasses

import numpy as np
import pytest

from chipbench.reference import jamba_family as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    ParallelConfig,
    SchedulerConfig,
    tiny_jamba_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models import registry

FLOAT32 = 2e-5


def model_config(**over):
    config = tiny_jamba_config()
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
                                  "deferred pallas-interpret-decode"])
def test_engine_prefill_chunks_and_bursts_agree_with_the_reference(form):
    """Through the scheduler, the cache manager and the decode burst:
    six prompts over four rows (more sequences than rows: two wait for
    a row and take a slot another left full), prompts of up to three
    chunks, bursts of four steps; the top log-probabilities of every
    answer agree."""
    # The Pallas kernels in interpret mode: ``-decode`` is what
    # ``auto`` resolves on the chip (the deferred burst attends through
    # the paged decode kernel, the tail's state merged beside it), the
    # other what it falls back to where the decode probe fails (the
    # burst attends through ``paged_attention``).
    over = {}
    if "pallas" in form:
        over = dict(attention_impl="pallas-interpret")
        if not form.endswith("-decode"):
            over["attention_impl_decode"] = "xla"
    model = model_config(**over)
    engine = LLMEngine(engine_config(
        model, deferred_kv_writes=form.startswith("deferred")))
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
    # The family keeps no counters: nothing to read after a burst.
    assert engine.runner.read_moe_stats() is None
    assert len(engine.runner.k_cache) == 4


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
    """Two rows in bursts of four: one stops after two steps of a
    burst. It emits what it emits step by step, and the row beside it
    goes on undisturbed (that the stopped row's slot is left as it was,
    to the bit: tests/test_jamba.py)."""
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
            scheduler=SchedulerConfig(speculative_k=2, unified_step=True))
    message = str(refusal.value)
    assert message.startswith(
        "jamba keeps a recurrent state beside its pages; refused: ")
    for feature in ("speculative decoding", "the unified ragged step",
                    "tensor parallelism", "weight quantization"):
        assert feature in message
    # Its own words, not the other hybrid's: it has no expert layer and
    # no fused projections.
    assert "Mamba mixer" in message
    assert "expert layer" not in message
    assert "fused projections" not in message


JAMBA2_3B = dict(
    architectures=["JambaForCausalLM"], model_type="jamba",
    attn_layer_offset=7, attn_layer_period=14, hidden_size=2560,
    intermediate_size=8192, mamba_conv_bias=True, mamba_d_conv=4,
    mamba_d_state=16, mamba_dt_rank=160, mamba_expand=2,
    mamba_proj_bias=False, num_attention_heads=20, num_experts=1,
    num_experts_per_tok=1, num_hidden_layers=28, num_key_value_heads=1,
    rms_norm_eps=1e-6, sliding_window=None, tie_word_embeddings=True,
    vocab_size=65536)


def test_the_published_config_is_read_as_the_family():
    config = ModelConfig.from_hf_config(JAMBA2_3B)
    assert config.architecture == "jamba"
    assert [i for i, m in enumerate(config.layer_is_linear) if not m] == [
        7, 21]
    assert (config.head_dim, config.mamba_d_inner) == (128, 5120)
    assert config.recurrent_state_shapes() == ((16, 5120), (3, 5120))
    assert config.recurrent_state_bytes() == 9318400
    # model_type alone names it too (the catalog's row has no
    # architectures key).
    hf = {k: v for k, v in JAMBA2_3B.items() if k != "architectures"}
    assert ModelConfig.from_hf_config(hf).architecture == "jamba"


@pytest.mark.parametrize("key,value,word", [
    ("num_experts", 16, "num_experts 16"),
    ("sliding_window", 4096, "sliding_window 4096"),
    ("mamba_proj_bias", True, "mamba_proj_bias"),
])
def test_a_jamba_this_engine_does_not_serve_is_refused_in_words(
        key, value, word):
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(dict(JAMBA2_3B, **{key: value}))


@pytest.mark.parametrize("hf", [
    dict(architectures=["FalconH1ForCausalLM"]),
    dict(model_type="rwkv7"),
])
def test_an_architecture_no_branch_knows_is_not_read_as_a_llama(hf):
    shape = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                 num_hidden_layers=2, num_attention_heads=4)
    with pytest.raises(ValueError, match="none this engine serves"):
        ModelConfig.from_hf_config(dict(shape, **hf))
    # No key at all is still a Llama, and the Llama shapes by either
    # key are read as before.
    assert ModelConfig.from_hf_config(shape).architecture == "llama"
    assert ModelConfig.from_hf_config(
        dict(shape, model_type="qwen2")).architecture == "qwen2"
    assert ModelConfig.from_hf_config(dict(
        shape, architectures=["MistralForCausalLM"])).architecture == "llama"


def test_a_jamba_checkpoint_is_refused_and_no_family_is_read_as_a_llamas(
        tmp_path):
    from production_stack_tpu.engine.weights import load_weights
    with pytest.raises(NotImplementedError, match="--random-weights"):
        load_weights(str(tmp_path), model_config())
    with pytest.raises(NotImplementedError, match="not read as a Llama"):
        load_weights(str(tmp_path),
                     dataclasses.replace(model_config(), architecture="x"))


def test_both_hybrids_declare_themselves_in_the_registry():
    assert "jamba" in registry.list_architectures()
    assert {"jamba", "qwen3_next", "llama"} <= set(
        registry.deferred_kv_architectures())
    for name in ("jamba", "qwen3_next"):
        fam = registry.family(name)
        assert fam.recurrent_layers and fam.state
        assert set(fam.refusals) == {"tensor parallelism",
                                     "weight quantization"}
    assert registry.family("jamba").counters == ()
    assert len(registry.family("qwen3_next").counters) == 6
    assert registry.family("llama").recurrent_layers is None
    # The runner names no model module.
    import inspect

    from production_stack_tpu.engine import model_runner
    source = inspect.getsource(model_runner)
    assert "models.qwen3_next" not in source
    assert "models.jamba" not in source
    k_cache, v_cache = registry.init_hybrid_cache(model_config(), 8, 16, 3)
    assert [a.shape for a in k_cache] == [
        (4, 8, 128), (1, 8, 16, 16), (4, 8, 128), (4, 8, 128)]
    assert [a.dtype for a in k_cache] == [np.float32] * 4
    assert v_cache[0].shape == (4, 3, 128)

