"""Unified ragged step (docs/unified_step.md): greedy byte-parity
with the bimodal scheduler over mixed staggered-admission runs (bf16
and int8 KV), spec-decode under async scheduling, executable-cache
stability across a repeated mixed run, dissolved exclusivity rules,
and page accounting when a row finishes inside a ragged batch."""

import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import (
    SamplingParams,
    SequenceState,
)


def _engine(unified=False, async_on=False, kv_dtype="auto",
            unified_impl=None, **sched_kw):
    model = tiny_model_config("llama")
    if unified_impl is not None:
        # Pin the unified step's kernel (e.g. the fused ragged kernel
        # in interpret mode — how CPU tier-1 holds the byte-parity
        # contract against the XLA-composed path).
        model.attention_impl_unified = unified_impl
    config = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=16, num_pages=128,
                          kv_cache_dtype=kv_dtype),
        scheduler=SchedulerConfig(max_num_seqs=4,
                                  max_model_len=256,
                                  prefill_chunk_size=32,
                                  unified_step=unified,
                                  async_scheduling=async_on,
                                  **sched_kw),
    )
    return LLMEngine(config)


def _prompts(seed=7):
    rs = np.random.RandomState(seed)
    return [
        [4, 5, 6] * 13,
        [8, 8, 8, 8, 8, 8, 8, 8, 8, 8],
        [21, 22, 23, 24] * 20,  # 80 tokens: 3 chunks under chunk 32
        [int(x) for x in rs.randint(1, 500, size=41)],
    ]


# Varied budgets so rows finish at different steps; the long third
# prompt keeps prefilling while rows 1-2 decode, so a unified
# scheduler plans genuinely mixed batches.
_MAX_TOKENS = [18, 9, 14, 25]


def _run_mixed(engine, seed=7):
    """~50-step run: chunked prefills, staggered admission (the 4th
    prompt arrives only after the 2nd finishes — mid-decode, so its
    chunks are admitted INTO live decode steps under unified
    scheduling), interleaved finishes."""
    prompts = _prompts(seed)
    seqs = []
    for p, m in zip(prompts[:3], _MAX_TOKENS[:3]):
        sid = engine.add_request(p, SamplingParams(
            temperature=0.0, max_tokens=m, ignore_eos=True))
        seqs.append(engine.sequences[sid])
    late_added = False
    for _ in range(500):
        engine.step()
        if (not late_added
                and seqs[1].state == SequenceState.FINISHED):
            sid = engine.add_request(prompts[3], SamplingParams(
                temperature=0.0, max_tokens=_MAX_TOKENS[3],
                ignore_eos=True))
            seqs.append(engine.sequences[sid])
            late_added = True
        if late_added and not engine.has_work():
            break
    assert late_added and not engine.has_work()
    return [list(s.output_token_ids) for s in seqs]


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_greedy_parity_bimodal_vs_unified(kv_dtype):
    bimodal = _engine(unified=False, kv_dtype=kv_dtype)
    expected = _run_mixed(bimodal)
    unified = _engine(unified=True, kv_dtype=kv_dtype)
    got = _run_mixed(unified)
    assert got == expected
    assert [len(t) for t in got] == _MAX_TOKENS
    # Mixed batches genuinely ran through the ragged program, and the
    # bimodal engine never did.
    assert unified.metrics.ragged_steps_total > 0
    assert bimodal.metrics.ragged_steps_total == 0


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_greedy_parity_composed_vs_ragged_kernel(kv_dtype):
    """Greedy streams must be byte-identical between the XLA-composed
    unified step and the fused Pallas ragged kernel (interpret mode)
    — over a staggered mixed run WITH drafted rows, so every row kind
    (decode, spec-verify with draft spans, prefill chunk, pad)
    crosses the kernel's in-kernel mask rebuild, for bf16 AND int8
    KV."""
    base = [3, 9, 27, 9] * 14
    prompts = [base, base[:24] * 2, list(reversed(base))]
    max_tokens = [14, 26, 20]

    def run(engine):
        seqs = []
        for p, m in zip(prompts, max_tokens):
            sid = engine.add_request(p, SamplingParams(
                temperature=0.0, max_tokens=m, ignore_eos=True))
            seqs.append(engine.sequences[sid])
        late_added = False
        for _ in range(500):
            engine.step()
            if (not late_added
                    and seqs[0].state == SequenceState.FINISHED):
                sid = engine.add_request(base[:20] * 2, SamplingParams(
                    temperature=0.0, max_tokens=10, ignore_eos=True))
                seqs.append(engine.sequences[sid])
                late_added = True
            if late_added and not engine.has_work():
                break
        assert late_added and not engine.has_work()
        return [list(s.output_token_ids) for s in seqs]

    composed = _engine(unified=True, kv_dtype=kv_dtype,
                       speculative_k=3)
    expected = run(composed)
    ragged = _engine(unified=True, kv_dtype=kv_dtype,
                     unified_impl="pallas_ragged-interpret",
                     speculative_k=3)
    got = run(ragged)
    assert got == expected
    # The run genuinely mixed AND drafted — both engines — and the
    # fused kernel genuinely served the unified phase (observatory
    # one-hot, the vllm:engine_attention_impl{phase="unified"} value).
    for eng in (composed, ragged):
        assert eng.metrics.ragged_steps_total > 0
        assert eng.stats()["spec_decode_num_draft_tokens_total"] > 0
    impls = ragged.runner.observatory.attention_impls()
    assert impls["unified"] == "pallas_ragged-interpret"
    assert composed.runner.observatory.attention_impls()[
        "unified"] == "xla"


def test_spec_decode_under_async_mixed():
    """speculative_k x async_scheduling is a dissolved rule: verify
    steps reconcile through the assume-1 stale-drop path
    (docs/unified_step.md section 'spec under async'). Greedy output
    must stay byte-identical to the plain synchronous loop."""
    # Repetitive prompts so the ngram proposer actually drafts, and a
    # late-admitted request: its prefill is a pipeline break, and the
    # re-plan after a break is where the async loop consults the
    # proposer (mid-chain ahead-dispatches never speculate).
    base = [3, 9, 27, 9] * 14
    prompts = [base, base[:24] * 2, list(reversed(base))]
    max_tokens = [14, 26, 20]

    def run(engine):
        seqs = []
        for p, m in zip(prompts, max_tokens):
            sid = engine.add_request(p, SamplingParams(
                temperature=0.0, max_tokens=m, ignore_eos=True))
            seqs.append(engine.sequences[sid])
        late_added = False
        for _ in range(500):
            engine.step()
            if (not late_added
                    and seqs[0].state == SequenceState.FINISHED):
                sid = engine.add_request(base[:20] * 2, SamplingParams(
                    temperature=0.0, max_tokens=10, ignore_eos=True))
                seqs.append(engine.sequences[sid])
                late_added = True
            if late_added and not engine.has_work():
                break
        assert late_added and not engine.has_work()
        return [list(s.output_token_ids) for s in seqs]

    expected = run(_engine())
    eng = _engine(unified=True, async_on=True, speculative_k=3)
    got = run(eng)
    assert got == expected
    st = eng.stats()
    assert st["spec_decode_num_draft_tokens_total"] > 0
    # Mixed ragged dispatch and speculation coexisted in one run.
    assert eng.metrics.ragged_steps_total > 0
    # The pipeline engaged around the verify steps rather than
    # degrading to fully synchronous stepping.
    assert eng.metrics.pipeline_ahead_steps_total > 0
    assert eng._in_flight is None


def test_mixed_run_zero_recompiles():
    """After one warm mixed staggered-admission run, a second one
    (fresh token values, same ~50-step shape) must add zero compiled
    executables: every ragged width buckets into the fixed shape
    lattice, so staggered admission cannot trigger recompilation."""
    engine = _engine(unified=True)
    # Warm both pure-prefill buckets (a 48-token prompt prefills as a
    # 32-chunk then a 16-chunk) and the decode step: the scheduler's
    # prefill/decode alternation phase carries across runs, so run 2
    # may legitimately hit a bimodal bucket run 1 skipped — those
    # shapes are not what this guard is about.
    engine.add_request(list(range(2, 50)), SamplingParams(
        temperature=0.0, max_tokens=2, ignore_eos=True))
    while engine.has_work():
        engine.step()
    _run_mixed(engine, seed=7)
    ragged0 = engine.metrics.ragged_steps_total
    assert ragged0 > 0
    obs = engine.runner.observatory
    assert obs.compile_events_total() > 0  # the warm-up compiled
    before_events = obs.compile_events_total()
    before_caches = obs.executable_cache_sizes()
    _run_mixed(engine, seed=13)
    assert engine.metrics.ragged_steps_total > ragged0
    assert obs.compile_events_total() == before_events
    assert obs.executable_cache_sizes() == before_caches


def test_mixed_run_zero_recompiles_with_ragged_kernel():
    """The recompile guard with the fused ragged kernel active: the
    kernel's [rows_pad, d_pad] padding and descriptor prefetch are
    functions of the (row bucket, W bucket) pair only, so repeated
    mixed runs must add zero compiled executables."""
    engine = _engine(unified=True,
                     unified_impl="pallas_ragged-interpret")
    engine.add_request(list(range(2, 50)), SamplingParams(
        temperature=0.0, max_tokens=2, ignore_eos=True))
    while engine.has_work():
        engine.step()
    _run_mixed(engine, seed=7)
    ragged0 = engine.metrics.ragged_steps_total
    assert ragged0 > 0
    obs = engine.runner.observatory
    before_events = obs.compile_events_total()
    before_caches = obs.executable_cache_sizes()
    _run_mixed(engine, seed=13)
    assert engine.metrics.ragged_steps_total > ragged0
    assert obs.compile_events_total() == before_events
    assert obs.executable_cache_sizes() == before_caches


def test_finish_mid_ragged_batch_no_page_leak():
    """A row that hits max_tokens inside a ragged batch (its final
    decode token sampled in the same dispatch that prefills another
    request's chunk) must return every page once the run drains."""
    engine = _engine(unified=True)
    free0 = engine.cache_manager.num_free_pages
    sid_a = engine.add_request([7, 11, 13] * 8, SamplingParams(
        temperature=0.0, max_tokens=20, ignore_eos=True))
    seq_a = engine.sequences[sid_a]
    # Decode A down to its last few tokens, then admit an 80-token
    # prompt: its 3 chunks ride the next ragged steps, so A's finish
    # lands inside one of them.
    for _ in range(100):
        engine.step()
        if len(seq_a.output_token_ids) >= 17:
            break
    assert seq_a.state == SequenceState.RUNNING
    engine.add_request(_prompts()[2], SamplingParams(
        temperature=0.0, max_tokens=8, ignore_eos=True))
    finished_in_ragged = False
    for _ in range(200):
        ragged_before = engine.metrics.ragged_steps_total
        engine.step()
        stepped_ragged = (
            engine.metrics.ragged_steps_total > ragged_before)
        if (stepped_ragged and seq_a.state == SequenceState.FINISHED
                and not finished_in_ragged):
            finished_in_ragged = True
        if not engine.has_work():
            break
    assert not engine.has_work()
    assert seq_a.state == SequenceState.FINISHED
    assert finished_in_ragged
    assert engine.cache_manager.num_free_pages == free0


def test_dissolved_exclusivity_rules():
    """The three rules dissolved by the unified step
    (docs/unified_step.md section 'dissolved rules') now construct —
    and the prefill-role x speculation rule still fires."""
    EngineConfig(scheduler=SchedulerConfig(async_scheduling=True,
                                           decode_steps=4))
    EngineConfig(scheduler=SchedulerConfig(async_scheduling=True,
                                           speculative_k=4))
    EngineConfig(engine_role="prefill",
                 scheduler=SchedulerConfig(async_scheduling=True))
    with pytest.raises(ValueError, match="engine_role"):
        EngineConfig(engine_role="prefill",
                     scheduler=SchedulerConfig(speculative_k=2))


def test_eligibility_and_server_resolution():
    from production_stack_tpu.engine.model_runner import (
        unified_step_eligible,
    )
    assert unified_step_eligible()
    assert not unified_step_eligible(distributed=True)
    assert not unified_step_eligible(engine_role="prefill")
    assert not unified_step_eligible(engine_role="decode")

    from production_stack_tpu.engine.server import (
        _resolve_unified_step,
        parse_args,
    )
    assert _resolve_unified_step(parse_args([]))
    assert not _resolve_unified_step(parse_args(["--unified-step", "off"]))
    assert _resolve_unified_step(
        parse_args(["--unified-step", "on", "--distributed"]))
    assert not _resolve_unified_step(parse_args(["--distributed"]))
    assert _resolve_unified_step(
        parse_args(["--pipeline-parallel-size", "4"]))
    assert not _resolve_unified_step(
        parse_args(["--engine-role", "prefill"]))


def test_ragged_metrics_rendered_and_scraped():
    from production_stack_tpu.engine.metrics import EngineMetrics
    m = EngineMetrics()
    m.on_ragged_step(prefill_rows=2, decode_rows=3, pad_rows=11)
    text = "\n".join(m.render())
    assert "vllm:engine_step_prefill_rows 2" in text
    assert "vllm:engine_step_decode_rows 3" in text
    assert "vllm:engine_step_pad_rows 11" in text
    assert "vllm:engine_ragged_steps_total 1" in text
    assert "vllm:engine_ragged_rows_total 16" in text
    assert "vllm:engine_ragged_pad_rows_total 11" in text
    from production_stack_tpu.router.stats.engine_stats import (
        EngineStats,
    )
    stats = EngineStats.from_prometheus_text(text + "\n")
    assert stats.engine_step_prefill_rows == 2.0
    assert stats.engine_step_decode_rows == 3.0
    assert stats.engine_step_pad_rows == 11.0
    assert stats.engine_ragged_steps == 1.0
    assert stats.engine_ragged_rows == 16.0
    assert stats.engine_ragged_pad_rows == 11.0


# ---- unified step on the pp / cp runners (docs/parallelism.md) ---------


def _parallel_engine(unified, pp=1, sp=1, kv_dtype="auto",
                     **sched_kw):
    """Engine on a (pp) or (sp) mesh over the virtual 8-device CPU
    harness (tests/conftest.py); pp needs layers % stages == 0."""
    from production_stack_tpu.engine.config import ParallelConfig
    from production_stack_tpu.parallel.mesh import build_mesh

    model = tiny_model_config("llama")
    model.num_hidden_layers = 4  # divisible by pp=2
    config = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=16, num_pages=128,
                          kv_cache_dtype=kv_dtype),
        scheduler=SchedulerConfig(max_num_seqs=4,
                                  max_model_len=256,
                                  prefill_chunk_size=32,
                                  unified_step=unified,
                                  **sched_kw),
        parallel=ParallelConfig(
            pipeline_parallel_size=pp,
            context_parallel_size=sp,
            long_prefill_threshold=64 if sp > 1 else None,
        ),
    )
    mesh = (build_mesh(pipeline_parallel_size=pp,
                       context_parallel_size=sp)
            if pp > 1 or sp > 1 else None)
    return LLMEngine(config, mesh=mesh)


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_greedy_parity_bimodal_vs_unified_pp(kv_dtype):
    """pp=2: the mixed staggered run through the staged ragged
    program is byte-identical to the bimodal pp scheduler — the
    dissolved int8 x pp rule rides the same congruent QuantKV specs."""
    bimodal = _parallel_engine(False, pp=2, kv_dtype=kv_dtype,
                               speculative_k=3)
    expected = _run_mixed(bimodal)
    unified = _parallel_engine(True, pp=2, kv_dtype=kv_dtype,
                               speculative_k=3)
    got = _run_mixed(unified)
    assert got == expected
    assert [len(t) for t in got] == _MAX_TOKENS
    assert unified.metrics.ragged_steps_total > 0
    assert bimodal.metrics.ragged_steps_total == 0


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_greedy_parity_bimodal_vs_unified_cp(kv_dtype):
    """cp=2: multi-token unified dispatches shard their W axis over
    sp (a parallel query axis — no numeric change), so the greedy
    stream matches the bimodal cp engine byte for byte."""
    bimodal = _parallel_engine(False, sp=2, kv_dtype=kv_dtype,
                               speculative_k=3)
    expected = _run_mixed(bimodal)
    unified = _parallel_engine(True, sp=2, kv_dtype=kv_dtype,
                               speculative_k=3)
    got = _run_mixed(unified)
    assert got == expected
    assert [len(t) for t in got] == _MAX_TOKENS
    assert unified.metrics.ragged_steps_total > 0
    assert bimodal.metrics.ragged_steps_total == 0


def test_pp_mixed_run_zero_recompiles():
    """The row-bucket lattice holds on the pp runner: a second mixed
    staggered run (fresh token values, same step shape) adds zero
    compiled executables — ragged microbatching through the ppermute
    ring reuses the same staged programs."""
    engine = _parallel_engine(True, pp=2)
    engine.add_request(list(range(2, 50)), SamplingParams(
        temperature=0.0, max_tokens=2, ignore_eos=True))
    while engine.has_work():
        engine.step()
    _run_mixed(engine, seed=7)
    ragged0 = engine.metrics.ragged_steps_total
    assert ragged0 > 0
    obs = engine.runner.observatory
    assert obs.compile_events_total() > 0
    before_events = obs.compile_events_total()
    before_caches = obs.executable_cache_sizes()
    _run_mixed(engine, seed=13)
    assert engine.metrics.ragged_steps_total > ragged0
    assert obs.compile_events_total() == before_events
    assert obs.executable_cache_sizes() == before_caches
