"""Deferred per-burst KV writes (SchedulerConfig.deferred_kv_writes):
the tail-buffer burst must generate exactly what the per-step-write
burst and single-step decoding generate.

Motivation (a decode ablation, builder-captured 2026-07-31, not
measured by the driver): per-step paged scatters cost ~5.1 of 11.1 ms/token-step
for ~1 MB of writes; deferring them to one batched write per layer
per burst removes that cost. Correctness risks covered here: tail
attention masking (positional), mid-burst row freeze (stop/budget),
page-boundary crossings inside a burst, flush-then-continue across
bursts, seeded sampling, and the capability guards.
"""

import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams


def _engine(decode_steps, deferred=False, max_num_seqs=4, arch="llama",
            quantization=None, cache_layout="auto", max_model_len=256,
            num_pages=128, prefill_chunk_size=32, attention_impl=None):
    model = tiny_model_config(arch)
    if attention_impl:
        model.attention_impl = attention_impl
    if quantization:
        model.quantization = quantization
    config = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=16, num_pages=num_pages,
                          cache_layout=cache_layout),
        scheduler=SchedulerConfig(max_num_seqs=max_num_seqs,
                                  max_model_len=max_model_len,
                                  prefill_chunk_size=prefill_chunk_size,
                                  decode_steps=decode_steps,
                                  deferred_kv_writes=deferred),
    )
    return LLMEngine(config)


def _gen(engine, prompts, **kw):
    sampling = dict(max_tokens=12, temperature=0.0, ignore_eos=True)
    sampling.update(kw)
    seqs = []
    for p in prompts:
        sid = engine.add_request(p, SamplingParams(**sampling))
        seqs.append(engine.sequences[sid])
    while engine.has_work():
        engine.step()
    return [s.output_token_ids for s in seqs]


def _prompts(sizes=(7, 20, 41), hi=500, seed=1):
    rs = np.random.RandomState(seed)
    return [[int(x) for x in rs.randint(1, hi, size=n)] for n in sizes]


def test_deferred_matches_single_step_greedy():
    prompts = _prompts()
    expected = _gen(_engine(decode_steps=1), prompts)
    got = _gen(_engine(decode_steps=4, deferred=True), prompts)
    assert got == expected
    assert all(len(t) == 12 for t in got)


def test_deferred_matches_eager_burst_multi_burst():
    """20 tokens at K=4 = 5 flush/continue cycles; page_size 16 puts
    page-boundary crossings inside bursts for every row."""
    prompts = _prompts(sizes=(15, 31, 16, 47))
    eager = _gen(_engine(decode_steps=4), prompts, max_tokens=20)
    deferred = _gen(_engine(decode_steps=4, deferred=True), prompts,
                    max_tokens=20)
    assert deferred == eager


def test_deferred_stop_token_mid_burst():
    """A row hitting its stop set mid-burst freezes; its tail slots
    must not pollute the flush (valid = emitted count)."""
    prompts = _prompts(sizes=(9, 12))
    ref = _gen(_engine(decode_steps=1), prompts, max_tokens=16,
               ignore_eos=False)
    # Use each row's 3rd greedy token as its stop token so the stop
    # fires mid-burst deterministically.
    stops = [r[2] for r in ref]
    eager, deferred = (
        [_gen(_engine(decode_steps=8, deferred=d), [p],
              max_tokens=16, stop_token_ids=[s], ignore_eos=False)[0]
         for p, s in zip(prompts, stops)]
        for d in (False, True))
    assert deferred == eager
    # The stop fired mid-burst: output ends at the stop token, short
    # of the 16-token budget.
    for t, s in zip(deferred, stops):
        assert t[-1] == s and len(t) < 16


def test_deferred_seeded_sampling_parity():
    """Seeded stochastic sampling depends only on (seed, emitted
    index), so deferred and eager bursts must sample identically."""
    prompts = _prompts(sizes=(11, 23))
    kw = dict(temperature=0.9, seed=1234, max_tokens=10)
    eager = _gen(_engine(decode_steps=4), prompts, **kw)
    deferred = _gen(_engine(decode_steps=4, deferred=True), prompts,
                    **kw)
    assert deferred == eager


def test_deferred_int8_and_stacked_layout():
    prompts = _prompts(sizes=(10, 33))
    for layout in ("per_layer", "stacked"):
        eager = _gen(_engine(decode_steps=4, cache_layout=layout,
                             quantization="int8"), prompts)
        deferred = _gen(_engine(decode_steps=4, deferred=True,
                                cache_layout=layout,
                                quantization="int8"), prompts)
        assert deferred == eager, layout


def test_deferred_penalties_and_logprobs_parity():
    """Penalties and logprob extraction run in the shared burst step
    body (_burst_sample_step) — pin that the deferred path reproduces
    the eager path's outputs AND per-token logprob records exactly."""
    prompts = _prompts(sizes=(13, 27))
    kw = dict(max_tokens=10, presence_penalty=0.8,
              frequency_penalty=0.3, logprobs=True, top_logprobs=3)

    def run(deferred):
        engine = _engine(decode_steps=4, deferred=deferred)
        seqs, lps = [], {}
        for p in prompts:
            sid = engine.add_request(p, SamplingParams(
                temperature=0.0, ignore_eos=True, **kw))
            seqs.append(engine.sequences[sid])
            lps[sid] = []
        while engine.has_work():
            for out in engine.step():
                if out.logprobs is not None:
                    lps[out.seq_id].append(out.logprobs)
        return [(s.output_token_ids, lps[s.seq_id]) for s in seqs]

    eager = run(False)
    deferred = run(True)
    for (et, elp), (dt, dlp) in zip(eager, deferred):
        assert dt == et
        assert len(dlp) == len(elp) == 10
        for (es, etop), (ds, dtop) in zip(elp, dlp):
            assert abs(es - ds) < 1e-3
            assert [t for t, _ in etop] == [t for t, _ in dtop]


def test_deferred_guards():
    with pytest.raises(ValueError, match="decode_steps"):
        _engine(decode_steps=1, deferred=True)
    with pytest.raises(NotImplementedError,
                       match=r"serves llama, .*qwen3_next.* \(got 'gpt2'\)"):
        _engine(decode_steps=4, deferred=True, arch="gpt2")


# ---- the attention's width follows the longest row (ops/attention.py) -----


def _wide_engine(decode_steps, deferred):
    """A table of 128 pages of 16: two of the attention's blocks of
    64 pages, their edge at 1024 tokens."""
    return _engine(decode_steps, deferred, max_model_len=2048,
                   num_pages=320, prefill_chunk_size=256)


def test_deferred_row_crossing_a_width_edge_matches_single_step():
    """1019 prompt tokens + 24 greedy ones at K=8: the first burst's
    pages hold 1018 tokens (one block of 64 pages holds them, the
    tail carries the row past 1024), the next burst starts past the
    edge and gathers two; the flush in between writes page 64 through
    the whole table."""
    prompts = _prompts(sizes=(1019, 40), seed=5)
    expected = _gen(_wide_engine(1, False), prompts, max_tokens=24)
    engine = _wide_engine(8, True)
    seen = []
    real = engine.runner._note_attn_pages

    def note(kv_lens):
        real(kv_lens)
        seen.append(engine.runner.last_attn_pages)

    engine.runner._note_attn_pages = note
    got = _gen(engine, prompts, max_tokens=24)
    assert got == expected
    # The short row bursts alone while the long prompt prefills.
    assert seen == sorted(seen) and set(seen) == {64, 128}


def test_deferred_longer_row_joining_compiles_no_new_burst():
    engine = _wide_engine(8, True)
    obs = engine.runner.observatory
    short = _prompts(sizes=(40, 200), seed=5)
    _gen(engine, short, max_tokens=16)
    warm = obs.compile_events_total("decode_burst")
    assert warm == 1
    _gen(engine, short + _prompts(sizes=(1100,), seed=6), max_tokens=16)
    assert obs.compile_events_total("decode_burst") == warm
    assert engine.runner.last_attn_pages == 128


# ---- the Pallas paged decode kernel serves the deferred burst -------------


@pytest.mark.parametrize("layout", ["per_layer", "stacked"])
def test_deferred_pallas_decode_matches_the_xla_burst(layout):
    """The deferred burst through the paged decode kernel (interpret
    mode) gives the XLA burst's greedy tokens, rows crossing a page's
    edge inside a burst and over several bursts, and flushes the same
    tails to the pages."""
    prompts = _prompts(sizes=(7, 20, 41))

    def run(impl):
        engine = _engine(decode_steps=4, deferred=True,
                         cache_layout=layout, attention_impl=impl)
        return _gen(engine, prompts), engine.runner

    want, xla = run("xla")
    got, pallas = run("pallas-interpret")
    assert got == want
    assert pallas.observatory.attention_impls()["decode"] == \
        "pallas-interpret"
    for name in ("k_cache", "v_cache"):
        for w, g in zip(getattr(xla, name), getattr(pallas, name)):
            # Page 0 is the trash page.
            w, g = np.asarray(w)[..., 1:, :, :], np.asarray(g)[..., 1:, :, :]
            assert np.abs(w).max() > 0
            np.testing.assert_allclose(g, w, rtol=0, atol=1e-5)


@pytest.mark.parametrize("impl", ["auto", "xla", "pallas",
                                  "pallas-interpret"])
def test_auto_defers_whatever_the_attention_impl(impl):
    """No decode form refuses the burst's tail, so the flag's 'auto'
    resolves on under every --attention-impl."""
    from production_stack_tpu.engine.server import (
        _resolve_deferred_kv,
        parse_args,
    )
    args = parse_args(["--model", "tiny-llama", "--random-weights",
                       "--decode-steps", "8", "--attention-impl", impl])
    assert args.deferred_kv_writes == "auto"
    assert _resolve_deferred_kv(args, tiny_model_config("llama")) is True
    args.decode_steps = 1
    assert _resolve_deferred_kv(args, tiny_model_config("llama")) is False


def _tpu_runner(monkeypatch, lowering_error, attention_impl, errors):
    """A deferred-write runner as a TPU host would build it, the
    kernels' compile probes stubbed; what it logs as an error goes to
    ``errors``."""
    import jax

    from production_stack_tpu.engine import model_runner
    from production_stack_tpu.engine.model_runner import ModelRunner

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(model_runner.logger, "error",
                        lambda msg, *args: errors.append(msg % args))
    monkeypatch.setattr(ModelRunner, "_lowering_error",
                        staticmethod(lowering_error))
    model = tiny_model_config("llama")
    model.attention_impl = attention_impl
    config = EngineConfig(
        model=model,
        cache=CacheConfig(page_size=128, num_pages=32),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64, decode_steps=8,
                                  deferred_kv_writes=True))
    return ModelRunner(config)


def test_auto_probes_the_decode_kernel_in_the_bursts_form(monkeypatch):
    """Under 'auto' on a TPU the decode kernel is compiled at the
    serving shapes as the prefill kernel is, with the tails the
    deferred burst hands it, and served where it compiles."""
    seen, errors = {}, []

    def compiles(fn, *args):
        seen.setdefault(fn.__name__, []).append(args)
        return None

    runner = _tpu_runner(monkeypatch, compiles, "auto", errors)
    assert runner.observatory.attention_impls()["decode"] == "pallas"
    assert runner.observatory.attention_impls()["prefill"] == "pallas"
    (q, _, _, table, lens, layer, k_tail, v_tail, q_pos), = \
        seen["paged_decode_attention"]
    kv, d = (runner.config.model.num_key_value_heads,
             runner.config.model.head_dim)
    assert q.shape[0] == 4 and table.shape == (4, 2) and layer is None
    assert k_tail.shape == v_tail.shape == (4, 8, kv, d)
    assert q_pos.shape == lens.shape == (4,)
    assert not errors


def test_auto_falls_back_to_xla_decode_where_the_kernel_does_not_compile(
        monkeypatch):
    errors = []

    def only_prefill_compiles(fn, *args):
        return ("Mosaic says no"
                if fn.__name__ == "paged_decode_attention" else None)

    runner = _tpu_runner(monkeypatch, only_prefill_compiles, "auto",
                         errors)
    assert runner.observatory.attention_impls()["decode"] == "xla"
    assert runner.observatory.attention_impls()["prefill"] == "pallas"
    assert any("DECODE" in e and "Mosaic says no" in e for e in errors)
    # The explicit form cannot be served: a start-up error.
    with pytest.raises(RuntimeError, match="Mosaic says no"):
        _tpu_runner(monkeypatch, only_prefill_compiles, "pallas", errors)


def test_the_constant_that_kept_the_decode_kernel_out_of_auto_is_gone():
    import os

    from production_stack_tpu.engine import model_runner

    assert not hasattr(model_runner, "PALLAS_DECODE_IN_AUTO")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = "PALLAS_" + "DECODE_IN_AUTO"
    for path in ("README.md", "tutorials/12-long-context-serving.md",
                 "production_stack_tpu/engine/model_runner.py",
                 "production_stack_tpu/engine/config.py",
                 "chip_smoke.py"):
        with open(os.path.join(root, path)) as f:
            assert name not in f.read(), path
