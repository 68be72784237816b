"""Multi-step decode: K fused decode iterations must generate exactly
what single-step decoding generates (greedy), handle stop tokens
mid-window (tail discarded), and respect max_tokens budgets."""

import numpy as np

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams


def _engine(decode_steps, max_num_seqs=4, max_model_len=256,
            num_pages=128, prefill_chunk_size=32):
    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=num_pages),
        scheduler=SchedulerConfig(max_num_seqs=max_num_seqs,
                                  max_model_len=max_model_len,
                                  prefill_chunk_size=prefill_chunk_size,
                                  decode_steps=decode_steps),
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


def test_multistep_matches_single_step_greedy():
    rs = np.random.RandomState(1)
    prompts = [[int(x) for x in rs.randint(1, 500, size=n)]
               for n in (7, 20, 41)]
    expected = _gen(_engine(decode_steps=1), prompts)
    got = _gen(_engine(decode_steps=4), prompts)
    assert got == expected
    assert all(len(t) == 12 for t in got)


def test_window_respects_max_tokens():
    """max_tokens not divisible by K: the tail runs single-step and the
    budget is met exactly."""
    prompts = [[5, 6, 7, 8]]
    got = _gen(_engine(decode_steps=4), prompts, max_tokens=10)
    assert len(got[0]) == 10
    expected = _gen(_engine(decode_steps=1), prompts, max_tokens=10)
    assert got == expected


def test_stop_token_mid_window_discards_tail():
    """Pick the greedy continuation's 2nd token as a stop token: with
    K=4 it fires mid-window and the tail must be dropped."""
    prompts = [[9, 10, 11, 12, 13]]
    ref = _gen(_engine(decode_steps=1), prompts, max_tokens=8)[0]
    stop = ref[1]
    kw = dict(max_tokens=8, ignore_eos=False, stop_token_ids=[stop])
    got1 = _gen(_engine(decode_steps=1), prompts, **kw)[0]
    got4 = _gen(_engine(decode_steps=4), prompts, **kw)[0]
    assert got1 == got4
    assert got4[-1] == stop
    assert len(got4) == 2


def test_mixed_sampling_batch_keeps_greedy_rows_deterministic():
    """A stochastic row in the burst batch must not perturb greedy
    rows (per-row temperature; the sampler only randomizes rows with
    temperature > 0)."""
    rs = np.random.RandomState(3)
    greedy_prompt = [int(x) for x in rs.randint(1, 500, size=23)]
    stoch_prompt = [int(x) for x in rs.randint(1, 500, size=17)]

    solo = _gen(_engine(decode_steps=4), [greedy_prompt])[0]

    engine = _engine(decode_steps=4)
    sids = [
        engine.add_request(greedy_prompt, SamplingParams(
            max_tokens=12, temperature=0.0, ignore_eos=True)),
        engine.add_request(stoch_prompt, SamplingParams(
            max_tokens=12, temperature=0.9, top_p=0.9,
            ignore_eos=True)),
    ]
    seqs = [engine.sequences[s] for s in sids]
    while engine.has_work():
        engine.step()
    assert seqs[0].output_token_ids == solo
    assert len(seqs[1].output_token_ids) == 12


def test_penalized_burst_matches_single_step():
    """Greedy + penalties must produce identical tokens whether the
    decode runs as fused bursts (counts tracked on device) or single
    steps (counts rebuilt on host per dispatch)."""
    from production_stack_tpu.engine.sequence import SamplingParams

    prompt = list(range(1, 30))
    sp = dict(max_tokens=12, temperature=0.0, ignore_eos=True,
              presence_penalty=1.5, frequency_penalty=0.5,
              repetition_penalty=1.3)

    def gen(steps):
        engine = _engine(decode_steps=steps)
        seq = engine.generate(prompt, SamplingParams(**sp))
        return seq.output_token_ids

    burst, single = gen(6), gen(1)
    assert burst == single


def test_seeded_requests_reproduce():
    """Identical seeded stochastic requests produce identical tokens —
    across engine instances and regardless of burst width — and a
    different seed diverges."""
    from production_stack_tpu.engine.sequence import SamplingParams

    prompt = list(range(1, 30))

    def gen(steps, seed):
        engine = _engine(decode_steps=steps)
        seq = engine.generate(prompt, SamplingParams(
            max_tokens=10, temperature=0.9, ignore_eos=True,
            seed=seed))
        return seq.output_token_ids

    a = gen(6, 1234)
    b = gen(6, 1234)
    c = gen(1, 1234)
    d = gen(6, 999)
    assert a == b == c
    assert d != a


# ---- the attention's width follows the longest row (ops/attention.py) -----


def _wide_engine(decode_steps):
    """A table of 128 pages of 16: two of the attention's blocks of
    64 pages, their edge at 1024 tokens."""
    return _engine(decode_steps, max_model_len=2048, num_pages=320,
                   prefill_chunk_size=256)


def _long_prompts(sizes, seed=5):
    rs = np.random.RandomState(seed)
    return [[int(x) for x in rs.randint(1, 500, size=n)] for n in sizes]


def test_row_crossing_a_width_edge_mid_burst_matches_single_step():
    """1019 prompt tokens + 24 greedy ones at K=8: the row passes 1024
    tokens inside the first burst, so the eager burst gathers one
    block of 64 pages, then two, between two of its steps."""
    from production_stack_tpu.ops.attention import block_pages
    assert block_pages(128, 16) == 64
    prompts = _long_prompts((1019, 40))
    expected = _gen(_wide_engine(1), prompts, max_tokens=24)
    got = _gen(_wide_engine(8), prompts, max_tokens=24)
    assert got == expected
    assert all(len(t) == 24 for t in got)


def test_a_longer_row_joining_the_batch_compiles_no_new_burst():
    """One burst program serves every length: the compile ledger's
    decode_burst count stands still when a row past the first block's
    edge joins rows under it."""
    engine = _wide_engine(8)
    obs = engine.runner.observatory
    short = _long_prompts((40, 200))
    _gen(engine, short, max_tokens=16)
    assert engine.runner.last_attn_pages == 64
    warm = obs.compile_events_total("decode_burst")
    assert warm == 1
    _gen(engine, short + _long_prompts((1100,), seed=6), max_tokens=16)
    assert obs.compile_events_total("decode_burst") == warm
