"""The half-width prefill program (model_runner.prefill_shape): a step
that is at most half full runs at half the rows of the top bucket,
where that is strictly fewer token places.

The rule alone, then through the tiny engines on the CPU: the same
tokens whichever width a step runs at, both widths of the top bucket
up after one request, an all-pad step that touches nothing live, and
the turn record and the counter that say which width ran."""

import dataclasses
import random

import numpy as np
import pytest

from production_stack_tpu.engine import model_runner
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_jamba_config,
    tiny_lfm2_moe_config,
    tiny_longcat_flash_config,
    tiny_model_config,
    tiny_qwen3_next_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.model_runner import (
    prefill_buckets,
    prefill_shape,
    prefill_shapes,
)
from production_stack_tpu.engine.sequence import SamplingParams

CHUNK = 32


# ---- the rule --------------------------------------------------------------

def the_issues_rule(rows, longest, pb, chunk):
    """ISSUE 42's words: the half-width shape iff the plan fits it and
    it is strictly fewer places than the wide step at the chunk's
    bucket."""
    t = next(b for b in prefill_buckets(chunk) if longest <= b)
    half = -(-pb // 2)
    if rows <= half and half * chunk < pb * t:
        return half, chunk
    return pb, t


@pytest.mark.parametrize("rows, longest, pb, chunk, expected", [
    # The Qwen2.5 cell: 8 rows of 256.
    (3, 250, 8, 256, (4, 256)),
    (4, 129, 8, 256, (4, 256)),
    (5, 250, 8, 256, (8, 256)),   # does not fit the half
    (3, 128, 8, 256, (8, 128)),   # the tie, 1024 places both: wide
    (1, 100, 8, 256, (8, 128)),
    (1, 16, 8, 256, (8, 16)),
    (8, 256, 8, 256, (8, 256)),
    # Chunks of 128 under 8 and 16 rows (Jamba, LFM2, LongCat).
    (4, 128, 8, 128, (4, 128)),
    (4, 64, 8, 128, (8, 64)),     # the tie again
    (8, 65, 16, 128, (8, 128)),
    (9, 128, 16, 128, (16, 128)),
    # One row a step: there is no half.
    (1, 256, 1, 256, (1, 256)),
    (1, 20, 1, 256, (1, 32)),
    # Two rows: the half is one row.
    (1, 200, 2, 256, (1, 256)),
    (1, 128, 2, 256, (2, 128)),
    (2, 200, 2, 256, (2, 256)),
    # An odd width: the half is rounded up, so it gains less.
    (3, 250, 5, 256, (3, 256)),
    (3, 128, 5, 256, (5, 128)),   # 768 places narrow, 640 wide
    (4, 250, 5, 256, (5, 256)),
    (2, 250, 3, 256, (2, 256)),
    # A chunk size that is no power of two: its top bucket is less
    # than twice the one below, and the half wins there too.
    (2, 200, 4, 384, (2, 384)),
    (2, 100, 4, 384, (4, 128)),
])
def test_shape_is_the_one_with_the_fewest_places(rows, longest, pb,
                                                 chunk, expected):
    assert prefill_shape(rows, longest, pb, chunk) == expected
    assert expected in prefill_shapes(pb, chunk)
    assert the_issues_rule(rows, longest, pb, chunk) == expected


@pytest.mark.parametrize("pb, chunk", [
    (1, 256), (2, 64), (3, 128), (8, 256), (8, 128), (16, 128),
    (5, 512)])
def test_one_more_shape_and_every_plan_fits_one(pb, chunk):
    shapes = prefill_shapes(pb, chunk)
    wide = [(pb, t) for t in prefill_buckets(chunk)]
    assert shapes[:len(wide)] == wide
    assert shapes[len(wide):] == ([(-(-pb // 2), chunk)] if pb > 1
                                  else [])
    for rows in range(1, pb + 1):
        for longest in (1, 16, 17, chunk // 2, chunk // 2 + 1, chunk):
            b, t = prefill_shape(rows, longest, pb, chunk)
            assert b >= rows and t >= longest
            assert (b, t) == the_issues_rule(rows, longest, pb, chunk)


# ---- through the engine ----------------------------------------------------

def _qwen2():
    return dataclasses.replace(tiny_model_config("llama"),
                               name="tiny-qwen2", architecture="qwen2",
                               attention_bias=True)


MODELS = {
    "qwen2": _qwen2,
    "qwen3_next": tiny_qwen3_next_config,
    "jamba": tiny_jamba_config,
    "lfm2_moe": tiny_lfm2_moe_config,
    "longcat_flash": tiny_longcat_flash_config,
}


def _engine(family="qwen2", prefill_batch_size=4, max_num_seqs=8):
    model = MODELS[family]()
    model.attention_impl = "xla"
    return LLMEngine(EngineConfig(
        model=model,
        cache=CacheConfig(page_size=16, num_pages=128,
                          enable_prefix_caching=False),
        scheduler=SchedulerConfig(max_num_seqs=max_num_seqs,
                                  max_model_len=256,
                                  prefill_chunk_size=CHUNK,
                                  prefill_batch_size=prefill_batch_size,
                                  decode_steps=4)))


def _prompts(lengths, seed=5):
    rs = random.Random(seed)
    return [[rs.randint(1, 500) for _ in range(n)] for n in lengths]


def _greedy(engine, prompts, max_tokens=7, between=None):
    """All the prompts at once, greedy; ``between`` runs after every
    step."""
    sampling = dict(max_tokens=max_tokens, temperature=0.0,
                    ignore_eos=True)
    seqs = [engine.sequences[engine.add_request(
        p, SamplingParams(**sampling))] for p in prompts]
    while engine.has_work():
        engine.step()
        if between is not None:
            between()
    return [s.output_token_ids for s in seqs]


def _only_wide(monkeypatch):
    monkeypatch.setattr(
        model_runner, "prefill_shapes",
        lambda pb, chunk: [(pb, t) for t in prefill_buckets(chunk)])


@pytest.mark.parametrize("family", sorted(MODELS))
def test_narrow_steps_give_the_tokens_wide_steps_give(family,
                                                      monkeypatch):
    """Two prompts at a time over four rows, the first of two chunks:
    every step is at most half full and reaches the top bucket, so the
    state, the tails and the pages of the first chunk are carried
    across narrow steps; then five prompts at once, a wide step. The
    same requests with the half width taken away give the same
    tokens."""
    first, second = _prompts([CHUNK + 18, 21]), _prompts([30] * 5, 6)
    narrow = _engine(family)
    got = [_greedy(narrow, first), _greedy(narrow, second)]
    # Two steps for the first pair, then five rows: two steps (4 + 1)
    # of which the last is narrow.
    assert narrow.runner.num_narrow_prefill_steps == 3

    _only_wide(monkeypatch)
    wide = _engine(family)
    want = [_greedy(wide, first), _greedy(wide, second)]
    assert wide.runner.num_narrow_prefill_steps == 0
    assert got == want


def test_one_request_at_the_top_bucket_brings_up_both_widths():
    """A single row over half a chunk warms the half width; the runner
    brings the full width up in the same turn, so the five-row step
    that follows compiles nothing."""
    engine = _engine(prefill_batch_size=8)
    obs = engine.runner.observatory
    _greedy(engine, _prompts([CHUNK - 2]))
    keys = [tuple(e["key"]) for e in obs.recent_compiles(limit=-1)
            if e["kind"] == "step"]
    assert (4, CHUNK) in keys and (8, CHUNK) in keys
    assert engine.runner.num_narrow_prefill_steps == 1
    before = obs.compile_events_total()
    _greedy(engine, _prompts([CHUNK - 3] * 5, seed=8))
    assert obs.compile_events_total() == before
    assert engine.runner.last_prefill_width == 8
    # And the other way round: a full step first, the half with it.
    engine = _engine(prefill_batch_size=8)
    _greedy(engine, _prompts([CHUNK - 3] * 5, seed=8))
    before = engine.runner.observatory.compile_events_total()
    _greedy(engine, _prompts([CHUNK - 2]))
    assert engine.runner.last_prefill_width == 4
    assert engine.runner.observatory.compile_events_total() == before


def test_a_low_bucket_brings_up_nothing_else():
    engine = _engine(prefill_batch_size=8)
    _greedy(engine, _prompts([9]))
    keys = [tuple(e["key"]) for e in
            engine.runner.observatory.recent_compiles(limit=-1)
            if e["kind"] == "step" and e["key"][1] > 1]
    assert keys == [(8, 16)]
    assert engine.runner.num_narrow_prefill_steps == 0


def _trash_only(before, after, engine):
    """Every element that differs lies in the trash page or the trash
    state slot (index 0 of a pages or a slots axis)."""
    sizes = {engine.config.cache.num_pages,
             engine.cache_manager.num_state_slots}
    for a, b in zip(before, after):
        where = np.argwhere(a != b)
        if len(where):
            assert any(a.shape[axis] in sizes
                       and not where[:, axis].any()
                       for axis in range(a.ndim)), a.shape


@pytest.mark.parametrize("family", ["qwen2", "qwen3_next", "jamba",
                                    "lfm2_moe"])
def test_the_all_pad_step_touches_nothing_live(family):
    """The step that brings up the other width, run again after every
    step of four live requests: pages, state slots and the serving
    key stream are as they were, and so are the tokens."""
    import jax

    prompts = _prompts([CHUNK + 9, 20, 31, 12], seed=11)
    want = _greedy(_engine(family), prompts)

    engine = _engine(family)
    runner = engine.runner
    widths = []

    def cache():
        return [np.asarray(x) for x in jax.tree_util.tree_leaves(
            (runner.k_cache, runner.v_cache))]

    def pad_steps():
        before, drawn = cache(), runner._keys._drawn
        counted = runner.num_narrow_prefill_steps
        for b in (2, 4):
            for payload in runner._other_width_payloads(b, CHUNK):
                runner._load_step_program(payload).join()
                runner._dispatch(1, CHUNK, payload)
        _trash_only(before, cache(), engine)
        assert runner._keys._drawn == drawn
        assert runner.num_narrow_prefill_steps == counted
        widths.append(runner.last_prefill_width)

    assert _greedy(engine, prompts, between=pad_steps) == want
    assert set(widths) == {4}  # four rows at once: the full width


def test_sampled_rows_draw_the_keys_they_drew():
    """A sampling request after the bring-up step draws the key it
    would have drawn without one: same seed, same tokens as an engine
    whose shapes hold no half."""
    sampling = SamplingParams(max_tokens=8, temperature=0.9,
                              ignore_eos=True)
    prompt = _prompts([CHUNK - 1])[0]
    with pytest.MonkeyPatch.context() as patch:
        _only_wide(patch)
        want = _engine().generate(prompt, sampling).output_token_ids
    # One row at either width: the same program but for its pad rows.
    assert _engine().generate(prompt, sampling).output_token_ids == want


def test_startup_probes_the_full_width_at_every_bucket(monkeypatch):
    """What ``auto`` compiles at start-up on a TPU, without one: the
    prefill kernel at the full width of every token bucket. The half
    width is rows fewer on the kernel's grid and the same blocks, so
    it is not probed (tests/test_pallas_lowering.py compiles it for a
    described v5e at the cells' head shapes)."""
    import jax

    from production_stack_tpu.engine.model_runner import ModelRunner

    seen = []

    def probe(fn, *args):
        if fn.__name__ == "paged_prefill_attention":
            seen.append(args[0].shape[:2])
        return None

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ModelRunner, "_lowering_error",
                        staticmethod(probe))
    model = tiny_model_config("llama")
    model.attention_impl = "auto"
    runner = ModelRunner(EngineConfig(
        model=model,
        cache=CacheConfig(page_size=128, num_pages=32),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64,
                                  prefill_batch_size=4)))
    assert runner.observatory.attention_impls()["prefill"] == "pallas"
    assert seen == [(4, t) for t in prefill_buckets(64)]
    assert set(prefill_shapes(4, 64)) - set(seen) == {(2, 64)}


# ---- the record and the counter --------------------------------------------

def test_turn_record_and_stats_say_which_width_ran():
    from production_stack_tpu.engine.tracing import EngineTracer

    engine = _engine()
    engine.tracer = EngineTracer()
    _greedy(engine, _prompts([CHUNK - 1, 12]))    # narrow: (2, 32)
    _greedy(engine, _prompts([10, 12]))           # a low bucket: wide
    _greedy(engine, _prompts([CHUNK - 1] * 3))    # three rows: wide
    prefill = [s for s in engine.tracer.recent_steps(limit=0)
               if s.get("kind") == "prefill"]
    assert [(s["prefill_rows"], s["prefill_width"]) for s in prefill] \
        == [(2, 2), (2, 4), (3, 4)]
    assert all(s["row_bucket"] == 4 for s in prefill)
    assert engine.stats()["engine_prefill_narrow_steps_total"] == 1


def test_metrics_exposes_the_narrow_steps_counter():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    async def run(engine, count):
        server = EngineServer(engine, "tiny-qwen2")
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            text = await (await client.get("/metrics")).text()
        finally:
            await client.close()
        assert ("# TYPE vllm:engine_prefill_narrow_steps_total counter"
                f"\nvllm:engine_prefill_narrow_steps_total {count}"
                ) in text

    asyncio.run(run(_engine(), 0.0))
    engine = _engine()
    _greedy(engine, _prompts([CHUNK - 1]))  # before the server's loop
    asyncio.run(run(engine, 1.0))
