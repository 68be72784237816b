"""A run of tokens a row goes to its pages a page at a time, in place
(ops/attention.py ``write_run_to_pages``): against the scatter of
``write_to_pages`` bit for bit outside page 0 (the trash page, which
the scatter fills with the pads and the run writer leaves as it was),
at a prefill chunk's shapes and at the shapes of a deferred burst's
flush, and served: the deferred burst, the drafting burst and the
prefill chunks of four families answer what they answer with the
scatter in the writer's place (the parent's programs).

float32 on the CPU; nothing is rounded, so every comparison is exact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine import config as cfg
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.ops.attention import (
    write_run_to_pages,
    write_to_pages,
)
from production_stack_tpu.ops.quant_kv import quant_cache_zeros


def runs(rows, t, page, heads=2, width=8, planes=1):
    """``(caches, news, table, start, count)``: every row its own
    pages, in a shuffled order. The first rows are the named ones: a
    full run from a page's first lane, one token on a page's first
    lane, a full run from inside a page, a run one short of full from
    a page's last lane (over every edge it can cross), a pad row (no
    token, its table all page 0), a run that ends in the table's last
    column, and one that ends where a page ends; the others are
    drawn."""
    rng = np.random.default_rng(rows * t + page + heads + width)
    pages_a_row = 2 * (-(-t // page)) + 2
    room = pages_a_row * page
    table = 1 + rng.permutation(rows * pages_a_row).reshape(
        rows, pages_a_row)
    start = rng.integers(0, room - t + 1, rows)
    count = rng.integers(0, t + 1, rows)
    named = [(0, t), (page, 1), (page + 3, t), (2 * page - 1, max(t - 1, 1)),
             (0, 0), (room - t, t), (2 * page - t % page, t)][:rows]
    start[:len(named)], count[:len(named)] = zip(*named)
    table[4] = 0
    shape = (heads, 1 + rows * pages_a_row, width, page)
    caches = tuple(jnp.asarray(rng.standard_normal(shape).astype(np.float32))
                   for _ in range(planes))
    news = tuple(jnp.asarray(rng.standard_normal(
        (rows, t, heads, width)).astype(np.float32)) for _ in range(planes))
    return (caches, news, jnp.asarray(table, jnp.int32),
            jnp.asarray(start, jnp.int32), jnp.asarray(count, jnp.int32))


def by_scatter(cache, new_kv, table, start, count):
    """The parent's writer under the run writer's contract: one
    ``write_to_pages`` a plane."""
    t = (new_kv[0] if isinstance(cache, tuple) else new_kv).shape[1]
    slot = jnp.arange(t)[None]
    args = (table, start[:, None] + slot, slot < count[:, None])
    if not isinstance(cache, tuple):
        return write_to_pages(cache, new_kv, *args)
    return tuple(write_to_pages(c, n, *args) for c, n in zip(cache, new_kv))


# id: (rows, T, page, heads, width, planes)
CASES = {
    # A prefill chunk of 8 rows: inside a page, over one edge, over
    # two (the cases tests/test_window_attention.py held until PR 52).
    "chunk16_page16": (8, 16, 16, 2, 8, 1),
    "chunk32_page16": (8, 32, 16, 2, 8, 1),
    "chunk24_page16": (8, 24, 16, 2, 8, 1),
    "chunk8_page16": (8, 8, 16, 2, 8, 1),
    "chunk17_page16": (8, 17, 16, 2, 8, 1),
    "chunk128_page16": (8, 128, 16, 2, 8, 1),
    "chunk256_page128": (8, 256, 128, 2, 8, 1),
    "chunk128_page128": (8, 128, 128, 2, 8, 1),
    "chunk32_page128": (8, 32, 128, 2, 8, 1),
    "one_token": (8, 1, 16, 2, 8, 1),
    # The planes of the cells: K/V of 8 heads x 64 and 2 x 128, the
    # latent's one head x 576; K and V under one table.
    "kv8x64_chunk128": (8, 128, 128, 8, 64, 2),
    "kv2x128_chunk256": (8, 256, 128, 2, 128, 2),
    "latent_chunk128": (8, 128, 128, 1, 576, 1),
    "latent_tail32": (8, 32, 128, 1, 576, 1),
    "latent_tail64": (8, 64, 128, 1, 576, 1),
    # A flush: 32 slots a row, 64 to 256 rows, every plane of a model
    # under the one table.
    "flush64_page16": (64, 32, 16, 2, 8, 2),
    "flush128_page16": (128, 32, 16, 2, 8, 2),
    "flush256_page16": (256, 32, 16, 2, 8, 2),
    "flush128_page128": (128, 32, 128, 2, 8, 2),
    "flush256_page128": (256, 32, 128, 2, 8, 2),
    "flush16_12planes": (16, 32, 16, 2, 8, 12),
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_run_written_page_wise_equals_the_scatter(case):
    rows, t, page, heads, width, planes = CASES[case]
    caches, news, table, start, count = runs(rows, t, page, heads, width,
                                             planes)
    before = [np.asarray(c) for c in caches]
    want = [np.asarray(w)
            for w in by_scatter(caches, news, table, start, count)]
    # Under jit with the planes donated, as a step program holds them;
    # one plane goes in bare and comes back bare.
    write = jax.jit(write_run_to_pages, donate_argnums=0)
    if planes == 1:
        got = [write(caches[0], news[0], table, start, count)]
    else:
        got = write(caches, news, table, start, count)
        assert isinstance(got, tuple) and len(got) == planes
    touched = np.zeros(before[0].shape[1], bool)
    for r in range(rows):
        if int(count[r]):
            first, last = (int(start[r]) // page,
                           (int(start[r]) + int(count[r]) - 1) // page)
            touched[np.asarray(table)[r, first:last + 1]] = True
    for b, w, g in zip(before, want, got):
        g = np.asarray(g)
        assert np.array_equal(g[:, 1:], w[:, 1:])
        # The trash page is as it was, and so is every page no run
        # reached; the runs' pages are not.
        assert np.array_equal(g[:, ~touched], b[:, ~touched])
        assert not touched[0]
        assert not np.array_equal(g[:, touched], b[:, touched])


def test_a_step_of_pad_rows_writes_nothing():
    caches, news, table, start, _ = runs(8, 32, 16)
    got = np.asarray(write_run_to_pages(
        caches[0], news[0], jnp.zeros_like(table), jnp.zeros_like(start),
        jnp.zeros_like(start)))
    assert np.array_equal(got, np.asarray(caches[0]))


def test_an_int8_plane_and_the_stacked_cache_are_refused():
    caches, news, *args = runs(8, 16, 16)
    with pytest.raises(ValueError, match="int8"):
        write_run_to_pages(quant_cache_zeros(caches[0].shape), news[0],
                           *args)
    with pytest.raises(ValueError, match="stacked"):
        write_run_to_pages(jnp.stack([caches[0]] * 2), news[0], *args)
    with pytest.raises(ValueError, match="stacked"):
        write_run_to_pages((caches[0], jnp.stack([caches[0]] * 2)),
                           (news[0], news[0]), *args)


# ---- served ----------------------------------------------------------

def _glm():
    return cfg.tiny_glm4_moe_lite_config(vocab_size=16)


def engine_of(model, deferred=True, **cache):
    """A tiny engine: pages of 16, chunks of 32, bursts of four steps."""
    model.attention_impl = "xla"
    return LLMEngine(cfg.EngineConfig(
        model=model,
        cache=cfg.CacheConfig(page_size=16, num_pages=64, **cache),
        scheduler=cfg.SchedulerConfig(
            max_num_seqs=4, max_model_len=256, prefill_chunk_size=32,
            prefill_batch_size=2, decode_steps=4,
            deferred_kv_writes=deferred,
            draft_module=deferred and model.has_draft_module)))


def answers(engine, requests, vocab=500):
    """Greedy answers to ``(prompt tokens, answer tokens)`` requests,
    the prompts drawn by their length."""
    ids = [engine.add_request(
        [int(x) for x in np.random.RandomState(n).randint(0, vocab, size=n)],
        SamplingParams(temperature=0.0, max_tokens=m, ignore_eos=True))
        for n, m in requests]
    seqs = [engine.sequences[i] for i in ids]
    while engine.has_work():
        engine.step()
    return [s.output_token_ids for s in seqs]


FAMILIES = {
    "llama": (lambda: cfg.tiny_model_config("llama"), 500),
    "lfm2_moe": (cfg.tiny_lfm2_moe_config, 500),
    "longcat_flash": (cfg.tiny_longcat_flash_config, 500),
    "glm4_moe_lite_drafting": (_glm, 16),
}

REQUESTS = ((70, 21), (20, 9), (45, 14), (33, 3), (64, 18), (12, 11))


def served(family, monkeypatch=None, deferred=True):
    """Six prompts through chunked prefill and bursts of four steps
    (deferred, and drafting where the family drafts): the answers and
    the page planes they left. ``monkeypatch`` puts the parent's
    writer, one ``write_to_pages`` a plane, wherever the run writer is
    called."""
    if monkeypatch is not None:
        for module in ("engine.model_runner", "models.llama",
                       "models.longcat_flash"):
            monkeypatch.setattr(
                f"production_stack_tpu.{module}.write_run_to_pages",
                by_scatter)
    make, vocab = FAMILIES[family]
    model = make()
    engine = engine_of(model, deferred)
    tokens = answers(engine, REQUESTS, vocab)
    runner = engine.runner
    planes = [np.asarray(c)[:, 1:]
              for cache in (runner.k_cache, runner.v_cache)
              for c, state in zip(cache, model.cache_entry_is_state)
              if c is not None and not state]
    return tokens, planes


@pytest.mark.parametrize("family", list(FAMILIES))
def test_served_answers_and_pages_are_the_scatters(family, monkeypatch):
    want_tokens, want_planes = served(family, monkeypatch)
    monkeypatch.undo()
    got_tokens, got_planes = served(family)
    assert got_tokens == want_tokens
    assert [len(t) for t in got_tokens] == [m for _, m in REQUESTS]
    assert len(got_planes) == len(want_planes) > 0
    for got, want in zip(got_planes, want_planes):
        assert np.array_equal(got, want)
        assert np.any(want != 0)


@pytest.mark.parametrize("family", ["llama", "lfm2_moe"])
def test_a_deferred_burst_answers_what_the_eager_burst_answers(family):
    """The eager burst writes a token a step by the scatter and never
    flushes: the witness the repo had before there was a run writer."""
    eager_tokens, eager_planes = served(family, deferred=False)
    tokens, planes = served(family)
    assert tokens == eager_tokens
    for got, want in zip(planes, eager_planes):
        assert np.allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("layout,kv_dtype,want", [
    ("auto", "auto", "in_place"), ("per_layer", "auto", "in_place"),
    ("stacked", "auto", "scatter"), ("per_layer", "int8", "scatter")])
def test_version_says_how_a_run_goes_to_its_pages(layout, kv_dtype, want):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer
    engine = engine_of(cfg.tiny_model_config("llama"), cache_layout=layout,
                       kv_cache_dtype=kv_dtype)

    async def version():
        client = TestClient(TestServer(
            EngineServer(engine, "tiny").build_app()))
        await client.start_server()
        try:
            return await (await client.get("/version")).json()
        finally:
            await client.close()

    assert asyncio.run(version())["page_writes"] == want


@pytest.mark.parametrize("layout", ["per_layer", "stacked"])
def test_int8_pages_keep_the_scatter_and_serve_what_the_eager_burst_serves(
        layout):
    """What the run writer refuses is not handed to it: a deferred
    burst over int8 pages flushes by ``write_to_pages`` and answers
    what the eager burst answers."""
    def served_by(deferred):
        return answers(
            engine_of(cfg.tiny_model_config("llama"), deferred,
                      cache_layout=layout, kv_cache_dtype="int8"),
            ((41, 13), (15, 13), (30, 13)))

    assert served_by(True) == served_by(False)
