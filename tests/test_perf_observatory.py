"""Device performance observatory (docs/observability.md): compile
ledger exactly-once semantics under shape perturbation, HBM ledger
page-math invariants for bf16 and int8 KV, useful-token MFU
arithmetic, zero-overhead byte parity with the observatory removed,
the /debug/compiles + /debug/memory endpoint matrix, the profiler
start/stop guard with span events, and the engine /metrics exposition
and its router scrape/re-export round trip.
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.perf_observatory import (
    PerfObservatory,
    resolve_peak_flops,
)
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.engine.server import EngineServer
from production_stack_tpu.engine.tracing import EngineTracer


def _engine(kv_dtype="auto", **sched_kw):
    config = EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=128,
                          kv_cache_dtype=kv_dtype),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=32, **sched_kw),
    )
    return LLMEngine(config)


def _run(engine, prompt, max_tokens=4):
    sid = engine.add_request(list(prompt), SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True))
    seq = engine.sequences[sid]
    while engine.has_work():
        engine.step()
    return list(seq.output_token_ids)


# ---- peak-FLOPs resolution ------------------------------------------------


def test_resolve_peak_flops():
    # Explicit override always wins.
    assert resolve_peak_flops("TPU v5e", 123.0) == 123.0
    # Prefix match against the device-kind table.
    assert resolve_peak_flops("TPU v4") == 275e12
    assert resolve_peak_flops("TPU v5 lite") == 197e12
    # Unknown devices (including CPU) report an honest 0.
    assert resolve_peak_flops("cpu") == 0.0
    assert resolve_peak_flops(None) == 0.0


def test_mfu_arithmetic():
    engine = _engine()
    obs = engine.runner.observatory
    # Unknown device on CPU: MFU must be 0, never a guess.
    assert obs.peak_flops == 0.0
    _run(engine, range(2, 12))
    assert obs.mfu() == 0.0
    # Pin the peak so the quotient is exact: 2 * params * tokens
    # FLOPs over device-seconds over peak.
    obs.peak_flops = 1e9
    expected = (2.0 * obs.param_count * obs.tokens_total
                / obs.device_seconds_total / 1e9)
    assert obs.mfu() == pytest.approx(expected)
    assert obs.tokens_total > 0 and obs.device_seconds_total > 0


# ---- compile ledger -------------------------------------------------------


def test_compile_ledger_first_run_then_stable():
    engine = _engine()
    obs = engine.runner.observatory
    # Registered at wrap time: the gauge exists at 0 pre-dispatch.
    assert obs.compile_events_total("step") == 0
    out1 = _run(engine, range(2, 12))
    assert len(out1) == 4
    first = obs.compile_events_total("step")
    assert first > 0
    assert sum(obs.compile_seconds_by_kind().values()) > 0
    assert obs.executable_cache_sizes()["step"] >= first
    for entry in obs.recent_compiles():
        assert entry["kind"] == "step"
        assert entry["seconds"] >= 0
        assert entry["cache"] in ("hit", "miss", "none")
        assert isinstance(entry["key"], list)
    # Same shapes again: a warm engine must not compile.
    _run(engine, range(30, 40))
    assert obs.compile_events_total("step") == first


def test_shape_perturbation_compiles_exactly_once():
    """A prompt that crosses into the next W bucket (16 -> 32) adds
    exactly one compile event for each program of that bucket, and
    the ledger records the shape keys: 32 is the top bucket here, whose
    two row widths come up together (model_runner.prefill_shapes), the
    half the one row runs at and the full one beside it."""
    engine = _engine()
    obs = engine.runner.observatory
    _run(engine, range(2, 12))  # 10 tokens: the W=16 prefill bucket
    warm = obs.compile_events_total("step")
    _run(engine, range(2, 22))  # 20 tokens: first W=32 prefill
    assert obs.compile_events_total("step") == warm + 2
    newest = obs.recent_compiles()[-2:]
    assert [e["kind"] for e in newest] == ["step", "step"]
    assert [e["key"] for e in newest] == [[2, 32], [4, 32]]
    _run(engine, range(2, 23))  # the bucket again: nothing compiles
    assert obs.compile_events_total("step") == warm + 2


def test_observatory_none_is_passthrough_byte_identical():
    """Removing the observatory flips every hook to its no-op branch;
    greedy output must stay byte-identical (zero-overhead contract)."""
    plain = _engine()
    expected = _run(plain, range(2, 20), max_tokens=8)
    bare = _engine()
    bare.runner.observatory = None
    got = _run(bare, range(2, 20), max_tokens=8)
    assert got == expected
    assert len(got) == 8


# ---- HBM memory ledger ----------------------------------------------------


def test_hbm_ledger_bf16_invariants():
    engine = _engine(kv_dtype="auto")
    obs = engine.runner.observatory
    cfg = engine.config
    hbm = obs.hbm_bytes()
    leaves = jax.tree_util.tree_leaves(engine.runner.params)
    assert hbm["weights"] == sum(int(x.nbytes) for x in leaves)
    # Full-precision KV: no scale tensors, and the page bytes equal
    # the config's own per-token accounting exactly.
    assert hbm["kv_scales"] == 0
    assert hbm["kv_pages"] == (
        cfg.cache.num_pages * cfg.cache.page_size
        * cfg.cache.kv_bytes_per_token(cfg.model))
    assert hbm["step_buffers"] > 0
    report = obs.memory_report()
    assert report["total_analytic_bytes"] == sum(hbm.values())
    assert report["kv_cache_dtype"] == "bf16"


def test_hbm_ledger_int8_exact_page_math():
    engine = _engine(kv_dtype="int8")
    obs = engine.runner.observatory
    cfg = engine.config
    model = cfg.model
    hbm = obs.hbm_bytes()
    slots = 2 * model.num_hidden_layers * model.num_key_value_heads
    tokens = cfg.cache.num_pages * cfg.cache.page_size
    assert hbm["kv_pages"] == slots * tokens * model.head_dim
    assert hbm["kv_scales"] == slots * tokens * 4
    # pages + scales is exactly the post-expansion slot budget.
    assert hbm["kv_pages"] + hbm["kv_scales"] == (
        tokens * cfg.cache.kv_bytes_per_token(model))
    # int8 capacity expansion actually happened and the ledger sees
    # the expanded page count.
    full_slot = model.head_dim * jnp.dtype(model.jax_dtype).itemsize
    assert cfg.cache.num_pages == max(
        128 * full_slot // (model.head_dim + 4), 128)
    assert obs.memory_report()["num_pages"] == cfg.cache.num_pages


# ---- debug endpoints + profiler guard -------------------------------------


def _server(engine=None):
    return EngineServer(engine or _engine(), "tiny-llama")


async def _with_client(server, fn):
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    try:
        await fn(client)
    finally:
        await client.close()


def test_debug_endpoint_matrix():
    engine = _engine()
    _run(engine, range(2, 12))
    server = _server(engine)

    async def run(client):
        resp = await client.get("/debug/compiles")
        assert resp.status == 200
        data = await resp.json()
        assert data["events"]["step"] > 0
        assert data["executable_cache_sizes"]["step"] >= 1
        assert data["recent"] and "timings" not in data
        resp = await client.get("/debug/compiles?limit=1")
        assert len((await resp.json())["recent"]) == 1
        assert (await client.get(
            "/debug/compiles?limit=nope")).status == 400
        resp = await client.get("/debug/memory")
        assert resp.status == 200
        mem = await resp.json()
        assert mem["analytic"]["weights"] > 0
        assert mem["total_analytic_bytes"] == sum(
            mem["analytic"].values())
    asyncio.run(_with_client(server, run))


def test_debug_endpoints_404_without_observatory():
    engine = _engine()
    engine.runner.observatory = None
    server = _server(engine)

    async def run(client):
        for path in ("/debug/compiles", "/debug/memory"):
            resp = await client.get(path)
            assert resp.status == 404
            assert "observatory" in (
                await resp.json())["error"]["message"]
    asyncio.run(_with_client(server, run))


def test_profiler_start_stop_guard_and_spans(monkeypatch):
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda trace_dir, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    engine = _engine()
    engine.tracer = EngineTracer(ring_size=8)
    server = _server(engine)

    async def run(client):
        # Double-stop before any capture: honest 409.
        assert (await client.post("/debug/profiler/stop")).status == 409
        resp = await client.post("/debug/profiler/start?dir=/tmp/t")
        assert resp.status == 200
        assert (await resp.json())["dir"] == "/tmp/t"
        # Single-capture guard.
        assert (await client.post(
            "/debug/profiler/start")).status == 409
        assert (await client.post("/debug/profiler/stop")).status == 200
        assert (await client.post("/debug/profiler/stop")).status == 409
        # The capture window is span-evented into the flight recorder.
        span = list(engine.tracer._ring)[-1]
        names = [e["event"] for e in span.events]
        assert "profiler_start" in names and "profiler_stop" in names
        assert span.seq_id.startswith("prof-")
    asyncio.run(_with_client(server, run))


# ---- /metrics exposition + router round trip ------------------------------


def test_metrics_exposition_and_router_roundtrip():
    engine = _engine()
    _run(engine, range(2, 12))
    server = _server(engine)
    text_holder = {}

    async def run(client):
        resp = await client.get("/metrics")
        assert resp.status == 200
        text_holder["text"] = await resp.text()
    asyncio.run(_with_client(server, run))
    text = text_holder["text"]
    for needle in (
        'vllm:engine_compile_events_total{kind="step"}',
        'vllm:engine_compile_seconds_total{kind="step"}',
        'vllm:engine_executable_cache_size{kind="step"}',
        'vllm:engine_hbm_bytes{category="weights"}',
        'vllm:engine_step_device_seconds_total{kind="prefill"}',
        "vllm:engine_mfu",
        'vllm:engine_attention_impl{phase="decode"',
    ):
        assert needle in text, needle

    from production_stack_tpu.router.stats.engine_stats import (
        EngineStats,
        initialize_engine_stats_scraper,
    )
    es = EngineStats.from_prometheus_text(text)
    assert es.compile_events_by_kind["step"] > 0
    assert es.executable_cache_size_by_kind["step"] >= 1
    assert es.hbm_bytes_by_category["weights"] > 0
    assert es.step_device_seconds_by_kind["prefill"] > 0
    assert es.engine_mfu == 0.0  # CPU: honest zero
    assert es.attention_impl_by_phase["decode"]

    # Router re-export: the scraped stats surface as per-server gauges.
    from production_stack_tpu.router.services import metrics_service
    from production_stack_tpu.router.stats.request_stats import (
        initialize_request_stats_monitor,
    )
    initialize_request_stats_monitor(60.0)
    scraper = initialize_engine_stats_scraper(scrape_interval=3600.0)
    try:
        with scraper._lock:
            scraper._stats = {"http://e1:8000": es}
        metrics_service.refresh_gauges()
        g = metrics_service.engine_compile_events
        assert g.labels(server="http://e1:8000",
                        kind="step")._value.get() > 0
        g = metrics_service.engine_hbm_bytes
        assert g.labels(server="http://e1:8000",
                        category="weights")._value.get() > 0
        g = metrics_service.engine_attention_impl
        impl = es.attention_impl_by_phase["decode"]
        assert g.labels(server="http://e1:8000", phase="decode",
                        impl=impl)._value.get() == 1.0
    finally:
        scraper.close()
