"""Crash-safe serving (docs/crash_recovery.md).

Covers the whole failure-domain story: the router's mid-stream
failover (kill an engine mid-greedy-stream, the client's concatenated
SSE bytes match an uninterrupted run), the real engine's checkpoint
ship + /v1/resume restore (bf16 and int8 KV, hit and miss-recompute
paths), honest terminal errors when no checkpoint exists, poison-
request quarantine after repeated crashes, the step watchdog flipping
/health, and the fleet manager's crash-loop containment (jittered
exponential backoff, per-pool breaker, crash vs drain-exit).

The router's protocol is tested against fake engines (crash fakes run
as subprocesses — the crash fault SIGKILLs its whole process); the
real-engine parity tests build LLMEngines (12 s together, PR 46).
"""

import asyncio
import json
import socket
import subprocess
import sys
import time
from types import SimpleNamespace

import aiohttp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.fleet.manager import FleetManager, LIVE
from production_stack_tpu.fleet.spec import FleetSpec, PoolSpec
from production_stack_tpu.router.resilience import (
    ResilienceConfig,
    initialize_resilience,
)
from production_stack_tpu.router.service_discovery import (
    initialize_service_discovery,
)
from production_stack_tpu.router.services import request_service
from production_stack_tpu.router.services.metrics_service import (
    fleet_crash_respawns,
)
from production_stack_tpu.router.services.rewriter import (
    initialize_request_rewriter,
)
from production_stack_tpu.router.stats.engine_stats import (
    initialize_engine_stats_scraper,
)
from production_stack_tpu.router.stats.request_stats import (
    initialize_request_stats_monitor,
)
from production_stack_tpu.testing.fake_engine import build_fake_engine


# ---- shared helpers -------------------------------------------------------

def _free_ports(n: int):
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    # Roundrobin sorts endpoints lexicographically by URL: hand back
    # the ports in that order so tests control who gets request #1.
    return sorted(ports, key=str)


def _chat_body(model="m1", stream=False, max_tokens=3):
    return {
        "model": model,
        "messages": [{"role": "user", "content": "hello"}],
        "max_tokens": max_tokens,
        "stream": stream,
    }


def _sse_contents(text: str):
    """Delta contents of an SSE chat stream, in order."""
    contents = []
    for line in text.splitlines():
        if not line.startswith("data: ") or line == "data: [DONE]":
            continue
        event = json.loads(line[len("data: "):])
        if "choices" not in event:  # terminal in-band error event
            continue
        choice = event["choices"][0]
        delta = choice.get("delta") or {}
        if delta.get("content"):
            contents.append(delta["content"])
    return contents


def _spawn_fake(port: int, *extra: str) -> subprocess.Popen:
    """A fake engine in its own process: the crash fault SIGKILLs the
    whole process, so an in-process fake would kill the test runner."""
    argv = [sys.executable, "-m",
            "production_stack_tpu.testing.fake_engine",
            "--host", "127.0.0.1", "--port", str(port),
            "--model", "m1", "--ttft", "0.0", "--speed", "200",
            *extra]
    return subprocess.Popen(argv, stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)


async def _wait_up(url: str, deadline_s: float = 15.0) -> None:
    deadline = time.monotonic() + deadline_s
    async with aiohttp.ClientSession() as session:
        while time.monotonic() < deadline:
            try:
                async with session.get(url + "/health") as resp:
                    if resp.status in (200, 503):
                        return
            except Exception:
                pass
            await asyncio.sleep(0.05)
    raise AssertionError(f"fake engine at {url} never came up")


def _reap(*procs: subprocess.Popen) -> None:
    for proc in procs:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=10)


async def _start_router(urls) -> TestClient:
    """Router singletons over *urls* (all model m1, role both), with
    the crash-recovery counters reset."""
    from production_stack_tpu.router.app import build_app
    from production_stack_tpu.router.routing.logic import (
        initialize_routing_logic,
    )
    request_service.stream_resumes_by_outcome.clear()
    request_service.poison_quarantines_total = 0
    request_service._poison_crashes.clear()
    initialize_service_discovery(
        "static", urls=list(urls), models=["m1"] * len(urls))
    initialize_request_stats_monitor(60.0)
    initialize_engine_stats_scraper(3600.0)
    initialize_routing_logic("roundrobin")
    initialize_request_rewriter("noop")
    initialize_resilience(ResilienceConfig(
        max_retries=2, backend_connect_timeout=1.0, backend_timeout=10.0,
        health_check_interval=0.0,
    ))
    client = TestClient(TestServer(build_app()))
    await client.start_server()
    return client


# ---- router chaos E2E: mid-stream failover --------------------------------

async def test_router_resumes_crashed_stream_byte_identical():
    """The acceptance kill test: the engine serving a greedy stream is
    SIGKILLed mid-generation; the router resumes it from the last
    checkpoint on the surviving replica and the client's concatenated
    stream is byte-identical to an uninterrupted run — same deltas,
    same response id, one role chunk, no leaked checkpoint frames, no
    client-visible error."""
    n = 10
    crash_port, ok_port = _free_ports(2)
    crash = _spawn_fake(crash_port, "--fault", "crash",
                        "--checkpoint-interval-tokens", "2",
                        "--crash-after-tokens", "4")
    ok = _spawn_fake(ok_port, "--checkpoint-interval-tokens", "2")
    crash_url = f"http://127.0.0.1:{crash_port}"
    ok_url = f"http://127.0.0.1:{ok_port}"
    router = None
    try:
        await _wait_up(crash_url)
        await _wait_up(ok_url)
        router = await _start_router([crash_url, ok_url])

        resp = await router.post(
            "/v1/chat/completions",
            json=_chat_body(stream=True, max_tokens=n))
        assert resp.status == 200  # never a client-visible 5xx
        text = await resp.text()

        # Byte identity with an uninterrupted run: every token exactly
        # once, in order, under the original response id.
        assert _sse_contents(text) == [f"tok{i} " for i in range(n)]
        ids = {json.loads(line[len("data: "):])["id"]
               for line in text.splitlines()
               if line.startswith("data: ") and line != "data: [DONE]"}
        assert len(ids) == 1
        roles = [line for line in text.splitlines()
                 if '"role"' in line]
        assert len(roles) == 1  # the resumed leg never re-sends it
        assert "data: [DONE]" in text
        assert "upstream_error" not in text
        # Checkpoint frames are router-internal control traffic.
        assert ": checkpoint" not in text

        # The crash fake really died (SIGKILL, not a clean finish).
        assert crash.wait(timeout=10) != 0
        assert request_service.stream_resumes_by_outcome == {
            "resumed": 1}

        # The recovery counters ride the router's /metrics.
        metrics = await (await router.get("/metrics")).text()
        assert ('vllm:stream_resumes_total{outcome="resumed"} 1.0'
                in metrics)
        assert "vllm:fleet_poison_quarantines_total 0.0" in metrics
    finally:
        if router is not None:
            await router.close()
        _reap(crash, ok)


async def test_crash_without_checkpoint_ends_with_terminal_error():
    """Checkpointing off: a mid-stream crash cannot be resumed, and
    the stream must end with an explicit in-band error event plus
    [DONE] — never a silent truncation the client could mistake for a
    completed response."""
    (port,) = _free_ports(1)
    crash = _spawn_fake(port, "--fault", "crash",
                        "--crash-after-tokens", "4")
    url = f"http://127.0.0.1:{port}"
    router = None
    try:
        await _wait_up(url)
        router = await _start_router([url])
        resp = await router.post(
            "/v1/chat/completions",
            json=_chat_body(stream=True, max_tokens=10))
        assert resp.status == 200  # headers were already streamed
        text = await resp.text()
        contents = _sse_contents(text)
        # A clean prefix of the generation, then the terminal error.
        assert contents == [f"tok{i} " for i in range(len(contents))]
        assert len(contents) <= 4
        assert '"type": "upstream_error"' in text
        assert "no resume checkpoint" in text
        assert text.rstrip().endswith("data: [DONE]")
        assert request_service.stream_resumes_by_outcome == {
            "no_checkpoint": 1}
    finally:
        if router is not None:
            await router.close()
        _reap(crash)


async def test_poison_request_quarantined_after_two_crashes():
    """A request that crashes two engines is poison: the router must
    stop resuming it (no third victim) and end the stream with a
    terminal quarantine error."""
    p_a, p_b, p_h = _free_ports(3)
    crash_a = _spawn_fake(p_a, "--fault", "crash",
                          "--checkpoint-interval-tokens", "2",
                          "--crash-after-tokens", "4")
    crash_b = _spawn_fake(p_b, "--fault", "crash",
                          "--checkpoint-interval-tokens", "2",
                          "--crash-after-tokens", "4")
    url_a = f"http://127.0.0.1:{p_a}"
    url_b = f"http://127.0.0.1:{p_b}"
    # The would-be third victim runs in-process so its state is
    # inspectable: quarantine means it is NEVER asked to resume.
    healthy = TestServer(
        build_fake_engine(model="m1", speed=200, ttft=0.0,
                          checkpoint_interval=2),
        port=p_h)
    await healthy.start_server()
    url_h = f"http://127.0.0.1:{p_h}"
    router = None
    try:
        await _wait_up(url_a)
        await _wait_up(url_b)
        router = await _start_router([url_a, url_b, url_h])

        resp = await router.post(
            "/v1/chat/completions",
            json=_chat_body(stream=True, max_tokens=12))
        assert resp.status == 200
        text = await resp.text()
        contents = _sse_contents(text)
        # Two crash legs delivered a gapless, duplicate-free prefix...
        assert contents == [f"tok{i} " for i in range(len(contents))]
        assert 4 <= len(contents) <= 8
        # ...then the honest quarantine verdict.
        assert "quarantined" in text
        assert text.rstrip().endswith("data: [DONE]")
        assert crash_a.wait(timeout=10) != 0
        assert crash_b.wait(timeout=10) != 0
        # No third retry: the healthy replica was never touched.
        assert healthy.app["state"].requests_received == 0
        assert healthy.app["state"].stream_resumes == 0
        assert request_service.poison_quarantines_total == 1
        assert request_service.stream_resumes_by_outcome == {
            "quarantined": 1}
        metrics = await (await router.get("/metrics")).text()
        assert "vllm:fleet_poison_quarantines_total 1.0" in metrics
        assert ('vllm:stream_resumes_total{outcome="quarantined"} 1.0'
                in metrics)
    finally:
        if router is not None:
            await router.close()
        await healthy.close()
        _reap(crash_a, crash_b)


# ---- step watchdog --------------------------------------------------------

async def test_fake_hang_step_flips_health_to_watchdog():
    client = TestClient(TestServer(build_fake_engine(
        model="m1", speed=200, ttft=0.0, fault="hang_step")))
    await client.start_server()
    try:
        resp = await client.get("/health")
        assert resp.status == 503
        payload = await resp.json()
        assert payload["status"] == "watchdog"
        assert payload["stuck_step_s"] > 0
        # Clearing the fault recovers the replica.
        await client.post("/fault", json={"mode": None})
        assert (await client.get("/health")).status == 200
    finally:
        await client.close()


class _StubEngine:
    """Just enough engine for EngineServer's health/watchdog surface."""

    tokenizer = None
    tracer = None

    def __init__(self, step_watchdog_s=0.0):
        self.config = SimpleNamespace(engine_role="both",
                                      step_watchdog_s=step_watchdog_s)

    def stats(self):
        return {"num_requests_running": 0, "num_requests_waiting": 0}

    def has_work(self):
        return False


def test_engine_server_watchdog_flips_health():
    """A device step exceeding --step-watchdog-s flips /health to 503
    {"status": "watchdog"}; a finished step recovers it. With the flag
    unset (0) a long step is never reported."""
    from production_stack_tpu.engine.server import EngineServer

    async def run():
        server = EngineServer(_StubEngine(step_watchdog_s=0.25), "m1")
        resp = await server.health(None)
        assert resp.status == 200

        # A step has been executing for ~1s: way past the 0.25s bound.
        server.async_engine._step_started = time.time() - 1.0
        resp = await server.health(None)
        assert resp.status == 503
        payload = json.loads(resp.body)
        assert payload["status"] == "watchdog"
        assert payload["stuck_step_s"] >= 0.9
        assert server._watchdog_tripped  # latched: logged once

        # Step finished: health recovers and the latch clears.
        server.async_engine._step_started = None
        resp = await server.health(None)
        assert resp.status == 200
        assert not server._watchdog_tripped

        # Watchdog disabled: a long step is not a trip.
        off = EngineServer(_StubEngine(step_watchdog_s=0.0), "m1")
        off.async_engine._step_started = time.time() - 60.0
        assert (await off.health(None)).status == 200

    asyncio.run(run())


# ---- fleet crash-loop containment -----------------------------------------

def _gauge_value(pool: str) -> float:
    return fleet_crash_respawns.labels(pool=pool)._value.get()


async def test_crash_loop_backoff_and_breaker():
    """A pool whose replicas die instantly must not fork-storm the
    host: respawns back off exponentially (jittered downward), the
    per-pool breaker opens after crash_loop_threshold crashes in the
    window, and respawning restarts once the window cools."""
    t = [1000.0]
    base = _free_ports(1)[0]
    spec = FleetSpec(
        pools=[PoolSpec(
            name="doomed", min_replicas=1, max_replicas=1,
            command=[sys.executable, "-c", "import sys; sys.exit(3)"],
            respawn_backoff_base_s=1.0, respawn_backoff_max_s=8.0,
            crash_loop_threshold=3, crash_loop_window_s=100.0)],
        port_start=base, port_end=base + 9,
    )
    mgr = FleetManager(spec, clock=lambda: t[0])
    respawns_before = _gauge_value("doomed")

    async def crash_once():
        """Reconcile until the current replica is spawned and reaped
        as a crash."""
        await mgr.reconcile_once()
        assert len(mgr.replicas["doomed"]) == 1
        mgr.replicas["doomed"][0].process.wait(timeout=10)
        streak = mgr._crash_streak["doomed"]
        await mgr.reconcile_once()
        assert mgr._crash_streak["doomed"] == streak + 1

    try:
        await crash_once()  # crash #1
        # Backoff gates the respawn: same clock, no new replica.
        await mgr.reconcile_once()
        assert mgr.replicas["doomed"] == []
        gate = mgr._next_spawn_ok["doomed"]
        assert 1000.0 + 0.5 <= gate <= 1000.0 + 1.0  # jitter in [.5,1]

        t[0] += 1.0
        await crash_once()  # crash #2 (respawn counted)
        assert _gauge_value("doomed") == respawns_before + 1
        gate = mgr._next_spawn_ok["doomed"]
        assert t[0] + 1.0 <= gate <= t[0] + 2.0  # doubled, jittered

        t[0] += 2.0
        await crash_once()  # crash #3: breaker threshold reached
        assert _gauge_value("doomed") == respawns_before + 2

        # Breaker open: even far past the backoff, no respawn while
        # three crashes sit inside the window.
        t[0] += 50.0
        for _ in range(3):
            await mgr.reconcile_once()
        assert mgr.replicas["doomed"] == []
        assert mgr._breaker_logged["doomed"]

        # Window cools: respawning resumes.
        t[0] += 200.0
        await mgr.reconcile_once()
        assert len(mgr.replicas["doomed"]) == 1
        assert _gauge_value("doomed") == respawns_before + 3
    finally:
        for reps in mgr.replicas.values():
            for r in reps:
                if r.process.poll() is None:
                    r.process.kill()
        await mgr.close()


async def test_drain_exit_is_not_a_crash():
    """Crash vs drain-exit is always distinguished: a replica that
    exits through the drain path advances neither the backoff streak
    nor the breaker window, and a healthy promotion resets a prior
    streak."""
    base = _free_ports(1)[0]
    spec = FleetSpec(
        pools=[PoolSpec(
            name="decode", min_replicas=1, max_replicas=2, model="m1",
            command=[sys.executable, "-m",
                     "production_stack_tpu.testing.fake_engine",
                     "--host", "127.0.0.1", "--port", "{port}",
                     "--model", "{model}", "--role", "{role}",
                     "--speed", "500", "--ttft", "0.0"])],
        port_start=base, port_end=base + 9,
        drain_timeout_s=30.0,
    )
    mgr = FleetManager(spec)
    try:
        # Pretend the pool crashed before: the healthy boot must
        # forgive the streak.  (The first spawn therefore counts as a
        # respawn — baseline the gauge after it.)
        mgr._crash_streak["decode"] = 2
        deadline = time.time() + 20.0
        while time.time() < deadline:
            await mgr.reconcile_once()
            live = [r for r in mgr.replicas["decode"]
                    if r.state == LIVE]
            if live:
                break
            await asyncio.sleep(0.05)
        assert live, "fake replica never went live"
        assert mgr._crash_streak["decode"] == 0
        respawns_before = _gauge_value("decode")

        await mgr.drain_all()
        assert mgr.replicas["decode"] == []
        assert mgr._crash_streak["decode"] == 0
        assert list(mgr._crash_times["decode"]) == []
        assert _gauge_value("decode") == respawns_before
    finally:
        for reps in mgr.replicas.values():
            for r in reps:
                if r.process.poll() is None:
                    r.process.kill()
        await mgr.close()


# ---- real-engine parity ---------------------------------------------------
#
# The fast tests above prove the router protocol against fakes; these
# prove the engine side of the contract with the REAL model: the
# shipped checkpoint restores on a fresh process (bf16 and int8 KV)
# and the concatenated stream is byte-identical to an uninterrupted
# run — on a checkpoint miss too, via journal recompute.

import threading

from aiohttp import web


def _serve_app_in_thread(app):
    """Run an aiohttp app on a real socket in a daemon thread (the
    engine's sync offload tier needs real HTTP); (url, stop_fn)."""
    loop = asyncio.new_event_loop()
    started = threading.Event()
    port_box = {}

    def serve():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        port_box["port"] = site._server.sockets[0].getsockname()[1]
        started.set()
        loop.run_forever()
        loop.run_until_complete(runner.cleanup())

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    started.wait(10.0)

    def stop():
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=10.0)

    return f"http://127.0.0.1:{port_box['port']}", stop


@pytest.fixture(scope="module")
def cache_server_url():
    from production_stack_tpu.engine.cache_server import build_cache_server
    url, stop = _serve_app_in_thread(build_cache_server(256 * 1024 ** 2))
    yield url
    stop()


def _engine_config(cache_url, kv_dtype="auto", checkpoint=4,
                   handoff_timeout_s=30.0):
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, OffloadConfig, SchedulerConfig,
        tiny_model_config,
    )
    return EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64,
                          kv_cache_dtype=kv_dtype),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=256,
                                  prefill_chunk_size=64),
        # host_pool_bytes=0: remote-only tier, so every restore is a
        # real cross-process fetch like a replacement pod would do.
        offload=OffloadConfig(enable=True, remote_url=cache_url,
                              host_pool_bytes=0),
        checkpoint_interval_tokens=checkpoint,
        handoff_timeout_s=handoff_timeout_s,
    )


def _engine_server(cache_url, **kwargs):
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.server import EngineServer
    from production_stack_tpu.engine.tokenizer import BenchTokenizer
    # BenchTokenizer: under random weights, greedy ids are almost
    # surely >= 256, which ByteTokenizer decodes to nothing — and a
    # stream with no content deltas relays no checkpoint frames (they
    # piggyback on deltas).  Bench decode emits one printable char per
    # token, like a real vocab would.
    return EngineServer(
        LLMEngine(_engine_config(cache_url, **kwargs),
                  tokenizer=BenchTokenizer(512)),
        "tiny-llama")


# Long prompt: several full KV pages committed before generation, so
# checkpoints have real pages to ship.
_LONG_CHAT = {
    "model": "tiny-llama",
    "messages": [{"role": "user",
                  "content": " ".join(["hello"] * 8)}],
    "max_tokens": 12,
    "temperature": 0,
    "ignore_eos": True,
    "stream": True,
}


def _parse_stream(raw: str):
    """Ordered (kind, payload) events: ("ckpt", descriptor dict) for
    checkpoint comment frames, ("data", event dict) for data events."""
    events = []
    for block in raw.split("\n\n"):
        block = block.strip()
        if block.startswith(": checkpoint "):
            events.append(
                ("ckpt", json.loads(block[len(": checkpoint "):])))
        elif block.startswith("data: ") and block != "data: [DONE]":
            events.append(
                ("data", json.loads(block[len("data: "):])))
    return events


def _delta_content(event: dict) -> str:
    return (event["choices"][0].get("delta") or {}).get("content") or ""


async def _capture_interrupted(client, page_size=16):
    """Stream _LONG_CHAT and pick a resume point: returns (full_text,
    rid, descriptor, delivered_chars_before_it)."""
    resp = await client.post("/v1/chat/completions", json=_LONG_CHAT)
    assert resp.status == 200
    raw = await resp.text()
    events = _parse_stream(raw)
    datas = [e for kind, e in events if kind == "data"]
    full_text = "".join(_delta_content(e) for e in datas)
    rid = datas[0]["id"]
    assert raw.rstrip().endswith("data: [DONE]")

    desc, delivered = None, 0
    seen = 0
    for kind, payload in events:
        if kind == "data":
            seen += len(_delta_content(payload))
        elif (kind == "ckpt"
              # Mid-stream (something left to generate) and the
              # journal doesn't end exactly on a page boundary, so the
              # last full page was shipped -> the restore probe hits.
              and payload["output_tokens"] < _LONG_CHAT["max_tokens"]
              and len(payload["tokens"]) % page_size != 0
              and desc is None):
            desc, delivered = payload, seen
    assert desc is not None, "no usable mid-stream checkpoint frame"
    assert len(desc["tokens"]) // page_size >= 1
    return full_text, rid, desc, delivered


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_resume_byte_identical_real_engine(cache_server_url, kv_dtype):
    """Kill-and-resume with the real engine: a fresh process restores
    the shipped checkpoint pages and continues the greedy stream; the
    concatenated text is byte-identical, under the original response
    id, with no second role chunk — for bf16 and int8 KV."""

    async def run():
        a = _engine_server(cache_server_url, kv_dtype=kv_dtype)
        client_a = TestClient(TestServer(a.build_app()))
        await client_a.start_server()
        try:
            full_text, rid, desc, delivered = await _capture_interrupted(
                client_a)
        finally:
            await client_a.close()
        assert desc["kv_dtype"] == a.engine.config.cache.resolved_kv_dtype()
        assert a.engine.stats()["checkpoint_ships_total"] > 0
        assert a.engine.stats()["checkpoint_kv_bytes_total"] > 0

        # "a" is dead now. A replacement pod picks up the descriptor.
        b = _engine_server(cache_server_url, kv_dtype=kv_dtype)
        client_b = TestClient(TestServer(b.build_app()))
        await client_b.start_server()
        try:
            # A different-dtype pod can NEVER restore these pages:
            # it must refuse with 409 so the router keeps looking.
            wrong = dict(desc)
            wrong["kv_dtype"] = ("int8" if desc["kv_dtype"] != "int8"
                                 else "bf16")
            resp = await client_b.post("/v1/resume", json={
                "descriptor": wrong, "delivered_text_chars": 0})
            assert resp.status == 409

            resp = await client_b.post("/v1/resume", json={
                "descriptor": desc,
                "delivered_text_chars": delivered,
                "stream": True,
            })
            assert resp.status == 200
            resumed = _parse_stream(await resp.text())
            assert all(kind in ("data", "ckpt") for kind, _ in resumed)
            datas = [e for kind, e in resumed if kind == "data"]
            tail = "".join(_delta_content(e) for e in datas)

            # Byte-exact continuation under the original identity.
            assert full_text[:delivered] + tail == full_text
            assert {e["id"] for e in datas} == {rid}
            assert all("role" not in (e["choices"][0].get("delta") or {})
                       for e in datas)
            assert datas[-1]["choices"][0]["finish_reason"] == "length"
            # The pages really came back from the tier (hit, not
            # recompute): the frame choice guarantees restorability.
            assert b.engine.offload.restored_pages > 0
            assert b.engine.stats()["stream_resumes_total"] == 1
        finally:
            await client_b.close()

    asyncio.run(run())


def test_resume_checkpoint_miss_recomputes_parity(cache_server_url):
    """Degraded-never-dropped: a replacement whose tier lost the pages
    (here: unreachable) recomputes from the token journal and still
    produces the byte-identical tail."""

    async def run():
        a = _engine_server(cache_server_url)
        client_a = TestClient(TestServer(a.build_app()))
        await client_a.start_server()
        try:
            full_text, rid, desc, delivered = await _capture_interrupted(
                client_a)
        finally:
            await client_a.close()

        b = _engine_server(_free_port_url(), checkpoint=0,
                           handoff_timeout_s=0.0)
        client_b = TestClient(TestServer(b.build_app()))
        await client_b.start_server()
        try:
            resp = await client_b.post("/v1/resume", json={
                "descriptor": desc,
                "delivered_text_chars": delivered,
                "stream": True,
            })
            assert resp.status == 200
            datas = [e for kind, e in
                     _parse_stream(await resp.text()) if kind == "data"]
            tail = "".join(_delta_content(e) for e in datas)
            assert full_text[:delivered] + tail == full_text
            assert {e["id"] for e in datas} == {rid}
            assert b.engine.offload.restored_pages == 0  # recomputed
            assert b.engine.stats()["stream_resumes_total"] == 1
        finally:
            await client_b.close()

    asyncio.run(run())


def _free_port_url() -> str:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return f"http://127.0.0.1:{port}"


def test_resume_abort_releases_nothing_awaiting_kv(cache_server_url):
    """Regression: a resume parked in AWAITING_KV holds zero pages, so
    a client abort while it waits must release nothing and leave no
    work behind."""
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sequence import (
        SamplingParams, SequenceState,
    )
    eng = LLMEngine(_engine_config(cache_server_url))
    # Pin the sequence in AWAITING_KV: no tier verdict, and the 30s
    # timeout never fires within the test.
    eng.offload.handoff_ready = lambda page_hash: None
    free_before = eng.cache_manager.num_free_pages
    sid = eng.add_resume(
        list(range(1, 50)), 7,
        SamplingParams(max_tokens=12, temperature=0.0, ignore_eos=True))
    seq = eng.sequences[sid]
    for _ in range(3):
        eng.step()
    assert seq.state == SequenceState.AWAITING_KV
    assert eng.stats()["num_requests_waiting"] == 1
    assert eng.stats()["stream_resumes_total"] == 1
    assert eng.cache_manager.num_free_pages == free_before

    eng.abort_request(sid)
    assert sid not in eng.sequences
    assert eng.stats()["num_requests_waiting"] == 0
    assert eng.cache_manager.num_free_pages == free_before
    assert not eng.scheduler.has_work()
