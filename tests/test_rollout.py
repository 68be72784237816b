"""Safe fleet rollouts (docs/fleet.md).

Canary-scored rolling upgrades end to end: spec parse/validation for
the revision + rollout knobs, the router's weighted canary split and
in-band migrate-marker relay, watchdog-aware drain escalation, the
operator pause/resume/abort control channel, the slow-exemplar
capture surviving a dead replica, and the two acceptance E2Es over
real fake-engine subprocesses — a good canary promotes fleet-wide
with a long in-flight stream migrated byte-identically across
revisions and zero 5xx, and a fault-injected bad canary is judged,
automatically rolled back behind a latched alarm, and recovers to
full SLO attainment.

Fast lane: fake engines only — no LLMEngine is ever built.
"""

import asyncio
import json
import os
import socket
import sys
import time
from types import SimpleNamespace

import aiohttp
import pytest
from aiohttp import web

from production_stack_tpu.fleet.manager import (
    DRAINING,
    LIVE,
    FleetManager,
    Replica,
)
from production_stack_tpu.fleet.spec import (
    AutoscalerSpec,
    FleetSpec,
    PoolSpec,
    RevisionSpec,
    RolloutSpec,
)
from production_stack_tpu.router.resilience import (
    ResilienceConfig,
    initialize_resilience,
)
from production_stack_tpu.router.service_discovery import (
    EndpointInfo,
    initialize_service_discovery,
)
from production_stack_tpu.router.services import request_service
from production_stack_tpu.router.services.rewriter import (
    initialize_request_rewriter,
)
from production_stack_tpu.router.stats.engine_stats import (
    initialize_engine_stats_scraper,
)
from production_stack_tpu.router.stats.request_stats import (
    initialize_request_stats_monitor,
)


def _free_port_range(n: int) -> int:
    """First of ``n`` consecutive ports, every one of which binds now.
    The fleet manager hands its replicas the ports of a range in order
    and asks nothing of them, so one free port and the nine after it
    (as this read until PR 46) met whatever another worker's server had
    been given meanwhile. Searched below the range the kernel hands out
    on its own, so that no later ``bind(0)`` of any test lands inside,
    and from a start of this process's own, so that two workers do not
    probe the same ports."""
    low, high = 20000, 32000
    start = low + (os.getpid() * 64) % (high - low - n)
    for base in list(range(start, high - n, n)) + list(range(low, start, n)):
        held = []
        try:
            for port in range(base, base + n):
                sock = socket.socket()
                held.append(sock)
                sock.bind(("127.0.0.1", port))
            return base
        except OSError:
            continue
        finally:
            for sock in held:
                sock.close()
    raise RuntimeError(f"no {n} free ports in a row in [{low}, {high})")


def _fake_pool_command(speed: float = 200.0, ckpt_every: int = 2):
    return [sys.executable, "-m",
            "production_stack_tpu.testing.fake_engine",
            "--host", "127.0.0.1", "--port", "{port}",
            "--model", "{model}", "--role", "{role}",
            "--speed", str(speed), "--ttft", "0.0",
            "--checkpoint-interval-tokens", str(ckpt_every)]


# ---- spec parse + validation ----------------------------------------------

def test_rollout_spec_parses_and_validates():
    spec = FleetSpec.from_json(json.dumps({
        "rollout_control_path": "/tmp/rollout-ctl.json",
        "pools": [{
            "name": "decode", "max_replicas": 4,
            "revision": {"build_id": "v2",
                         "engine_flags": ["--speed", "50"]},
            "rollout": {"canary_weight": 0.25, "bake_s": 30.0,
                        "max_slo_burn_rate_5m": 2.0,
                        "fail_on_perf_drift": False,
                        "max_crash_streak": 2,
                        "max_server_errors": 3.0,
                        "max_latency_ratio": 2.5,
                        "drain_mode": "wait"},
        }],
    }))
    pool = spec.pools[0]
    assert spec.rollout_control_path == "/tmp/rollout-ctl.json"
    assert pool.revision.build_id == "v2"
    assert pool.revision.key() == ("v2", ("--speed", "50"))
    assert pool.rollout.canary_weight == 0.25
    assert pool.rollout.drain_mode == "wait"
    assert not pool.rollout.fail_on_perf_drift
    # Two revisions are the same iff build id AND flags match.
    assert RevisionSpec(build_id="v2").key() != pool.revision.key()

    with pytest.raises(ValueError, match="canary_weight"):
        RolloutSpec(canary_weight=0.0)
    with pytest.raises(ValueError, match="canary_weight"):
        RolloutSpec(canary_weight=1.5)
    with pytest.raises(ValueError, match="drain_mode"):
        RolloutSpec(drain_mode="teleport")
    with pytest.raises(ValueError, match="bake_s"):
        RolloutSpec(bake_s=-1.0)
    with pytest.raises(ValueError, match="max_crash_streak"):
        RolloutSpec(max_crash_streak=-1)


# ---- router: canary split + migrate marker --------------------------------

def test_canary_split_weighted_dispatch():
    from production_stack_tpu.router.routing import logic

    stable = [EndpointInfo(url="http://s1"), EndpointInfo(url="http://s2")]
    canary = EndpointInfo(url="http://c1")
    eps = stable + [canary]
    logic.set_canary_weights({"http://c1": 0.5})
    try:
        # Deterministic rng: below the weight -> canaries only;
        # above -> stable set only.
        logic._canary_rng = SimpleNamespace(random=lambda: 0.1)
        assert logic.canary_split(eps) == [canary]
        logic._canary_rng = SimpleNamespace(random=lambda: 0.9)
        assert logic.canary_split(eps) == stable
        # Degenerate cases pass through untouched: no canaries in the
        # candidate list, or nothing BUT canaries (failover paths).
        assert logic.canary_split(stable) == stable
        assert logic.canary_split([canary]) == [canary]
    finally:
        logic.set_canary_weights(None)
        logic._canary_rng = __import__("random").Random()
    assert logic.canary_split(eps) == eps


def test_sse_relay_migrate_marker():
    """The in-band ``: migrating`` comment from a migrate-draining
    engine sets the relay's flag and is never forwarded to the
    client; a resume leg resets the flag so a later genuine crash is
    not misclassified as a migration."""
    relay = request_service._SseRelay()
    out = relay.feed(
        b': checkpoint {"a": 1}\n\n'
        b'data: {"choices":[{"delta":{"content":"hi"}}]}\n\n'
        b": migrating\n\n")
    assert relay.migrating
    assert relay.descriptor == {"a": 1}
    assert b"migrating" not in out and b"hi" in out
    assert relay.delivered_chars == 2
    # _pipe_resume resets the flag per leg.
    relay.migrating = False
    relay.feed(b'data: {"choices":[{"delta":{"content":"yo"}}]}\n\n')
    assert not relay.migrating


# ---- satellite: watchdog-aware drain escalation ---------------------------

def _manager_with_stub_replica(drain_timeout_s=5.0):
    t = [1000.0]
    spec = FleetSpec(
        pools=[PoolSpec(name="decode", command=["true"])],
        port_start=9000, port_end=9001,
        drain_timeout_s=drain_timeout_s)
    mgr = FleetManager(spec, clock=lambda: t[0])
    calls = []
    proc = SimpleNamespace(
        terminate=lambda: calls.append("terminate"),
        kill=lambda: calls.append("kill"),
        poll=lambda: None, pid=0)
    replica = Replica(pool="decode", port=9000,
                      url="http://127.0.0.1:9000", process=proc,
                      state=DRAINING, drain_started=0.0)
    return mgr, replica, calls, t


async def test_escalate_drain_waits_for_busy_healthy_replica():
    mgr, replica, calls, _ = _manager_with_stub_replica()

    async def raw(r):
        return 200, {"status": "draining", "active_requests": 2}

    mgr._probe_health_raw = raw
    await mgr._escalate_drain(replica)
    assert calls == []  # never kills a busy, healthy engine


async def test_escalate_drain_escalates_watchdog_wedged_replica():
    """A watchdog-tripped draining replica never reaches idle; without
    the wedged override one stuck replica wedges the whole rollout."""
    mgr, replica, calls, t = _manager_with_stub_replica()

    async def raw(r):
        return 503, {"status": "watchdog", "active_requests": 2,
                     "stuck_step_s": 9.0}

    mgr._probe_health_raw = raw
    await mgr._escalate_drain(replica)
    assert calls == ["terminate"]
    assert replica.sigterm_sent >= 0
    # Ignored SIGTERM escalates to SIGKILL after the grace window.
    t[0] += 60.0
    await mgr._escalate_drain(replica)
    assert calls == ["terminate", "kill"]


async def test_escalate_drain_respects_timeout_clock():
    mgr, replica, calls, _ = _manager_with_stub_replica(
        drain_timeout_s=5000.0)

    async def raw(r):
        return 503, {"status": "watchdog", "active_requests": 1}

    mgr._probe_health_raw = raw
    await mgr._escalate_drain(replica)  # timeout not yet reached
    assert calls == []


# ---- satellite: operator control channel ----------------------------------

async def test_rollout_cli_pause_resume_abort(tmp_path):
    from production_stack_tpu.fleet.__main__ import send_rollout_command

    ctl = tmp_path / "ctl.json"
    spec = FleetSpec(
        pools=[PoolSpec(name="decode", command=["true"])],
        port_start=9100, port_end=9103,
        rollout_control_path=str(ctl))
    mgr = FleetManager(spec)
    st = mgr.rollout._state["decode"]

    send_rollout_command(spec, "pause", pool="decode")
    st.phase = "bake"
    cmd = mgr.rollout._poll_control()
    assert cmd and cmd["cmd"] == "pause"
    assert await mgr.rollout._apply_command(cmd)
    assert st.phase == "paused" and st.paused_from == "bake"
    # The same command file is never applied twice (ts dedupe).
    assert mgr.rollout._poll_control() is None

    send_rollout_command(spec, "resume")
    assert await mgr.rollout._apply_command(mgr.rollout._poll_control())
    assert st.phase == "bake"

    # resume also unlatches a rolled-back pool's alarm.
    st.phase, st.alarm = "rolled_back", True
    send_rollout_command(spec, "resume")
    assert await mgr.rollout._apply_command(mgr.rollout._poll_control())
    assert st.phase == "idle" and not st.alarm and st.target is None

    # abort abandons the target revision for good.
    st.phase = "bake"
    st.target = RevisionSpec(build_id="v9")
    send_rollout_command(spec, "abort", pool="decode")
    assert await mgr.rollout._apply_command(mgr.rollout._poll_control())
    assert st.phase == "idle" and ("v9", ()) in st.abandoned

    spec.rollout_control_path = ""
    with pytest.raises(SystemExit, match="rollout_control_path"):
        send_rollout_command(spec, "pause")
    await mgr.close()


# ---- satellite: slow-exemplar capture vs dead replica ---------------------

async def test_slow_exemplar_archives_router_side_when_replica_gone():
    """The /debug/trace pull racing a drained replica's exit must not
    cost the exemplar: the router-side waterfall archives alone."""
    from production_stack_tpu import obs
    from production_stack_tpu.obs.slow_archive import SlowArchive

    archive = SlowArchive(capacity=4)
    obs.install(archive=archive)
    session = aiohttp.ClientSession(
        timeout=aiohttp.ClientTimeout(total=1.0))
    router_span = {
        "span": "request", "request_id": "req-dead", "model": "m1",
        "path": "/v1/chat/completions", "priority_class": "default",
        "tenant": None, "backend": "http://127.0.0.1:1",
        "arrival_ts": 100.0, "queue_delay_ms": None, "ttft_ms": 900.0,
        "latency_ms": 1000.0, "chunks": 3, "status": "ok",
    }
    entry = {"request_id": "req-dead", "class": "default",
             "model": "m1", "server": "http://127.0.0.1:1",
             "breach": [{"metric": "ttft", "value_s": 0.9,
                         "target_s": 0.5}]}
    try:
        # Port 1 is never listening: the trace fetch fails instantly,
        # which is exactly the drained-and-exited replica race.
        await request_service._capture_slow_exemplar(
            {"backend_session": session}, "http://127.0.0.1:1",
            "req-dead", router_span, entry)
    finally:
        await session.close()
        obs.install()
    assert archive.depth() == 1
    (archived,) = archive.snapshot()
    assert archived["spans"] == [router_span]
    assert "req-dead" in archived["waterfall"]


# ---- E2E rig ---------------------------------------------------------------

async def _rollout_rig(tmp_path, pool: PoolSpec):
    """Router (real socket, so subprocess engines and the relay talk
    to it over HTTP) + fleet manager + dynamic-config watcher."""
    from production_stack_tpu.router.app import build_app
    from production_stack_tpu.router.dynamic_config import (
        initialize_dynamic_config_watcher,
    )
    from production_stack_tpu.router.routing.logic import (
        initialize_routing_logic,
    )

    request_service.stream_resumes_by_outcome.clear()
    request_service._poison_crashes.clear()
    initialize_service_discovery("static", urls=[], models=[], roles=[])
    initialize_request_stats_monitor(60.0)
    initialize_engine_stats_scraper(3600.0)
    initialize_routing_logic("roundrobin")
    initialize_request_rewriter("noop")
    initialize_resilience(ResilienceConfig(
        max_retries=2, backend_connect_timeout=2.0,
        backend_timeout=60.0, health_check_interval=0.0))
    runner = web.AppRunner(build_app())
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    router_url = ("http://127.0.0.1:"
                  f"{site._server.sockets[0].getsockname()[1]}")

    config_path = tmp_path / "dyn.json"
    base = _free_port_range(10)
    spec = FleetSpec(
        pools=[pool], port_start=base, port_end=base + 9,
        router_url=router_url, router_config_path=str(config_path),
        drain_timeout_s=30.0)
    mgr = FleetManager(spec)
    watcher = initialize_dynamic_config_watcher(str(config_path), 3600.0)
    session = aiohttp.ClientSession(
        timeout=aiohttp.ClientTimeout(total=60.0))
    return mgr, watcher, session, router_url, runner


async def _stream_one(session, router_url, n_tokens, sink=None):
    rec = {"status": None, "error": None, "text": ""}
    body = {"model": "m1",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": n_tokens, "stream": True}
    parts = []
    try:
        async with session.post(router_url + "/v1/chat/completions",
                                json=body) as resp:
            rec["status"] = resp.status
            async for raw in resp.content:
                line = raw.decode("utf-8", "replace").strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                event = json.loads(line[len("data: "):])
                if "choices" not in event:
                    rec["error"] = "terminal SSE error"
                    continue
                delta = event["choices"][0].get("delta") or {}
                if delta.get("content"):
                    parts.append(delta["content"])
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["text"] = "".join(parts)
    if sink is not None:
        sink.append(rec)
    return rec


async def _drive_until(mgr, watcher, pred, desc, deadline_s=60.0,
                       traffic=None):
    deadline = time.time() + deadline_s
    i = 0
    while time.time() < deadline:
        await mgr.reconcile_once()
        watcher.check_and_apply()
        if pred():
            return
        if traffic is not None and i % 3 == 0:
            await traffic()
        i += 1
        await asyncio.sleep(0.05)
    raise AssertionError(f"never reached: {desc}")


def _all_on(mgr, build, count=2):
    reps = mgr.replicas["decode"]
    return (mgr.current_revision["decode"].build_id == build
            and len(reps) == count
            and all(r.build_id == build and r.state == LIVE
                    for r in reps))


async def _teardown_rig(mgr, session, runner):
    try:
        await mgr.drain_all()
    finally:
        for reps in mgr.replicas.values():
            for r in reps:
                if r.process.poll() is None:
                    r.process.kill()
        await mgr.close()
        await session.close()
        await runner.cleanup()


# ---- satellite: drain escalation racing an in-flight migration ------------

async def test_migrate_drain_with_sigterm_escalation_keeps_stream(
        tmp_path):
    """SIGTERM escalation racing a migrate-mode drain: the draining
    replica's checkpointed stream must land on a survivor
    byte-identical under the ``migrated`` outcome, not broken."""
    pool = PoolSpec(
        name="decode", role="decode", min_replicas=2, max_replicas=3,
        model="m1", command=_fake_pool_command(speed=200.0),
        autoscaler=AutoscalerSpec(enable=False),
        revision=RevisionSpec(build_id="v1"),
        rollout=RolloutSpec(enable=False))
    mgr, watcher, session, router_url, runner = await _rollout_rig(
        tmp_path, pool)
    # An aggressive escalation deadline: the reconciler fires SIGTERM
    # at the draining replica while its stream is still migrating.
    mgr.spec.drain_timeout_s = 0.05
    try:
        await _drive_until(mgr, watcher, lambda: _all_on(mgr, "v1"),
                           "2x v1 live")
        victim = min(mgr.replicas["decode"], key=lambda r: r.port)
        n = 400  # 2s at speed=200, checkpoint every 2 tokens
        task = asyncio.ensure_future(
            _stream_one(session, router_url, n))
        # Roundrobin visits sorted URLs, so the first request lands on
        # the min-port replica — the one we drain.
        await asyncio.sleep(0.3)
        await mgr._start_drain(victim, migrate=True)
        watcher.check_and_apply()
        deadline = time.time() + 30.0
        while time.time() < deadline and not task.done():
            await mgr.reconcile_once()  # reap + escalate + respawn
            watcher.check_and_apply()
            await asyncio.sleep(0.05)
        rec = await task
        assert rec["error"] is None and rec["status"] == 200
        assert rec["text"] == "".join(f"tok{i} " for i in range(n))
        outcomes = dict(request_service.stream_resumes_by_outcome)
        assert outcomes.get("migrated", 0) >= 1, outcomes
        assert victim.process.poll() is not None
    finally:
        await _teardown_rig(mgr, session, runner)


# ---- acceptance E2E: good canary + bad canary -----------------------------

async def test_rollout_e2e_good_then_bad_canary(tmp_path):
    """The PR's acceptance invariant: a good canary completes the
    roll with every replica on the new revision and one long
    in-flight stream migrated byte-identically across revisions; a
    fault-injected bad canary is judged, automatically rolled back
    (old revision restored, alarm latched), and post-rollback traffic
    is clean — zero 5xx / dropped requests throughout."""
    from production_stack_tpu.fleet.autoscaler import (
        parse_prometheus_text,
    )

    pool = PoolSpec(
        name="decode", role="decode", min_replicas=2, max_replicas=4,
        model="m1", command=_fake_pool_command(speed=200.0),
        autoscaler=AutoscalerSpec(enable=False),
        revision=RevisionSpec(build_id="v1"),
        # No SLO ledger or drift sentinel in this rig: judge on crash
        # streak + canary-vs-stable p99 latency ratio.
        rollout=RolloutSpec(
            enable=True, canary_weight=0.5, bake_s=1.5,
            max_slo_burn_rate_5m=0.0, fail_on_perf_drift=False,
            max_crash_streak=1, max_latency_ratio=3.0,
            drain_mode="migrate"))
    mgr, watcher, session, router_url, runner = await _rollout_rig(
        tmp_path, pool)
    results = []

    async def burst():
        await asyncio.gather(*(
            _stream_one(session, router_url, 16, sink=results)
            for _ in range(4)))

    async def gauge(name):
        async with session.get(router_url + "/metrics") as resp:
            text = await resp.text()
        for mname, labels, value in parse_prometheus_text(text):
            if mname == name and labels.get("pool") == "decode":
                return value
        return -1.0

    try:
        await _drive_until(mgr, watcher, lambda: _all_on(mgr, "v1"),
                           "2x v1 live")

        # -- good canary: long stream in flight across the whole roll
        n = 1600  # 8s at speed=200: outlives canary+bake+judge+roll
        long_task = asyncio.ensure_future(
            _stream_one(session, router_url, n))
        await asyncio.sleep(0.3)
        pool.revision = RevisionSpec(build_id="v2")
        await _drive_until(mgr, watcher, lambda: _all_on(mgr, "v2"),
                           "fleet rolled to v2", deadline_s=90.0,
                           traffic=burst)
        long_rec = await long_task
        assert long_rec["error"] is None and long_rec["status"] == 200
        assert long_rec["text"] == \
            "".join(f"tok{i} " for i in range(n))
        outcomes = dict(request_service.stream_resumes_by_outcome)
        assert outcomes.get("migrated", 0) >= 1, outcomes
        # Every replica reports the new build from /health.
        for replica in mgr.replicas["decode"]:
            payload = await mgr._probe_health(replica)
            assert payload and payload["build_id"] == "v2"
        assert mgr.rollout.status() == {}  # idle again, no alarm

        # -- bad canary: degraded TTFT must fail the latency judge
        pool.rollout.bake_s = 4.0
        pool.revision = RevisionSpec(
            build_id="v3",
            engine_flags=["--fault", "degrade_new_revision",
                          "--slow-ttft-s", "1.0",
                          "--slow-itl-s", "0.05"])

        def rolled_back():
            st = mgr.rollout.status().get("decode") or {}
            return st.get("phase") == "rolled_back"

        await _drive_until(mgr, watcher, rolled_back,
                           "bad canary rolled back", deadline_s=90.0,
                           traffic=burst)
        status = mgr.rollout.status()["decode"]
        assert status["alarm"] and status["rollbacks"] >= 1
        assert "canary" in status["verdict"]
        # Old revision restored; the alarm gauge is latched on
        # /metrics until an operator resumes.
        await _drive_until(mgr, watcher, lambda: _all_on(mgr, "v2"),
                           "stable set restored on v2",
                           deadline_s=60.0)
        assert await gauge("vllm:rollout_alarm") == 1.0
        assert await gauge("vllm:rollout_rollbacks_total") >= 1.0
        # A frozen pool ignores the (still-bad) spec revision.
        await mgr.reconcile_once()
        assert mgr.rollout.status()["decode"]["phase"] == "rolled_back"

        # Post-rollback traffic is clean.
        await burst()
        assert results and all(
            r["status"] == 200 and r["error"] is None
            for r in results), [r for r in results
                                if r["status"] != 200 or r["error"]]
    finally:
        await _teardown_rig(mgr, session, runner)
