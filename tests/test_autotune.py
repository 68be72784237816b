"""Self-tuning controllers (docs/autotuning.md).

Framework semantics (mode gate, cadence, dead-band, clamps, span
emission), the drift-sentinel guardrail's freeze/latch/reset contract,
each engine-side controller's closed loop against fake engine state,
the fleet pool-split controller, config validation, and the fake
engine's autotune surface. All host-side — fake clocks, fake engines,
no device programs.
"""

from types import SimpleNamespace

import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.autotune import (
    Autotuner,
    CheckpointIntervalController,
    Controller,
    DriftGuardrail,
    PoolSplitController,
    PrefillBudgetController,
    QoSShedController,
    SpecKController,
)
from production_stack_tpu.engine.config import AutotuneConfig
from production_stack_tpu.testing.fake_engine import build_fake_engine


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


class FakeTracer:
    """Records (span_id, event_name, fields) like engine tracing."""

    def __init__(self):
        self.events = []

    def start(self, sid, **kw):
        pass

    def event(self, sid, name, **fields):
        self.events.append((sid, name, fields))

    def finish(self, sid, **kw):
        pass


class ScriptedController(Controller):
    """Observes a scripted signal; proposes signal as the target."""

    name = "scripted"

    def __init__(self, lo=0.0, hi=100.0, value=10.0):
        super().__init__(lo=lo, hi=hi)
        self.value = value
        self.signal = None
        self.applied = []

    def observe(self):
        return self.signal

    def current(self):
        return self.value

    def propose(self, signal):
        return signal

    def apply(self, target):
        self.applied.append(target)
        self.value = target


def _cfg(**kw):
    defaults = dict(mode="on", interval_s=1.0, dead_band=0.05)
    defaults.update(kw)
    return AutotuneConfig(**defaults)


def _tuner(ctrl, clock, drift_flags=None, burn_rate=None,
           tracer=None, **cfg_kw):
    return Autotuner(_cfg(**cfg_kw), [ctrl], tracer=tracer,
                     clock=clock, drift_flags=drift_flags,
                     burn_rate=burn_rate)


# ---------------------------------------------------------------------------
# Guardrail: freeze on drift flip, latch, never re-apply until reset.
# ---------------------------------------------------------------------------


def test_guardrail_freezes_latches_and_resets():
    """The satellite contract: a controller whose applied decisions
    precede an injected perf-drift flip must freeze, latch the
    frozen gauge, and never apply again until an operator reset."""
    clock = FakeClock()
    flags = {"decode": 0.0}
    ctrl = ScriptedController(value=10.0)
    tuner = _tuner(ctrl, clock, drift_flags=lambda: dict(flags))

    # Healthy tick: decision applies.
    ctrl.signal = 20.0
    tuner.tick()
    assert ctrl.applied == [20.0]
    assert tuner.frozen_flags() == {"scripted": False}

    # Drift flips 0 -> 1 within the freeze window of that decision.
    clock.advance(5.0)
    flags["decode"] = 1.0
    ctrl.signal = 30.0
    tuner.tick()
    assert tuner.frozen_flags() == {"scripted": True}
    # The tick that froze it must not have applied.
    assert ctrl.applied == [20.0]

    # Latched: the flag staying high (no new flip) keeps it frozen,
    # and decisions keep being computed (shadow) but never applied.
    for _ in range(5):
        clock.advance(60.0)  # far outside the blame window
        ctrl.signal = 40.0
        tuner.tick()
    assert tuner.frozen_flags() == {"scripted": True}
    assert ctrl.applied == [20.0]
    assert tuner.decisions_total["scripted"] > 1
    assert tuner.applied_total["scripted"] == 1
    assert tuner.active_count() == 0

    # Operator reset unlatches; the next decision applies again and
    # the old decisions carry no blame (no instant re-freeze).
    assert tuner.reset() == ["scripted"]
    ctrl.signal = 50.0
    tuner.tick()
    assert ctrl.applied == [20.0, 50.0]
    assert tuner.frozen_flags() == {"scripted": False}


def test_guardrail_burn_rise_freezes_only_recent_deciders():
    clock = FakeClock()
    burn = {"v": 0.2}
    rail = DriftGuardrail(freeze_window_s=30.0, burn_threshold=1.0,
                          burn_rate=lambda: burn["v"], clock=clock)
    rail.note_applied("old")
    clock.advance(100.0)
    rail.note_applied("recent")
    clock.advance(1.0)
    burn["v"] = 0.5  # rise below threshold: no trip
    assert rail.scan() == []
    burn["v"] = 1.5  # rise to/above threshold: trip
    assert rail.scan() == ["recent"]
    assert rail.is_frozen("recent") and not rail.is_frozen("old")
    # A falling burn never trips.
    burn["v"] = 0.1
    rail.note_applied("old")
    assert rail.scan() == []


def test_guardrail_reset_single_controller():
    clock = FakeClock()
    rail = DriftGuardrail(clock=clock)
    rail._frozen = {"a": 1.0, "b": 2.0}
    assert rail.reset("a") == ["a"]
    assert not rail.is_frozen("a") and rail.is_frozen("b")
    assert rail.reset("missing") == []
    assert rail.reset() == ["b"]
    assert rail.frozen() == {}


# ---------------------------------------------------------------------------
# Autotuner framework: modes, cadence, dead-band, clamps, spans.
# ---------------------------------------------------------------------------


def test_off_mode_never_ticks():
    clock = FakeClock()
    ctrl = ScriptedController()
    tuner = _tuner(ctrl, clock, mode="off")
    ctrl.signal = 99.0
    for _ in range(5):
        clock.advance(10.0)
        assert tuner.maybe_tick() is False
    assert ctrl.applied == []
    assert tuner.active_count() == 0


def test_shadow_computes_and_logs_but_never_applies():
    clock = FakeClock()
    tracer = FakeTracer()
    ctrl = ScriptedController(value=10.0)
    tuner = _tuner(ctrl, clock, tracer=tracer, mode="shadow")
    ctrl.signal = 20.0
    tuner.tick()
    assert ctrl.applied == []
    assert tuner.decisions_total["scripted"] == 1
    assert tuner.applied_total["scripted"] == 0
    assert tuner.active_count() == 0  # nothing is being applied
    [(_, name, fields)] = tracer.events
    assert name == "autotune_decision"
    assert fields["mode"] == "shadow"
    assert fields["applied"] is False
    assert fields["target"] == 20.0


def test_on_mode_span_marks_applied():
    clock = FakeClock()
    tracer = FakeTracer()
    ctrl = ScriptedController(value=10.0)
    tuner = _tuner(ctrl, clock, tracer=tracer)
    ctrl.signal = 20.0
    tuner.tick()
    [(_, name, fields)] = tracer.events
    assert fields["applied"] is True
    assert ctrl.applied == [20.0]
    assert tuner.active_count() == 1


def test_cadence_is_bounded_by_interval():
    clock = FakeClock()
    ctrl = ScriptedController(value=10.0)
    tuner = _tuner(ctrl, clock, interval_s=2.0)
    ctrl.signal = 20.0
    assert tuner.maybe_tick() is True
    ctrl.signal = 30.0
    clock.advance(1.0)
    assert tuner.maybe_tick() is False  # inside the interval
    clock.advance(1.0)
    assert tuner.maybe_tick() is True
    assert ctrl.applied == [20.0, 30.0]


def test_dead_band_drops_small_moves():
    clock = FakeClock()
    ctrl = ScriptedController(hi=200.0, value=100.0)
    tuner = _tuner(ctrl, clock, dead_band=0.1)
    ctrl.signal = 105.0  # within 10% of 100
    tuner.tick()
    assert ctrl.applied == []
    ctrl.signal = 120.0
    tuner.tick()
    assert ctrl.applied == [120.0]


def test_targets_are_clamped_to_controller_band():
    clock = FakeClock()
    ctrl = ScriptedController(lo=5.0, hi=15.0, value=10.0)
    tuner = _tuner(ctrl, clock)
    ctrl.signal = 1000.0
    tuner.tick()
    assert ctrl.applied == [15.0]
    ctrl.signal = -1000.0
    tuner.tick()
    assert ctrl.applied == [15.0, 5.0]


def test_no_signal_and_hold_proposals_are_skipped():
    clock = FakeClock()
    ctrl = ScriptedController(value=10.0)
    ctrl.propose = lambda s: None  # hold
    tuner = _tuner(ctrl, clock)
    ctrl.signal = None
    tuner.tick()
    ctrl.signal = 50.0
    tuner.tick()
    assert ctrl.applied == []
    assert tuner.decisions_total["scripted"] == 0


def test_broken_controller_is_contained():
    clock = FakeClock()
    ctrl = ScriptedController(value=10.0)
    boom = ScriptedController(value=1.0)
    boom.name = "boom"

    def explode():
        raise RuntimeError("tick bomb")

    boom.observe = explode
    tuner = Autotuner(_cfg(), [boom, ctrl], clock=clock)
    ctrl.signal = 20.0
    tuner.tick()  # must not raise, and the healthy controller runs
    assert ctrl.applied == [20.0]


def test_controller_selection_allowlist():
    clock = FakeClock()
    a = ScriptedController()
    b = ScriptedController()
    b.name = "other"
    tuner = Autotuner(_cfg(controllers="other"), [a, b], clock=clock)
    assert [c.name for c in tuner.controllers] == ["other"]


def test_status_payload_shape():
    clock = FakeClock()
    ctrl = ScriptedController(lo=0.0, hi=100.0, value=10.0)
    tuner = _tuner(ctrl, clock)
    status = tuner.status()
    assert status["mode"] == "on"
    assert status["active_controllers"] == 1
    [entry] = status["controllers"]
    assert entry["name"] == "scripted"
    assert entry["knob"] == 10.0
    assert entry["frozen"] is False


# ---------------------------------------------------------------------------
# AutotuneConfig validation.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [
    dict(mode="auto"),
    dict(interval_s=0.0),
    dict(dead_band=1.0),
    dict(dead_band=-0.1),
    dict(freeze_window_s=-1.0),
    dict(min_spec_k=0),
    dict(min_checkpoint_interval_tokens=0),
    dict(min_checkpoint_interval_tokens=8192,
         max_checkpoint_interval_tokens=4096),
    dict(min_shed_threshold=0.0),
    dict(min_shed_threshold=1.5),
])
def test_autotune_config_rejects_bad_values(kw):
    with pytest.raises(ValueError):
        AutotuneConfig(**kw)


def test_autotune_config_defaults_are_off():
    cfg = AutotuneConfig()
    assert cfg.mode == "off"


# ---------------------------------------------------------------------------
# Engine-side controllers against fake engine state.
# ---------------------------------------------------------------------------


def _fake_seq(seq_id, drafted=0, accepted=0, cap=None):
    return SimpleNamespace(seq_id=seq_id, spec_drafted_total=drafted,
                           spec_accepted_total=accepted,
                           spec_k_cap=cap)


def test_spec_k_controller_cuts_on_collapse_and_regrows():
    seqs = [_fake_seq("a"), _fake_seq("b")]
    engine = SimpleNamespace(
        config=SimpleNamespace(
            scheduler=SimpleNamespace(speculative_k=6)),
        scheduler=SimpleNamespace(running=seqs))
    ctrl = SpecKController(engine, _cfg(min_spec_k=1))
    assert ctrl.enabled()
    assert ctrl.observe() is None  # no drafts yet: no signal

    # Acceptance collapse: lots drafted, almost nothing accepted.
    for s in seqs:
        s.spec_drafted_total = 40
        s.spec_accepted_total = 2
    signal = ctrl.observe()
    assert signal == pytest.approx(4 / 80)
    target = ctrl.propose(signal)
    assert target < ctrl.current()
    ctrl.apply(ctrl.clamp(target))
    assert all(s.spec_k_cap == 5 for s in seqs)

    # Sustained collapse walks the caps to the floor, never below.
    for _ in range(10):
        for s in seqs:
            s.spec_drafted_total += 40
            s.spec_accepted_total += 2
        ctrl.apply(ctrl.clamp(ctrl.propose(ctrl.observe())))
    assert all(s.spec_k_cap == 1 for s in seqs)

    # Recovery: high acceptance grows the caps back toward k.
    for _ in range(10):
        for s in seqs:
            s.spec_drafted_total += 40
            s.spec_accepted_total += 38
        ctrl.apply(ctrl.clamp(ctrl.propose(ctrl.observe())))
    assert all(s.spec_k_cap == 6 for s in seqs)


def test_spec_k_controller_disabled_without_speculation():
    engine = SimpleNamespace(
        config=SimpleNamespace(
            scheduler=SimpleNamespace(speculative_k=0)),
        scheduler=SimpleNamespace(running=[]))
    assert not SpecKController(engine, _cfg()).enabled()


def _prefill_engine():
    from production_stack_tpu.engine.metrics import EngineMetrics
    metrics = EngineMetrics()
    return SimpleNamespace(
        config=SimpleNamespace(scheduler=SimpleNamespace(
            unified_step=True, prefill_chunk_size=64,
            prefill_batch_size=4)),
        scheduler=SimpleNamespace(mixed_prefill_budget=256),
        metrics=metrics)


def test_prefill_budget_controller_shrinks_over_target():
    engine = _prefill_engine()
    ctrl = PrefillBudgetController(
        engine, _cfg(target_itl_ms=50.0))
    assert ctrl.enabled()
    for _ in range(32):
        engine.metrics.itl.observe(0.2)  # way over 50ms
    p99 = ctrl.observe()
    assert p99 is not None and p99 > 0.05
    ctrl.apply(ctrl.clamp(ctrl.propose(p99)))
    assert engine.scheduler.mixed_prefill_budget == 192
    # Sustained pressure bottoms out at one chunk.
    for _ in range(5):
        for _ in range(32):
            engine.metrics.itl.observe(0.2)
        target = ctrl.propose(ctrl.observe())
        if target is not None:
            ctrl.apply(ctrl.clamp(target))
    assert engine.scheduler.mixed_prefill_budget == 64


def test_prefill_budget_controller_grows_with_headroom():
    engine = _prefill_engine()
    engine.scheduler.mixed_prefill_budget = 64
    ctrl = PrefillBudgetController(
        engine, _cfg(target_itl_ms=50.0))
    for _ in range(32):
        engine.metrics.itl.observe(0.002)  # far under target
    ctrl.apply(ctrl.clamp(ctrl.propose(ctrl.observe())))
    assert engine.scheduler.mixed_prefill_budget == 128


def test_prefill_budget_needs_window_volume():
    engine = _prefill_engine()
    ctrl = PrefillBudgetController(engine, _cfg())
    engine.metrics.itl.observe(0.2)  # below MIN_WINDOW_TOKENS
    assert ctrl.observe() is None


def test_checkpoint_interval_halves_on_resume_and_relaxes():
    engine = SimpleNamespace(
        config=SimpleNamespace(checkpoint_interval_tokens=1024),
        stream_resumes=0)
    ctrl = CheckpointIntervalController(
        engine, _cfg(min_checkpoint_interval_tokens=64,
                     max_checkpoint_interval_tokens=4096))
    assert ctrl.enabled()
    assert ctrl.observe() is None  # first tick primes the window
    engine.stream_resumes = 2  # a crash replayed somewhere
    ctrl.apply(ctrl.clamp(ctrl.propose(ctrl.observe())))
    assert engine.config.checkpoint_interval_tokens == 512
    # Quiet ticks relax it back up (doubling after the quiet run).
    for _ in range(ctrl.QUIET_TICKS_TO_RELAX - 1):
        assert ctrl.propose(ctrl.observe()) is None
    ctrl.apply(ctrl.clamp(ctrl.propose(ctrl.observe())))
    assert engine.config.checkpoint_interval_tokens == 1024


def _qos_engine(waiting=0):
    return SimpleNamespace(
        config=SimpleNamespace(
            qos=SimpleNamespace(shed_threshold=0.95),
            scheduler=SimpleNamespace(max_queue_len=100)),
        scheduler=SimpleNamespace(num_waiting=waiting,
                                  spec_degrade_clamp=False))


def test_qos_shed_tightens_on_queue_growth_and_relaxes():
    engine = _qos_engine(waiting=10)
    ctrl = QoSShedController(engine, _cfg(min_shed_threshold=0.5))
    assert ctrl.observe() is None  # primes the window
    engine.scheduler.num_waiting = 40  # growing and deep
    ctrl.apply(ctrl.clamp(ctrl.propose(ctrl.observe())))
    assert engine.config.qos.shed_threshold == pytest.approx(0.90)
    assert engine.scheduler.spec_degrade_clamp is True
    # Drained queue relaxes back to the static and lifts the clamp.
    engine.scheduler.num_waiting = 2
    ctrl.apply(ctrl.clamp(ctrl.propose(ctrl.observe())))
    assert engine.config.qos.shed_threshold == pytest.approx(0.95)
    assert engine.scheduler.spec_degrade_clamp is False


# ---------------------------------------------------------------------------
# Fleet-side pool split controller.
# ---------------------------------------------------------------------------


def _pools():
    from production_stack_tpu.fleet.spec import PoolSpec
    return [
        PoolSpec(name="prefill", role="prefill", min_replicas=1,
                 max_replicas=4),
        PoolSpec(name="decode", role="decode", min_replicas=1,
                 max_replicas=4),
    ]


def _signals(pmean, dmean, burn=-1.0):
    return {"prefill": SimpleNamespace(prefill_time_mean_s=pmean,
                                       decode_time_mean_s=dmean,
                                       slo_burn_rate=burn)}


def test_pool_split_moves_replica_on_phase_drift():
    clock = FakeClock()
    ctrl = PoolSplitController(ratio_band=0.5, cooldown_s=60.0,
                               clock=clock)
    pools = _pools()
    desired = {"prefill": 2, "decode": 2}
    # First complete observation sets the baseline; no move.
    out = ctrl.rebalance(pools, _signals(1.0, 1.0), desired)
    assert out == desired
    # Prefill phase slows past the band: decode lends a replica.
    clock.advance(61.0)
    out = ctrl.rebalance(pools, _signals(2.0, 1.0), desired)
    assert out == {"prefill": 3, "decode": 1}
    assert ctrl.moves_total == 1
    # Cooldown blocks an immediate second move.
    clock.advance(1.0)
    assert ctrl.rebalance(pools, _signals(2.0, 1.0),
                          desired) == desired
    # Drift the other way (after cooldown) moves it back.
    clock.advance(61.0)
    out = ctrl.rebalance(pools, _signals(0.4, 1.0), desired)
    assert out == {"prefill": 1, "decode": 3}


def test_pool_split_respects_replica_bands():
    clock = FakeClock()
    ctrl = PoolSplitController(ratio_band=0.5, cooldown_s=0.0,
                               clock=clock)
    pools = _pools()
    ctrl.rebalance(pools, _signals(1.0, 1.0), {"prefill": 2,
                                               "decode": 2})
    clock.advance(1.0)
    # Source already at min: no move.
    out = ctrl.rebalance(pools, _signals(2.0, 1.0),
                         {"prefill": 2, "decode": 1})
    assert out == {"prefill": 2, "decode": 1}


def test_pool_split_freezes_on_burn_rise_until_reset():
    clock = FakeClock()
    ctrl = PoolSplitController(ratio_band=0.5, cooldown_s=0.0,
                               burn_threshold=1.0, clock=clock)
    pools = _pools()
    desired = {"prefill": 2, "decode": 2}
    ctrl.rebalance(pools, _signals(1.0, 1.0, burn=0.1), desired)
    clock.advance(1.0)
    out = ctrl.rebalance(pools, _signals(2.0, 1.0, burn=0.1), desired)
    assert out == {"prefill": 3, "decode": 1}
    # Burn rises past threshold within the freeze window of the move.
    clock.advance(1.0)
    out = ctrl.rebalance(pools, _signals(2.0, 1.0, burn=2.0), desired)
    assert out == desired
    assert ctrl.frozen
    # Latched: even with the drift persisting, no more moves.
    clock.advance(120.0)
    assert ctrl.rebalance(pools, _signals(3.0, 1.0, burn=2.0),
                          desired) == desired
    ctrl.reset()
    assert not ctrl.frozen
    clock.advance(1.0)
    out = ctrl.rebalance(pools, _signals(3.0, 1.0, burn=2.0), desired)
    assert out == {"prefill": 3, "decode": 1}


# ---------------------------------------------------------------------------
# Fake engine autotune surface (knob echo + metrics + status).
# ---------------------------------------------------------------------------


async def test_fake_engine_autotune_knob_echo_roundtrip():
    app = build_fake_engine()
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        # Default: off, nothing frozen, no knobs.
        resp = await client.get("/autotune/status")
        status = await resp.json()
        assert status["mode"] == "off"
        assert status["active_controllers"] == 0

        # Seed knobs via the echo endpoint.
        resp = await client.post("/autotune/knobs", json={
            "mode": "on",
            "knobs": {"spec_k": 4.0, "qos_shed": 0.9},
            "frozen": {"spec_k": True},
            "decisions": {"spec_k": 7},
        })
        status = await resp.json()
        assert status["mode"] == "on"
        assert status["active_controllers"] == 1  # qos_shed only
        by_name = {c["name"]: c for c in status["controllers"]}
        assert by_name["spec_k"]["frozen"] is True
        assert by_name["spec_k"]["knob"] == 4.0
        assert by_name["spec_k"]["decisions"] == 7

        # The gauges show up in /metrics with the controller label.
        resp = await client.get("/metrics")
        text = await resp.text()
        assert 'vllm:autotune_frozen{controller="spec_k"} 1.0' in text
        assert ('vllm:autotune_knob_value{controller="qos_shed"} 0.9'
                in text)
        assert "vllm:autotune_active_controllers 1" in text

        # Reset unfreezes; clear empties the echo state.
        resp = await client.post("/autotune/reset", json={})
        assert (await resp.json())["reset"] == ["spec_k"]
        resp = await client.get("/autotune/status")
        status = await resp.json()
        assert status["active_controllers"] == 2
        await client.post("/autotune/knobs", json={"clear": True})
        resp = await client.get("/autotune/status")
        assert (await resp.json())["mode"] == "off"
    finally:
        await client.close()


def test_autotune_decision_span_event_is_registered():
    from production_stack_tpu.engine.tracing import SPAN_EVENTS
    assert "autotune_decision" in SPAN_EVENTS
