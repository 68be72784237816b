"""Two threads, one interpreter (engine/tracing.py): the loop thread's
CPU clock by phase (``cpu``), the event loop's side of a turn
(``FrontClock``, the record's ``front``), its three ``server.*``
profiler events, and the two /metrics counters."""

import asyncio
import contextlib
import inspect
import json
import re
import threading
import time
import types

import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine import tracing
from production_stack_tpu.engine.tracing import (
    PARKED_PHASES, TURN_PHASES, EngineTracer, FrontClock,
)
from test_turn_phases import _engine, _serve, _turns

# By name: the benchmark's readers take these keys from the records.
FRONT_KEYS = {"cpu_ms", "tokens"}


def _cpu_tick_ms():
    """The step of this host's thread CPU clock: under a microsecond on
    a plain kernel, 10 ms on the v5e hosts (PERF.md, PR 39). A CPU
    figure of one phase or one turn can exceed its wall by that much
    where a tick lands in it; a sum over contiguous turns by no more."""
    first = time.thread_time()
    while (a := time.thread_time()) == first:
        pass
    while (b := time.thread_time()) == a:
        pass
    return (b - a) * 1e3


# ---- the loop thread's CPU clock by phase ----------------------------------


@pytest.mark.parametrize("scheduler", [{}, {"decode_steps": 4}],
                         ids=["single-step", "burst"])
async def test_cpu_has_the_keys_of_phases_and_stays_under_the_wall(
        scheduler):
    engine = _engine(**scheduler)
    engine.tracer = EngineTracer(ring_size=8)
    await _serve(engine)
    turns = _turns(engine.tracer)
    assert len(turns) >= 4
    slack = 0.5 + _cpu_tick_ms()
    for turn in turns:
        assert set(turn["cpu"]) == set(turn["phases"])
        for name, wall_ms in turn["phases"].items():
            assert 0.0 <= turn["cpu"][name] <= wall_ms + slack
        wall_ms = (turn["t_end"] - turn["t_start"]) * 1e3
        assert sum(turn["cpu"].values()) <= wall_ms + slack
        assert sum(turn["phases"].values()) == pytest.approx(
            wall_ms, rel=0.01, abs=0.02)
        # AsyncEngine.start bound the front to this test's event loop.
        assert set(turn["front"]) == FRONT_KEYS
        assert turn["front"]["cpu_ms"] >= 0.0
    # The turns are contiguous: over all of them the CPU clock is off
    # by one tick at most, however coarse it is.
    assert (sum(sum(t["cpu"].values()) for t in turns)
            <= sum(sum(t["phases"].values()) for t in turns) + slack)
    # Blocked on the device the thread is off the CPU: that is what
    # ``wait`` is, and no part of the counter, which is the signed sum
    # of the other phases' differences.
    own = sum(t["phases"][p] - t["cpu"][p] for t in turns
              for p in t["phases"] if p not in PARKED_PHASES)
    assert engine.tracer._offcpu_s == pytest.approx(own / 1e3, abs=1e-3)
    assert engine.tracer.loop_offcpu_s >= engine.tracer._offcpu_s


def test_the_offcpu_counter_is_the_signed_sum_on_a_clock_that_ticks(
        monkeypatch):
    """The v5e hosts' thread CPU clock advances in steps of 10 ms: a
    phase of 3 ms reads 0 or 10 ms of CPU.  Each turn here works 6 ms
    in ``build`` and is kept off a core for 2 ms in ``dispatch``; the
    counter reads the 2 ms a turn over many turns (clamping every
    phase at zero would read more than twice that), and never
    decreases on the way though single turns read below zero."""
    now, cpu = [100.0], [0.0]
    monkeypatch.setattr(tracing, "time", types.SimpleNamespace(
        time=time.time, perf_counter=lambda: now[0],
        thread_time=lambda: int(cpu[0] * 100 + 1e-6) / 100))
    tracer = EngineTracer(ring_size=4)
    tracer.start_turns()
    seen, below_zero = [], 0
    for _ in range(500):
        tracer.phase("build")
        now[0] += 0.006
        cpu[0] += 0.006
        tracer.phase("dispatch")
        now[0] += 0.002
        tracer.phase("wait")
        now[0] += 0.030  # blocked on the device: no CPU, and not counted
        tracer.on_step(kind="decode")
        record = tracer.end_turn(emitted=1)
        below_zero += sum(record["phases"][p] - record["cpu"][p]
                          for p in ("build", "dispatch")) < 0
        seen.append(tracer.loop_offcpu_s)
    assert below_zero > 100
    assert seen == sorted(seen)
    assert seen[-1] == pytest.approx(500 * 0.002, abs=0.010)
    assert tracer._offcpu_s == pytest.approx(500 * 0.002, abs=0.010)


def _python_work(rounds):
    total = 0
    for i in range(rounds):
        total += i * i % 7
    return total


def _rounds_for(seconds):
    """How many rounds of ``_python_work`` take about ``seconds`` of this
    thread's CPU clock here."""
    rounds, took = 20_000, 0.0
    while took < seconds / 4:
        rounds *= 2
        start = time.thread_time()
        _python_work(rounds)
        took = time.thread_time() - start
    return int(rounds * seconds / took)


def _off_cpu_share(rounds, beside_a_spinner):
    """``phases - cpu`` over ``phases`` of one pure-Python ``build``."""
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass

    spinner = threading.Thread(target=spin, daemon=True)
    if beside_a_spinner:
        spinner.start()
    try:
        tracer = EngineTracer()
        tracer.start_turns()
        tracer.phase("build")
        _python_work(rounds)
        tracer.on_step(kind="decode")
        record = tracer.end_turn(emitted=0)
    finally:
        stop.set()
    wall, cpu = record["phases"]["build"], record["cpu"]["build"]
    return (wall - cpu) / wall


def test_wall_less_cpu_is_the_wait_for_the_interpreter():
    """A phase of some 50 ms of pure Python beside a thread that spins
    holds the interpreter half the time: the other half is wall without
    CPU.  Alone it is on a core nearly throughout.  Relative, because
    the operating system's scheduler keeps a thread off a core too where
    the box has fewer free cores than runnable threads, and the record
    cannot tell that from the interpreter's lock; the best of as many
    pairs as it takes, up to a deadline, for the same reason: on a box
    whose every core is taken (six workers of the lane on eight cores)
    a phase alone is off its core a third of the time in three
    readings out of three often enough to be seen (PR 43), and one
    quiet reading is all that "alone" asks."""
    rounds = _rounds_for(0.05)
    beside, alone = 0.0, 1.0
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        beside = max(beside, _off_cpu_share(rounds, True))
        alone = min(alone, _off_cpu_share(rounds, False))
        if beside >= 0.25 and alone < beside / 2:
            break
    assert beside >= 0.25
    assert alone < beside / 2


def test_a_tracer_with_no_event_loop_bound_has_cpu_and_no_front():
    """The fake engine's use: the real EngineTracer, no AsyncEngine."""
    tracer = EngineTracer()
    tracer.start_turns()
    tracer.phase("wait")
    tracer.on_step(kind="decode")
    record = tracer.end_turn(emitted=1)
    assert set(record["cpu"]) == set(record["phases"]) == {"other", "wait"}
    assert "front" not in record
    assert tracer.front.cpu_s() is None


def test_a_platform_without_the_call_leaves_cpu_ms_out(monkeypatch):
    """Absent, never zero."""
    monkeypatch.delattr(tracing.time, "pthread_getcpuclockid")
    front = FrontClock()
    front.bind()
    front.wake_done(3)
    assert front.close_interval() == {"tokens": 3}
    assert front.cpu_s() is None


# ---- the event loop's side --------------------------------------------------


def test_front_differences_sum_to_the_totals_across_every_close():
    """The event loop's thread counts while the loop thread closes turn
    after turn: what the closes read, and what is left after the last,
    is every token, none twice and none lost."""
    front = FrontClock()
    stop = threading.Event()

    def event_loop():
        front.bind()
        bound.set()
        while not stop.is_set():
            front.consume_begin()
            front.wake_done(3)

    bound = threading.Event()
    thread = threading.Thread(target=event_loop)
    thread.start()
    bound.wait()
    closes = []
    while len(closes) < 2000 or not closes[-1]["tokens"]:
        closes.append(front.close_interval())
    stop.set()
    thread.join()
    closes.append(front.close_interval())
    assert sum(c["tokens"] for c in closes) == front.tokens > 0
    assert all(c["tokens"] >= 0 and c["tokens"] % 3 == 0 for c in closes)
    # The thread's CPU clock from another thread: it ran all the while.
    # The last close came after the thread had gone, and with it,
    # sooner or later, its clock: absent then, never zero.
    assert sum(c["cpu_ms"] for c in closes[:-1]) > 0.0
    assert all(c["cpu_ms"] >= 0.0 for c in closes[:-1])
    assert closes[-1].get("cpu_ms", 1.0) >= 0.0
    assert front.close_interval()["tokens"] == 0


async def test_the_turns_front_is_what_the_consumers_did_meanwhile(
        monkeypatch):
    """Through the server: every token a consumer took is in some
    turn's ``front`` or in what is left after the last turn, streaming
    or not, and a wake is not a token."""
    engine = _engine(decode_steps=4)
    engine.tracer = EngineTracer(ring_size=8)
    wakes = _count_wakes(monkeypatch)
    client = await _client(engine)
    try:
        for stream in (True, False):
            resp = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "a b c", "stream": stream,
                "max_tokens": 14, "temperature": 0.0, "ignore_eos": True})
            assert resp.status == 200
            await resp.read()
    finally:
        await client.close()
    front = engine.tracer.front
    assert front.tokens == 28
    assert 2 <= wakes[0] < 28  # a burst's tokens are one wake
    closed = [t["front"] for t in _turns(engine.tracer)]
    closed.append(front.close_interval())
    assert sum(c["tokens"] for c in closed) == 28


def _count_wakes(monkeypatch):
    wakes, wake_done = [0], FrontClock.wake_done

    def counted(self, tokens):
        wakes[0] += 1
        wake_done(self, tokens)

    monkeypatch.setattr(FrontClock, "wake_done", counted)
    return wakes


async def test_outside_a_slice_the_front_adds_tokens_and_nothing_else(
        monkeypatch):
    """A tracer and no slice, the server's default: the write is the
    response's own (no stepping of its coroutine), no event is opened,
    and the event loop's thread reads no clock for the front."""
    engine = _engine(decode_steps=4)
    engine.tracer = EngineTracer(ring_size=8)

    def never(*args, **kwargs):
        raise AssertionError("stepped a write outside a slice")

    monkeypatch.setattr(FrontClock, "write", never)
    client = await _client(engine)
    front = engine.tracer.front
    try:
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "a b c", "stream": True,
            "max_tokens": 9, "temperature": 0.0, "ignore_eos": True})
        assert resp.status == 200
        body = (await resp.read()).decode()
        assert front.annotate is None and front._mark is None
    finally:
        await client.close()
    assert body.endswith("[DONE]\n\n") and front.tokens == 9
    source = inspect.getsource(FrontClock)
    event_loop_side = source[source.index("def consume_begin"):
                             source.index("def close_interval")]
    assert "perf_counter" not in event_loop_side
    assert "thread_time" not in event_loop_side


async def _client(engine):
    from production_stack_tpu.engine.server import EngineServer

    server = EngineServer(engine, "tiny-llama")
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    client.async_engine = server.async_engine
    return client


class _Marks:
    """An annotation factory that keeps which events are open."""

    def __init__(self):
        self.open, self.seen, self.deepest = [], [], 0

    @contextlib.contextmanager
    def __call__(self, name, **_):
        self.open.append(name)
        self.seen.append(name)
        self.deepest = max(self.deepest, len(self.open))
        try:
            yield
        finally:
            self.open.remove(name)


@pytest.mark.parametrize("checkpoint_interval_tokens", [0, 4],
                         ids=["plain", "relays-resume-descriptors"])
async def test_no_annotation_is_open_while_a_paused_transport_parks_a_write(
        monkeypatch, checkpoint_interval_tokens):
    """``resp.write`` drains when the transport is over its high-water
    mark: here every write parks, and whatever runs on the event loop
    meanwhile finds no ``server.*`` event open; none ever overlaps
    another."""
    engine = _engine(decode_steps=4)
    engine.config.checkpoint_interval_tokens = checkpoint_interval_tokens
    engine.tracer = EngineTracer(ring_size=8)
    marks, parked, wakes = _Marks(), [], _count_wakes(monkeypatch)
    real_write = web.StreamResponse.write

    async def paused_write(self, data):
        await real_write(self, data)
        asyncio.get_running_loop().call_soon(
            lambda: parked.append(list(marks.open)))
        await asyncio.sleep(0)  # parked; the callback above runs now
        await asyncio.sleep(0.05)

    monkeypatch.setattr(web.StreamResponse, "write", paused_write)
    client = await _client(engine)
    try:
        client.async_engine.stream_annotation = marks
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "a b c", "stream": True,
            "max_tokens": 14, "temperature": 0.0, "ignore_eos": True})
        assert resp.status == 200
        body = (await resp.read()).decode()
    finally:
        await client.close()
    assert body.count("data: {") >= 2 and body.endswith("[DONE]\n\n")
    # Relaying, every frame's flush sits inside a wake, which stops for
    # it and goes on after it: more ``server.consume`` events than wakes.
    assert (marks.seen.count("server.consume") > wakes[0]
            ) == bool(checkpoint_interval_tokens)
    assert len(parked) >= 3 and not any(parked)
    assert {"server.stream_token", "server.consume",
            "server.write"} == set(marks.seen)
    assert marks.deepest == 1 and marks.open == []
    assert engine.tracer.front.tokens == 14


@pytest.mark.parametrize("parks", [False, True], ids=["returns", "parks"])
async def test_a_write_marks_its_synchronous_part_and_passes_all_through(
        parks):
    front, marks = FrontClock(), _Marks()
    front.annotate = marks
    gate = asyncio.get_running_loop().create_future()

    async def writing():
        if parks:
            assert marks.open == ["server.write"]
            return await gate
        return 7

    task = asyncio.ensure_future(front.write(writing()))
    await asyncio.sleep(0)
    assert marks.open == [] and marks.seen == ["server.write"]
    if parks:
        assert not task.done()
        gate.set_result(9)
    assert await task == (9 if parks else 7)
    assert marks.seen == ["server.write"]
    # What is thrown into the parked write reaches the coroutine.
    caught = []

    async def cancelled():
        try:
            await asyncio.get_running_loop().create_future()
        except asyncio.CancelledError:
            caught.append(list(marks.open))
            raise

    task = asyncio.ensure_future(front.write(cancelled()))
    await asyncio.sleep(0)
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task
    assert caught == [[]] and marks.seen == ["server.write"] * 2


async def test_without_a_tracer_none_of_the_fronts_sites_runs(monkeypatch):
    """``--trace-ring-size 0`` and no span log: no tracer, no front,
    and neither thread reads a clock for one."""
    from production_stack_tpu.engine.server import (
        build_engine_from_args, parse_args,
    )

    def never(*args, **kwargs):
        raise AssertionError("a front site ran without a tracer")

    for name in ("bind", "consume_begin", "consume_end", "wake_done",
                 "write", "close_interval", "cpu_s"):
        monkeypatch.setattr(FrontClock, name, never)
    engine, _ = build_engine_from_args(parse_args([
        "--model", "tiny-llama", "--random-weights", "--page-size", "16",
        "--num-pages", "64", "--max-model-len", "128",
        "--trace-ring-size", "0"]))
    assert engine.tracer is None
    client = await _client(engine)
    try:
        assert client.async_engine.front is None
        for stream in (True, False):
            resp = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "a b c", "stream": stream,
                "max_tokens": 6, "temperature": 0.0, "ignore_eos": True})
            assert resp.status == 200
            await resp.read()
        text = await (await client.get("/metrics")).text()
    finally:
        await client.close()
    assert "engine_front_cpu" not in text and "loop_offcpu" not in text


def _counter(text, name):
    found = re.search(rf"^vllm:{name} (\S+)$", text, re.MULTILINE)
    assert found, f"/metrics has no vllm:{name}"
    assert f"# TYPE vllm:{name} counter" in text
    return float(found.group(1))


async def test_the_two_counters_are_on_metrics_and_never_decrease():
    engine = _engine(decode_steps=4)
    engine.tracer = EngineTracer(ring_size=8)
    names = ("engine_front_cpu_seconds_total",
             "engine_loop_offcpu_seconds_total")
    client = await _client(engine)
    try:
        readings = []
        for _ in range(3):
            text = await (await client.get("/metrics")).text()
            readings.append([_counter(text, name) for name in names])
            resp = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "a b c", "stream": True,
                "max_tokens": 9, "temperature": 0.0, "ignore_eos": True})
            await resp.read()
    finally:
        await client.close()
    for before, after in zip(readings, readings[1:]):
        assert all(b <= a for b, a in zip(before, after))
    # The event loop's thread served the requests in between.
    assert readings[-1][0] > readings[0][0] >= 0.0
    assert readings[-1][1] == pytest.approx(
        engine.tracer.loop_offcpu_s, abs=0.5)


def test_the_new_names_are_in_the_docs_and_the_vocabulary():
    """The three events, the record's two fields and the two counters,
    where docs/observability.md lists the others; the phases whose wall
    less CPU counts are the benchmark's LOOP_PHASES."""
    import pathlib

    from chipbench import front_phases, host_phases

    root = pathlib.Path(__file__).resolve().parent.parent
    docs = (root / "docs" / "observability.md").read_text()
    for name in front_phases.FRONT_EVENTS + (
            "vllm:engine_front_cpu_seconds_total",
            "vllm:engine_loop_offcpu_seconds_total", "`cpu`", "`front`"):
        assert name in docs, name
    assert front_phases.DEVICE_PHASES + ("idle",) == PARKED_PHASES
    # The readers still name ``rng``, which no turn has since PR 48
    # (tests/test_turn_phases.py): it reads 0 there.
    assert sorted(set(host_phases.LOOP_PHASES) - {"rng"}
                  | set(PARKED_PHASES)) == sorted(TURN_PHASES)
    record = json.loads(
        (root / "chipbench" / "tests" / "small_tpu_front.steps.json")
        .read_text())[0]
    assert set(record["front"]) == FRONT_KEYS
