"""Turn phases of the server loop (engine/tracing.py TURN_PHASES): the
records, the profiler annotations, the slow-turn line, the hand-over to
the event loop, and the benchmark's reduction that reads them
(chipbench/host_phases.py and its four readers)."""

import ast
import asyncio
import glob
import importlib
import json
import logging
import pathlib
import re
import statistics
import time

import pytest

from production_stack_tpu.engine import tracing
from production_stack_tpu.engine.tracing import TURN_PHASES, EngineTracer

ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = ROOT / "chipbench" / "tests"


def _engine(page_size=16, num_pages=64, max_model_len=128,
            prefill_chunk_size=32, **scheduler):
    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config,
    )
    from production_stack_tpu.engine.engine import LLMEngine

    return LLMEngine(EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=page_size, num_pages=num_pages),
        scheduler=SchedulerConfig(max_num_seqs=4,
                                  max_model_len=max_model_len,
                                  prefill_chunk_size=prefill_chunk_size,
                                  **scheduler),
    ))


async def _serve(engine, requests=2, max_tokens=10, hold_s=0.0):
    """Drives ``engine`` through the server's loop thread, as the HTTP
    handlers do; ``hold_s`` blocks the event loop once tokens flow."""
    from production_stack_tpu.engine.sequence import SamplingParams
    from production_stack_tpu.engine.server import AsyncEngine

    served = AsyncEngine(engine)
    served.start(asyncio.get_running_loop())
    streams = [await served.submit(
        [5 + i, 6, 7] * 13, SamplingParams(
            temperature=0.0, max_tokens=max_tokens, ignore_eos=True))
        for i in range(requests)]
    tokens = 0
    for _, stream in streams:
        while True:
            out = await asyncio.wait_for(stream.get(), 120)
            tokens += out.new_token is not None
            if hold_s and tokens == 2:
                time.sleep(hold_s)  # the event loop stands still
                hold_s = 0.0
            if out.finished:
                break
    assert tokens == requests * max_tokens
    if engine.tracer is not None:
        await _settled(engine.tracer, tokens)
    return served


def _turns(tracer):
    return [s for s in tracer.recent_steps(limit=0) if "phases" in s]


async def _settled(tracer, emitted):
    """A client has its last token before the turn that handed it over
    closes and enters the ring, and that turn's handoff_ms is stamped
    later still: wait for the turns that gave ``emitted`` outputs."""
    for _ in range(3000):
        gave = [t for t in _turns(tracer) if t["emitted"]]
        if (sum(t["emitted"] for t in gave) >= emitted
                and "handoff_ms" in gave[-1]):
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"the turns of {emitted} outputs never closed")


@pytest.mark.parametrize("scheduler", [
    {}, {"decode_steps": 4}, {"async_scheduling": True}],
    ids=["sync", "sync-burst", "async"])
async def test_phases_sum_to_the_wall_and_turns_are_contiguous(scheduler):
    engine = _engine(**scheduler)
    engine.tracer = EngineTracer(ring_size=8)
    assert engine.runner.tracer is engine.tracer
    await _serve(engine)
    steps = engine.tracer.recent_steps(limit=0)
    turns = _turns(engine.tracer)
    assert len(turns) == len(steps) >= 4  # every record is a turn
    kinds = {t["kind"] for t in turns}
    assert "prefill" in kinds and kinds & {"decode", "decode_dispatch"}
    for turn in turns:
        wall_ms = (turn["t_end"] - turn["t_start"]) * 1e3
        assert set(turn["phases"]) <= set(TURN_PHASES)
        assert sum(turn["phases"].values()) == pytest.approx(
            wall_ms, rel=0.01, abs=0.02)
        assert turn["emitted"] >= 0 and turn["host_ms"] >= 0
    for before, after in zip(turns, turns[1:]):
        assert after["step"] == before["step"] + 1
        assert after["t_start"] == before["t_end"]
    # Every way through the step names where the device was waited for
    # and where the results were committed and handed over.
    seen = set().union(*(t["phases"] for t in turns))
    assert {"plan", "build", "dispatch", "wait", "parse", "commit",
            "emit", "other"} <= seen
    assert sum(t["emitted"] for t in turns) == 20
    assert all("handoff_ms" in t for t in turns if t["emitted"])


async def test_without_a_tracer_no_record_and_no_annotation(monkeypatch):
    import jax

    from production_stack_tpu.engine.server import (
        build_engine_from_args, parse_args,
    )

    made = []
    real = jax.profiler.TraceAnnotation
    monkeypatch.setattr(
        jax.profiler, "TraceAnnotation",
        lambda *a, **k: made.append(a) or real(*a, **k))
    # What the server builds with the recorder off and no span log.
    engine, _ = build_engine_from_args(parse_args([
        "--model", "tiny-llama", "--random-weights", "--page-size", "16",
        "--num-pages", "64", "--max-model-len", "128",
        "--trace-ring-size", "0"]))
    assert engine.tracer is None and engine.runner.tracer is None
    served = await _serve(engine, requests=1, max_tokens=4)
    assert served.stream_annotation is None and served.front is None
    assert made == []
    # And with the recorder on (the default) the tracer has the factory.
    engine, _ = build_engine_from_args(parse_args([
        "--model", "tiny-llama", "--random-weights", "--page-size", "16",
        "--num-pages", "64", "--max-model-len", "128"]))
    assert engine.tracer.annotate is not None


@pytest.mark.parametrize("scheduler", [
    {"decode_steps": 4}, {"decode_steps": 4, "deferred_kv_writes": True},
    {}, {"async_scheduling": True}],
    ids=["burst", "deferred-burst", "single-step", "async"])
def test_a_decode_record_says_how_many_pages_the_attention_gathered(
        scheduler):
    """``attn_pages``: rows of 300 and 1300 tokens at page 128 under a
    table of 32 pages (blocks of 8) take 16, and 8 once the long row
    has left."""
    from production_stack_tpu.engine.sequence import SamplingParams

    engine = _engine(page_size=128, num_pages=32, max_model_len=4096,
                     prefill_chunk_size=256, **scheduler)
    engine.tracer = EngineTracer(ring_size=256)
    for prompt, max_tokens in ((1300, 5), (300, 24)):
        engine.add_request(
            [7 + i % 400 for i in range(prompt)], SamplingParams(
                temperature=0.0, max_tokens=max_tokens, ignore_eos=True))
    while engine.has_work():
        engine.step()
    decodes = [s for s in engine.tracer.recent_steps(limit=0)
               if s.get("kind") == "decode"]
    both = [s["attn_pages"] for s in decodes if s["decode_rows"] == 2]
    alone = [s["attn_pages"] for s in decodes if s["decode_rows"] == 1]
    assert both and set(both) == {16}
    assert alone and alone[-1] == 8
    assert {s["attn_pages"] for s in decodes} == {8, 16}


def test_a_tracer_outside_the_server_loop_records_steps_as_before():
    tracer = EngineTracer()
    assert tracer.phase("build") is None  # no loop keeps turns
    tracer.on_step(kind="decode", host_ms=1.0)
    assert tracer.end_turn(emitted=1) is None
    assert [s["step"] for s in tracer.recent_steps()] == [0]
    assert "phases" not in tracer.recent_steps()[0]


def test_a_turn_without_a_step_goes_on_into_the_next():
    tracer = EngineTracer()
    tracer.start_turns()
    tracer.phase("plan")
    assert tracer.end_turn(emitted=0) is None  # nothing was planned
    tracer.phase("wait")
    tracer.on_step(kind="decode")
    record = tracer.end_turn(emitted=3)
    assert set(record["phases"]) == {"other", "plan", "wait"}
    assert tracer.recent_steps() == [record]


def test_a_record_stays_small_however_long_the_loop_idles():
    """An idle server alternates idle and other once a second, a
    starved one spins through other 500 times a second: neither may
    grow the next record, which the ring keeps, /debug/steps serves
    and a slow-turn line prints whole."""
    tracer = EngineTracer()
    tracer.front.bind()  # as AsyncEngine.start does
    tracer.start_turns(compiles=40)  # start-up's compiles
    for _ in range(100_000):
        tracer.phase("idle")
        tracer.phase("other")
        assert tracer.end_turn(emitted=0) is None
    tracer.phase("wait")
    tracer.on_step(kind="decode")
    record = tracer.end_turn(emitted=1, compiles=40)
    assert set(record["phases"]) == {"idle", "other", "wait"}
    assert set(record["cpu"]) == set(record["phases"])
    assert set(record["front"]) == {"cpu_ms", "tokens"}
    assert "compiles" not in record
    assert len(json.dumps(record)) < 500


def _phase_literals():
    for path in sorted((ROOT / "production_stack_tpu").rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "phase"):
                for arg in node.args[:1]:
                    for leaf in ast.walk(arg):
                        if (isinstance(leaf, ast.Constant)
                                and isinstance(leaf.value, str)):
                            yield path.name, node.lineno, leaf.value


def test_every_phase_name_is_in_the_vocabulary_and_in_the_docs():
    used = list(_phase_literals())
    stray = [u for u in used if u[2] not in TURN_PHASES]
    assert not stray, f"phase names outside TURN_PHASES: {stray}"
    assert {u[2] for u in used} == set(TURN_PHASES)
    docs = (ROOT / "docs" / "observability.md").read_text()
    block = re.search(r"<!--\s*turn-phases:begin\s*-->(.*?)"
                      r"<!--\s*turn-phases:end\s*-->", docs, re.DOTALL)
    assert block, "docs/observability.md has no turn-phases table"
    rows = dict(re.findall(r"^\|\s*`([a-z_]+)`\s*\|\s*`([a-z_.]+)`",
                           block.group(1), re.MULTILINE))
    assert rows == {name: f"engine.{name}" for name in TURN_PHASES}


def test_tracing_imports_the_standard_library_only():
    import sys

    tree = ast.parse((ROOT / "production_stack_tpu" / "engine"
                      / "tracing.py").read_text())
    modules = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            modules.add(node.module)
    modules.discard("__future__")
    modules.discard("production_stack_tpu.utils.log")
    assert modules <= set(sys.stdlib_module_names), modules


@pytest.mark.parametrize("server_options", [True, False],
                         ids=["as-the-server-starts-it", "jax-defaults"])
async def test_a_profiler_slice_holds_the_turns_of_the_records(
        tmp_path, server_options):
    """With the options /debug/profiler/start passes (no Python
    tracer) as with jax's defaults: the annotations are what the
    reduction reads, and they are in both."""
    import jax
    from jax.profiler import ProfileData

    from chipbench import host_phases, reduce
    from production_stack_tpu.engine.server import slice_options

    engine = _engine(decode_steps=4)
    engine.tracer = EngineTracer(annotate=jax.profiler.TraceAnnotation)
    options = slice_options() if server_options else None
    assert options is None or options.python_tracer_level == 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        served = await _serve(engine, requests=1, max_tokens=4)
        served.stream_annotation = engine.tracer.annotate
        hand_over, handed = served._hand_over, []
        served._hand_over = lambda outputs, stamp=None: (
            handed.append(len(outputs)), hand_over(outputs, stamp))
        for _, stream in [await served.submit(
                [9, 8, 7] * 5, _greedy(8))]:
            while not (await asyncio.wait_for(stream.get(), 120)).finished:
                pass
        await _settled(engine.tracer, 4 + 8)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    events = [e for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events]
    records = {r["step"]: r for r in engine.tracer.recent_steps(limit=0)}
    turns = [e for e in events if e.name == "engine.turn"]
    assert len(turns) >= 4
    for event in turns:
        record = records[dict(event.stats)["step"]]
        wall_ns = (record["t_end"] - record["t_start"]) * 1e9
        assert event.duration_ns == pytest.approx(wall_ns, rel=0.05,
                                                  abs=2e5)
    names = {e.name for e in events}
    assert {f"engine.{p}" for p in ("plan", "build", "dispatch", "wait",
                                    "commit", "emit")} <= names
    # One delivery event a hand-over, not one a token: the first
    # request's turns (4 outputs) ran before the annotation was set,
    # the second's 8 tokens came in fewer hand-overs than tokens (each
    # turn's behind the next turn's dispatch, the last turn's at once).
    assert sum(handed) == 8 and 2 <= len(handed) < 8
    assert (sum(e.name == "server.stream_token" for e in events)
            == len(handed))
    turns = _turns(engine.tracer)
    assert sum(t["emitted"] for t in turns) == 4 + 8
    assert [t.get("handover") for t in turns[-len(handed) + 1:]] == [
        "behind"] * (len(handed) - 1)
    # Python frames ("$" + file:line function) only from the tracer
    # that the server's slices leave off.
    assert any(e.name.startswith("$") for e in events) != server_options
    # The reduction joins each turn event to its record by step,
    # whatever the clocks say: its start and its end are two pairs.
    summary = host_phases.summarize(
        reduce.read_planes(path, "cpu"), host_phases.read_host(path),
        list(records.values()))
    assert summary["clock_pairs"] == 2 * len(turns)
    assert abs(summary["clock_offset_ns"]) < 5e6
    assert summary["idle_by_phase_s"]["unattributed"] < summary["idle_s"]


async def test_stopping_a_slice_does_not_hold_the_streams(monkeypatch):
    """Writing a trace takes seconds (here: a stop_trace that sleeps
    4 s): POST /debug/profiler/stop waits for it off the event loop,
    so a stream's tokens keep arriving, a second stop or a start
    meanwhile gets 409, and the slice's span closes after it. A stop
    that held the loop would show as one gap of the whole 4 s; the
    bound on a gap is 1.5 s and not a few token times because on a box
    whose every core is taken a decode step that meets a new bucket's
    compile was seen to take 0.43 s (PR 46: at a stop of 2 s and a
    bound of 0.5 s the case failed at the floor, PR 44)."""
    import jax
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda trace_dir, **kw: started.append(kw))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: time.sleep(4.0))
    engine = _engine(max_model_len=1024, num_pages=80)
    engine.tracer = EngineTracer(ring_size=8)
    begin = engine.begin_step
    # A token every 10 ms or slower: the stream outlasts the stop.
    engine.begin_step = lambda: (time.sleep(0.01), begin())[1]
    client = TestClient(TestServer(
        EngineServer(engine, "tiny-llama").build_app()))
    await client.start_server()
    arrivals, ticks = [], []

    async def read(resp):
        async for _ in resp.content.iter_any():
            arrivals.append(time.perf_counter())

    async def tick():
        while True:
            ticks.append(time.perf_counter())
            await asyncio.sleep(0.005)

    ticker = asyncio.ensure_future(tick())
    try:
        assert (await client.post("/debug/profiler/start")).status == 200
        assert started[0]["profiler_options"].python_tracer_level == 0
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "a b c", "stream": True,
            "max_tokens": 900, "temperature": 0.0, "ignore_eos": True})
        assert resp.status == 200
        reader = asyncio.ensure_future(read(resp))
        while len(arrivals) < 5:
            await asyncio.sleep(0.01)
        t0 = time.perf_counter()
        stop = asyncio.ensure_future(client.post("/debug/profiler/stop"))
        await asyncio.sleep(0.5)
        assert not stop.done()
        assert (await client.post("/debug/profiler/stop")).status == 409
        assert (await client.post("/debug/profiler/start")).status == 409
        assert (await stop).status == 200
        t1 = time.perf_counter()
        assert t1 - t0 >= 4.0
        assert not reader.done()  # the stream outlasted the stop
        during = [t for t in arrivals if t0 + 0.1 < t < t1 - 0.1]
        assert len(during) >= 20
        assert max(b - a for a, b in zip(during, during[1:])) < 1.5
        inside = [t for t in ticks if t0 <= t <= t1]
        assert max(b - a for a, b in zip(inside, inside[1:])) < 1.5
        span = list(engine.tracer._ring)[-1]
        assert span.seq_id.startswith("prof-")
        assert "profiler_stop" in [e["event"] for e in span.events]
        # The slice is over: a stop has nothing to stop, a start starts.
        assert (await client.post("/debug/profiler/stop")).status == 409
        assert (await client.post("/debug/profiler/start")).status == 200
        reader.cancel()
        resp.close()
    finally:
        ticker.cancel()
        await client.close()


class _Loop:
    """Stands in for the event loop: keeps what other threads ask it to
    call, and passes it on to the running loop where ``forward``."""

    def __init__(self, forward=False):
        self.real = asyncio.get_running_loop() if forward else None
        self.calls = []

    def call_soon_threadsafe(self, fn, *args):
        self.calls.append((fn, args))
        if self.real is not None:
            self.real.call_soon_threadsafe(fn, *args)

    def run(self):
        calls, self.calls = self.calls, []
        for fn, args in calls:
            fn(*args)


class _KeptStream:
    def __init__(self):
        self.got = []

    def put_nowait(self, item):
        self.got.append(item)


def _out(seq_id, token, finished=False):
    from production_stack_tpu.engine.engine import StepOutput
    return StepOutput(seq_id=seq_id, new_token=token, finished=finished,
                      finish_reason="length" if finished else None)


def _kept(*seq_ids):
    from production_stack_tpu.engine.server import AsyncEngine

    served = AsyncEngine(engine=None)
    served._loop = _Loop()
    for seq_id in seq_ids:
        served._streams[seq_id] = _KeptStream()
    return served


def test_a_turn_handed_over_inside_a_slice_is_delivered_after_it():
    """The event loop may get to a delivery only after the slice has
    ended (stopping a trace holds it for seconds): the annotation is
    bound when the turn is handed over, and is one event a turn."""
    import contextlib

    names = []

    @contextlib.contextmanager
    def annotate(name):
        names.append(name)
        yield

    served = _kept("s")
    served.stream_annotation = annotate
    served._hand_over([_out("s", 1), _out("s", 2)])
    served.stream_annotation = None  # /debug/profiler/stop
    served._hand_over([_out("s", 3)])
    assert len(served._loop.calls) == 2
    served._loop.run()
    assert [o.new_token for o in served._streams["s"].got] == [1, 2, 3]
    assert names == ["server.stream_token"]


def test_a_stream_gone_before_the_delivery_is_skipped_and_the_rest_fed():
    """finish_stream() and abort() run on the event loop and can come
    between the hand-over and its delivery."""
    served = _kept("a", "b", "c")
    a, b, c = (served._streams[k] for k in "abc")
    stamped = []
    outputs = [_out("a", 1), _out("b", 2), _out("c", 3), _out("a", 4),
               _out("b", 5, finished=True), _out("never-known", 6)]
    served._hand_over(outputs, lambda: stamped.append(True))
    served.finish_stream("a")  # its client has left
    assert len(served._loop.calls) == 1
    served._loop.run()
    assert a.got == []
    assert [o.new_token for o in b.got] == [2, 5] and b.got[-1].finished
    assert [o.new_token for o in c.got] == [3]
    assert stamped == [True]  # the last act, whoever was skipped
    # Nothing to hand over is no call at all.
    served._hand_over([], lambda: stamped.append(True))
    assert served._loop.calls == [] and stamped == [True]


async def test_a_turn_of_many_outputs_is_one_call_and_streams_keep_order():
    """Three rows in bursts of four: a decode turn hands up to twelve
    outputs over three streams to the event loop in one call; each
    stream gets its own tokens in the engine's order, its finish last."""
    from production_stack_tpu.engine.server import AsyncEngine

    engine = _engine(decode_steps=4)
    engine.tracer = EngineTracer()
    take, taken = engine.take_owed, []
    engine.take_owed = lambda: taken.append(take()) or taken[-1]
    served = AsyncEngine(engine)
    loop = _Loop(forward=True)
    served.start(loop)
    streams = [await served.submit([5 + i, 6, 7] * 13, _greedy(11))
               for i in range(3)]
    got = {}
    for seq_id, stream in streams:
        got[seq_id] = []
        while not got[seq_id] or not got[seq_id][-1].finished:
            got[seq_id].append(await asyncio.wait_for(stream.get(), 120))
    await _settled(engine.tracer, 33)
    turns = [t for t in _turns(engine.tracer) if t["emitted"]]
    # A turn's outputs go behind the next turn's dispatch, so they are
    # in the next turn's record; the last turn's own go at once, in
    # its record too: one call more than records.
    assert [fn for fn, _ in loop.calls] == [served._deliver] * (
        len(turns) + 1)
    sizes = [len(args[0]) for _, args in loop.calls]
    assert sizes[:-2] == [t["emitted"] for t in turns[:-1]]
    assert sum(sizes[-2:]) == turns[-1]["emitted"]
    assert [t["handover"] for t in turns] == ["behind"] * len(turns)
    assert max(t["emitted"] for t in turns) > len(streams)
    assert len(turns) < 33 == sum(t["emitted"] for t in turns)
    produced = [out for outputs in taken for out in outputs]
    for seq_id, outs in got.items():
        assert outs == [o for o in produced if o.seq_id == seq_id]
        assert [o.finished for o in outs] == [False] * 10 + [True]


async def test_refusals_and_step_failure_aborts_take_the_same_path():
    from production_stack_tpu.engine.server import AsyncEngine

    class Engine:
        tracer = runner = None

        def __init__(self):
            self.live = []

        def has_work(self):
            return bool(self.live)

        def add_request(self, prompt, sampling, seq_id, **kw):
            if not prompt:
                raise ValueError("the queue is full")
            self.live.append(seq_id)

        def begin_step(self):
            raise RuntimeError("the device program failed")

        def abort_after_step_failure(self):
            live, self.live = self.live, []
            return [_out(seq_id, None, finished=True) for seq_id in live]

    served = AsyncEngine(Engine())
    loop = _Loop(forward=True)
    served.start(loop)
    _, refused = await served.submit([], _greedy(4))
    out = await asyncio.wait_for(refused.get(), 30)
    assert (out.finished, out.new_token, out.finish_reason) == (
        True, None, "abort")
    assert len(loop.calls) == 1
    admitted = [await served.submit([1, 2], _greedy(4)) for _ in range(2)]
    for seq_id, stream in admitted:
        out = await asyncio.wait_for(stream.get(), 30)
        assert out.finished and out.seq_id == seq_id
    assert served.consecutive_step_failures >= 1
    # A failed step's aborts are one call, however many rows it held
    # (two calls where the step ran between the two submissions).
    assert 2 <= len(loop.calls) <= 3
    assert {fn for fn, _ in loop.calls} == {served._deliver}


async def test_a_client_sees_one_frame_a_token_in_the_engines_order():
    """The wire is as it was: a burst's four tokens reach the stream in
    one delivery and still leave as four SSE frames, in order."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer
    from production_stack_tpu.engine.tokenizer import ByteTokenizer

    class Tokenizer(ByteTokenizer):
        def decode(self, token_ids):  # every id is visible text
            return "".join(f"<{t}>" for t in token_ids)

    engine = _engine(decode_steps=4)
    engine.tokenizer = Tokenizer()
    take, tokens = engine.take_owed, []
    engine.take_owed = lambda: [
        tokens.append(o.new_token) or o for o in take()]
    server = EngineServer(engine, "tiny-llama")
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    try:
        resp = await client.post("/v1/completions", json={
            "model": "tiny-llama", "prompt": "a b c", "stream": True,
            "max_tokens": 14, "temperature": 0.0, "ignore_eos": True})
        assert resp.status == 200
        frames = (await resp.read()).decode().split("\n\n")
    finally:
        await client.close()
    assert frames[-2:] == ["data: [DONE]", ""]
    choices = [json.loads(f[len("data: "):])["choices"][0]
               for f in frames[:-2]]
    assert len(tokens) == 14 and None not in tokens
    # A frame a token, not a frame a burst, and the finish after them.
    assert [c["text"] for c in choices] == [f"<{t}>" for t in tokens] + [""]
    assert [c["finish_reason"] for c in choices] == [None] * 14 + ["length"]


def _greedy(max_tokens):
    from production_stack_tpu.engine.sequence import SamplingParams
    return SamplingParams(temperature=0.0, max_tokens=max_tokens,
                          ignore_eos=True)


class _Clock:
    """Stands in for ``time`` inside engine/tracing.py."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self):
        return self.now

    def thread_time(self):  # a thread that is never kept off a core
        return self.now

    def time(self):
        return self.now + 1.7e9


@pytest.fixture
def slow_lines(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(tracing, "time", clock)
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    handler = Keep(level=logging.WARNING)
    tracing.logger.addHandler(handler)
    yield clock, lines
    tracing.logger.removeHandler(handler)


def _turn(tracer, clock, kind="decode", compiles=0, **seconds):
    for name, s in seconds.items():
        tracer.phase(name)
        clock.now += s
    tracer.on_step(kind=kind, decode_rows=64)
    tracer.phase("emit")
    clock.now += 0.01
    return tracer.end_turn(emitted=1, compiles=compiles)


@pytest.mark.parametrize("phase", ["wait", "plan", "emit", "other"])
def test_a_stalled_phase_gives_one_slow_turn_line_that_names_it(
        slow_lines, phase):
    clock, lines = slow_lines
    tracer = EngineTracer()
    tracer.start_turns()
    for _ in range(10):
        _turn(tracer, clock, build=0.01, wait=0.2)
    stalled = {"build": 0.01, "wait": 0.2}
    stalled[phase] = stalled.get(phase, 0.0) + 1.5  # the injected sleep
    record = _turn(tracer, clock, **stalled)
    for _ in range(10):
        _turn(tracer, clock, build=0.01, wait=0.2)
    assert len(lines) == 1 and lines[0].startswith("slow turn: decode")
    assert f"most of it in {phase} " in lines[0]
    logged = json.loads(lines[0][lines[0].index("{"):])
    assert logged == record and logged["decode_rows"] == 64
    assert logged["phases"][phase] >= 1500.0


@pytest.mark.parametrize("history,extra_s,idle_s,lines_expected", [
    (7, 1.5, 0.0, 0),    # a kind says nothing before 8 turns of history
    (8, 1.5, 0.0, 1),
    (10, 0.9, 0.0, 0),   # four times the median, yet under 1 s longer
    (10, 0.0, 30.0, 0),  # parked without work is no stall,
    (10, 1.5, 30.0, 1),  # nor the name of one that follows it
])
def test_slow_turn_thresholds(slow_lines, history, extra_s, idle_s,
                              lines_expected):
    clock, lines = slow_lines
    tracer = EngineTracer()
    tracer.start_turns()
    for _ in range(history):
        _turn(tracer, clock, wait=0.2)
    _turn(tracer, clock, kind="prefill", wait=5.0)  # a kind of its own
    _turn(tracer, clock, idle=idle_s, wait=0.2 + extra_s)
    assert len(lines) == lines_expected
    if lines:
        assert "slow turn: decode 1710.0 ms against" in lines[0]
        assert "most of it in wait (1700.0 ms)" in lines[0]


def test_a_slow_turn_that_held_a_compile_says_so(slow_lines):
    clock, lines = slow_lines
    tracer = EngineTracer()
    tracer.start_turns()
    _turn(tracer, clock, compiles=7, dispatch=0.01, wait=0.2)
    for _ in range(9):
        assert "compiles" not in _turn(tracer, clock, compiles=7,
                                       dispatch=0.01, wait=0.2)
    record = _turn(tracer, clock, compiles=9, dispatch=40.0, wait=0.2)
    assert record["compiles"] == 2
    assert len(lines) == 1 and '"compiles": 2' in lines[0]
    assert "most of it in dispatch" in lines[0]


def test_a_late_handoff_gives_a_slow_turn_line_too(slow_lines):
    clock, lines = slow_lines
    tracer = EngineTracer()
    tracer.start_turns()
    for late in [0.1] * 9 + [2.5]:
        record = _turn(tracer, clock, wait=0.2)
        emit_start = clock.now
        clock.now += late
        tracer.on_handoff(record, emit_start)
    assert record["handoff_ms"] == pytest.approx(2500.0)
    assert len(lines) == 1 and "handoff_ms 2500.0" in lines[0]
    assert "the event loop was late" in lines[0]


async def test_handoff_ms_is_larger_when_the_event_loop_is_held():
    def handoffs(engine):
        return [t["handoff_ms"] for t in _turns(engine.tracer)
                if "handoff_ms" in t]

    free = _engine(decode_steps=2)
    free.tracer = EngineTracer()
    await _serve(free, requests=1, max_tokens=12)
    held = _engine(decode_steps=2)
    held.tracer = EngineTracer()
    await _serve(held, requests=1, max_tokens=12, hold_s=1.0)
    assert len(handoffs(free)) >= 6 and max(handoffs(free)) < 500.0
    # The turn whose outputs were queued behind the held loop waited
    # for it; the loop thread's own emit phase did not.
    assert max(handoffs(held)) >= 800.0
    late = max(_turns(held.tracer), key=lambda t: t.get("handoff_ms", 0))
    assert late["phases"]["emit"] < 20.0
    # One call a turn: the loop thread is out of ``emit`` in well under
    # a millisecond, whatever the event loop is doing.
    emits = [t["phases"]["emit"] for t in _turns(held.tracer)
             if t["emitted"]]
    assert statistics.median(emits) < 2.0


# ---- the benchmark's reduction ---------------------------------------------


@pytest.mark.parametrize("intervals,expected", [
    # A gap inside one phase, one across two, one that no phase covers.
    ([(10, 20)], {"a": 10e-9}),
    ([(90, 130)], {"a": 10e-9, "b": 20e-9}),
    ([(200, 260)], {"c": 10e-9}),
    ([(0, 400)], {"a": 100e-9, "b": 50e-9, "c": 50e-9}),
    ([(150, 200), (300, 310)], {}),
])
def test_cut_gives_each_phase_its_share_of_an_interval(intervals,
                                                       expected):
    from chipbench.host_phases import cut

    phases = [(0, 100, "a"), (110, 150, "b"), (250, 300, "c"),
              (320, 330, "b")]
    assert cut(intervals, phases) == pytest.approx(expected)


def test_clock_pairs_are_the_instants_that_records_and_events_share():
    """Two whole turns give their starts and ends; the turn the slice's
    start cut gives its end, the one its end cut its start; a step
    without a record gives nothing."""
    from chipbench.host_phases import clock_offsets

    def record(step, t_start, t_end):
        return {"step": step, "t_start": t_start, "t_end": t_end}

    host = {"start_unix_ns": 5_000_000_000,
            "turns": [(100, 200, 5), (200, 300, 6)],
            "phases": [(60, 80, "wait", 4), (80, 100, "emit", 4),
                       (100, 150, "wait", 5), (150, 200, "emit", 5),
                       (200, 300, "wait", 6), (300, 320, "other", 7),
                       (320, 350, "build", 7), (350, 360, "other", 8),
                       (10, 20, "other", None)]}
    late = 7e-6  # the records' clock is 7 us ahead of the profiler's
    records = [record(4, 0.0, 5.0 + 100e-9 + late),
               record(5, 5.0 + 100e-9 + late, 5.0 + 200e-9 + late),
               record(6, 5.0 + 200e-9 + late, 5.0 + 300e-9 + late),
               record(7, 5.0 + 300e-9 + late, 9.0),
               {"step": 9, "kind": "decode"}]
    offsets = clock_offsets(host, records)
    assert len(offsets) == 6
    assert offsets == pytest.approx([7000.0] * 6, abs=2.0)
    assert clock_offsets(dict(host, turns=[]), records) == []


def test_the_loops_own_phases_are_all_but_wait_and_idle():
    from chipbench.host_phases import DEVICE_PHASES, LOOP_PHASES

    read = set(LOOP_PHASES + DEVICE_PHASES + ("idle",))
    assert set(TURN_PHASES) <= read
    # What the readers still name and no turn has any more reads 0
    # there: the key is made on the host inside ``build`` (PR 48).
    assert read - set(TURN_PHASES) == {"rng"}


def test_idle_by_phase_on_the_recorded_chip_trace():
    """chipbench/tests/small_tpu_host.xplane.pb, recorded on a v5e by
    record_host_trace.py: four turns of build, dispatch, wait, commit,
    emit around one jitted program.  The expected seconds were counted
    from the file's events one by one, every hole between the device's
    40 ``XLA Ops`` against every ``engine.*`` event, by a plain double
    loop that shares no code with host_phases.py."""
    from chipbench import host_phases, reduce

    path = str(FIXTURES / "small_tpu_host.xplane.pb")
    with open(FIXTURES / "small_tpu_host.steps.json") as f:
        records = json.load(f)
    summary = host_phases.summarize(
        reduce.read_planes(path, "tpu"), host_phases.read_host(path),
        records)
    assert summary["span_s"] == pytest.approx(0.047147538, abs=1e-9)
    assert summary["idle_s"] == pytest.approx(0.044263376, abs=1e-9)
    assert summary["idle_by_phase_s"] == pytest.approx({
        "build": 0.010846559, "dispatch": 0.00095398, "wait": 0.00385437,
        "commit": 0.008471749, "emit": 0.019834008, "other": 2.5629e-05,
        "unattributed": 0.000277081}, abs=1e-9)
    assert summary["phase_s"] == pytest.approx({
        "build": 0.013730721, "dispatch": 0.00095398, "wait": 0.00385437,
        "commit": 0.008471749, "emit": 0.019834008, "other": 2.5629e-05},
        abs=1e-9)
    assert summary["stream_busy_by_phase_s"] == pytest.approx({
        "build": 0.00721271, "wait": 0.00158467, "commit": 0.005033219,
        "emit": 0.009829909}, abs=1e-9)
    # The device's span opens in turn 0 and closes in turn 3; each of
    # the four turn events has its record: a start and an end each.
    assert summary["turn_steps"] == [1, 2]
    assert summary["clock_pairs"] == 8 and summary["engine_events"] == 28
    assert abs(summary["clock_offset_ns"]) < 1e6
    # The device idles while the host builds, commits and emits.
    idle = summary["idle_by_phase_s"]
    assert idle["emit"] > idle["build"] > idle["commit"] > idle["wait"]
    assert host_phases.host_idle_s(summary) == pytest.approx(
        summary["idle_s"] - idle["wait"] - idle["unattributed"])


def _run_dir(tmp_path, steps, host=None):
    from chipbench.runfiles import RunFiles

    files = {"cell.json": {"t0_unix": 1000.0, "seconds": 20.0,
                           "version": {"platform": "tpu"}},
             "steps.json": steps}
    if host is not None:
        files["host_phases.json"] = host
    for name, content in files.items():
        (tmp_path / name).write_text(json.dumps(content))
    return RunFiles(str(tmp_path))


def _step(ts, kind="decode", **phases):
    return {"step": int(ts), "ts": ts, "kind": kind, "window": 32,
            "host_ms": 0.2, "device_wait_ms": 2400.0, "phases": phases}


@pytest.mark.parametrize("name,expected", [
    ("loop_host_ms", 150.0),     # wall less wait (and idle): the median
    ("dispatch_prep_ms", 9.0),   # admit + plan + build + rng + dispatch
    ("emit_ms", 135.0),
])
def test_span_readers_take_the_median_over_the_windows_decode_turns(
        tmp_path, name, expected):
    turn = dict(admit=1.0, plan=1.0, build=2.0, rng=3.0, dispatch=2.0,
                wait=2400.0, parse=1.0, commit=4.0, emit=135.0, other=1.0)
    steps = [_step(990.0, **dict(turn, emit=900.0)),   # before the window
             _step(1001.0, **turn),
             _step(1002.0, **dict(turn, idle=700.0)),
             _step(1003.0, **dict(turn, emit=235.0, plan=11.0)),
             _step(1004.0, kind="prefill", **dict(turn, emit=1.0)),
             _step(1021.0, **dict(turn, emit=900.0))]  # after it
    reader = importlib.import_module(f"chipbench.layer_metrics.{name}")
    assert reader.read(_run_dir(tmp_path, steps)) == pytest.approx(expected)
    # A program without the phases (the parent commit) has nothing to
    # read, and the reader says so without raising.
    bare = [{k: v for k, v in s.items() if k != "phases"} for s in steps]
    assert reader.read(_run_dir(tmp_path, bare)) is None


@pytest.mark.parametrize("host,expected", [
    ({"span_s": 8.0, "idle_s": 0.55, "engine_events": 35,
      "stand_in": False,
      "idle_by_phase_s": {"emit": 0.45, "dispatch": 0.02, "commit": 0.02,
                          "rng": 0.01, "wait": 0.03,
                          "unattributed": 0.02}}, 6.25),
    # No annotations in the slice (the parent commit), the CPU's
    # stand-in threads, no slice at all: nothing to read.
    ({"span_s": 8.0, "idle_s": 0.7, "engine_events": 0, "stand_in": False,
      "idle_by_phase_s": {"unattributed": 0.7}}, None),
    ({"span_s": 8.0, "idle_s": 0.7, "engine_events": 35, "stand_in": True,
      "idle_by_phase_s": {"emit": 0.7}}, None),
    (None, None),
])
def test_host_idle_is_the_idle_under_the_hosts_own_phases(tmp_path, host,
                                                          expected):
    from chipbench.layer_metrics import host_idle

    value = host_idle.read(_run_dir(tmp_path, [], host))
    assert value == (pytest.approx(expected) if expected else None)
