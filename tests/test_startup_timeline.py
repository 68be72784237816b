"""Set-up from inside (docs/observability.md, "Why is a start slow?"):
the start's spans (engine/tracing.py StartupTimeline), the split of
every program load that jax.monitoring publishes
(engine/perf_observatory.py listen_for_loads), and where the server
says both (/version, /debug/compiles, /metrics). Nothing here waits on
a wall clock."""

import ast
import asyncio
import pathlib
import re

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.engine import perf_observatory
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_model_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.perf_observatory import (
    LOAD_PARTS,
    program_key,
)
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.engine.server import (
    EngineServer,
    build_engine_from_args,
    parse_args,
)
from production_stack_tpu.engine.tracing import (
    STARTUP_SPANS,
    StartupTimeline,
)

ROOT = pathlib.Path(__file__).resolve().parent.parent
CHUNK = 32


def _engine(**scheduler):
    return LLMEngine(EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=128),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=CHUNK,
                                  **scheduler)))


def _run(engine, prompt, max_tokens=4):
    sid = engine.add_request(list(prompt), SamplingParams(
        temperature=0.0, max_tokens=max_tokens, ignore_eos=True))
    seq = engine.sequences[sid]
    while engine.has_work():
        engine.step()
    return list(seq.output_token_ids)


async def _get(server, *paths):
    client = TestClient(TestServer(server.build_app()))
    await client.start_server()
    try:
        out = []
        for path in paths:
            resp = await client.get(path)
            assert resp.status == 200, path
            out.append(await (resp.text() if path == "/metrics"
                              else resp.json()))
        return out
    finally:
        await client.close()


def _fake_clock(step=1.0, start=100.0):
    now = [start - step]

    def clock():
        now[0] += step
        return now[0]
    return clock


# ---- the vocabulary ---------------------------------------------------------


def _span_literals():
    for path in sorted((ROOT / "production_stack_tpu").rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("enter", "within", "_open")
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith("boot")):
                yield path.name, node.lineno, node.args[0].value


def test_every_span_name_is_in_the_vocabulary_and_in_the_docs():
    used = list(_span_literals())
    stray = [u for u in used if u[2] not in STARTUP_SPANS]
    assert not stray, f"span names outside STARTUP_SPANS: {stray}"
    assert {u[2] for u in used} == set(STARTUP_SPANS)
    docs = (ROOT / "docs" / "observability.md").read_text()
    block = re.search(r"<!--\s*startup-spans:begin\s*-->(.*?)"
                      r"<!--\s*startup-spans:end\s*-->", docs, re.DOTALL)
    assert block, "docs/observability.md has no startup-spans table"
    rows = re.findall(r"^\|\s*`(boot[a-z_.]*)`\s*\|", block.group(1),
                      re.MULTILINE)
    assert rows == list(STARTUP_SPANS)


# ---- the timeline on a faked clock -----------------------------------------


def _a_start(annotate=None):
    timeline = StartupTimeline(annotate=annotate, clock=_fake_clock(),
                               process_start=90.0)
    with timeline.within("boot.claim_devices", platform="cpu"):
        pass
    with timeline.within("boot.probes"):
        with timeline.probe(kernel="decode") as span:
            span["result"] = "served"
        with timeline.probe(kernel="prefill"):
            pass
    with timeline.within("boot.weights") as span:
        span["params_bytes"] = 7
    resume = timeline.enter("boot.cache", bytes=11)
    assert timeline.enter(resume) == "boot.cache"
    with timeline.within("boot.tokenizer"):
        pass
    with timeline.probe(kernel="ragged"):  # outside boot.probes
        pass
    timeline.enter("boot.listen")
    timeline.ready()
    return timeline


def test_children_are_contiguous_and_inside_their_parent():
    timeline = _a_start()
    spans = timeline.to_dict()["spans"]
    assert {s["name"] for s in spans} == set(STARTUP_SPANS)
    assert all(s["seconds"] is not None for s in spans)
    boot = spans[0]
    assert (boot["name"], boot["parent"]) == ("boot", None)
    assert boot["t_start"] == boot["process_start_unix"] == 90.0
    assert boot["t_start"] + boot["seconds"] == boot["ready_unix"]
    children = [s for s in spans if s["parent"] == "boot"]
    assert children[0]["name"] == "boot.imports"
    assert children[0]["t_start"] == 90.0
    assert children[-1]["name"] == "boot.listen"
    for before, after in zip(children, children[1:]):
        assert before["t_start"] + before["seconds"] == after["t_start"]
    last = children[-1]
    assert last["t_start"] + last["seconds"] == boot["ready_unix"]
    # So the parent has no time of its own.
    assert sum(c["seconds"] for c in children) == boot["seconds"]
    assert timeline.seconds_by_span()["boot"] == boot["seconds"]
    assert sum(v for k, v in timeline.seconds_by_span().items()
               if k != "boot") == boot["seconds"]
    # A probe lies inside a boot.probes span, wherever it was made.
    probes = [s for s in spans if s["name"] == "boot.probe"]
    assert [p["kernel"] for p in probes] == ["decode", "prefill",
                                             "ragged"]
    for probe in probes:
        assert probe["parent"] == "boot.probes"
        assert any(s["name"] == "boot.probes"
                   and s["t_start"] <= probe["t_start"]
                   and probe["t_start"] + probe["seconds"]
                   <= s["t_start"] + s["seconds"] for s in spans)
    assert [s for s in spans if s["name"] == "boot.weights"][0][
        "params_bytes"] == 7


def test_after_ready_a_start_has_no_more_spans():
    timeline = _a_start()
    before = timeline.to_dict()
    timeline.enter("boot.engine")
    timeline.ready()
    assert timeline.to_dict() == before


def test_live_spans_are_profiler_events_and_the_past_is_not():
    events = []

    class Mark:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            events.append(("in", self.name))

        def __exit__(self, *exc):
            events.append(("out", self.name))

    _a_start(annotate=Mark)
    names = [name for way, name in events if way == "in"]
    assert "engine.boot" not in names
    assert "engine.boot.imports" not in names
    assert names[:3] == ["engine.boot.engine",
                         "engine.boot.claim_devices",
                         "engine.boot.engine"]
    assert names.count("engine.boot.probe") == 3
    # Each opened once and closed once, innermost first.
    stack = []
    for way, name in events:
        if way == "in":
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack


def test_the_kernel_says_when_the_process_began():
    from production_stack_tpu.engine import tracing

    began = tracing.process_start_unix()
    if began is None:
        pytest.skip("/proc/self/stat or /proc/uptime cannot be read")
    # Before this module's import, and not before the machine came up.
    assert began <= tracing._IMPORTED_UNIX + 0.05
    timeline = StartupTimeline()
    assert timeline.process_start_unix <= timeline.spans[1]["t_start"] \
        + timeline.spans[1]["seconds"]


# ---- the server says it -----------------------------------------------------


def test_a_built_engine_answers_version_with_the_whole_boot():
    startup = StartupTimeline()
    engine, name = build_engine_from_args(
        parse_args(["--model", "tiny-llama", "--random-weights",
                    "--num-pages", "64", "--max-num-seqs", "4",
                    "--max-model-len", "128"]), startup)
    assert engine.runner.startup is startup
    server = EngineServer(engine, name)
    startup.enter("boot.listen")
    version, = asyncio.run(_get(server, "/version"))
    timeline = version["startup"]
    spans = timeline["spans"]
    assert all(s["seconds"] is not None for s in spans)
    assert {s["name"] for s in spans} >= {
        "boot", "boot.imports", "boot.weights", "boot.cache",
        "boot.engine", "boot.listen"}
    assert {s["name"] for s in spans} <= set(STARTUP_SPANS)
    assert timeline["process_start_unix"] <= min(
        s["t_start"] for s in spans)
    assert timeline["ready_unix"] == pytest.approx(
        spans[0]["t_start"] + spans[0]["seconds"], abs=1e-5)
    weights = next(s for s in spans if s["name"] == "boot.weights")
    assert weights["params_bytes"] == \
        engine.runner.observatory.params_bytes > 0
    cache = next(s for s in spans if s["name"] == "boot.cache")
    assert cache["bytes"] == \
        engine.runner.observatory.hbm_bytes()["kv_pages"]


def test_a_probe_is_a_span_with_its_kernel_its_split_and_its_result(
        monkeypatch):
    from production_stack_tpu.engine.model_runner import ModelRunner

    # What the runner sees on a TPU host, without one: the kernels are
    # traced, and Mosaic refuses to lower them for the CPU.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = tiny_model_config("llama")
    model.attention_impl = "auto"
    runner = ModelRunner(EngineConfig(
        model=model, cache=CacheConfig(page_size=128, num_pages=32),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64)))
    probes = [s for s in runner.startup.spans
              if s["name"] == "boot.probe"]
    # One case a kernel: the first refusal of a kernel ends its cases.
    assert [p["kernel"] for p in probes] == [
        "paged_decode_attention", "paged_prefill_attention"]
    for probe in probes:
        assert probe["result"] == "degraded"
        assert len(probe["shape"]) >= 3
        assert probe["cache"] in ("hit", "miss", "none")
        assert probe["trace_s"] > 0
        assert sum(probe[p] for p in LOAD_PARTS[:3]) <= \
            probe["seconds"] + 1e-3
        assert any(s["name"] == "boot.probes"
                   and s["t_start"] <= probe["t_start"]
                   for s in runner.startup.spans)
    assert (model.attention_impl_decode,
            model.attention_impl_prefill) == ("xla", "xla")
    # The caller's frames are the ones they were: no method of the
    # runner stands between a resolver and ``_lowering_error``.
    assert not hasattr(ModelRunner, "_probe")


# ---- a load, split -----------------------------------------------------------


def test_a_compile_record_carries_the_split():
    engine = _engine()
    _run(engine, range(2, 12))
    obs = engine.runner.observatory
    records = obs.recent_compiles(limit=-1)
    assert records
    for record in records:
        assert set(record) == {"kind", "key", "seconds", "ts",
                               *LOAD_PARTS, "cache"}
        assert record["cache"] in ("hit", "miss", "none")
        # Tracing, lowering and the backend's compile follow one
        # another on the calling thread; the cache's read is inside
        # the backend's stage.
        assert all(record[p] >= 0 for p in LOAD_PARTS)
        assert (record["trace_s"] + record["lower_s"]
                + record["backend_s"]) <= record["seconds"] + 1e-3
        assert record["cache_read_s"] <= record["backend_s"] + 1e-3
        assert record["trace_s"] > 0 and record["lower_s"] > 0
    report = obs.compile_report()
    for part in LOAD_PARTS:
        assert report["parts"]["step"][part] == pytest.approx(
            sum(r[part] for r in records if r["kind"] == "step"),
            abs=1e-4)
    assert sum(report["cache"].values()) == sum(
        r["cache"] != "none" for r in records)
    # The same shapes again hear nothing.
    heard = len(perf_observatory._heard.entries)
    _run(engine, range(30, 40))
    assert obs.recent_compiles(limit=-1) == records
    assert len(perf_observatory._heard.entries) == heard


def test_a_function_traced_inside_anothers_trace_is_counted_once():
    perf_observatory.listen_for_loads()
    inner = jax.jit(lambda x: x * 3)
    outer = jax.jit(lambda x: inner(inner(x) + 1) - 2)
    import time
    since = time.perf_counter()
    outer.lower(jax.ShapeDtypeStruct((7, 3), "float32")).compile()
    wall = time.perf_counter() - since
    split = perf_observatory.take_load_split(since)
    assert 0 < split["trace_s"] + split["lower_s"] + split["backend_s"] \
        <= wall
    # And what was heard before ``since`` is nobody's.
    outer.lower(jax.ShapeDtypeStruct((9, 3), "float32")).compile()
    late = perf_observatory.take_load_split(time.perf_counter())
    assert late == {**dict.fromkeys(LOAD_PARTS, 0.0), "cache": "none"}
    assert not perf_observatory._heard.entries


def test_hundreds_of_stages_inside_a_stage_leave_the_outer_its_seconds():
    """A kernel's lowering traces hundreds of jitted helpers (630 a
    prefill probe): they are inside the stage that runs, and the first
    listener's list of them crowded the burst's own trace out on the
    chip (PERF.md section 6, PR 53)."""
    import time

    perf_observatory.listen_for_loads()
    trace, lower, backend = perf_observatory._STAGES
    since = time.perf_counter()

    def stage(event, seconds, inside=0, asked=False):
        perf_observatory._hear_stage_start(event, 0.0, fun_name="f")
        for _ in range(inside):
            stage(trace, 0.001)
            stage(backend, 0.002, asked=True)  # a helper compiled
        if asked:
            perf_observatory._hear_event(perf_observatory._CACHE_ASKED)
            if not inside:
                perf_observatory._hear_event(perf_observatory._CACHE_HIT)
            perf_observatory._hear_duration(
                perf_observatory._CACHE_READ, seconds / 2)
        perf_observatory._hear_duration(event, seconds, fun_name="f")

    stage(trace, 3.0, inside=400)
    stage(lower, 2.0, inside=630)
    stage(backend, 5.0, asked=True)
    assert perf_observatory.take_load_split(since) == {
        "trace_s": 3.0, "lower_s": 2.0, "backend_s": 5.0,
        "cache_read_s": 2.5, "cache": "hit"}
    assert not perf_observatory._heard.marks


def test_a_burst_over_a_kernel_keeps_its_trace():
    model = tiny_model_config("llama")
    model.attention_impl = "pallas-interpret"
    engine = LLMEngine(EngineConfig(
        model=model, cache=CacheConfig(page_size=128, num_pages=32),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64, decode_steps=4,
                                  deferred_kv_writes=True)))
    _run(engine, range(2, 20), max_tokens=9)
    records = engine.runner.observatory.recent_compiles(limit=-1)
    assert {r["kind"] for r in records} == {"step", "decode_burst"}
    for record in records:
        assert record["trace_s"] > 0 and record["lower_s"] > 0
        assert (record["trace_s"] + record["lower_s"]
                + record["backend_s"]) <= record["seconds"] + 1e-3


@pytest.fixture
def empty_compile_cache(tmp_path):
    """A persistent cache of this test's own that keeps every program,
    and the session's back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    jax.config.update(names[0], str(tmp_path))
    jax.config.update(names[1], 0)
    jax.config.update(names[2], -1)
    compilation_cache.reset_cache()
    yield tmp_path
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_a_second_build_of_a_program_reads_hit_where_the_first_read_miss(
        empty_compile_cache):
    def loads():
        engine = _engine()
        _run(engine, range(2, 12))
        return {tuple(r["key"]): r for r in
                engine.runner.observatory.recent_compiles(limit=-1)}

    first = loads()
    if not any(empty_compile_cache.iterdir()):
        pytest.skip("this backend writes no executable to the "
                    "persistent cache")
    second = loads()
    assert set(first) == set(second) and first
    assert {r["cache"] for r in first.values()} == {"miss"}
    assert {r["cache"] for r in second.values()} == {"hit"}
    for record in second.values():
        assert 0 < record["cache_read_s"] <= record["backend_s"] + 1e-3
    assert all(r["cache_read_s"] == 0 for r in first.values())


def test_the_program_compiled_on_a_thread_has_a_record_of_its_own(
        monkeypatch):
    import threading

    engine = _engine(prefill_batch_size=8)
    obs = engine.runner.observatory
    threads = []
    loaded = obs.load_ahead

    def load_ahead(kind, key, seconds, split):
        threads.append((threading.current_thread().name, tuple(key)))
        loaded(kind, key, seconds, split)

    monkeypatch.setattr(obs, "load_ahead", load_ahead)
    # Rows over half a chunk: a step at one width of the top bucket,
    # and the other width's program beside it.
    for i in range(5):
        engine.add_request(list(range(2 + i, CHUNK + i)), SamplingParams(
            temperature=0.0, max_tokens=2, ignore_eos=True))
    while engine.has_work():
        engine.step()
    # Whichever width the first step ran at, the other came up on
    # the thread.
    assert len(threads) == 1
    assert threads[0][0] == "prefill-width-compile"
    assert threads[0][1] in ((4, CHUNK), (8, CHUNK))
    by_key = {tuple(r["key"]): r for r in obs.recent_compiles(limit=-1)
              if r["kind"] == "step"}
    half, full = by_key[(4, CHUNK)], by_key[(8, CHUNK)]
    # Its lowering (the loop thread's) and its compile (the other
    # thread's) are its own record's, not the step's beside it.
    for record in (half, full):
        assert record["trace_s"] > 0 and record["lower_s"] > 0
        assert record["backend_s"] > 0
        assert (record["trace_s"] + record["lower_s"]
                + record["backend_s"]) <= record["seconds"] + 1e-3
    assert not obs._ahead
    assert obs.compile_events_total("step") == len(by_key)


def test_the_listener_is_registered_once_however_many_engines():
    from jax._src import monitoring

    _engine()
    _engine()
    assert monitoring.get_event_duration_listeners().count(
        perf_observatory._hear_duration) == 1
    assert monitoring.get_event_listeners().count(
        perf_observatory._hear_event) == 1
    assert monitoring.get_scalar_listeners().count(
        perf_observatory._hear_stage_start) == 1


def test_a_stage_of_a_load_is_a_profiler_event_on_its_thread(
        monkeypatch):
    perf_observatory.listen_for_loads()
    events = []

    class Mark:
        def __init__(self, name, **fields):
            self.name = name
            events.append(("made", name, fields))

        def __enter__(self):
            events.append(("in", self.name))

        def __exit__(self, *exc):
            events.append(("out", self.name))

    monkeypatch.setattr(perf_observatory, "_annotate", Mark)
    jax.jit(lambda x: x - 5).lower(
        jax.ShapeDtypeStruct((11, 2), "float32")).compile()
    # The subtraction is a jitted function of its own, traced inside
    # the lambda's trace: stages nest, and close innermost first.
    entered = [e[1] for e in events if e[0] == "in"]
    assert entered[0] == "engine.load.trace"
    assert entered[-2:] == ["engine.load.lower", "engine.load.backend"]
    assert set(entered[:-2]) == {"engine.load.trace"}
    stack = []
    for event in events:
        if event[0] == "in":
            stack.append(event[1])
        elif event[0] == "out":
            assert stack.pop() == event[1]
    assert not stack
    assert all(e[2]["fun"] for e in events if e[0] == "made")
    assert not perf_observatory._heard.marks


@pytest.mark.parametrize("shape, kwargs, key", [
    ((8, 256), {}, (8, 256)),                    # a prefill step
    ((64,), {}, (64, 1)),                        # a single decode step
    ((64, 1), {"num_steps": 32}, (64, 32)),      # a burst
    ((64, 1), {"num_steps": 16, "draft_rows": None}, (64, 16)),
    ((72, 4), {"want_logprobs": False}, (72, 4)),  # verify, unified
])
def test_the_key_names_the_program(shape, kwargs, key):
    tokens = jax.ShapeDtypeStruct(shape, "int32")
    assert program_key((None, None, None, tokens), kwargs) == key
    assert program_key((None, None), kwargs) is None


def test_a_burst_is_recorded_under_its_rows_and_steps():
    engine = _engine(decode_steps=4)
    _run(engine, range(2, 12), max_tokens=9)
    bursts = [r["key"] for r in
              engine.runner.observatory.recent_compiles(limit=-1)
              if r["kind"] == "decode_burst"]
    assert bursts and all(key[0] == 4 and key[1] > 1 for key in bursts)


# ---- /debug/compiles, /metrics and the fake engine ---------------------------


def test_metrics_has_the_start_the_parts_and_the_caches_answers():
    engine = _engine()
    _run(engine, range(2, 12))
    server = EngineServer(engine, "tiny-llama")
    text, compiles = asyncio.run(
        _get(server, "/metrics", "/debug/compiles"))

    def value(line_start):
        line = next(line for line in text.splitlines()
                    if line.startswith(line_start))
        return float(line.rsplit(" ", 1)[1])

    by_span = engine.runner.startup.seconds_by_span()
    assert by_span["boot"] > 0  # on_startup closed it
    for span, seconds in by_span.items():
        assert value('vllm:engine_startup_seconds{span="%s"}' % span) \
            == pytest.approx(seconds)
    parts = compiles["parts"]
    for part in LOAD_PARTS:
        assert value('vllm:engine_compile_part_seconds_total{part="%s"}'
                     % part[:-2]) == pytest.approx(
            sum(kind[part] for kind in parts.values()), abs=1e-4)
    for result in ("hit", "miss"):
        assert value('vllm:engine_compile_cache_total{result="%s"}'
                     % result) == compiles["cache"][result]
    assert sum(compiles["cache"].values()) > 0
    # The totals by kind stay as they were, for the router's scrape.
    assert value('vllm:engine_compile_seconds_total{kind="step"}') \
        == pytest.approx(compiles["seconds"]["step"], abs=1e-4)


def test_the_fake_engine_answers_in_the_real_ones_shape():
    from production_stack_tpu.testing.fake_engine import (
        build_fake_engine,
    )

    async def fake():
        client = TestClient(TestServer(build_fake_engine()))
        await client.start_server()
        try:
            return [await (await client.get(path)).json()
                    for path in ("/version", "/debug/compiles")]
        finally:
            await client.close()

    engine = _engine()
    _run(engine, range(2, 12))
    real_version, real_compiles = asyncio.run(_get(
        EngineServer(engine, "tiny-llama"), "/version",
        "/debug/compiles"))
    fake_version, fake_compiles = asyncio.run(fake())
    assert set(fake_version["startup"]) == set(real_version["startup"])
    assert all(s["seconds"] is not None
               for s in fake_version["startup"]["spans"])
    assert {s["name"] for s in fake_version["startup"]["spans"]} == {
        "boot", "boot.imports", "boot.engine", "boot.listen"}
    assert set(fake_compiles) == set(real_compiles)
    assert set(fake_compiles["recent"][0]) == set(
        real_compiles["recent"][0])
    assert set(fake_compiles["parts"]["step"]) == set(
        real_compiles["parts"]["step"]) == set(LOAD_PARTS)
    for record in fake_compiles["recent"]:
        assert (record["trace_s"] + record["lower_s"]
                + record["backend_s"]) <= record["seconds"]
