"""The benchmark's side of the two threads' account: the five readers
of the turn records' ``cpu`` and ``front`` and of the ``server.*``
events (chipbench/layer_metrics/), and the reduction that cuts the
device's idle by whether the event loop's thread was busy
(chipbench/front_phases.py), against run directories made by hand and
against a slice recorded on the v5e."""

import importlib
import json
import pathlib

import pytest

from chipbench import front_phases, host_phases, reduce
from chipbench.runfiles import RunFiles

ROOT = pathlib.Path(__file__).resolve().parent.parent
FIXTURES = ROOT / "chipbench" / "tests"
READERS = ("front_cpu_share", "front_token_us", "loop_offcpu_ms",
           "handoff_p90_ms", "front_idle")


def _reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


def _run_dir(tmp_path, steps, front=None):
    files = {"cell.json": {"t0_unix": 1000.0, "seconds": 20.0,
                           "version": {"platform": "tpu"}},
             "steps.json": steps}
    if front is not None:
        files["front_phases.json"] = front
    for name, content in files.items():
        (tmp_path / name).write_text(json.dumps(content))
    return RunFiles(str(tmp_path))


def _turn(ts, wall_s, kind="decode", emitted=8, handoff_ms=1.0,
          front_cpu_ms=0.0, tokens=0, off=None):
    """A turn that ends at ``ts``; ``off``: ms off the CPU by phase."""
    phases = dict(admit=1.0, plan=1.0, build=2.0, rng=3.0, dispatch=16.0,
                  wait=wall_s * 1e3 - 40.0, parse=1.0, commit=12.0,
                  emit=0.1, other=3.9)
    cpu = {k: v - (off or {}).get(k, 0.0) for k, v in phases.items()}
    cpu["wait"] = 0.5  # blocked on the device: off the CPU, and no wait
    return {"step": int(ts), "ts": ts, "kind": kind, "window": 32,
            "t_start": ts - wall_s, "t_end": ts, "phases": phases,
            "cpu": cpu, "emitted": emitted, "handoff_ms": handoff_ms,
            "front": {"cpu_ms": front_cpu_ms, "tokens": tokens}}


STEPS = [
    _turn(990.0, 0.5, front_cpu_ms=499.0, tokens=1, handoff_ms=900.0,
          off={"dispatch": 15.0}),              # before the window
    _turn(1001.0, 0.7, front_cpu_ms=420.0, tokens=8000, handoff_ms=2.0,
          off={"dispatch": 9.0, "commit": 3.0}),
    _turn(1002.0, 0.1, kind="prefill", front_cpu_ms=10.0, tokens=0,
          handoff_ms=40.0, off={"dispatch": 1.0}),
    _turn(1003.0, 0.7, front_cpu_ms=350.0, tokens=8400, handoff_ms=3.0,
          off={"dispatch": 7.0, "plan": 0.5}),
    _turn(1004.0, 0.5, front_cpu_ms=220.0, tokens=3600, emitted=0,
          handoff_ms=77.0, off={"dispatch": 10.0, "commit": 4.0}),
    _turn(1021.0, 0.7, front_cpu_ms=699.0, tokens=1, handoff_ms=900.0),
]


@pytest.mark.parametrize("name,expected", [
    # Every kind of turn in the window: 1000 ms of CPU over 2000 of wall.
    ("front_cpu_share", 50.0),
    ("front_token_us", 1e6 / 20000),
    # The decode turns' 12.0, 7.5 and 14.0 ms, summed and divided by
    # three (no median of single records); the wait phase's is not in.
    ("loop_offcpu_ms", 33.5 / 3),
    # The turns that emitted: 2, 40 and 3 ms.
    ("handoff_p90_ms", 3.0 + 0.8 * 37.0),
])
def test_record_readers_against_a_run_made_by_hand(tmp_path, name,
                                                   expected):
    reader = _reader(name)
    assert reader.read(_run_dir(tmp_path, STEPS)) == pytest.approx(expected)
    assert (reader.UNIT, reader.MOVES) == (
        {"front_cpu_share": "%", "front_token_us": "us"}.get(name, "ms"),
        "output_tok_s")


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_fields_gives_the_readers_nothing(tmp_path,
                                                               name):
    """The parent commit's records: phases and no ``cpu``, no ``front``
    (and, for one reader, no ``handoff_ms``), and a slice without the
    consumers' events."""
    bare = [{k: v for k, v in s.items()
             if k not in ("cpu", "front", "handoff_ms")} for s in STEPS]
    parent_slice = dict(SUMMARY, front_events={
        "server.stream_token": 11, "server.consume": 0, "server.write": 0})
    run = _run_dir(tmp_path, bare, parent_slice)
    assert _reader(name).read(run) is None


def test_the_cpu_clock_absent_is_no_reading_and_no_zero(tmp_path):
    """A platform without another thread's CPU clock writes ``front``
    without ``cpu_ms``."""
    steps = [dict(s, front={k: v for k, v in s["front"].items()
                            if k != "cpu_ms"}) for s in STEPS]
    run = _run_dir(tmp_path, steps)
    assert _reader("front_cpu_share").read(run) is None
    assert _reader("front_token_us").read(run) is None
    assert _reader("loop_offcpu_ms").read(run) == pytest.approx(33.5 / 3)


SUMMARY = {"span_s": 8.0, "idle_s": 0.7, "engine_events": 5600,
           "stand_in": False, "front_overlaps": 0, "front_busy_s": 4.4,
           "front_events": {"server.stream_token": 11,
                            "server.consume": 2816, "server.write": 2816},
           "front_busy_by_phase_s": {"wait": 4.0, "dispatch": 0.2},
           "idle_contended_s": 0.36, "idle_alone_s": 0.29}


@pytest.mark.parametrize("front,expected", [
    (SUMMARY, 4.5),
    # The CPU's stand-in threads, a slice without the loop thread's
    # events, no slice at all: nothing to read.
    (dict(SUMMARY, stand_in=True), None),
    (dict(SUMMARY, engine_events=0), None),
    ({"span_s": 0.0, "idle_s": 0.0, "engine_events": 0,
      "front_events": SUMMARY["front_events"]}, None),
    (None, None),
])
def test_front_idle_is_the_host_idle_beside_a_busy_event_loop(
        tmp_path, front, expected):
    value = _reader("front_idle").read(_run_dir(tmp_path, [], front))
    assert value == (pytest.approx(expected) if expected else None)


def test_the_five_readers_say_where_they_belong():
    layers = {name: (_reader(name).LAYER, _reader(name).SOURCE)
              for name in READERS}
    assert layers == {
        "front_cpu_share": ("engine HTTP front", "program_counter"),
        "front_token_us": ("engine HTTP front", "program_counter"),
        "loop_offcpu_ms": ("engine loop + scheduler", "program_span"),
        "handoff_p90_ms": ("engine HTTP front", "program_span"),
        "front_idle": ("device", "device_trace")}
    # The layers' names are the accepted benchmark's, letter for letter.
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert {layer for layer, _ in layers.values()} <= {
        m["layer"] for m in manifest["per_layer"]}


# ---- the reduction ---------------------------------------------------------


def test_merged_is_the_union_as_intervals():
    assert front_phases.merged(
        [(50, 60), (0, 10), (5, 20), (20, 30), (58, 59)]) == [
            (0, 30), (50, 60)]
    assert front_phases.merged([]) == []


@pytest.mark.parametrize("busy,expected", [
    ([], [(0, 100, "alone"), (110, 150, "alone")]),
    ([(0, 200)], [(0, 100, "contended"), (110, 150, "contended")]),
    # Across the first phase's end, inside the second, touching its end.
    ([(90, 105), (120, 130), (150, 170)], [
        (0, 90, "alone"), (90, 100, "contended"), (110, 120, "alone"),
        (120, 130, "contended"), (130, 150, "alone")]),
    ([(0, 10), (10, 20)], [(0, 10, "contended"), (10, 20, "contended"),
                           (20, 100, "alone"), (110, 150, "alone")]),
])
def test_split_cuts_the_phases_at_the_busy_edges(busy, expected):
    phases = [(0, 100, "build"), (110, 150, "commit")]
    pieces = front_phases.split(phases, busy)
    assert pieces == expected
    assert sum(e - s for s, e, _ in pieces) == 140


def test_overlaps_counts_an_event_that_begins_inside_another():
    assert front_phases.overlaps([[(0, 10), (10, 20), (30, 40)]]) == 0
    assert front_phases.overlaps([[(0, 10), (9, 20)], [(5, 8)]]) == 1


def test_summarize_against_a_slice_made_by_hand():
    """One device, three operations with two holes; the loop thread in
    build, wait, commit; the event loop busy across part of each."""
    planes = {"/device:TPU:0": {"ops": [("a", 0, 100), ("b", 300, 100),
                                        ("c", 700, 100)], "modules": []}}
    host = {
        "phases": [(0, 250, "build"), (250, 450, "wait"),
                   (450, 800, "commit")],
        "front_lines": [[(150, 280), (500, 520), (520, 600)],
                        [(790, 900)]],
        "front_events": {"server.stream_token": 1, "server.consume": 2,
                         "server.write": 1}}
    summary = front_phases.summarize(planes, host)
    # Holes (100, 300) and (400, 700): 500 ns idle.  Under wait: 50 + 50.
    # Under build (100, 250): busy 150..250.  Under commit (450, 700):
    # busy 500..600.
    assert summary["span_s"] == pytest.approx(800e-9)
    assert summary["idle_s"] == pytest.approx(500e-9)
    assert summary["idle_contended_s"] == pytest.approx(200e-9)
    assert summary["idle_alone_s"] == pytest.approx(200e-9)
    assert summary["front_busy_s"] == pytest.approx((130 + 100 + 10) * 1e-9)
    assert summary["front_busy_by_phase_s"] == pytest.approx(
        {"build": 100e-9, "wait": 30e-9, "commit": 110e-9})
    assert summary["front_overlaps"] == 0 and summary["engine_events"] == 3
    # What host_phases.py calls the idle the host explains, cut in two.
    records = host_phases.summarize(
        planes, {"phases": [p + (None,) for p in host["phases"]],
                 "turns": [], "stream": [], "start_unix_ns": 0}, [])
    assert host_phases.host_idle_s(records) == pytest.approx(
        summary["idle_contended_s"] + summary["idle_alone_s"])


def test_the_readers_names_are_the_programs():
    """What front_phases.py looks for is what the server writes."""
    package = "".join(
        (ROOT / "production_stack_tpu" / "engine" / f).read_text()
        for f in ("tracing.py", "server.py"))
    assert front_phases.FRONT_EVENTS == (
        "server.stream_token", "server.consume", "server.write")
    for name in front_phases.FRONT_EVENTS:
        assert f'"{name}"' in package, name
    assert front_phases.STREAM == host_phases.STREAM


def test_contended_and_alone_idle_on_the_recorded_chip_trace():
    """chipbench/tests/small_tpu_front.xplane.pb, recorded on a v5e by
    record_front_trace.py: four turns around one jitted program while a
    second thread drives the tracer's own FrontClock through deliveries,
    wakes and writes.  The expected seconds were counted from the file's
    events by a plain sweep over every edge of the device's 40 ``XLA
    Ops``, the 24 ``engine.*`` events and the 51 ``server.*`` events,
    which shares no code with front_phases.py or host_phases.py."""
    path = str(FIXTURES / "small_tpu_front.xplane.pb")
    planes = reduce.read_planes(path, "tpu")
    host = front_phases.read_host(path)
    assert host["front_events"] == {
        "server.stream_token": 17, "server.consume": 17, "server.write": 17}
    assert len(host["front_lines"]) == 1  # one thread stood in for it
    summary = front_phases.summarize(planes, host)
    assert summary["span_s"] == pytest.approx(0.052823502, abs=1e-9)
    assert summary["idle_s"] == pytest.approx(0.049939348, abs=1e-9)
    assert summary["idle_contended_s"] == pytest.approx(0.017528833,
                                                        abs=1e-9)
    assert summary["idle_alone_s"] == pytest.approx(0.026202294, abs=1e-9)
    assert summary["front_busy_s"] == pytest.approx(0.022145065, abs=1e-9)
    assert summary["front_busy_by_phase_s"] == pytest.approx({
        "build": 0.005849686, "wait": 0.00181018, "emit": 0.01086948,
        "commit": 0.003615719}, abs=1e-9)
    assert summary["front_overlaps"] == 0
    assert summary["engine_events"] == 24
    # Cut on the profiler's clock alone, as host_phases.json is: the two
    # are the idle that the host explains, and nothing of ``wait``'s
    # 0.00575651 s or of the 0.000451711 s that no phase covers.
    with open(FIXTURES / "small_tpu_front.steps.json") as f:
        records = json.load(f)
    loop_side = host_phases.summarize(
        planes, host_phases.read_host(path), records)
    assert (summary["idle_contended_s"] + summary["idle_alone_s"]
            == pytest.approx(host_phases.host_idle_s(loop_side), rel=1e-9))
    assert loop_side["clock_pairs"] == 8
    # The records of that run: the front's side closed into every turn,
    # by the tracer's own FrontClock on the second thread.
    assert [r["front"]["tokens"] for r in records] == [40, 32, 32, 32]
    assert all(set(r["front"]) == {"cpu_ms", "tokens"} for r in records)
    assert all(set(r["cpu"]) == set(r["phases"]) for r in records)
    # The chip host's thread CPU clock ticks in steps of 10 ms (PERF.md,
    # PR 39): a single turn's figure is coarse there, a window's sum is
    # not.
    assert {r["front"]["cpu_ms"] % 10.0 for r in records} == {0.0}
