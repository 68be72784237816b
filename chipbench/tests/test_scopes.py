"""Device time by name: ``reduce.summarize``'s ``scopes`` and
``sources`` on events made by hand and on a small trace recorded on a
TPU v5e (``record_scopes.py``: three executions of one program with two
named scopes, a loop whose body has a third, and a Pallas kernel with a
``name``), the reader of the plane's metadata, and a named kernel's
share of its roofline."""

import json
import os
import statistics
import subprocess
import sys

import pytest

from chipbench import reduce, roofline, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "small_tpu_scopes.xplane.pb")
with open(os.path.join(HERE, "small_tpu_scopes.json")) as f:
    FACTS = json.load(f)
STACK = "jit(scoped_program)"
KERNEL = f"{STACK}/scope_kernel/named_scale_kernel/pallas_call"
LOOP_BODY = f"{STACK}/while/body/closed_call/scope_loop_body/dot_general"


def test_the_two_maps_by_hand():
    ms = 1_000_000
    a, b = "jit(a)/attn/dot_general", "jit(a)/attn/kern/pallas_call"
    planes = {"/device:TPU:0": {
        "ops": [("fusion.1", 0, 2 * ms), ("kern.1", 2 * ms, 3 * ms),
                ("while.3", 5 * ms, 4 * ms), ("fusion.1", 5 * ms, 2 * ms),
                ("copy.2", 7 * ms, 2 * ms), ("fusion.9", 20 * ms, 1 * ms)],
        "op_meta": [(a, "pkg/ops/attention.py", "a"),
                    (b, "pkg/ops/attention.py", "a"),
                    ("jit(a)/while", "pkg/models/x.py", "a"),
                    (a, "pkg/ops/attention.py", "a"),
                    (reduce.NO_NAME, reduce.NO_SOURCE, "a"),
                    ("jit(b)/add", "pkg/ops/sampling.py", "b")],
        "modules": [("jit_a(1)", 0, 9 * ms), ("jit_b(2)", 20 * ms, 1 * ms)]}}
    out = reduce.summarize(planes)
    # The loop's own event spans its body's and is counted in neither.
    assert out["scopes"] == {
        reduce.NO_NAME: {"seconds": pytest.approx(0.002), "count": 1},
        a: {"seconds": pytest.approx(0.004), "count": 2},
        b: {"seconds": pytest.approx(0.003), "count": 1},
        "jit(b)/add": {"seconds": pytest.approx(0.001), "count": 1}}
    assert out["sources"] == {
        "a": {reduce.NO_SOURCE: pytest.approx(0.002),
              "pkg/ops/attention.py": pytest.approx(0.007)},
        "b": {"pkg/ops/sampling.py": pytest.approx(0.001)}}
    assert sum(s["seconds"] for s in out["scopes"].values()) == (
        pytest.approx(out["busy_s"]))
    # What was there before has not moved.
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    assert out["busy_s"] == pytest.approx(0.010)
    # A reader sums by a component of the stack, or a run of them.
    assert roofline.scope_time(out, "attn") == (pytest.approx(0.007), 3)
    assert roofline.scope_time(out, "kern") == (pytest.approx(0.003), 1)
    assert roofline.scope_time(out, "attn/kern") == (
        pytest.approx(0.003), 1)
    assert roofline.scope_time(out, "jit(b)") == (pytest.approx(0.001), 1)
    assert roofline.scope_time(out, "att") == (0, 0)


def test_the_result_lines_breakdown_names_programs_and_files():
    from chipbench import run as bench_run
    summary = {"device_ops": [["fusion.1", 3.0]],
               "sources": {"a": {"pkg/x.py": 1.0, reduce.NO_SOURCE: 0.5},
                           "b": {"pkg/y.py": 2.0}}}
    assert bench_run.device_time_by_source(summary) == [
        ["b: pkg/y.py", 2.0], ["a: pkg/x.py", 1.0],
        [f"a: {reduce.NO_SOURCE}", 0.5]]
    # A stand-in trace names no source: nothing, and ``device_ops`` stays
    # the operations in every run.
    assert bench_run.device_time_by_source({"sources": {}}) == []
    assert bench_run.device_time_by_source({"device_ops": [["x", 1.0]]}) == []


def test_planes_made_by_hand_without_metadata_still_reduce():
    ms = 1_000_000
    one = {"ops": [("x", 0, 4 * ms)], "modules": [("jit_p", 0, 4 * ms)]}
    out = reduce.summarize({"/device:TPU:0": one, "/device:TPU:1": one})
    assert out["busy_s"] == pytest.approx(0.004)
    assert out["scopes"] == {} and out["sources"] == {}


def test_source_files_are_relative_to_the_checkout():
    assert reduce.source_file("/c/o/pkg/ops/x.py:57", "/c/o") == (
        "pkg/ops/x.py")
    assert reduce.source_file("/c/other/pkg/x.py:5", "/c/o") == (
        "/c/other/pkg/x.py")
    assert reduce.source_file("") == reduce.NO_SOURCE
    assert reduce.program_id("jit__step_impl(1234)") == 1234
    assert reduce.program_id("jit__step_impl") is None


@pytest.fixture(scope="module")
def recorded():
    planes = reduce.read_planes(RECORDED, "tpu")
    assert list(planes) == ["/device:TPU:0"]
    return reduce.summarize(planes)


def test_recorded_scopes(recorded):
    assert FACTS["device_kind"] == "TPU v5 lite"
    assert os.path.getsize(RECORDED) == FACTS["trace_bytes"] < 1_000_000
    runs, trips = FACTS["runs"], FACTS["loop_trips"]
    scopes = recorded["scopes"]
    assert scopes[KERNEL]["count"] == runs
    assert scopes[f"{STACK}/scope_matmul/dot_general"]["count"] == runs
    assert scopes[LOOP_BODY]["count"] == runs * trips
    # One trip of the loop multiplies what the first product did.
    assert scopes[LOOP_BODY]["seconds"] / trips == pytest.approx(
        scopes[f"{STACK}/scope_matmul/dot_general"]["seconds"], rel=0.2)
    for scope, count in (("scope_kernel", runs), ("named_scale_kernel", runs),
                         ("scope_matmul", runs),
                         ("scope_loop_body", runs * trips)):
        seconds, events = roofline.scope_time(recorded, scope)
        assert events == count and seconds > 0
    assert roofline.scope_time(recorded, "scope_kernel") == (
        roofline.scope_time(recorded, "named_scale_kernel"))
    assert roofline.scope_time(recorded, "no_such_scope") == (0, 0)
    # The loop is counted by its body: every name stack's seconds, the
    # nameless ones' too, add up to the plane's busy time, less what
    # the loop's own event spends between its trips (0.5 us of 40 ms).
    total = sum(s["seconds"] for s in scopes.values())
    assert total <= recorded["busy_s"]
    assert total == pytest.approx(recorded["busy_s"], rel=1e-4)
    nameless = scopes[reduce.NO_NAME]["seconds"]
    assert nameless < 1e-6
    named = sum(s["seconds"] for k, s in scopes.items()
                if k != reduce.NO_NAME)
    assert named == pytest.approx(recorded["busy_s"] - nameless, rel=1e-4)
    assert f"{STACK}/while" in scopes  # the loop's copies, not its event
    assert scopes[f"{STACK}/while"]["count"] == runs * trips


def test_recorded_sources(recorded):
    assert list(recorded["sources"]) == ["scoped_program"]
    by_file = recorded["sources"]["scoped_program"]
    # Recorded from /root/repo; read wherever the checkout is.
    here = [k for k in by_file if k.endswith(
        "chipbench/tests/record_scopes.py")]
    assert len(here) == 1 and set(by_file) == {here[0], reduce.NO_SOURCE}
    assert sum(by_file.values()) == pytest.approx(recorded["busy_s"],
                                                  rel=1e-4)
    assert by_file[here[0]] > 0.99 * recorded["busy_s"]


def test_the_named_kernel_in_the_trace_is_the_kernel_timed_alone(recorded):
    """The kernel's device seconds by its name against the same kernel
    jitted alone and timed on the host clock by ``block_until_ready``
    on the same chip: within 10%, the host's reading the longer by
    about one dispatch."""
    seconds, events = roofline.scope_time(recorded, "named_scale_kernel")
    in_trace = seconds / events
    alone = statistics.median(FACTS["kernel_alone_s"])
    dispatch = statistics.median(FACTS["dispatch_alone_s"])
    assert in_trace > 0.005
    assert 0 < alone - in_trace < 0.10 * alone
    assert abs(alone - dispatch - in_trace) < 0.05 * alone


def test_a_named_kernels_share_of_its_roofline(recorded):
    seconds, events = roofline.scope_time(recorded, "named_scale_kernel")
    elements = FACTS["kernel_shape"][0] * FACTS["kernel_shape"][1]
    # x * 2 + 1 over float32: two operations, four bytes in, four out.
    share, bound = roofline.kernel_roofline(
        seconds / events, 2 * elements, 8 * elements, FACTS["device_kind"])
    assert bound == "memory"
    assert 60 < share < 100
    assert share == pytest.approx(
        100 * (8 * elements / 819e9) / (seconds / events))
    # 100 operations a byte would make it compute-bound.
    assert roofline.kernel_roofline(1.0, 1e14, 1e9, "TPU v5e") == (
        pytest.approx(100 * 1e14 / 197e12), "compute")
    # Over 100% is an error, never a value.
    with pytest.raises(ValueError, match="memory roofline"):
        roofline.kernel_roofline(seconds / events / 2, 2 * elements,
                                 8 * elements, FACTS["device_kind"])
    with pytest.raises(ValueError):
        roofline.kernel_roofline(0.0, 1, 1, "TPU v5e")
    with pytest.raises(KeyError):
        roofline.kernel_roofline(1.0, 1, 1, "TPU v9")


def test_the_metadata_reader_against_profile_data():
    """Every event of both recorded traces, by the protobuf and by
    ``jax.profiler.ProfileData``: the same names and the same whole
    nanoseconds, so that what ``reduce.py`` reported before it read the
    protobuf it reports still."""
    from jax.profiler import ProfileData
    for name in ("small_tpu.xplane.pb", "small_tpu_scopes.xplane.pb",
                 "small_tpu_host.xplane.pb"):
        path = os.path.join(HERE, name)
        compared = 0
        for old, new in zip(ProfileData.from_file(path).planes,
                            xplane.read_space(path).planes):
            assert old.name == new.name
            metadata = xplane.event_metadata(new)
            for old_line, new_line in zip(old.lines, new.lines):
                assert old_line.name == new_line.name
                events = list(xplane.line_events(new_line))
                assert len(events) == len(list(old_line.events))
                for e, (i, start, duration) in zip(old_line.events, events):
                    assert (e.name, int(e.start_ns), int(e.duration_ns)) == (
                        metadata[i]["name"], start, duration)
                    compared += 1
        assert compared > 100


def test_what_the_metadata_says_of_an_operation():
    space = xplane.read_space(RECORDED)
    plane = next(p for p in space.planes if p.name == "/device:TPU:0")
    kernel = [m for m in xplane.event_metadata(plane).values()
              if m.get("tf_op") == KERNEL + ":"]
    assert len(kernel) == 1
    assert kernel[0]["name"].startswith("%named_scale_kernel")
    assert kernel[0]["source"].endswith("record_scopes.py:51")
    assert kernel[0]["hlo_category"] == "custom-call"
    assert isinstance(kernel[0]["program_id"], int)


def test_the_reader_imports_the_protobuf_runtime_and_nothing_heavier():
    """What the chip machine and this sandbox can both import: the
    reduction's child must not need tensorflow or a profiler plugin."""
    code = ("import sys; sys.path.insert(0, %r); "
            "from chipbench import xplane; xplane.read_space(%r); "
            "heavy = [m for m in ('tensorflow', 'xprof', 'jax', "
            "'tensorboard_plugin_profile', 'numpy') if m in sys.modules]; "
            "print(heavy)" % (os.path.dirname(os.path.dirname(HERE)),
                              RECORDED))
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == "[]"
