"""The trace reduction: on events made by hand, and on a small trace
recorded on a TPU v5e (``record_trace.py``; three executions each of
two jitted programs with 10 ms host pauses between them)."""

import os

import pytest

from chipbench import reduce

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "small_tpu.xplane.pb")


def test_union_and_gaps_by_hand():
    spans = [(0, 10), (5, 20), (30, 40), (40, 45), (100, 101)]
    assert reduce.union_s(spans) == pytest.approx(36e-9)
    assert reduce.gaps(spans) == [(20, 30), (45, 100)]
    assert reduce.union_s([]) == 0.0


def test_program_names():
    assert reduce.program_name("jit__decode_burst_impl(123)") == (
        "_decode_burst_impl")
    assert reduce.program_name("jit__step_impl") == "_step_impl"


def test_summary_by_hand():
    ms = 1_000_000
    planes = {"/device:TPU:0": {
        "ops": [("fusion.1", 0, 2 * ms), ("fusion.2", 2 * ms, 1 * ms),
                ("fusion.1", 10 * ms, 2 * ms), ("copy", 11 * ms, 2 * ms)],
        "modules": [("jit_a(1)", 0, 3 * ms), ("jit_b(2)", 10 * ms, 3 * ms)]}}
    out = reduce.summarize(planes)
    assert out["window_s"] == pytest.approx(0.013)
    assert out["busy_s"] == pytest.approx(0.006)
    assert out["programs"] == {
        "a": {"count": 1, "seconds": pytest.approx(0.003),
              "whole_s": pytest.approx(0.003)},
        "b": {"count": 1, "seconds": pytest.approx(0.003),
              "whole_s": pytest.approx(0.003)}}
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.004)]
    assert out["idle_gaps"] == [["before b", pytest.approx(0.007)]]
    assert out["longest_gap_s"] == pytest.approx(0.007)


def test_two_planes_are_averaged():
    ms = 1_000_000
    one = {"ops": [("x", 0, 4 * ms)], "modules": [("jit_p", 0, 4 * ms)]}
    two = {"ops": [("x", 0, 2 * ms)], "modules": [("jit_p", 0, 2 * ms)]}
    out = reduce.summarize({"/device:TPU:0": one, "/device:TPU:1": two})
    assert out["busy_s"] == pytest.approx(0.003)
    assert out["programs"]["p"]["count"] == 1
    assert out["programs"]["p"]["seconds"] == pytest.approx(0.003)


def test_an_execution_cut_by_the_edge_is_left_out():
    # Three whole bursts and the stub of one the slice's end cut.
    assert reduce.whole_execution_s([2.4, 2.5, 2.45, 0.3]) == 2.45
    assert reduce.whole_execution_s([0.19]) == 0.19
    assert reduce.whole_execution_s([2.4, 2.6]) == pytest.approx(2.5)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in the directory")
def test_recorded_tpu_trace():
    planes = reduce.read_planes(RECORDED, "tpu")
    assert list(planes) == ["/device:TPU:0"]
    out = reduce.summarize(planes)
    programs = out["programs"]
    assert programs["_small_matmul"]["count"] == 3
    assert programs["_small_sum"]["count"] == 3
    # Six executions with 10 ms pauses: the window is tens of
    # milliseconds, nearly all of it idle, and the op intervals lie
    # inside the program intervals.
    assert 0.02 < out["window_s"] < 1.0
    total = sum(p["seconds"] for p in programs.values())
    assert 0 < out["busy_s"] <= total * 1.01
    assert out["busy_s"] < 0.2 * out["window_s"]
    labels = {label for label, _ in out["idle_gaps"]}
    assert {"before _small_matmul", "before _small_sum"} <= labels
    # A hand count straight from the events agrees with the reduction.
    ops = planes["/device:TPU:0"]["ops"]
    covered = reduce.union_s([(s, s + d) for _, s, d in ops])
    assert out["busy_s"] == pytest.approx(covered)


def test_a_tpu_run_without_a_device_plane_is_refused(tmp_path):
    """A trace recorded here, on the CPU, has host threads only: read
    as a TPU's it is an error, never a device metric; the rehearsal
    reads it as what it is."""
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64))
                                         ).block_until_ready()
    out = tmp_path / "summary.json"
    assert reduce.main([str(tmp_path), str(out), "tpu"]) == 1
    assert not out.exists()
    assert reduce.main([str(tmp_path), str(out), "gpu"]) == 1
    assert reduce.main([str(tmp_path), str(out), "cpu"]) == 0
    import json
    summary = json.loads(out.read_text())
    assert summary["stand_in"] is True
    assert summary["planes"] in ([], ["/host:CPU"])


def test_the_recorded_tpu_trace_is_no_stand_in(tmp_path):
    import json
    import shutil
    where = tmp_path / "plugins" / "profile" / "x"
    where.mkdir(parents=True)
    shutil.copy(RECORDED, where / "small.xplane.pb")
    out = tmp_path / "summary.json"
    assert reduce.main([str(tmp_path), str(out), "tpu"]) == 0
    summary = json.loads(out.read_text())
    assert summary["stand_in"] is False
    assert summary["planes"] == ["/device:TPU:0"]
