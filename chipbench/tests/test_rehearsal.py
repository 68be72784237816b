"""A rehearsal run end to end on the CPU: the real server behind the
real router at a tiny size, the reference, the window, the traced side
and the result line.  What it prints says ``cpu`` and is no device
metric."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import e2e, run as bench_run
from chipbench.runfiles import RunFiles


@pytest.mark.parametrize("cell,trace", [("rehearsal-open", 0),
                                        ("rehearsal-closed", 1)])
def test_rehearsal_run(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(bench_run.BENCH, "run.py"),
         "--workload", cell, "--seed", str(2**31 + 5), "--seconds", "6",
         "--trace", str(trace)],
        cwd=bench_run.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(result) - {"breakdown"} == {
        "correct", "attempted", "failed", "unfinished", "metrics", "device"}
    assert result["correct"] is True
    assert (result["failed"], result["unfinished"]) == (0, 0)
    assert result["attempted"] > 10
    assert result["device"]["platform"] == "cpu"
    # The arrivals the run kept hold every token of every request, in
    # time order, and give the line's tokens per second again.
    files = RunFiles(os.path.join(bench_run.STATE, "runs", cell))
    times = [t for t, _ in files.arrivals]
    assert times == sorted(set(times)) and times[0] < 0 < times[-1]
    assert sum(n for _, n in files.arrivals) == sum(
        r["tokens"] for r in files.records)
    if not trace:
        assert e2e.output_tok_s(files.arrivals, 6.0) == result["metrics"][
            "output_tok_s"]["value"]
    wanted = bench_run.find_cell(cell)[
        "per_layer" if trace else "end_to_end"]
    assert set(result["metrics"]) <= set(wanted)
    if trace:
        assert result["metrics"]["window_compiles"]["value"] == 0
        assert result["device"]["window_s"] > 0
        # Host threads stood in for the device: no idle share from them.
        assert "device_idle" not in result["metrics"]
    else:
        assert set(result["metrics"]) == set(wanted)
        assert all(m["value"] > 0 for m in result["metrics"].values())


def test_a_cell_of_the_benchmark_refuses_the_cpu():
    """No accelerator: non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(bench_run.BENCH, "run.py"),
         "--workload", "rehearsal-open-as-tpu", "--seconds", "2"],
        cwd=bench_run.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode != 0 and done.stdout.strip() == ""
