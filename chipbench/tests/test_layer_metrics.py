"""Each per-layer reader's arithmetic on a run directory made by hand,
and that a reader with nothing to read returns nothing."""

import importlib
import json
import os

import pytest

from chipbench import roofline
from chipbench.runfiles import RunFiles

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")
READERS = sorted(f[:-3] for f in os.listdir(os.path.join(
    os.path.dirname(CONFIGS), "layer_metrics"))
    if f.endswith(".py") and f != "__init__.py")


def reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


def record(i, **over):
    r = {"id": f"r{i}", "phase": "window", "due": float(i),
         "sent": i + 0.002 * i, "first": i + 0.5, "last": i + 2.5,
         "tokens": 21, "max_tokens": 21, "usage_tokens": 21, "done": True,
         "error": None, "prompt_tokens": 100}
    r.update(over)
    return r


@pytest.fixture
def run(tmp_path):
    with open(os.path.join(CONFIGS, "qwen2.5-3b.json")) as f:
        config = json.load(f)
    t0 = 1000.0
    files = {
        "cell.json": {"t0_unix": t0, "seconds": 20.0,
                      "slice_unix": [t0 + 8.0, t0 + 11.0],
                      "config_as_run": config,
                      "version": {"device_kind": "TPU v5 lite"}},
        "records.json": [record(i) for i in range(10)]
        + [record(99, phase="ramp")],
        "steps.json": [
            {"step": 1, "ts": t0 - 1, "host_ms": 50, "device_wait_ms": 50,
             "kind": "decode", "decode_rows": 1, "window": 32},
            {"step": 2, "ts": t0 + 8.5, "host_ms": 10, "device_wait_ms": 630,
             "kind": "decode", "decode_rows": 30, "window": 32},
            {"step": 3, "ts": t0 + 9.5, "host_ms": 30, "device_wait_ms": 130,
             "kind": "prefill", "prefill_rows": 2},
            {"step": 4, "ts": t0 + 12, "host_ms": 0, "device_wait_ms": 0}],
        "compiles.json": {"before": {"events": {"step": 7, "decode_burst": 2}},
                          "after": {"events": {"step": 8, "decode_burst": 2}}},
        "memory.json": {"devices": [{"peak_bytes_in_use": 14.3e9},
                                    {"peak_bytes_in_use": 1e9}]},
        "cache_usage.json": [0.1, 0.42, 0.3],
        "trace_summary.json": {
            "window_s": 3.0, "busy_s": 2.4,
            "programs": {"_decode_burst_impl": {"count": 5, "seconds": 2.2,
                                                "whole_s": 0.5},
                         "_step_impl": {"count": 1, "seconds": 0.3,
                                        "whole_s": 0.3}}},
    }
    for name, content in files.items():
        with open(tmp_path / name, "w") as f:
            json.dump(content, f)
    spans = [{"span": "engine_request", "request_id": f"r{i}",
              "queue_ms": 10.0 * i, "ttft_ms": 450.0,
              "events": [{"event": "prefill_chunk", "ts": t0 + 9.4,
                          "start": 0, "tokens": 100, "last": True}]
              if i < 2 else []} for i in range(10)]
    with open(tmp_path / "spans.jsonl", "w") as f:
        f.write("\n".join(map(json.dumps, spans)) + "\n")
    return RunFiles(str(tmp_path))


def test_step_record_readers_keep_to_the_window(run):
    # Steps 2, 3 and 4 are inside; step 1 is before the window.
    assert reader("host_share").read(run) == pytest.approx(100 * 40 / 800)
    assert reader("rows_per_step").read(run) == pytest.approx(16.0)
    assert reader("decode_step_ms").read(run) == pytest.approx(20.0)


def test_counter_readers(run):
    assert reader("window_compiles").read(run) == 1
    assert reader("kv_pages_peak").read(run) == pytest.approx(42.0)
    assert reader("hbm_peak").read(run) == pytest.approx(14.3)
    assert reader("device_idle").read(run) == pytest.approx(20.0)


def test_host_threads_standing_in_give_no_idle_share(run):
    run.trace["stand_in"] = True
    assert reader("device_idle").read(run) is None


def test_roofline_readers(run):
    cfg = run.cell["config_as_run"]
    # A whole burst of 32 token-steps takes 0.5 s of device time:
    # 15.625 ms a step.  Live during the slice (8..11 s): requests 6..10 decode
    # from i+0.5 to i+2.5, each 100 prompt tokens plus its share of 21.
    live = reader("decode_roofline").live_context_tokens
    assert live(run.records, 9.0) == pytest.approx(
        (100 + 21 * 0.25) + (100 + 21 * 0.75))
    mean_live = sum(live(run.records, 8 + 3 * (i + 0.5) / 8)
                    for i in range(8)) / 8
    least = roofline.decode_step_bytes(cfg, mean_live) / 819e9
    assert reader("decode_roofline").read(run) == pytest.approx(
        100 * least / 0.015625)
    # Two chunks of 100 tokens in one prefill step record, one
    # execution in the trace, 0.3 s of device time.
    flops = roofline.prefill_flops(cfg, [(0, 100, True)] * 2)
    assert reader("prefill_roofline").read(run) == pytest.approx(
        100 * flops / 197e12 / 0.3)


@pytest.mark.parametrize("name", READERS)
def test_nothing_to_read_returns_nothing(name, tmp_path):
    with open(tmp_path / "cell.json", "w") as f:
        json.dump({"t0_unix": 0.0, "seconds": 1.0, "slice_unix": None}, f)
    assert reader(name).read(RunFiles(str(tmp_path))) is None
