"""The six set-up readers on a run directory made by hand: each one's
sum, that they add up to the server's side of ``setup_s``, and that a
server without a timeline gives them nothing to read."""

import importlib
import json

import pytest

from chipbench import setup_parts
from chipbench.runfiles import RunFiles

READERS = ("setup_boot_s", "setup_probes_s", "setup_lower_s",
           "setup_load_s", "setup_cache_misses", "setup_rest_s")
T0 = 1200.0
START = 1000.0


def reader(name):
    return importlib.import_module(f"chipbench.layer_metrics.{name}")


def span(name, t_start, seconds, **fields):
    parent = {"boot": None, "boot.probe": "boot.probes"}.get(name, "boot")
    return {"name": name, "parent": parent, "t_start": t_start,
            "seconds": seconds, **fields}


def load(ts, trace_s, lower_s, backend_s, cache_read_s, cache, key):
    return {"kind": "step", "key": key, "seconds": trace_s + lower_s
            + backend_s + 0.1, "ts": ts, "trace_s": trace_s,
            "lower_s": lower_s, "backend_s": backend_s,
            "cache_read_s": cache_read_s, "cache": cache}


STARTUP = {
    "process_start_unix": START, "ready_unix": START + 80.0,
    "spans": [
        span("boot", START, 80.0),
        span("boot.imports", START, 6.0),
        span("boot.engine", START + 6.0, 1.0),
        span("boot.claim_devices", START + 7.0, 9.0, platform="tpu"),
        span("boot.probes", START + 16.0, 30.0),
        span("boot.probe", START + 16.5, 20.0, kernel="decode",
             cache="hit"),
        span("boot.probe", START + 37.0, 8.0, kernel="prefill",
             cache="miss"),
        span("boot.weights", START + 46.0, 20.0),
        span("boot.cache", START + 66.0, 2.0),
        span("boot.probes", START + 68.0, 11.0),
        span("boot.probe", START + 68.0, 11.0, kernel="ragged",
             cache="none"),
        span("boot.engine", START + 79.0, 0.5),
        span("boot.listen", START + 79.5, 0.5)]}
LOADS = [load(START + 95.0, 3.0, 2.5, 6.0, 4.0, "hit", [8, 64]),
         load(START + 110.0, 3.5, 2.0, 14.0, 0.0, "miss", [8, 256]),
         load(START + 111.0, 0.0, 0.0, 0.0, 0.0, "none", [4, 256]),
         # Stamped inside the window: set-up's no more.
         load(T0 + 3.0, 9.0, 9.0, 9.0, 0.0, "miss", [64, 32])]


def run_dir(tmp_path, version, recent=LOADS):
    files = {"cell.json": {"t0_unix": T0, "seconds": 20.0,
                           "traffic_params": {"ramp_s": 10.0},
                           "version": version},
             "compiles.json": {"before": {"events": {"step": 3},
                                          "recent": recent}}}
    for name, content in files.items():
        with open(tmp_path / name, "w") as f:
            json.dump(content, f)
    return RunFiles(str(tmp_path))


@pytest.fixture
def run(tmp_path):
    return run_dir(tmp_path, {"platform": "tpu", "startup": STARTUP})


@pytest.mark.parametrize("name, value", [
    ("setup_boot_s", 80.0),
    ("setup_probes_s", 30.0 + 11.0),
    ("setup_lower_s", 3.0 + 2.5 + 3.5 + 2.0),
    ("setup_load_s", 6.0 + 14.0),
    ("setup_cache_misses", 2),  # one program, one probe
    ("setup_rest_s", 200.0 - 80.0 - 11.0 - 20.0 - 10.0 - 0.5)])
def test_each_reader_on_a_run_made_by_hand(run, name, value):
    assert reader(name).read(run) == pytest.approx(value)


def test_the_parts_add_up_to_the_servers_side_of_set_up(run):
    boot, lower, load_s, rest = (reader(n).read(run) for n in (
        "setup_boot_s", "setup_lower_s", "setup_load_s", "setup_rest_s"))
    ramp = run.cell["traffic_params"]["ramp_s"]
    assert boot + lower + load_s + rest + ramp + setup_parts.START_IN_S \
        == pytest.approx(T0 - START)
    # The probes are inside the boot, and the boot's children tile it.
    assert reader("setup_probes_s").read(run) < boot
    assert sum(s["seconds"] for s in STARTUP["spans"]
               if s["parent"] == "boot") == pytest.approx(boot)


def test_a_record_stamped_after_the_windows_start_is_not_counted(
        tmp_path):
    early = run_dir(tmp_path, {"startup": STARTUP}, recent=LOADS[:3])
    assert reader("setup_lower_s").read(early) == pytest.approx(11.0)
    assert reader("setup_load_s").read(early) == pytest.approx(20.0)
    assert reader("setup_cache_misses").read(early) == 2


@pytest.mark.parametrize("name", READERS)
def test_a_server_without_a_timeline_gives_nothing_to_read(tmp_path,
                                                           name):
    # A parent's run: no ``startup`` in /version, no split in a record.
    old = [{"kind": "step", "key": [8, 64], "seconds": 11.0,
            "cache_size": 1, "ts": START + 95.0}]
    assert reader(name).read(
        run_dir(tmp_path, {"platform": "tpu"}, recent=old)) is None


@pytest.mark.parametrize("name", READERS)
def test_each_reader_says_what_it_is(name):
    module = reader(name)
    assert module.MOVES == "setup_s"
    assert module.UNIT == ("count" if name == "setup_cache_misses"
                           else "s")
    assert module.SOURCE == ("program_counter"
                             if name == "setup_cache_misses"
                             else "program_span")
    assert module.LAYER in ("engine HTTP front", "step programs",
                            "engine loop + scheduler")
    assert module.__doc__
