"""The benchmark's own cases about families and named scopes, run from
tier 1 so that they count and guard: a change to the program that
breaks a family's reference, its counts or the device time found by
name fails here and not first on the chip.

The cases live with the benchmark (``chipbench/tests/``, run there by
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``); this module
takes the test functions and fixtures of eight of its files as they
are, so each is collected, run and counted here under its own name.
No two of the files give a test or a fixture the same name.

One of those cases cannot pass since PR 36 and is collected here as
it is, marked for what it is: ``test_jamba_family``'s manifest case
asks for Jamba's configuration and cell in the LAST place of their
lists. ``BENCHMARK.json`` only grows at the end of its lists (an entry
put before another reads to the driver as a change to what was there,
and a PR that changes an entry is refused), so the next cell to be
added had to break it, and a ``model_config`` PR may not edit a file
the benchmark has. The mark is strict: once a ``benchmark`` PR makes
the case ask by name it passes, the mark fails, and the mark and the
case after it go (PERF.md section 7 (29)). What the case asks of the
entries themselves is asked by the case after it, of the places they
were accepted in.
"""

import os

import pytest

from chipbench.tests.test_family import *  # noqa: F401,F403
from chipbench.tests.test_glm4_moe_lite_family import *  # noqa: F401,F403
from chipbench.tests.test_jamba_family import *  # noqa: F401,F403
from chipbench.tests.test_lfm2_family import *  # noqa: F401,F403
from chipbench.tests.test_longcat_family import *  # noqa: F401,F403
from chipbench.tests.test_mixtral_family import *  # noqa: F401,F403
from chipbench.tests.test_qwen3_next_family import *  # noqa: F401,F403
from chipbench.tests.test_scopes import *  # noqa: F401,F403


@pytest.fixture(autouse=True)
def one_cpu_device_for_the_servers_these_cases_start(monkeypatch):
    """tests/conftest.py gives this process eight virtual CPU devices
    through ``XLA_FLAGS``; a rehearsal cell's server, started by a case
    as a child, must hold the one device its cell asks for."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    monkeypatch.setenv("XLA_FLAGS", " ".join(flags))


pytest.mark.xfail(
    strict=True, raises=AssertionError,
    reason="asks for Jamba's entries in the last place of lists that "
           "only grow at the end; chipbench/tests/test_jamba_family.py "
           "is a benchmark PR's to edit (PERF.md section 7 (29))")(
    test_the_manifest_names_the_cell_and_its_three_shares)  # noqa: F405


def test_jambas_entries_stand_where_they_were_accepted():
    """Everything the marked case asks, with third place (where PR 34
    put them, and where they stay) for last place."""
    import json

    from chipbench import run as bench_run
    cell = "jamba2-3b.decode-closed"
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["configs"][2]["name"] == "jamba2-3b"
    assert manifest["configs"][2]["reduced"] == []
    assert manifest["workloads"][2] == {
        "name": cell, "config": "jamba2-3b", "traffic": "decode-closed",
        "chips": 1, "why": bench_run.find_cell(cell)["why"]}
    mine = [m for m in manifest["per_layer"] if m["workloads"] == [cell]]
    assert [m["name"] for m in mine] == [
        "ssm_decode_roofline", "ssm_prefill_roofline", "ssm_step_roofline"]
    listed = {m["name"] for m in manifest["per_layer"]
              if cell in m["workloads"]}
    assert listed == set(bench_run.find_cell(cell)["per_layer"])


# The same kind of case, one PR on (PR 43): LongCat's manifest case asks
# that its three shares list its cell ALONE. A later cell whose family
# gives the same counts appends its name to those shares' lists (the
# driver's rule for a share a new cell reports), which the case reads
# as the shares having gone. Marked, strictly, and what it asks of the
# entries themselves is asked below with "lists its cell first".
pytest.mark.xfail(
    strict=True, raises=AssertionError,
    reason="asks that LongCat's three shares list its cell alone; a "
           "later latent-attention cell reports them too; "
           "chipbench/tests/test_longcat_family.py is a benchmark PR's "
           "to edit (PERF.md section 7 (29))")(
    test_the_manifest_names_the_longcat_cell_and_its_three_shares)  # noqa: F405


def test_longcats_entries_stand_as_they_were_accepted():
    """Everything the marked case asks, with "lists its cell first,
    and after it only cells added later" for "lists its cell alone"."""
    import json

    from chipbench import run as bench_run
    config, cell = ("longcat-flash-omni-ep32",
                    "longcat-flash-omni-ep32.decode-closed")
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [c for c in manifest["configs"] if c["name"] == config]
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["file"] == f"chipbench/configs/{config}.json"
    assert manifest["workloads"][4] == {
        "name": cell, "config": config, "traffic": "decode-closed",
        "chips": 1, "why": bench_run.find_cell(cell)["why"]}
    later = [w["name"] for w in manifest["workloads"][5:]]
    mine = [m for m in manifest["per_layer"]
            if m["workloads"][0] == cell]
    assert [m["name"] for m in mine] == [
        "mla_decode_roofline", "mla_prefill_roofline",
        "routed_experts_roofline"]
    assert all(set(m["workloads"][1:]) <= set(later) for m in mine)
    listed = {m["name"] for m in manifest["per_layer"]
              if cell in m["workloads"]}
    assert listed == set(bench_run.find_cell(cell)["per_layer"])
    assert len(listed) == 16


def test_no_two_files_share_a_name():
    import importlib
    seen = {}
    for name in ("test_family", "test_glm4_moe_lite_family",
                 "test_jamba_family", "test_lfm2_family",
                 "test_longcat_family", "test_mixtral_family",
                 "test_qwen3_next_family", "test_scopes"):
        module = importlib.import_module(f"chipbench.tests.{name}")
        for attr, value in vars(module).items():
            ours = getattr(value, "__module__", None) == module.__name__
            fixture = "fixture" in type(value).__name__.lower()
            if ours and (attr.startswith("test_") or fixture):
                assert attr not in seen, (attr, name, seen[attr])
                seen[attr] = name
    assert len(seen) > 30
