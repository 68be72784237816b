"""``chipbench/tests/test_jamba_family.py``, collected, run and counted
in tier 1 as it is (tests/chipbench_cases.py says why and how).

One of its cases cannot pass since PR 36 and is collected here as it
is, marked for what it is: the manifest case asks for Jamba's
configuration and cell in the LAST place of their lists.
``BENCHMARK.json`` only grows at the end of its lists (an entry put
before another reads to the driver as a change to what was there, and
a PR that changes an entry is refused), so the next cell to be added
had to break it, and a ``model_config`` PR may not edit a file the
benchmark has. The mark is strict: once a ``benchmark`` PR makes the
case ask by name it passes, the mark fails, and the mark and the case
after it go (PERF.md section 7 (29)). What the case asks of the
entries themselves is asked by the case after it, of the places they
were accepted in.
"""

import json
import os

import pytest

from chipbench import run as bench_run
from chipbench.tests.test_jamba_family import *  # noqa: F401,F403
from chipbench_cases import (  # noqa: F401
    one_cpu_device_for_the_servers_these_cases_start,
)

pytest.mark.xfail(
    strict=True, raises=AssertionError,
    reason="asks for Jamba's entries in the last place of lists that "
           "only grow at the end; chipbench/tests/test_jamba_family.py "
           "is a benchmark PR's to edit (PERF.md section 7 (29))")(
    test_the_manifest_names_the_cell_and_its_three_shares)  # noqa: F405


def test_jambas_entries_stand_where_they_were_accepted():
    """Everything the marked case asks, with third place (where PR 34
    put them, and where they stay) for last place."""
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
