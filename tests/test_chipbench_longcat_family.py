"""``chipbench/tests/test_longcat_family.py``, collected, run and
counted in tier 1 as it is (tests/chipbench_cases.py says why and how).

One of its cases cannot pass since PR 43 and is collected here as it
is, marked for what it is (the same kind of case as the one
tests/test_chipbench_jamba_family.py marks, one PR on): LongCat's
manifest case asks that its three shares list its cell ALONE. A later
cell whose family gives the same counts appends its name to those
shares' lists (the driver's rule for a share a new cell reports),
which the case reads as the shares having gone, and a ``model_config``
PR may not edit a file the benchmark has. The mark is strict: once a
``benchmark`` PR makes the case ask "first" for "alone" it passes, the
mark fails, and the mark and the case after it go (PERF.md section 7
(29)). What the case asks of the entries themselves is asked by the
case after it, with "lists its cell first".
"""

import json
import os

import pytest

from chipbench import run as bench_run
from chipbench.tests.test_longcat_family import *  # noqa: F401,F403
from chipbench_cases import (  # noqa: F401
    one_cpu_device_for_the_servers_these_cases_start,
)

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
