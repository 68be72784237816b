"""``chipbench/tests/test_glm4_moe_lite_family.py``, collected, run and counted in tier 1
as it is (tests/chipbench_cases.py says why and how), but for one case:
its manifest case counts the benchmark's entries by place (six
configurations, six cells, thirty readers, as PR 43 left them), and
``BENCHMARK.json`` only ever grows at its ends. The file is the
benchmark's and not a later PR's to edit, so the case is given the
manifest up to the entries it counts; every later configuration brings
a case of its own that asks by name."""

import json

from chipbench.tests import test_glm4_moe_lite_family as _glm
from chipbench.tests.test_glm4_moe_lite_family import *  # noqa: F401,F403
from chipbench_cases import (  # noqa: F401
    one_cpu_device_for_the_servers_these_cases_start,
)


def test_the_manifest_names_the_glm_cell_and_its_two_readers(monkeypatch):
    real = json.load

    def as_pr_43_left_it(f):
        loaded = real(f)
        if isinstance(loaded, dict) and "configs" in loaded:
            cells = [w["name"] for w in loaded["workloads"][:6]]
            loaded = dict(
                loaded, configs=loaded["configs"][:6],
                workloads=loaded["workloads"][:6],
                per_layer=[dict(m, workloads=[
                    c for c in m["workloads"] if c in cells])
                    for m in loaded["per_layer"][:30]])
        return loaded

    monkeypatch.setattr(json, "load", as_pr_43_left_it)
    _glm.test_the_manifest_names_the_glm_cell_and_its_two_readers()
