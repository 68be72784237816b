"""The benchmark's own cases about families and named scopes, run from
tier 1 so that they count and guard: a change to the program that
breaks a family's reference, its counts or the device time found by
name fails here and not first on the chip.

The cases live with the benchmark (``chipbench/tests/``, run there by
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``); this module
takes the test functions and fixtures of five of its files as they
are, so each is collected, run and counted here under its own name.
No two of the files give a test or a fixture the same name.
"""

import os

import pytest

from chipbench.tests.test_family import *  # noqa: F401,F403
from chipbench.tests.test_jamba_family import *  # noqa: F401,F403
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


def test_no_two_files_share_a_name():
    import importlib
    seen = {}
    for name in ("test_family", "test_jamba_family", "test_mixtral_family",
                 "test_qwen3_next_family", "test_scopes"):
        module = importlib.import_module(f"chipbench.tests.{name}")
        for attr, value in vars(module).items():
            ours = getattr(value, "__module__", None) == module.__name__
            fixture = "fixture" in type(value).__name__.lower()
            if ours and (attr.startswith("test_") or fixture):
                assert attr not in seen, (attr, name, seen[attr])
                seen[attr] = name
    assert len(seen) > 30
