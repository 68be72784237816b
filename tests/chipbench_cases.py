"""What every ``tests/test_chipbench_<name>.py`` shares.

The benchmark's own cases live with the benchmark (``chipbench/tests/``,
run there by ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``).
Tier 1 takes them in so that they count and guard: a change to the
program that breaks a family's reference, its counts, the device time
found by name or what the trace readers read fails here and not first
on the chip. There is one collecting module a file of
``chipbench/tests/``: a star-import of that file's test functions and
fixtures as they are, plus the fixture below. ``--dist loadfile`` hands
a worker one module at a time, so the files spread over the workers
(one module of all of them was 975 s of a 995 s run at PR 45).
"""

import os

import pytest


@pytest.fixture(autouse=True)
def one_cpu_device_for_the_servers_these_cases_start(monkeypatch):
    """tests/conftest.py gives this process eight virtual CPU devices
    through ``XLA_FLAGS``; a rehearsal cell's server, started by a case
    as a child, must hold the one device its cell asks for."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    monkeypatch.setenv("XLA_FLAGS", " ".join(flags))
