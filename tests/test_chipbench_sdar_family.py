"""``chipbench/tests/test_sdar_family.py``, collected, run and counted
in tier 1 as it is (tests/chipbench_cases.py says why and how)."""

from chipbench.tests.test_sdar_family import *  # noqa: F401,F403
from chipbench_cases import (  # noqa: F401
    one_cpu_device_for_the_servers_these_cases_start,
)
