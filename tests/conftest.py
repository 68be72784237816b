"""Test harness configuration.

- Forces JAX onto a virtual 8-device CPU mesh so every sharding/parallel
  test runs without TPU hardware (the driver dry-runs the real multi-chip
  path separately via __graft_entry__.dryrun_multichip).
- Runs ``async def`` tests via asyncio.run (no pytest-asyncio in env).
- Resets all process-wide singletons between tests.
"""

import asyncio
import inspect
import os
import sys
import threading

# Must happen before any jax backend init anywhere in the test session:
# tests run on the CPU with 8 virtual devices (tiny models, Pallas
# kernels in interpret mode) whatever the host has.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402

# What the lane pays for is compiling tiny programs, not running them:
# the float32 references run op by op (some 1600 compiles of 40 ms in
# one case of a family's engine module), and a family's engine programs
# are traced in many shapes. So the CPU backend compiles at its lowest
# optimisation level, through its older emitters (the two together: an
# eager op's compile 36 -> 15 ms, that case 101 -> 51 s, the whole lane
# 1107 -> 784 s of wall, most of it theirs; PR 46's runs),
# and without fused multiply-adds (``max_isa=AVX``). The third is what
# keeps "the same arithmetic gives the same bits" true of two programs:
# with the first two alone the five cases that compare a hybrid's
# carried tails with its pool path to the bit read one unit in the last
# place apart in 3-6 of 20 log-probabilities; at level 1, or with no
# fused instruction to choose, they agree. Every comparison in the lane
# is between programs compiled the same way, and nothing in it measures
# the CPU's speed. The servers that rehearsal cases start inherit the
# flags. A flag the installed XLA does not know ends the process, hence
# the version: look at all three again when jax moves.
if jax.__version_info__[:2] == (0, 9):
    for _flag in ("--xla_backend_optimization_level=0",
                  "--xla_cpu_use_fusion_emitters=false",
                  "--xla_cpu_max_isa=AVX"):
        if _flag.split("=")[0] not in os.environ["XLA_FLAGS"]:
            os.environ["XLA_FLAGS"] += " " + _flag

from production_stack_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

# The suite is dominated by recompiles of the same programs: keep every
# one in the persistent cache, the eager ops of 15 ms included (a
# family's reference runs the same ops in its three modules, on
# whichever of the six workers; writing an entry costs nothing that
# shows, reading one back is a few ms), between sessions too.
configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

# One lane. The driver's command (``-m 'not slow'``, six workers,
# ``--dist loadfile``; docs/source/dev_guide/testing.md) is the one gate
# a PR meets, so a module is in it unless this rule keeps it out:
#   (a) it guards only a path no benchmark cell serves AND costs over
#       60 s, or
#   (b) it starts more than one ``jax.distributed`` process.
# Seconds are a module's cases summed (PR 46: these four in a run of
# ``-m slow`` alone, 100 s of wall on five workers; the lane's modules
# below inside a six-worker run of the whole lane). A module that costs
# under 40 s is in whatever it guards (``test_ring_attention``, 30 s,
# came in so); ``pytest -m slow`` runs what is listed here.
_SLOW_MODULES = {
    "test_context_parallel_serving": "67 s, (a): context-parallel serving",
    "test_multihost": "20 s, (b): two jax.distributed processes",
    "test_pipeline_parallel": "82 s, (a): pipeline stages",
    "test_real_checkpoint_sharded": "22 s, (b): two jax.distributed "
                                    "processes",
}


# ``--dist loadfile`` hands modules to the workers in the order they were
# collected, and a long module handed out last is the lane's tail (the
# alphabet ends on ``test_turn_phases`` and ``test_unified_step``, 66 and
# 146 s). So the modules measured over 60 s are collected first, longest
# first; the rest keep the alphabet. Seconds as above. A stale entry
# costs balance and nothing else; a module that nears 300 s is split
# (testing.md).
_LONG_MODULES = {
    "test_glm4_moe_lite_engine": 264,
    "test_qwen3_next_deferred": 257,
    "test_conv_tails_burst": 245,
    "test_pallas_lowering": 205,
    "test_sdar_moe_engine": 196,
    "test_granitemoehybrid": 150,
    "test_exaone_moe": 140,
    "test_exaone_moe_engine": 132,
    "test_chipbench_exaone_moe_family": 120,
    "test_chipbench_glm4_moe_lite_family": 177,
    "test_longcat_flash": 169,
    "test_lfm2_moe_engine": 164,
    "test_glm4_moe_lite": 155,
    "test_unified_step": 146,
    "test_chipbench_lfm2_family": 144,
    "test_pallas_attention": 142,
    "test_longcat_flash_engine": 130,
    "test_prefill_width": 113,
    "test_chipbench_longcat_family": 106,
    "test_chipbench_sdar_family": 102,
    "test_chipbench_granitemoehybrid_family": 105,
    "test_granitemoehybrid_engine": 100,
    "test_qwen3_next_engine": 105,
    "test_chipbench_rehearsal": 105,
    "test_jamba_engine": 99,
    "test_deferred_kv": 98,
    "test_qwen3_next": 98,
    "test_pallas_lowering_latent": 95,
    "test_jamba": 71,
    "test_lfm2_moe": 68,
    "test_turn_phases": 66,
    "test_chipbench_mixtral_family": 66,
    "test_chip_smoke": 66,
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__ in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
    items.sort(key=lambda item: -_LONG_MODULES.get(item.module.__name__, 0))


def pytest_pyfunc_call(pyfuncitem):
    """Execute coroutine test functions with asyncio.run."""
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(func(**kwargs))
        return True
    return None


def _stop_singleton_threads(instances) -> None:
    """Stop the background threads of a finished test's singletons
    (config watcher, stats scraper). Clearing the registry alone
    leaves them running: their next tick looks the singletons up by
    class and writes the OLD test's config into the NEXT test's
    instances — which is how a watcher test could pass alone and fail
    in suite order."""
    for inst in instances:
        close = getattr(inst, "close", None)
        if callable(close):
            close()
    for inst in instances:
        thread = getattr(inst, "_thread", None)
        if isinstance(thread, threading.Thread) and thread.is_alive():
            thread.join(timeout=5)


@pytest.fixture(autouse=True)
def reset_singletons():
    """Each test gets fresh router singletons, and leaves no thread
    behind that could touch the next test's."""
    from production_stack_tpu.utils import SingletonMeta
    SingletonMeta._instances.clear()
    yield
    _stop_singleton_threads(list(SingletonMeta._instances.values()))
    SingletonMeta._instances.clear()


@pytest.fixture
def latent_walk_at(monkeypatch):
    """``at(fn, pages)``: the latent kernel's wrapper ``fn``
    (ops/mla_attention_pallas.py) walking ``pages`` pages a link,
    whatever its rule says of the shape; ``pages`` None is ``fn`` as it
    stands. The rule is read at trace time, so the steered form is a
    jit of its own and leaves no trace in ``fn``'s cache."""
    def at(fn, pages):
        if pages is None:
            return fn
        from production_stack_tpu.ops import mla_attention_pallas
        monkeypatch.setattr(mla_attention_pallas, "latent_pages_per_chunk",
                            lambda *shape: min(pages, shape[-1]))
        return jax.jit(fn.__wrapped__,
                       static_argnames=("scale", "interpret"))
    return at
