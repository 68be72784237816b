"""chip_smoke.py on the CPU, and the fallbacks it exists to catch.

The smoke's own contract (pass at ``--expect-platform cpu --model
tiny-llama`` with children and the router; fail without an
accelerator at the default expectation; a parent that never imports
jax), the compile-cache helper, and the places where the program used
to step aside quietly: a failing engine step, an explicit
``--attention-impl pallas`` that cannot be served; and what
``--attention-impl auto`` resolves to on a TPU, site by site.
"""

import asyncio
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout=600, env=None):
    return subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                          timeout=timeout, env=env)


def _result_lines(stdout):
    return [line for line in stdout.splitlines()
            if line.startswith("{")]


# ---- the smoke itself ------------------------------------------------------


def test_smoke_passes_on_cpu_and_parent_stays_off_jax():
    # The parent runs through main() in a wrapper that checks, after
    # the whole run, that neither jax nor the engine package (which
    # imports it) was ever loaded into the parent process.
    wrapper = (
        "import sys, chip_smoke\n"
        "rc = chip_smoke.main(['--expect-platform', 'cpu',"
        " '--model', 'tiny-llama'])\n"
        "loaded = [m for m in sys.modules if m == 'jax' or"
        " m.startswith(('jax.', 'production_stack_tpu.engine'))]\n"
        "assert not loaded, loaded\n"
        "sys.exit(rc)\n")
    proc = _run([sys.executable, "-c", wrapper])
    assert proc.returncode == 0, proc.stderr[-4000:]
    report, result = map(json.loads,
                         proc.stdout.strip().splitlines()[-2:])
    # The last line is the result and nothing else; the findings are
    # the line before it.
    assert result == {"ok": True,
                      "device": {"platform": "cpu", "kind": "cpu",
                                 "count": report["num_devices"]}}
    assert isinstance(result["device"]["count"], int)
    assert report["attention_impl"] == {
        "decode": "xla", "prefill": "xla", "unified": "xla"}
    assert {(k["kernel"], k["ok"]) for k in report["kernels"]} == {
        ("decode", True), ("prefill", True), ("ragged", True)}
    assert {k["kv"] for k in report["kernels"]} == {"float32", "int8"}
    assert all(n >= 1 for n in report["engine_compile_events"].values())
    assert report["requests_served"] == 10
    assert report["generation_tokens"] == 10 * 12
    assert "/tmp" not in report["compile_cache_dir"]


def test_default_expectation_fails_without_an_accelerator():
    # This sandbox exports JAX_PLATFORMS=cpu; the default expectation
    # is a TPU, and a mismatch must fail, not fall back.
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = _run([sys.executable, "chip_smoke.py"], env=env)
    assert proc.returncode != 0
    assert _result_lines(proc.stdout) == []
    assert "expected 'tpu'" in proc.stderr


# ---- compile-cache helper --------------------------------------------------


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


def test_compile_cache_env_set_means_no_directory_in_code(
        monkeypatch, config_updates):
    from production_stack_tpu.utils import compile_cache
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.configure_compile_cache() == "/some/dir"
    # The operator's flag loses to the variable too.
    assert compile_cache.configure_compile_cache("/pvc") == "/some/dir"
    assert config_updates == []


def test_compile_cache_unset_is_the_fixed_in_repo_path(
        monkeypatch, config_updates):
    from production_stack_tpu.utils import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.configure_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert not path.startswith("/tmp")
    assert config_updates == [("jax_compilation_cache_dir", path)]
    # Twice gives the same place: nothing in it comes from a pid, a
    # temp name or the time.
    assert compile_cache.configure_compile_cache() == path


def test_compile_cache_operator_dir_when_env_unset(
        monkeypatch, config_updates):
    from production_stack_tpu.utils import compile_cache
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.configure_compile_cache("/pvc") == "/pvc"
    assert config_updates == [("jax_compilation_cache_dir", "/pvc")]


# ---- a failing step fails its sequences ------------------------------------


def _tiny_engine():
    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        SchedulerConfig,
        tiny_model_config,
    )
    from production_stack_tpu.engine.engine import LLMEngine
    return LLMEngine(EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=128,
                                  prefill_chunk_size=32)))


def test_failed_step_ends_its_requests_and_flips_health():
    from production_stack_tpu.engine import server as engine_server
    from production_stack_tpu.engine.sequence import SamplingParams

    engine = _tiny_engine()
    free_pages = engine.cache_manager.num_free_pages

    def refuse(plan):
        raise RuntimeError("Mosaic says no")

    engine.runner.dispatch_prefill = refuse
    srv = engine_server.EngineServer(engine, "tiny-llama")

    async def run():
        srv.async_engine.start(asyncio.get_running_loop())
        outs = []
        for _ in range(engine_server.STEP_FAILURE_LIMIT):
            _, stream = await srv.async_engine.submit(
                list(range(2, 40)), SamplingParams(max_tokens=4))
            # Bounded: the request used to wait forever while the loop
            # retried the same step every 50 ms.
            outs.append(await asyncio.wait_for(stream.get(), timeout=60))
        health = await srv.health(None)
        return outs, health

    outs, health = asyncio.run(run())
    assert all(o.finished and o.finish_reason == "abort"
               and o.new_token is None for o in outs)
    assert not engine.has_work()
    assert engine.cache_manager.num_free_pages == free_pages
    assert health.status == 503
    assert json.loads(health.text)["status"] == "step_failures"


# ---- explicit kernel selection that cannot be honoured ---------------------


def _runner_config(page_size, attention_impl="pallas", unified=None,
                   tp=1, unified_step=False):
    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        ParallelConfig,
        SchedulerConfig,
        tiny_model_config,
    )
    model = tiny_model_config("llama")
    model.attention_impl = attention_impl
    model.attention_impl_unified = unified
    return EngineConfig(
        model=model,
        cache=CacheConfig(page_size=page_size, num_pages=32),
        scheduler=SchedulerConfig(max_num_seqs=4, max_model_len=256,
                                  prefill_chunk_size=64,
                                  unified_step=(unified_step
                                                or unified is not None)),
        parallel=ParallelConfig(tensor_parallel_size=tp))


def test_explicit_pallas_that_cannot_be_served_fails_at_startup(
        monkeypatch):
    import jax

    from production_stack_tpu.engine.model_runner import ModelRunner

    # What the runner sees on a TPU host, without one.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # A page size the kernels cannot DMA.
    with pytest.raises(ValueError, match="page_size"):
        ModelRunner(_runner_config(page_size=16))
    # Plain tensor parallelism: GSPMD cannot partition a Mosaic call
    # (the first sharded step raised exactly that at tp=4 on a v5e).
    with pytest.raises(ValueError, match="shard_map"):
        ModelRunner(_runner_config(page_size=128, tp=2))
    # A kernel the compiler refuses.
    monkeypatch.setattr(ModelRunner, "_lowering_error",
                        staticmethod(lambda fn, *args: "Mosaic says no"))
    with pytest.raises(RuntimeError, match="Mosaic says no"):
        ModelRunner(_runner_config(page_size=128))


def test_explicit_unified_impl_that_cannot_be_served_fails(monkeypatch):
    import jax

    from production_stack_tpu.engine.model_runner import ModelRunner

    runner = ModelRunner(_runner_config(page_size=128,
                                        attention_impl="xla"))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ModelRunner, "_lowering_error",
                        staticmethod(lambda fn, *args: "Mosaic says no"))
    config = _runner_config(page_size=128, attention_impl="xla",
                            unified="pallas_ragged")
    with pytest.raises(RuntimeError, match="pallas_ragged"):
        runner._resolve_unified_impl(config.model, config,
                                     auto_impl=False)


# ---- what 'auto' serves on a TPU -----------------------------------------


@pytest.mark.parametrize("page_size, attention_impl, served, probed", [
    # 'auto': the decode and the prefill kernel where each compiles,
    # the prefill kernel composed for the unified step; the constant
    # does not admit the fused ragged kernel, so it is not compiled.
    (128, "auto",
     {"decode": "pallas", "prefill": "pallas", "unified": "pallas"},
     {"paged_decode_attention", "paged_prefill_attention"}),
    # The default page size cannot serve any kernel: XLA at all three
    # sites, and nothing compiled to find that out.
    (16, "auto",
     {"decode": "xla", "prefill": "xla", "unified": "xla"}, set()),
    # An explicit 'pallas' skips the constant.
    (128, "pallas",
     {"decode": "pallas", "prefill": "pallas",
      "unified": "pallas_ragged"},
     {"paged_decode_attention", "paged_prefill_attention",
      "paged_ragged_attention"}),
], ids=["auto-page128", "auto-page16", "pallas-page128"])
def test_attention_impl_resolution_on_a_tpu(
        monkeypatch, page_size, attention_impl, served, probed):
    import jax

    from production_stack_tpu.engine.model_runner import ModelRunner

    seen = set()

    def every_probe_compiles(fn, *args):
        seen.add(fn.__name__)
        return None

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ModelRunner, "_lowering_error",
                        staticmethod(every_probe_compiles))
    runner = ModelRunner(_runner_config(
        page_size=page_size, attention_impl=attention_impl,
        unified_step=True))
    assert runner.observatory.attention_impls() == served
    assert seen == probed
