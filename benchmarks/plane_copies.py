"""Does a cell's burst or prefill step copy a whole page plane around a
write? Compiled for a described (not attached) v5e, nothing runs.

    JAX_PLATFORMS=cpu python3 benchmarks/plane_copies.py --compile-for-v5e \
        [--cells qwen2.5-3b,lfm2-8b-a1b-ep4] [--repo-root scratch/parent] \
        [--out DIR]

For each configuration of ``chipbench/configs`` (all of them, or
``--cells``) the two programs its cell runs are built from the
configuration file alone (the published widths, the cell's
``server_flags``: pages, rows, chunk, burst steps): the deferred decode
burst (the drafting burst where the family drafts) at ``--max-num-seqs``
rows, and the prefill step at ``--prefill-batch-size`` x
``--prefill-chunk-size``, Pallas attention and the family's own kernels
as a TPU resolves them. A ``ModelRunner`` of the family's tiny
configuration lends its methods; the published configuration replaces
its own, and every argument is a shape, so no array of that size is
ever made.

Prints one JSON line a program: ``plane_copies``, the ``copy`` (or
``copy-start``) instructions of the compiled text whose result is as
large as one page plane (``plane_copy_census``: a scatter whose index
lies in the plane's minor dimension is compiled on another layout and
so stands between two of them, ops/attention.py ``write_to_pages``; ``write_run_to_pages`` leaves none),
every other plane-sized result by opcode, the compile's seconds and the
program's temporaries. ``--repo-root`` compiles another checkout's
programs (the parent's); ``--out`` keeps the texts. PERF.md section 6,
PR 52.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def plane_copy_census(text: str, plane_elements: int) -> dict:
    """``{"plane_copies": n, "plane_sized": {"bf16[...] opcode": n}}``
    of a compiled program's text: the instructions whose result holds
    an array of ``plane_elements`` elements (``array_census`` of
    benchmarks/latent_walk_iteration.py), and how many of them are
    copies. A ``copy-start``'s result names the plane twice."""
    from benchmarks.latent_walk_iteration import array_census
    sized = array_census(text, 0, 0, 0, plane_elements)["plane_sized"]
    by_opcode = {}
    for key, n in sized.items():
        opcode = key.rsplit(" ", 1)[1]
        by_opcode[opcode] = by_opcode.get(opcode, 0) + n
    return {"plane_copies": (by_opcode.get("copy", 0)
                             + by_opcode.get("copy-start", 0) // 2),
            "plane_sized": sized}


def tiny_of(cfg, model):
    """The family's tiny configuration: a runner's methods need a
    model of their family, not of its size."""
    tiny = {
        "qwen2": lambda: cfg.tiny_model_config("llama"),
        "qwen3_next": cfg.tiny_qwen3_next_config,
        "jamba": cfg.tiny_jamba_config,
        "lfm2_moe": cfg.tiny_lfm2_moe_config,
        "longcat_flash": cfg.tiny_longcat_flash_config,
        "glm4_moe_lite": cfg.tiny_glm4_moe_lite_config,
        "granitemoehybrid": cfg.tiny_granitemoehybrid_config,
        "exaone_moe": cfg.tiny_exaone_moe_config,
    }[model.architecture]()
    tiny.attention_impl, tiny.dtype = "xla", "float32"
    return tiny


# The sizes of the runner that lends its methods.
TINY_FLAGS = {"page-size": 16, "num-pages": 32, "max-num-seqs": 4,
              "max-model-len": 128, "prefill-chunk-size": 32,
              "prefill-batch-size": 2}


def engine_config(cfg, model, flags: dict):
    return cfg.EngineConfig(
        model=model,
        cache=cfg.CacheConfig(page_size=flags["page-size"],
                              num_pages=flags["num-pages"]),
        scheduler=cfg.SchedulerConfig(
            max_num_seqs=flags["max-num-seqs"],
            max_model_len=flags["max-model-len"],
            prefill_chunk_size=flags["prefill-chunk-size"],
            prefill_batch_size=flags["prefill-batch-size"],
            decode_steps=flags["decode-steps"],
            deferred_kv_writes=True,
            draft_module=model.has_draft_module))


def programs(config_file: str, chip):
    """``(plane elements, {"burst": lowered, "step": lowered})`` of one
    configuration file's cell."""
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.engine import config as cfg
    from production_stack_tpu.engine.model_runner import ModelRunner
    from production_stack_tpu.models import registry

    with open(config_file) as f:
        hf = json.load(f)
    bench = hf.pop("chipbench")
    flags = bench["server_flags"]
    model = cfg.ModelConfig.from_hf_config(hf, bench["name"])
    model.dtype = bench["dtype"]
    # What a TPU's start-up resolves (the backend here is the CPU):
    # Pallas attention, but a latent's prefill, which has no kernel.
    model.attention_impl = "pallas"
    if model.family.page_cache is not None:
        model.attention_impl_prefill = "xla"
    real = engine_config(cfg, model, flags)
    runner = ModelRunner(engine_config(
        cfg, tiny_of(cfg, model),
        {**TINY_FLAGS, "decode-steps": flags["decode-steps"]}))
    runner.config = real
    runner.max_pages_per_seq = real.scheduler.max_pages_per_seq(
        real.cache.page_size)

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), tree)

    params = shaped(jax.eval_shape(
        lambda: runner._init_fn(model, jax.random.PRNGKey(0))))
    pages = registry.page_cache(model)
    plane = (pages.heads, real.cache.num_pages, pages.width,
             real.cache.page_size)
    if model.has_recurrent_state or model.family.page_cache is not None:
        k_cache, v_cache = shaped(jax.eval_shape(
            lambda: registry.init_hybrid_cache(
                model, real.cache.num_pages, real.cache.page_size,
                real.cache.num_state_slots)))
    else:
        k_cache = v_cache = tuple(
            jax.ShapeDtypeStruct(plane, model.jax_dtype, sharding=chip)
            for _ in range(model.num_hidden_layers))

    def arr(dtype, *dims):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    rows, width = real.scheduler.max_num_seqs, runner.max_pages_per_seq

    def sampling(b):
        return (arr(jnp.float32, b), arr(jnp.float32, b),
                arr(jnp.int32, b), arr(jnp.uint32, 2)) + (None,) * 7

    state = (lambda b: {"state_slots": arr(jnp.int32, b)}
             if model.has_recurrent_state else {})
    drafts = real.scheduler.draft_module
    burst = jax.jit(
        runner._decode_burst_draft_impl if drafts
        else runner._decode_burst_deferred_impl,
        static_argnames=("num_steps",), donate_argnums=(1, 2)).lower(
        params, k_cache, v_cache, arr(jnp.int32, rows, 1),
        arr(jnp.int32, rows, 1), arr(jnp.int32, rows, width),
        arr(jnp.int32, rows), arr(jnp.bool_, rows), arr(jnp.int32, rows),
        arr(jnp.int32, rows, 16), *sampling(rows),
        num_steps=real.scheduler.decode_steps, **state(rows),
        **({"draft_rows": arr(jnp.bool_, rows)} if drafts else {}))
    b, t = (real.scheduler.prefill_batch_size,
            real.scheduler.prefill_chunk_size)
    step = jax.jit(
        runner._step_impl,
        static_argnames=("sample_index_mode", "want_logprobs"),
        donate_argnums=(1, 2)).lower(
        params, k_cache, v_cache, arr(jnp.int32, b, t),
        arr(jnp.int32, b, t), arr(jnp.int32, b, width), arr(jnp.int32, b),
        arr(jnp.bool_, b, t), arr(jnp.int32, b), *sampling(b),
        sample_index_mode="last", **state(b),
        **({"next_tokens": arr(jnp.int32, b)} if drafts else {}))
    elements = plane[0] * plane[1] * plane[2] * plane[3]
    return elements, {"burst": burst, "step": step}


def compile_for_v5e(config_files, out_dir) -> list:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import production_stack_tpu.models.llama as llama
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    # The families' own kernels as a TPU serves them; each family's
    # module holds the rule under its own name.
    for module in list(sys.modules.values()):
        if getattr(module, "hybrid_kernel_impl", None) is (
                llama.hybrid_kernel_impl):
            module.hybrid_kernel_impl = lambda config: "pallas"
    lines = []
    for config_file in config_files:
        cell = pathlib.Path(config_file).stem
        elements, lowered = programs(config_file, chip)
        for name, program in lowered.items():
            start = time.perf_counter()
            compiled = program.compile()
            seconds = time.perf_counter() - start
            text = compiled.as_text()
            if out_dir:
                pathlib.Path(out_dir).mkdir(parents=True, exist_ok=True)
                pathlib.Path(out_dir, f"{cell}.{name}.txt").write_text(text)
            memory = compiled.memory_analysis()
            line = {"cell": cell, "program": name,
                    **plane_copy_census(text, elements),
                    "compile_s": round(seconds, 1),
                    "temp_bytes": memory.temp_size_in_bytes,
                    "text_lines": text.count("\n")}
            print(json.dumps(line), flush=True)
            lines.append(line)
    return lines


def main(argv=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--compile-for-v5e", action="store_true",
                    required=True,
                    help="the one thing this script does; it names that "
                         "nothing runs and no time is a chip's")
    ap.add_argument("--cells", default=None,
                    help="comma-separated names of chipbench/configs "
                         "(default: all)")
    ap.add_argument("--repo-root", default=str(ROOT),
                    help="compile that checkout's programs")
    ap.add_argument("--out", default=None, help="keep the texts there")
    args = ap.parse_args(argv)
    root = pathlib.Path(args.repo_root).resolve()
    # The program from ``--repo-root``, this file's neighbours from here.
    sys.path.insert(0, str(root))
    sys.path.append(str(ROOT))
    files = sorted(glob.glob(str(root / "chipbench/configs/*.json")))
    if args.cells:
        files = [f for f in files
                 if pathlib.Path(f).stem in args.cells.split(",")]
    return compile_for_v5e(files, args.out)


if __name__ == "__main__":
    main()
