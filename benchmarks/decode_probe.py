"""Decode-step roofline probe: where do the ms/step go?

Builder-captured 2026-07-30 (not measured by the driver): XLA decode
at the 1B bench config measured ~42 ms/token-step vs a ~5 ms weights-
bound roofline — ~34 GB of traffic/step ≈ one full-cache copy per
layer. This probe isolates the burst body's cost on the chip across
the factors that could explain it (each case: chain N invocations in
ONE compiled program, wait once with a host read, subtract the cost
of a host read of ready data):

  1. forward-only, single decode step (stacked vs per_layer caches)
  2. forward+sampling chained K steps under lax.scan — the real
     _decode_burst_impl via the engine's jit, both layouts
  3. KV-write-only step (the round-3 16x pathology's isolated form)

Run on a live chip:  python benchmarks/decode_probe.py
Artifacts: benchmarks/results/decode_probe.json + markdown to stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rtt_timer():
    import jax

    def sync(o):
        jax.device_get(o)

    def measure(fn, out_probe, repeats=3):
        """min wall time of fn() followed by one host read, minus the
        cost of a host read of ready data."""
        out = fn()
        sync(out_probe(out))
        rtt = float("inf")
        probe = out_probe(out)
        for _ in range(3):
            t0 = time.perf_counter()
            sync(probe)
            rtt = min(rtt, time.perf_counter() - t0)
        samples = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            out = fn()
            sync(out_probe(out))
            total = time.perf_counter() - t0
            if total > rtt:
                samples.append(total - rtt)
        return (min(samples) if samples else 0.0), rtt

    return measure


def probe_engine(layout: str, impl: str, burst: int = 32):
    """Build the bench engine and time one real decode burst dispatch."""
    import jax

    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        SchedulerConfig,
        bench_1b_model_config,
    )
    from production_stack_tpu.engine.engine import LLMEngine
    from production_stack_tpu.engine.sequence import (
        SamplingParams,
        SequenceState,
    )

    config = EngineConfig(
        model=bench_1b_model_config(),
        cache=CacheConfig(page_size=128, num_pages=512,
                          cache_layout=layout),
        scheduler=SchedulerConfig(max_num_seqs=32, max_model_len=1024,
                                  prefill_chunk_size=512,
                                  prefill_batch_size=8,
                                  decode_steps=burst),
    )
    config.model.attention_impl = impl
    engine = LLMEngine(config)
    rs = np.random.RandomState(0)
    seqs = []
    for i in range(32):
        prompt = [int(x) for x in rs.randint(
            1, config.model.vocab_size - 1, size=512)]
        sid = engine.add_request(prompt, SamplingParams(
            max_tokens=burst * 4, temperature=0.0, ignore_eos=True))
        seqs.append(engine.sequences[sid])
    # Prefill everything (and compile the burst) before timing.
    while any(s.num_computed_tokens < s.num_prompt_tokens
              for s in seqs):
        engine.step()
    engine.step()  # one burst: compile + warm

    t0 = time.perf_counter()
    engine.step()
    wall = time.perf_counter() - t0
    alive = sum(s.state not in (SequenceState.FINISHED,) for s in seqs)
    return {
        "case": f"engine_burst_{impl}_{layout}",
        "burst": burst, "batch": 32, "alive_rows": alive,
        "wall_s_per_burst": round(wall, 4),
        "ms_per_token_step": round(wall / burst * 1e3, 2),
    }


def probe_kv_write(layout: str):
    """Isolated per-layer KV write cost (the round-3 16x pathology)."""
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.engine.config import bench_1b_model_config
    from production_stack_tpu.ops.attention import write_to_pages

    m = bench_1b_model_config()
    L, kv, d, ps, pages = (m.num_hidden_layers,
                           m.num_key_value_heads, m.head_dim, 128, 512)
    b = 32
    rng = np.random.RandomState(0)
    new_kv = jnp.asarray(rng.randn(b, 1, kv, d), m.jax_dtype)
    pt = jnp.asarray(
        np.arange(1, b * 8 + 1, dtype=np.int32).reshape(b, 8))
    pos = jnp.full((b, 1), 17, jnp.int32)
    valid = jnp.ones((b, 1), bool)

    measure = _rtt_timer()
    if layout == "per_layer":
        caches = tuple(jnp.zeros((kv, pages, d, ps), m.jax_dtype)
                       for _ in range(L))

        @jax.jit
        def step(caches, new_kv):
            return tuple(
                write_to_pages(c, new_kv, pt, pos, valid)
                for c in caches)

        arg = caches

        def run():
            return step(arg, new_kv)

        def out_probe(o):
            return o[0][0, 0, 0, 0]
    else:
        cache = jnp.zeros((L, kv, pages, d, ps), m.jax_dtype)

        @jax.jit
        def step(cache, new_kv):
            for layer in range(L):
                cache = write_to_pages(cache, new_kv, pt, pos, valid,
                                       layer=layer)
            return cache

        arg = cache

        def run():
            return step(arg, new_kv)

        def out_probe(o):
            return o[0, 0, 0, 0, 0]

    wall, rtt = measure(run, out_probe)
    return {"case": f"kv_write_all_layers_{layout}",
            "wall_ms": round(wall * 1e3, 3),
            "rtt_ms": round(rtt * 1e3, 1)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out",
                    default="benchmarks/results/decode_probe.json")
    ap.add_argument("--quick", action="store_true",
                    help="kv-write probes only (CI smoke)")
    args = ap.parse_args(argv)

    import jax

    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )
    configure_compile_cache()
    rows = []
    backend = jax.default_backend()
    for layout in ("stacked", "per_layer"):
        rows.append(probe_kv_write(layout))
        print(json.dumps(rows[-1]), flush=True)
    if not args.quick:
        for layout in ("stacked", "per_layer"):
            for impl in ("xla", "pallas"):
                try:
                    rows.append(probe_engine(layout, impl))
                except Exception as e:  # noqa: BLE001 — record, go on
                    rows.append({
                        "case": f"engine_burst_{impl}_{layout}",
                        "error": repr(e)[:300]})
                print(json.dumps(rows[-1]), flush=True)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"backend": backend, "rows": rows}, f, indent=1)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
