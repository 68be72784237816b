"""Attention kernel microbenchmark: Pallas page-walk vs XLA gather.

Times the decode and prefill attention implementations in isolation on
the current backend (intended for the real TPU chip) across batch and
context length (B=8-32, 2-16k ctx). Page size is
pinned to the engine's 128 (one full lane tile per page; Mosaic
rejects smaller minor-dim slices of an HBM ref).

Writes a JSON table to ``--out`` (default
benchmarks/results/kernel_microbench.json) and prints a markdown table.

Usage:
    python benchmarks/kernel_microbench.py            # full sweep
    python benchmarks/kernel_microbench.py --quick    # CI smoke
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


def _make_state(b, ctx, page_size, kv_heads, head_dim, max_ctx,
                dtype):
    """Random cache + page tables for ``b`` sequences of ``ctx`` tokens."""
    import jax.numpy as jnp
    max_pages_per_seq = -(-max_ctx // page_size)
    num_pages = b * max_pages_per_seq + 2
    rng = np.random.RandomState(0)
    # Token-minor page layout, matching the engine and both kernels
    # (ops/attention.py: [kv_heads, num_pages, head_dim, page_size]).
    kc = jnp.asarray(
        rng.randn(kv_heads, num_pages, head_dim, page_size),
        dtype)
    vc = jnp.asarray(
        rng.randn(kv_heads, num_pages, head_dim, page_size),
        dtype)
    pt = np.zeros((b, max_pages_per_seq), np.int32)
    nxt = 1
    for i in range(b):
        for j in range(-(-ctx // page_size)):
            pt[i, j] = nxt
            nxt += 1
    kl = np.full((b,), ctx, np.int32)
    return kc, vc, jnp.asarray(pt), jnp.asarray(kl)


def _time(step, x0, args=(), *, iters=64, warmup=1, repeats=3):
    """Per-invocation device time of ``step`` (a shape-preserving fn).

    One dispatch costs the host tens of µs — as much as the kernels
    timed here — so the kernel is chained ``iters`` times *inside one
    compiled program* (each iteration feeds its output back as the
    next query, so nothing can be DCE'd or overlapped away) and the
    program is waited for once. Min over ``repeats`` suppresses
    jitter.
    """
    import jax
    import jax.numpy as jnp

    # The KV caches are ARGUMENTS, not closure constants: closed-over
    # arrays are compiled into the program as constants, hundreds of
    # MB of them here.
    @jax.jit
    def chained(x, *rest):
        def body(_, xc):
            return step(xc, *rest)
        return jnp.sum(
            jax.lax.fori_loop(0, iters, body, x).astype(jnp.float32))

    for _ in range(warmup):
        jax.block_until_ready(chained(x0, *args))
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(chained(x0, *args))
        samples.append((time.perf_counter() - t0) / iters)
    return min(samples)


def bench_decode(b, ctx, page_size, *, kv_heads=8, q_heads=32,
                 head_dim=64, max_ctx=None, iters=20):
    import jax.numpy as jnp
    from production_stack_tpu.ops.attention import paged_attention
    from production_stack_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
    )
    max_ctx = max_ctx or ctx
    dtype = jnp.bfloat16
    kc, vc, pt, kl = _make_state(
        b, ctx, page_size, kv_heads, head_dim, max_ctx, dtype)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, q_heads, head_dim), dtype)

    # Both paths run inside one compiled program (as in the engine's
    # jitted forward); the output feeds back as the next query.
    t_pallas = _time(
        lambda x, kc, vc, pt, kl: paged_decode_attention(
            x, kc, vc, pt, kl),
        q, (kc, vc, pt, kl), iters=iters)
    t_xla = _time(
        lambda x, kc, vc, pt, kl: paged_attention(
            x[:, None], kc, vc, pt, (kl - 1)[:, None], kl)[:, 0],
        q, (kc, vc, pt, kl), iters=iters)
    return t_pallas, t_xla


def bench_prefill(b, t, prior_ctx, page_size, *, kv_heads=8,
                  q_heads=32, head_dim=64, max_ctx=None, iters=20):
    import jax.numpy as jnp
    from production_stack_tpu.ops.attention import paged_attention
    from production_stack_tpu.ops.prefill_attention_pallas import (
        paged_prefill_attention,
    )
    ctx = prior_ctx + t
    max_ctx = max_ctx or ctx
    dtype = jnp.bfloat16
    kc, vc, pt, kl = _make_state(
        b, ctx, page_size, kv_heads, head_dim, max_ctx, dtype)
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, t, q_heads, head_dim), dtype)
    pos = jnp.asarray(
        np.broadcast_to(
            np.arange(prior_ctx, prior_ctx + t, dtype=np.int32)[None],
            (b, t)).copy())

    t_pallas = _time(
        lambda x, *r: paged_prefill_attention(x, *r),
        q, (kc, vc, pt, pos, kl), iters=iters)
    t_xla = _time(
        lambda x, *r: paged_attention(x, *r),
        q, (kc, vc, pt, pos, kl), iters=iters)
    return t_pallas, t_xla


def bench_ragged(r, w, mix, page_size, *, kv_heads=8, q_heads=32,
                 head_dim=64, int8=False, iters=20):
    """Fused ragged kernel vs the XLA gather at a mixed-row shape.

    ``mix = (decode_rows, verify_rows, prefill_rows, decode_ctx,
    prefill_prior)``; remaining rows are pads (kv_lens 0), matching
    the unified planner's common case of a lightly mixed step. Verify
    rows carry a 3-draft span. The XLA side runs ops.attention
    .paged_attention over the same [r, w] block with the positions the
    composed path materializes — exactly what _unified_impl composed
    before the fused kernel. The model runner's empirical 'auto' gate
    (_ragged_microbench_verdict) reads these rows (kind == 'ragged')
    and serves the kernel only when every measured cell wins.
    """
    import jax.numpy as jnp
    from production_stack_tpu.ops.attention import paged_attention
    from production_stack_tpu.ops.ragged_attention_pallas import (
        paged_ragged_attention,
    )
    n_dec, n_ver, n_pre, dec_ctx, pre_prior = mix
    span = 4  # 1 committed + 3 drafts on verify rows
    kv = np.zeros((r,), np.int32)
    li = np.zeros((r,), np.int32)
    dl = np.zeros((r,), np.int32)
    i = 0
    for _ in range(n_dec):
        kv[i], li[i] = dec_ctx, 0
        i += 1
    for _ in range(n_ver):
        kv[i], li[i], dl[i] = dec_ctx + span - 1, span - 1, span - 1
        i += 1
    for _ in range(n_pre):
        kv[i], li[i] = pre_prior + w, w - 1
        i += 1

    max_ctx = int(kv.max())
    max_pages_per_seq = -(-max_ctx // page_size)
    num_pages = r * max_pages_per_seq + 2
    rng = np.random.RandomState(0)
    dtype = jnp.bfloat16
    kc = jnp.asarray(
        rng.randn(kv_heads, num_pages, head_dim, page_size), dtype)
    vc = jnp.asarray(
        rng.randn(kv_heads, num_pages, head_dim, page_size), dtype)
    if int8:
        from production_stack_tpu.ops.quant_kv import (
            QuantKV,
            quantize_kv,
        )

        def _q(c):
            qc, scale = quantize_kv(jnp.transpose(c, (0, 1, 3, 2)))
            return QuantKV(jnp.transpose(qc, (0, 1, 3, 2)), scale)

        kc, vc = _q(kc), _q(vc)
    pt = np.zeros((r, max_pages_per_seq), np.int32)
    nxt = 1
    for row in range(r):
        for j in range(-(-int(kv[row]) // page_size)):
            pt[row, j] = nxt
            nxt += 1
    # The engine's layout invariant recovers each row's first query
    # position (docs/unified_step.md).
    pos = np.maximum(
        (kv - 1 - li)[:, None] + np.arange(w, dtype=np.int32)[None],
        0).astype(np.int32)
    pt, pos = jnp.asarray(pt), jnp.asarray(pos)
    kv, li, dl = map(jnp.asarray, (kv, li, dl))
    q = jnp.asarray(rng.randn(r, w, q_heads, head_dim), dtype)

    t_pallas = _time(
        lambda x, kc, vc, pt, kv, li, dl: paged_ragged_attention(
            x, kc, vc, pt, kv, li, dl),
        q, (kc, vc, pt, kv, li, dl), iters=iters)
    t_xla = _time(
        lambda x, kc, vc, pt, pos, kv: paged_attention(
            x, kc, vc, pt, pos, kv),
        q, (kc, vc, pt, pos, kv), iters=iters)
    return t_pallas, t_xla


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="tiny sweep (CI smoke)")
    ap.add_argument("--out",
                    default="benchmarks/results/kernel_microbench.json")
    args = ap.parse_args()

    import jax

    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )
    configure_compile_cache()
    device = jax.devices()[0]
    print(f"# backend: {jax.default_backend()} "
          f"({device.device_kind})")

    rows = []
    # Page size is fixed at 128: the v2 kernels DMA whole token-minor
    # pages, whose minor dim must be a full 128-lane tile (Mosaic
    # rejects smaller slices of an HBM ref). The engine serves with
    # page_size=128 for the same reason.
    if args.quick:
        decode_cases = [(8, 512, 128)]
        prefill_cases = [(4, 128, 0, 128)]
        ragged_cases = [(4, 128, (2, 1, 1, 96, 0), 128, False)]
        iters = 3
    else:
        decode_cases = [
            (b, ctx, 128)
            for b, ctx in ((8, 512), (8, 2048), (16, 2048),
                           (32, 2048), (32, 8192), (8, 16384))
        ]
        prefill_cases = [
            (b, t, prior, 128)
            for b, t, prior in ((4, 512, 0), (4, 512, 1536),
                                (8, 512, 1536), (4, 512, 7680),
                                (1, 512, 15872))
        ]
        # Mixed-row shapes the unified planner actually emits
        # (docs/unified_step.md): mostly-decode steps with one or two
        # chunks riding along, with and without verify spans, bf16
        # AND int8 (one kernel serves both caches).
        ragged_cases = [
            (r, w, mix, 128, int8)
            for r, w, mix in (
                (8, 128, (6, 0, 1, 2048, 1536)),
                (8, 128, (4, 2, 1, 2048, 1536)),
                (16, 512, (12, 0, 2, 4096, 3584)),
                (16, 512, (8, 4, 2, 8192, 7680)),
            )
            for int8 in (False, True)
        ]
        iters = 256

    for b, ctx, ps in decode_cases:
        t_pal, t_xla = bench_decode(b, ctx, ps, iters=iters)
        rows.append({
            "kind": "decode", "batch": b, "ctx": ctx,
            "page_size": ps, "pallas_us": round(t_pal * 1e6, 1),
            "xla_us": round(t_xla * 1e6, 1),
            "speedup": round(t_xla / t_pal, 2),
        })
        print(rows[-1])
    for b, t, prior, ps in prefill_cases:
        t_pal, t_xla = bench_prefill(b, t, prior, ps, iters=iters)
        rows.append({
            "kind": "prefill", "batch": b, "chunk": t,
            "prior_ctx": prior, "page_size": ps,
            "pallas_us": round(t_pal * 1e6, 1),
            "xla_us": round(t_xla * 1e6, 1),
            "speedup": round(t_xla / t_pal, 2),
        })
        print(rows[-1])
    for r, w, mix, ps, int8 in ragged_cases:
        t_pal, t_xla = bench_ragged(r, w, mix, ps, int8=int8,
                                    iters=iters)
        rows.append({
            "kind": "ragged", "rows": r, "width": w,
            "mix": "dec%d/ver%d/pre%d" % mix[:3],
            "ctx": mix[3], "page_size": ps,
            "kv_dtype": "int8" if int8 else "bf16",
            "pallas_us": round(t_pal * 1e6, 1),
            "xla_us": round(t_xla * 1e6, 1),
            "speedup": round(t_xla / t_pal, 2),
        })
        print(rows[-1])

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({
            "backend": jax.default_backend(),
            "device_kind": device.device_kind,
            "notes": (
                "Per-kernel device time vs the XLA gather path "
                "(speedup = xla_us / pallas_us). Consumed by the "
                "model runner's empirical 'auto' gates: decode rows "
                "retired the decode kernel (PALLAS_DECODE_IN_AUTO); "
                "ragged rows (kind='ragged', the fused unified-step "
                "kernel, bf16 + int8 kv_dtype) gate "
                "attention_impl_unified resolution — 'auto' serves "
                "the fused kernel only when backend=='tpu' and every "
                "ragged cell wins (_ragged_microbench_verdict)."),
            "rows": rows,
        }, f, indent=1)
    print(f"# wrote {args.out}")

    # Markdown table for the docs.
    print("\n| kind | B/R | ctx/chunk | page | pallas µs | xla µs | "
          "xla/pallas |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        ctx = r.get("ctx", f"{r.get('chunk')}+{r.get('prior_ctx')}")
        if r["kind"] == "ragged":
            ctx = f"{r['mix']}@w{r['width']} ({r['kv_dtype']})"
        b = r.get("batch", r.get("rows"))
        print(f"| {r['kind']} | {b} | {ctx} | "
              f"{r['page_size']} | {r['pallas_us']} | {r['xla_us']} | "
              f"{r['speedup']} |")


if __name__ == "__main__":
    main()
