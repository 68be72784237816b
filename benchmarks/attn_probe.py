"""Decode attention micro-probe: where do the ~5.9 ms/step go?

The round-5 ablation attributed ~5.9 of 11.1 ms/token-step to the
paged attention READ side (gather + softmax + AV) at the 1B bench
config — ~4.5x its ~1.3 ms HBM-traffic floor. This probe times ONE
layer's decode attention (chained K times in one program, honest RTT
protocol) across implementations to locate the overhead:

  gather_dps    page gather only ([kv, pages, d, ps] layout), summed
  attend_dps    full paged_attention (the served path)
  attend_tm     same math on a token-major [kv, pages, ps, d] cache
  attend_dense  per-row dense [B, ctx, kv, d] K/V (no page table):
                the no-gather upper bound
  attend_flat   gather flattened to [B, ctx, kv, d] then dense math
                (isolates einsum-on-gathered-shape vs gather itself)

ms are per chained invocation of ONE layer; multiply by 2*L mentally
(16 layers, K and V) only for the gather-traffic cases — the full
attention cases already read both K and V.

Run on a live chip:  python benchmarks/attn_probe.py
Artifact: benchmarks/results/attn_probe.json
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

B, NH, KV, D, PS, PAGES_PER_SEQ, NUM_PAGES, STEPS = (
    32, 32, 8, 64, 128, 8, 512, 32)


def build():
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    k_dps = jnp.asarray(
        rs.randn(KV, NUM_PAGES, D, PS), jnp.bfloat16)
    v_dps = jnp.asarray(
        rs.randn(KV, NUM_PAGES, D, PS), jnp.bfloat16)
    k_tm = jnp.transpose(k_dps, (0, 1, 3, 2))  # [kv, pages, ps, d]
    v_tm = jnp.transpose(v_dps, (0, 1, 3, 2))
    pt = jnp.asarray(
        np.arange(1, B * PAGES_PER_SEQ + 1, dtype=np.int32)
        .reshape(B, PAGES_PER_SEQ))
    ctx = PAGES_PER_SEQ * PS
    # Dense per-row copies of the same values (parity-checkable).
    k_dense = jnp.transpose(
        k_dps[:, pt], (1, 2, 4, 0, 3)
    ).reshape(B, ctx, KV, D)
    v_dense = jnp.transpose(
        v_dps[:, pt], (1, 2, 4, 0, 3)).reshape(B, ctx, KV, D)
    q = jnp.asarray(rs.randn(B, 1, NH, D), jnp.bfloat16)
    q_pos = jnp.full((B, 1), ctx - 64, jnp.int32)
    kv_lens = jnp.full((B,), ctx - 63, jnp.int32)
    return (k_dps, v_dps, k_tm, v_tm, k_dense, v_dense, pt, q, q_pos,
            kv_lens)


def chain(step, xs_n=STEPS):
    """Run ``step`` STEPS times in one jitted program with the OUTPUT
    fed back into the next step's query.

    Two liveness guarantees, both load-bearing (the first version of
    this probe lacked them and produced a physically impossible
    negative ms/step on one leg — the scan body's work was sliced
    down to the single emitted element):
      - the full output contributes to the carried q, so no part of
        the per-step computation is dead;
      - each step's inputs depend on the previous step's output, so
        nothing loop-invariant about the attention math can be
        hoisted out of the scan (the page table is additionally
        rotated by i inside each case).
    """
    import jax
    import jax.numpy as jnp

    def body(q, i):
        out = step(q, i)  # [B,1,NH,D] (attend) or [B] (gather)
        if out.ndim == 1:
            contrib = out[:, None, None, None]
        else:
            contrib = out
        q_next = (q + contrib.astype(jnp.float32) * 1e-6).astype(
            q.dtype)
        return q_next, out.reshape(-1)[0]

    def prog(q):
        _, outs = jax.lax.scan(body, q, jax.numpy.arange(xs_n))
        return outs

    return jax.jit(prog)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out",
                    default="benchmarks/results/attn_probe.json")
    args = ap.parse_args(argv)

    import jax

    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )
    configure_compile_cache()
    import jax.numpy as jnp

    from production_stack_tpu.ops.attention import (
        NEG_INF,
        paged_attention,
    )

    (k_dps, v_dps, k_tm, v_tm, k_dense, v_dense, pt, q, q_pos,
     kv_lens) = build()
    scale = 1.0 / float(np.sqrt(D))
    ctx = PAGES_PER_SEQ * PS
    rows = []

    # Every case takes (q, i): q is the chain-carried query (output
    # feedback — see chain()); the page table is rotated by i so the
    # gather itself is loop-variant and cannot be hoisted. At i=0 the
    # rotation is identity, so the parity checks compare like-for-like.
    def pt_i(i):
        return (pt + i) % NUM_PAGES

    # 1. gather only (one layer's K pages), reduced (the sum keeps
    # every gathered element live).
    def gather_dps(qq, i):
        k = k_dps[:, pt_i(i)]  # [kv, B, P, d, ps]
        return k.sum(axis=(0, 2, 3, 4))

    # 2. the served path.
    def attend_dps(qq, i):
        return paged_attention(qq, k_dps, v_dps, pt_i(i), q_pos,
                               kv_lens)

    # 3. token-major layout, same math in its native order.
    def attend_tm(qq, i):
        qg = qq.reshape(B, 1, KV, NH // KV, D)
        k = k_tm[:, pt_i(i)]  # [kv, B, P, ps, d]
        v = v_tm[:, pt_i(i)]
        scores = jnp.einsum(
            "btkgd,kbpcd->bkgtpc", qg, k,
            preferred_element_type=jnp.float32) * scale
        token_pos = (jnp.arange(PAGES_PER_SEQ)[:, None] * PS
                     + jnp.arange(PS)[None, :])
        mask = ((token_pos[None, None] <= q_pos[:, :, None, None])
                & (token_pos[None] < kv_lens[:, None, None])[:, None])
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
        shape = scores.shape
        probs = jax.nn.softmax(
            scores.reshape(*shape[:-2], -1), axis=-1).reshape(shape)
        out = jnp.einsum(
            "bkgtpc,kbpcd->btkgd", probs.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return out.reshape(B, 1, NH, D).astype(qq.dtype)

    # 4. dense per-row K/V: the no-gather bound.
    def attend_dense(qq, i):
        qg = qq.reshape(B, 1, KV, NH // KV, D)
        scores = jnp.einsum(
            "btkgd,bckd->bkgtc", qg, k_dense,
            preferred_element_type=jnp.float32) * scale
        token_pos = jnp.arange(ctx)
        mask = ((token_pos[None, None] <= q_pos[:, :, None])
                & (token_pos[None] < kv_lens[:, None])[:, None])
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bkgtc,bckd->btkgd", probs.astype(v_dense.dtype), v_dense,
            preferred_element_type=jnp.float32)
        return out.reshape(B, 1, NH, D).astype(qq.dtype)

    # 5. gather, flatten to dense shape, then dense math.
    def attend_flat(qq, i):
        qg = qq.reshape(B, 1, KV, NH // KV, D)
        k = jnp.transpose(k_dps[:, pt_i(i)], (1, 2, 4, 0, 3)).reshape(
            B, ctx, KV, D)
        v = jnp.transpose(v_dps[:, pt_i(i)], (1, 2, 4, 0, 3)).reshape(
            B, ctx, KV, D)
        scores = jnp.einsum(
            "btkgd,bckd->bkgtc", qg, k,
            preferred_element_type=jnp.float32) * scale
        token_pos = jnp.arange(ctx)
        mask = ((token_pos[None, None] <= q_pos[:, :, None])
                & (token_pos[None] < kv_lens[:, None])[:, None])
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bkgtc,bckd->btkgd", probs.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return out.reshape(B, 1, NH, D).astype(qq.dtype)

    cases = [("gather_dps", gather_dps), ("attend_dps", attend_dps),
             ("attend_tm", attend_tm), ("attend_dense", attend_dense),
             ("attend_flat", attend_flat)]

    # Numerical parity across implementations first (same inputs;
    # i=0 makes the table rotation the identity).
    ref = np.asarray(attend_dps(q, jnp.int32(0)), np.float32)
    for name, fn in cases[2:]:
        got = np.asarray(fn(q, jnp.int32(0)), np.float32)
        err = float(np.max(np.abs(got - ref)))
        print(f"# parity {name}: max|diff| = {err:.5f}")
        assert err < 0.1, (name, err)

    # Paired-length differencing: time an N-step and a 5N-step chain
    # and take (T5N - TN) / 4N. The constant per-dispatch cost
    # (dispatch, host sync, scan setup) cancels EXACTLY.
    n_lo, n_hi = STEPS, STEPS * 5
    for name, fn in cases:
        p_lo, p_hi = chain(fn, n_lo), chain(fn, n_hi)
        walls = {}
        for tag, prog in (("lo", p_lo), ("hi", p_hi)):
            jax.device_get(prog(q)[-1])  # compile + warm
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.device_get(prog(q)[-1])
                best = min(best, time.perf_counter() - t0)
            walls[tag] = best
        per = (walls["hi"] - walls["lo"]) / (n_hi - n_lo)
        row = {"case": name,
               "ms_per_invocation": round(per * 1e3, 3),
               "wall_lo_ms": round(walls["lo"] * 1e3, 1),
               "wall_hi_ms": round(walls["hi"] * 1e3, 1)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"backend": jax.default_backend(),
                   "shape": {"B": B, "NH": NH, "KV": KV, "D": D,
                             "PS": PS, "P": PAGES_PER_SEQ,
                             "steps": STEPS},
                   "rows": rows}, f, indent=1)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
