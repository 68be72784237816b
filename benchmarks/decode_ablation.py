"""Decode-step ablation: attribute the per-token-step milliseconds.

Builder-captured 2026-07-31 (not measured by the driver): widening
the decode batch LOWERED throughput (1B b32 11.07 -> b64 6.99 -> b128
4.26 req/s), so the 13.5 ms/token-step at the served config is NOT
weight-stream bound — some per-row cost dominates. This probe attributes the step
by re-timing the real burst program with individual components
knocked out via monkeypatching the model's module globals (no product
code changes):

  full          the real body: forward + greedy sampling + feedback
  no_attn       paged_attention -> q (skip gather + softmax reads)
  no_kv_write   write_to_pages -> identity (skip the per-layer scatters)
  matmul_floor  both knocked out: weights/norms/rope/lm_head/sampling
  no_sample     full forward, sampling replaced by constant feedback
  deferred      the kv_tail burst body (read-only caches in the scan,
                one batched flush per layer at the end) — the served
                deferred_kv_writes path

All variants run b=32 rows x K chained steps in ONE compiled program
(lax.scan, caches donated) and are timed by PAIRED-LENGTH
DIFFERENCING: wall(K=160) - wall(K=32) over 128 steps, which cancels
the constant per-dispatch cost (dispatch, host sync, scan setup)
exactly. Deltas
vs `full` give the attribution; `matmul_floor` is the measured
weights floor to compare against the analytic ~3-4 ms (853M bf16
params / 819 GB/s + lm_head).

Run on a live chip:  python benchmarks/decode_ablation.py
Artifact: benchmarks/results/decode_ablation.json + markdown stdout.
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

# Served bench config shapes; --tiny shrinks them for the CPU smoke.
BATCH = 32
BURST = 32
PROMPT = 512
PAGE_SIZE = 128
NUM_PAGES = 512
TINY = False


def build_state():
    """1B bench geometry, per_layer caches, b rows mid-generation."""
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.engine.config import (
        bench_1b_model_config,
        tiny_model_config,
    )
    from production_stack_tpu.models import llama

    m = tiny_model_config("llama") if TINY else bench_1b_model_config()
    kv, d, ps, pages = (m.num_key_value_heads, m.head_dim,
                        PAGE_SIZE, NUM_PAGES)
    L = m.num_hidden_layers
    params = llama.init_params(m, jax.random.PRNGKey(0))
    k_cache = tuple(jnp.zeros((kv, pages, d, ps), m.jax_dtype)
                    for _ in range(L))
    v_cache = tuple(jnp.zeros((kv, pages, d, ps), m.jax_dtype)
                    for _ in range(L))
    rs = np.random.RandomState(0)
    # Page-table WIDTH must match the engine's (max_model_len /
    # page_size = 8 at the served config): the XLA gather reads every
    # table slot regardless of kv_lens, so width is a cost factor.
    if TINY:
        pages_per_seq = (PROMPT + BURST) // PAGE_SIZE + 2
    else:
        pages_per_seq = 1024 // PAGE_SIZE
    assert BATCH * pages_per_seq < pages
    pt = jnp.asarray(
        np.arange(1, BATCH * pages_per_seq + 1, dtype=np.int32)
        .reshape(BATCH, pages_per_seq))
    tokens = jnp.asarray(rs.randint(1, m.vocab_size - 1,
                                    size=(BATCH, 1)), jnp.int32)
    positions = jnp.full((BATCH, 1), PROMPT, jnp.int32)
    kv_lens = jnp.full((BATCH,), PROMPT + 1, jnp.int32)
    active = jnp.ones((BATCH,), bool)
    return m, params, k_cache, v_cache, tokens, positions, pt, kv_lens, active


def make_burst(m, variant: str, page_table, active):
    """The burst program for one ablation variant.

    Mirrors model_runner._decode_burst_impl's carry structure (token
    feedback, position/kv_len advance, donated caches) minus the
    lifecycle bookkeeping that is pure [B]-vector arithmetic.
    """
    import jax
    import jax.numpy as jnp

    from production_stack_tpu.models import llama
    from production_stack_tpu.ops.sampling import sample_tokens

    def sample(variant_tok, logits, step_rng):
        if variant == "no_sample":
            return variant_tok[:, 0]
        return sample_tokens(
            logits[:, 0, :], jnp.zeros((BATCH,)),
            jnp.ones((BATCH,)),
            jnp.zeros((BATCH,), jnp.int32), step_rng)

    def body(params, carry, step_rng):
        tok, pos, kvl, kc, vc = carry
        logits, kc, vc = llama.forward(
            params, m, tok, pos, page_table, kvl,
            active[:, None], kc, vc)
        sampled = sample(tok, logits, step_rng)
        return (sampled[:, None], pos + 1, kvl + 1, kc, vc), sampled

    def burst(params, tokens, positions, kv_lens, k_cache, v_cache,
              rng, num_steps):
        rngs = jax.random.split(rng, num_steps)
        carry = (tokens, positions, kv_lens, k_cache, v_cache)

        def scan_body(c, r):
            return body(params, c, r)

        (_, _, _, kc, vc), out = jax.lax.scan(scan_body, carry, rngs)
        return out, kc, vc

    def burst_deferred(params, tokens, positions, kv_lens, k_cache,
                       v_cache, rng, num_steps):
        """The served deferred path, at the SERVED tail width: chains
        of num_steps run as num_steps/BURST sequential BURST-wide
        bursts with a flush between each — tail width must NOT scale
        with the chain length or the paired-length differencing
        overstates tail-attention work that serving never does
        (mirrors model_runner._decode_burst_deferred_impl per burst).
        """
        from production_stack_tpu.ops.attention import write_to_pages

        assert num_steps % BURST == 0
        outs = []
        for chunk in range(num_steps // BURST):
            kv0 = positions[:, 0]
            tails = tuple(
                jnp.zeros((BATCH, BURST, m.num_key_value_heads,
                           m.head_dim), m.jax_dtype)
                for _ in range(m.num_hidden_layers))

            def dbody(carry, step_rng, kv0=kv0):
                tok, pos, kt, vt = carry
                logits, kt, vt = llama.forward(
                    params, m, tok, pos, page_table, kv0,
                    active[:, None], k_cache, v_cache,
                    kv_tail=(kt, vt))
                sampled = sample(tok, logits, step_rng)
                return (sampled[:, None], pos + 1, kt, vt), sampled

            rng, sub = jax.random.split(rng)
            rngs = jax.random.split(sub, BURST)
            (tokens, positions, kt, vt), out = jax.lax.scan(
                dbody, (tokens, positions, tails, tails), rngs)
            outs.append(out)
            tail_pos = kv0[:, None] + jnp.arange(BURST)[None, :]
            tail_valid = jnp.ones((BATCH, BURST), bool)
            k_cache = tuple(
                write_to_pages(c, kt[i], page_table, tail_pos,
                               tail_valid)
                for i, c in enumerate(k_cache))
            v_cache = tuple(
                write_to_pages(c, vt[i], page_table, tail_pos,
                               tail_valid)
                for i, c in enumerate(v_cache))
        return jnp.concatenate(outs, axis=0), k_cache, v_cache

    fn = burst_deferred if variant == "deferred" else burst
    return jax.jit(fn, donate_argnums=(4, 5), static_argnums=(7,))


def run_variant(variant: str):
    import jax.numpy as jnp

    from production_stack_tpu.models import llama

    orig_attn = llama.paged_attention
    orig_write = llama.write_to_pages
    try:
        if variant in ("no_attn", "matmul_floor"):
            llama.paged_attention = (
                lambda q, kc, vc, pt, pos, kl, layer=None: q)
        if variant in ("no_kv_write", "matmul_floor"):
            llama.write_to_pages = (
                lambda cache, new, pt, pos, valid, layer=None: cache)
        (m, params, k_cache, v_cache, tokens, positions, pt, kv_lens,
         active) = build_state()

        import jax

        # Paired-length differencing: (T_hi - T_lo) / (hi - lo) steps
        # cancels the constant per-dispatch cost exactly.
        n_lo, n_hi = BURST, BURST * 5
        walls = {}
        burst = make_burst(m, variant, pt, active)
        # Donated caches thread through both chain lengths (contents
        # don't affect timing; re-donating avoids 2 GB copies/call).
        state = {"kc": k_cache, "vc": v_cache}
        for tag, n in (("lo", n_lo), ("hi", n_hi)):

            def fn():
                out, kc2, vc2 = burst(
                    params, tokens, positions, kv_lens,
                    state["kc"], state["vc"], jax.random.PRNGKey(1),
                    n)
                state["kc"], state["vc"] = kc2, vc2
                return out

            jax.device_get(fn()[-1])  # compile + warm
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.device_get(fn()[-1])
                best = min(best, time.perf_counter() - t0)
            walls[tag] = best
        per = (walls["hi"] - walls["lo"]) / (n_hi - n_lo)
        return {
            "case": variant, "batch": BATCH,
            "burst_lo": n_lo, "burst_hi": n_hi,
            "ms_per_token_step": round(per * 1e3, 2),
            "wall_lo_ms": round(walls["lo"] * 1e3, 1),
            "wall_hi_ms": round(walls["hi"] * 1e3, 1),
        }
    finally:
        llama.paged_attention = orig_attn
        llama.write_to_pages = orig_write


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out",
                    default="benchmarks/results/decode_ablation.json")
    ap.add_argument("--variants", default=(
        "full,no_attn,no_kv_write,matmul_floor,no_sample"))
    ap.add_argument("--tiny", action="store_true",
                    help="tiny model + small shapes (CPU/CI smoke)")
    args = ap.parse_args(argv)
    if args.tiny:
        global BATCH, BURST, PROMPT, PAGE_SIZE, NUM_PAGES, TINY
        BATCH, BURST, PROMPT, PAGE_SIZE, NUM_PAGES, TINY = (
            2, 4, 16, 16, 32, True)

    import jax

    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )
    configure_compile_cache()
    backend = jax.default_backend()
    rows = []
    for v in args.variants.split(","):
        try:
            rows.append(run_variant(v))
        except Exception as e:  # noqa: BLE001 — record, continue
            rows.append({"case": v, "error": repr(e)[:300]})
        print(json.dumps(rows[-1]), flush=True)

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"backend": backend, "batch": BATCH, "burst": BURST,
                   "rows": rows}, f, indent=1)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
