"""The window's two served forms on the chip, at the published head
shapes of k-exaone-236b-a23b-ep16 (64 query heads over 8 KV heads of
128, a window of 128, bfloat16): a prefill chunk through the compiled
Pallas form (ops/window_attention.py ``window_prefill_pallas``) and a
burst's decode step through the XLA form (``window_attention``), each
against a float32 softmax under an explicit mask ``i - 128 < j <= i``
over the row's own sequence, written here in numpy with no ring and
none of the program's code; and the decode form's time a call.

    chiprun -- python3 benchmarks/window_ring_check.py

What the benchmark's own check cannot reach: its prompts are 129-256
tokens, one chunk of 256, so no served prefill chunk of the check reads
a ring the chunk before wrote. Here a chunk does, at every fill of the
ring (rows that hold nothing, less than a window, exactly one, and
many, so that the ring has wrapped), and a burst's tail crosses the
ring's edge. The rings are laid out by hand from the sequences (token
``p`` at place ``p mod 128``). Prints one JSON line; exits 1 where a
form differs from the reference by more than bfloat16's rounding of a
softmax over 128 keys and of its output allows: 2e-2 of the larger of
1 and the value (a value of 4 rounds by 0.0156 alone; a key wrongly
in or out of sight moves a value by a tenth and more).

Without a TPU it exits 2 and measures nothing; ``--interpret`` runs
the kernel in Pallas interpret mode, float32, as a dry run of the
script itself, says so in its line (``"interpret": true``) and times
nothing: no number of such a run is a device's.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from production_stack_tpu.ops import window_attention as wa  # noqa: E402

KV, HEADS, D, W = 8, 64, 128, 128
SLOTS = 137
LIMIT = 2e-2


def masked_softmax(q, keys, values, q_at, key_at):
    """float32 attention of one row under an explicit mask: q [T,
    HEADS, D] at positions ``q_at [T]``, keys/values [N, KV, D] at
    ``key_at [N]``; key j is in sight of query i iff ``i - W < j <=
    i``. Returns [T, HEADS, D]."""
    mask = ((key_at[None, :] <= q_at[:, None])
            & (key_at[None, :] > q_at[:, None] - W))        # [T, N]
    k = np.repeat(keys, HEADS // KV, axis=1)                # [N, HEADS, D]
    v = np.repeat(values, HEADS // KV, axis=1)
    scores = np.einsum("thd,nhd->htn", q, k) / np.sqrt(np.float32(D))
    scores = np.where(mask[None], scores, -np.inf)
    probs = np.exp(scores - scores.max(axis=-1, keepdims=True))
    probs /= probs.sum(axis=-1, keepdims=True)
    return np.einsum("htn,nhd->thd", probs, v)


def off(got, want) -> float:
    """The largest difference, as a share of the larger of 1 and the
    reference's value."""
    return float((np.abs(got - want) / np.maximum(1.0, np.abs(want))).max())


def rings_of(rng, held, dtype):
    """Each row's last ``min(held, W)`` tokens, drawn, and the ring
    pools that hold them: ``(k_seq, v_seq, first, k_ring, v_ring,
    slots)``, ``k_seq[r]`` [n, KV, D] float32 (rounded to ``dtype``)
    from position ``first[r]`` on; row r owns slot r + 1; what a row
    does not hold is noise, which no mask may let through."""
    def noise(*shape):
        return np.array(jnp.asarray(rng.standard_normal(shape), dtype)
                        .astype(jnp.float32))
    k_ring, v_ring = noise(KV, SLOTS, D, W), noise(KV, SLOTS, D, W)
    k_seq, v_seq, first = [], [], []
    for r, n in enumerate(held):
        lo = max(0, n - W)
        k, v = noise(n - lo, KV, D), noise(n - lo, KV, D)
        for p in range(lo, n):
            k_ring[:, r + 1, :, p % W] = k[p - lo]
            v_ring[:, r + 1, :, p % W] = v[p - lo]
        k_seq.append(k), v_seq.append(v), first.append(lo)
    slots = jnp.arange(1, len(held) + 1, dtype=jnp.int32)
    return (k_seq, v_seq, first, jnp.asarray(k_ring, dtype),
            jnp.asarray(v_ring, dtype), slots)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--interpret", action="store_true",
                        help="dry run on a host without a TPU: Pallas "
                             "interpret mode, float32, nothing timed")
    args = parser.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.interpret:
        print(f"window_ring_check: backend {jax.default_backend()!r} is "
              "no TPU; nothing measured (--interpret for a dry run)",
              file=sys.stderr)
        return 2
    interpret = not on_chip
    dtype = jnp.float32 if interpret else jnp.bfloat16
    rng = np.random.default_rng(0)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def f32(x):
        return np.asarray(x.astype(jnp.float32))

    out = {"device": jax.devices()[0].device_kind, "interpret": interpret}

    # Prefill: 8 rows, chunks of 256 and of 16, after 0..3000 tokens.
    held = [0, 5, 127, 128, 129, 300, 1000, 3000]
    k_seq, v_seq, first, k_ring, v_ring, slots = rings_of(rng, held, dtype)
    held_j = jnp.asarray(held, jnp.int32)
    prefill = jax.jit(lambda *a: wa.window_prefill_pallas(
        *a, interpret=interpret))
    for chunk in (256, 16):
        q, k, v = (draw(8, chunk, HEADS, D), draw(8, chunk, KV, D),
                   draw(8, chunk, KV, D))
        n = [chunk, chunk, 7, chunk, chunk, 1, chunk, chunk]
        got = f32(prefill(q, k_ring, v_ring, slots, held_j, k, v,
                          held_j + jnp.asarray(n, jnp.int32)))
        worst = 0.0
        for r in range(8):
            want = masked_softmax(
                f32(q[r, :n[r]]),
                np.concatenate([k_seq[r], f32(k[r, :n[r]])]),
                np.concatenate([v_seq[r], f32(v[r, :n[r]])]),
                held[r] + np.arange(n[r]),
                np.arange(first[r], held[r] + n[r]))
            worst = max(worst, off(got[r, :n[r]], want))
        out[f"prefill_{chunk}_max_diff"] = worst

    # Decode, the XLA form: 128 rows, a tail of 32, four of its steps.
    rows, steps = 128, 32
    held = [int(n) for n in rng.integers(1, 5000, rows)]
    k_seq, v_seq, first, k_ring, v_ring, slots = rings_of(rng, held, dtype)
    held_j = jnp.asarray(held, jnp.int32)
    k_tail, v_tail = draw(rows, steps, KV, D), draw(rows, steps, KV, D)
    tail_at = held_j[:, None] + jnp.arange(steps)[None]
    decode = jax.jit(wa.window_attention)
    worst = 0.0
    for s in (0, 1, 17, 31):
        q = draw(rows, 1, HEADS, D)
        at = (held_j + s)[:, None]
        got, seen = decode(q, k_ring, v_ring, slots, held_j, at, k_tail,
                           v_tail, tail_at, tail_at <= at)
        got = f32(got)
        for r in range(rows):
            want = masked_softmax(
                f32(q[r]),
                np.concatenate([k_seq[r], f32(k_tail[r, :s + 1])]),
                np.concatenate([v_seq[r], f32(v_tail[r, :s + 1])]),
                np.asarray([held[r] + s]),
                np.arange(first[r], held[r] + s + 1))
            worst = max(worst, off(got[r], want))
            assert int(seen[r, 0]) == min(held[r] + s + 1, W)
    out["decode_max_diff"] = worst

    # Time a decode call: 64 calls in one program, the output fed back.
    if on_chip:
        at = (held_j + 31)[:, None]

        def body(q, _):
            return wa.window_attention(
                q, k_ring, v_ring, slots, held_j, at, k_tail, v_tail,
                tail_at, tail_at <= at)[0].astype(q.dtype), None
        run = jax.jit(lambda q: jax.lax.scan(body, q, None, length=64)[0])
        q = draw(rows, 1, HEADS, D)
        jax.block_until_ready(run(q))
        t0 = time.perf_counter()
        for _ in range(5):
            q = run(q)
        jax.block_until_ready(q)
        out["decode_xla_us_a_call"] = (
            (time.perf_counter() - t0) / (5 * 64) * 1e6)
    out["ok"] = all(v <= LIMIT for k, v in out.items()
                    if k.endswith("max_diff"))
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
