"""The block-diffusion sampler alone: what a denoising pass does between
the head's product and the next pass's forward, the parent's form
(PR 56's) against the one the program runs.

    chiprun -- python3 benchmarks/unmask_iteration.py
    JAX_PLATFORMS=cpu python3 benchmarks/unmask_iteration.py \
        --rows 8 --vocab 2048 --passes 4                  # a rehearsal
    JAX_PLATFORMS=cpu python3 benchmarks/unmask_iteration.py \
        --compile-for-v5e /root/scratch/unmask            # the text

A denoising pass of the block burst (``engine/model_runner.py``
``_decode_burst_block_impl``) hands ``ops/sampling.py`` ``unmask_block``
the block's logits, position-major ``[T, B, V]``, and gets a token and
a confidence for every place and the places to commit. Both forms run
MANY passes inside one program (a ``lax.scan``: a call under 0.25 ms
reads Python's dispatch, ROADMAP S18 (3)) on the same logits at the
SDAR cell's shapes: 256 rows, 4 places, a vocabulary of 151936,
temperature 0.7, no top-k and no top-p (``--top-k`` gives every row
one: the sorted form). The logits ride the scan's carry and one
element of each plane is nudged by the drawn tokens every pass, so the
compiler can hoist none of a pass's reads out of the loop.

``parent``: Gumbel-max, one variate a logit and three reductions a
plane (``unmask_block`` as PR 56 had it, kept below verbatim but for
the names).
``two_pass``: ``ops/sampling.py`` ``unmask_block`` as it stands: one
uniform variate a (row, place), the argmax and the block sums of each
plane, the rest on ``[B, blocks]`` and ``[B, 128]``.

Prints one JSON line: milliseconds a pass of each form, each form's
largest difference between the frequencies of the ids it drew and the
softmax on a small vocabulary (``--check-vocab`` ids, wider than one
block; some 0.01 is what 65536 draws leave), and the device. On the CPU
the times are the CPU's and say nothing of the chip (PERF.md section 6,
PR 57).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from production_stack_tpu.ops.sampling import (  # noqa: E402
    _inverse_temperature,
    _mask_top_k_top_p,
    _needs_mask,
    unmask_block,
)

ROWS, PLACES, VOCAB, TEMPERATURE = 256, 4, 151936, 0.7
SPREAD = 2.0        # the logits are N(0, SPREAD)


# ---- the parent's form (PR 56, ops/sampling.py at 362f4bc) -----------------


def parent_unmask_block(logits, masked, quota, strategy, threshold,
                        temperature, top_p, top_k, key):
    t = logits.shape[0]
    b = masked.shape[0]
    stochastic = temperature > 0
    scale = _inverse_temperature(temperature)
    keys = jax.random.split(key, t)
    rows = jnp.arange(b)

    def draw(j, mask):
        scaled = mask(logits[j] * scale[:, None])
        noise = jax.random.gumbel(keys[j], scaled.shape, scaled.dtype)
        x = jnp.argmax(
            scaled + noise * stochastic.astype(scaled.dtype)[:, None],
            axis=-1)
        m = jnp.max(scaled, axis=-1)
        lse = jnp.log(jnp.sum(jnp.exp(scaled - m[:, None]), axis=-1))
        return x.astype(jnp.int32), jnp.exp(scaled[rows, x] - m - lse)

    with jax.named_scope("unmask_block"):
        needs_mask = _needs_mask(top_p, top_k)
        drawn = [jax.lax.cond(
            needs_mask,
            lambda j=j: draw(j, lambda x: _mask_top_k_top_p(x, top_p,
                                                             top_k)),
            lambda j=j: draw(j, lambda x: x)) for j in range(t)]
        x0 = jnp.stack([x for x, _ in drawn], axis=1)
        conf = jnp.stack([c for _, c in drawn], axis=1)
        place = jnp.arange(t)[None, :]
        n = quota[:, None]
        first = jnp.argmax(masked, axis=1)[:, None]
        sequential = (place >= first) & (place < first + n)
        c = jnp.where(masked, conf, -jnp.inf)
        ahead = ((c[:, None, :] > c[:, :, None])
                 | ((c[:, None, :] == c[:, :, None])
                    & (place[:, None, :] < place[:, :, None])))
        static = jnp.sum(ahead, axis=2) < n
        high = c > threshold[:, None]
        dynamic = jnp.where(
            jnp.sum(high, axis=1, keepdims=True) >= n, high, static)
        kind = strategy[:, None]
        commit = jnp.where(kind == 0, sequential,
                           jnp.where(kind == 1, static, dynamic))
        return x0, commit & masked, conf


FORMS = {"parent": parent_unmask_block, "two_pass": unmask_block}


# ---- many passes in one program --------------------------------------------


def rows_of(rows: int, places: int, temperature: float, top_k: int):
    """A pass's arguments after the logits and before the key: every
    place masked, two of them to commit, the static rule (the cell's)."""
    return (jnp.ones((rows, places), bool),
            jnp.full((rows,), max(places // 2, 1), jnp.int32),
            jnp.ones((rows,), jnp.int32),
            jnp.full((rows,), 0.9, jnp.float32),
            jnp.full((rows,), temperature, jnp.float32),
            jnp.ones((rows,), jnp.float32),
            jnp.full((rows,), top_k, jnp.int32))


def run(form: str, logits, how, key, passes: int):
    """``passes`` of ``form`` in one program on ``logits [T, B, V]``:
    (a checksum of the tokens drawn, the places committed, the summed
    confidence)."""
    step = FORMS[form]

    def body(carry, step_key):
        logits, seen = carry
        # A nudge that hangs on the last draw, in place: the planes are
        # the loop's own, so no pass over them is loop-invariant.
        nudge = 1e-6 * (seen[0] % 3)
        logits = logits.at[:, 0, 0].add(nudge)
        x0, commit, conf = step(logits, *how, step_key)
        seen = seen + jnp.stack([
            (jnp.sum(x0) % 1024).astype(jnp.float32),
            jnp.sum(commit).astype(jnp.float32), jnp.sum(conf)])
        return (logits, seen), None

    (_, seen), _ = jax.lax.scan(
        body, (logits, jnp.zeros((3,), jnp.float32)),
        jax.random.split(key, passes))
    return seen


def frequency_gap(form: str, vocab: int, temperature: float,
                  seed: int) -> float:
    """The largest difference, over the ids of one distribution of
    ``vocab`` ids, between the share of 65536 draws that ``form`` gave
    an id and its softmax probability (float64)."""
    rng = np.random.default_rng(seed)
    base = (SPREAD * rng.standard_normal(vocab)).astype(np.float32)
    rows, places, rounds = 1024, 4, 16
    logits = jnp.asarray(np.tile(base, (places, rows, 1)))
    how = rows_of(rows, places, temperature, 0)
    draw = jax.jit(FORMS[form])
    counts = np.zeros(vocab)
    for r in range(rounds):
        x0, _, _ = draw(logits, *how, jax.random.PRNGKey(seed + r))
        counts += np.bincount(np.asarray(x0).ravel(), minlength=vocab)
    p = np.exp(base.astype(np.float64) / temperature)
    return float(np.abs(counts / counts.sum() - p / p.sum()).max())


# ---- the compiled text, without a chip --------------------------------------


def pass_shapes(rows: int, places: int, vocab: int, sharding=None):
    """One pass's arguments, as shapes."""
    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    return (shape((places, rows, vocab)), shape((rows, places), bool),
            shape((rows,), jnp.int32), shape((rows,), jnp.int32),
            shape((rows,)), shape((rows,)), shape((rows,)),
            shape((rows,), jnp.int32), shape((2,), jnp.uint32))


def plane_census(text: str, rows: int, vocab: int) -> dict:
    """What a compiled pass's text does with whole planes, by
    computation (fused ones left out; a branch that sorts the
    vocabulary is marked ``sorts``): ``reads`` are the instructions
    with a plane of ``rows x vocab`` elements (or all of them) among
    their operands, ``gathers`` those of them that are the device's
    gather (they read what they are asked for, not the plane),
    ``writes`` the instructions that make a plane. The two-pass form
    reads each plane twice in its plain branch, gathers from it twice
    (an element a row, a block a row) and writes none."""
    padded = -(-vocab // 128) * 128
    sizes = {rows * vocab, rows * padded}
    shape = re.compile(r"(?:f32|s32|u32)\[([\d,]+)\]")
    instruction = re.compile(
        r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?)\s([a-z][a-z\-]*)\((.*)")
    handed_on = ("parameter", "get-tuple-element", "tuple", "bitcast",
                 "constant", "copy-done", "conditional", "while")

    def planes_in(result):
        found = 0
        for dims in shape.findall(result):
            n = 1
            for d in dims.split(","):
                n *= int(d)
            found += any(n % s == 0 and n // s in (1, 2, 4) for s in sizes)
        return found

    census, made, sorts, computation = {}, {}, set(), None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
        if head:
            computation = head.group(1)
            continue
        m = instruction.match(line)
        if (not m or computation is None
                or computation.startswith("%fused_computation")):
            continue
        name, result, opcode, operands = m.groups()
        if opcode == "sort":
            sorts.add(computation)
        made[(computation, name)] = planes_in(result)
        if opcode in handed_on:
            continue
        reads = sum(made.get((computation, operand), 0) for operand in
                    re.findall(r"%[\w.\-]+", operands.split("),")[0]))
        entry = census.setdefault(
            computation, {"reads": [], "gathers": [], "writes": []})
        if reads:
            gathers = "kind=kCustom" in line and "/gather" in line
            entry["gathers" if gathers else "reads"].append(
                name.lstrip("%"))
        if made[(computation, name)]:
            entry["writes"].append(name.lstrip("%"))
    return {name + (" sorts" if name in sorts else ""): entry
            for name, entry in census.items()
            if entry["reads"] or entry["gathers"] or entry["writes"]}


def compile_for_v5e(out_dir: str, rows: int, places: int,
                    vocab: int) -> dict:
    """Both forms' one pass compiled for a described (not attached)
    v5e; the texts go to ``out_dir``. Returns each form's census."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    pathlib.Path(out_dir).mkdir(parents=True, exist_ok=True)
    found = {}
    for form, step in FORMS.items():
        compiled = jax.jit(step).lower(
            *pass_shapes(rows, places, vocab, chip)).compile()
        text = compiled.as_text()
        pathlib.Path(out_dir, f"{form}.txt").write_text(text)
        found[form] = {
            "temporaries_bytes":
                compiled.memory_analysis().temp_size_in_bytes,
            "planes": plane_census(text, rows, vocab)}
    return found


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--places", type=int, default=PLACES)
    ap.add_argument("--vocab", type=int, default=VOCAB)
    ap.add_argument("--temperature", type=float, default=TEMPERATURE)
    ap.add_argument("--top-k", type=int, default=0,
                    help="every row's top-k (0: none, the plain form)")
    ap.add_argument("--passes", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--check-vocab", type=int, default=300)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compile-for-v5e", metavar="DIR", default=None,
                    help="compile one pass of each form for a described "
                         "v5e, write the texts there and print the "
                         "census; nothing runs")
    args = ap.parse_args()
    if args.compile_for_v5e:
        print(json.dumps(compile_for_v5e(
            args.compile_for_v5e, args.rows, args.places, args.vocab)))
        return
    logits = SPREAD * jax.random.normal(
        jax.random.PRNGKey(args.seed),
        (args.places, args.rows, args.vocab), jnp.float32)
    how = rows_of(args.rows, args.places, args.temperature, args.top_k)
    device = jax.devices()[0]
    line = {"rows": args.rows, "places": args.places, "vocab": args.vocab,
            "temperature": args.temperature, "top_k": args.top_k,
            "passes": args.passes,
            "device": {"platform": device.platform,
                       "kind": device.device_kind}}
    program = jax.jit(run, static_argnames=("form", "passes"))
    for form in FORMS:
        times = []
        for repeat in range(args.repeats + 1):      # the first compiles
            key = jax.random.PRNGKey(args.seed + 1 + repeat)
            start = time.perf_counter()
            seen = jax.block_until_ready(
                program(form, logits, how, key, args.passes))
            times.append(time.perf_counter() - start)
        drawn = args.passes * args.rows * args.places
        line[form] = {
            "ms_per_pass": [round(1e3 * t / args.passes, 4)
                            for t in times[1:]],
            "compile_and_first_s": round(times[0], 2),
            "committed_share": round(float(seen[1]) / drawn, 4),
            "mean_confidence": round(float(seen[2]) / drawn, 6),
            "frequency_gap": round(frequency_gap(
                form, args.check_vocab, args.temperature, args.seed), 5)}
    print(json.dumps(line))


if __name__ == "__main__":
    main()
