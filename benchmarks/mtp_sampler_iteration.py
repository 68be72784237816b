"""The drafting burst's sampler alone: what an iteration does between
the head's two products and the next iteration's forward, the parent's
form (PR 43's) against the one the program runs.

    chiprun -- python3 benchmarks/mtp_sampler_iteration.py
    JAX_PLATFORMS=cpu python3 benchmarks/mtp_sampler_iteration.py \
        --rows 8 --vocab 2048 --iterations 4              # a rehearsal
    JAX_PLATFORMS=cpu python3 benchmarks/mtp_sampler_iteration.py \
        --compile-for-v5e /root/scratch/sampler           # the text

An iteration verifies each row's draft against two positions' logits
under the module's proposal, commits one or two tokens, and draws the
next draft from the module's next logits (``engine/model_runner.py``
``_decode_burst_draft_impl``, scopes ``mtp_verify`` and the draw of
``mtp_draft``). Both forms run MANY iterations inside one program (a
``lax.scan``: a call under 0.25 ms reads Python's dispatch, ROADMAP S18
(3)) on the same logits at the GLM cell's shapes: 160 rows, a
vocabulary of 154880, temperature 0.7, a proposer correlated with the
target so that about 0.35 of the drafts are accepted, as the cell's
random weights accept. The logits ride the scan's carry and one
element of each is nudged by the committed tokens every iteration, so
the compiler can hoist none of an iteration's passes out of the loop.

``parent``: logits ``[B, 2, V]`` with the positions in a minor axis,
``spec_verify(draft_probs=)`` as PR 43 had it (kept below, verbatim but
for the names), the proposal a plane of probabilities on the carry, the
draft drawn from ``log(softmax(.))``.
``planes``: each position a dense ``[B, V]`` plane, ``ops/sampling.py``
``verify_proposal`` and ``draw_proposal``, the proposal the module's
logits.

Prints one JSON line: milliseconds an iteration of each form, the share
of drafts each accepted, and the device. On the CPU the times are the
CPU's and say nothing of the chip (PERF.md section 6, PR 44).
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from production_stack_tpu.ops.sampling import (  # noqa: E402
    NEG_INF,
    _mask_top_k_top_p,
    draw_proposal,
    verify_proposal,
)

ROWS, VOCAB, TEMPERATURE = 160, 154880, 0.7
# Target logits N(0, SPREAD); the proposer's are MIX of the first
# position's and the rest its own: sum(min(p, q)) is 0.358 at
# temperature 0.7 and a vocabulary of 154880 (float64 NumPy, four
# draws), the share a drafting row accepts.
SPREAD, MIX = 2.0, 0.8


# ---- the parent's form (PR 43, ops/sampling.py at a705785) -----------------


def parent_sampling_probs(logits, temperature, top_p, top_k):
    safe_temp = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_temp[:, None]
    needs_mask = jnp.any((top_k > 0) | (top_p < 1.0))
    return jax.nn.softmax(jax.lax.cond(
        needs_mask, lambda: _mask_top_k_top_p(scaled, top_p, top_k),
        lambda: scaled), axis=-1)


def _parent_verify_proposal(logits, drafts, in_draft, draft_probs,
                            temperature, top_p, top_k, key, accept_greedy,
                            greedy_final):
    b, s, vocab = logits.shape
    stochastic = temperature > 0
    dsafe = jnp.clip(drafts, 0)
    probs = parent_sampling_probs(
        logits.reshape(b * s, vocab), jnp.repeat(temperature, s),
        jnp.repeat(top_p, s), jnp.repeat(top_k, s)).reshape(b, s, vocab)
    q = jnp.where(in_draft[..., None], draft_probs, 0.0)
    p_draft = jnp.take_along_axis(
        probs[:, :-1], dsafe[..., None], axis=-1)[..., 0]
    q_draft = jnp.take_along_axis(q, dsafe[..., None], axis=-1)[..., 0]
    key_u, key_r = jax.random.split(key)
    u = jax.random.uniform(key_u, (b, s - 1))
    accept = jnp.where(stochastic[:, None], u * q_draft < p_draft,
                       accept_greedy) & in_draft
    a = jnp.cumprod(accept.astype(jnp.int32), axis=-1).sum(axis=-1)
    at = a[:, None, None]
    p_a = jnp.take_along_axis(probs, at, axis=1)[:, 0]
    q_a = jnp.take_along_axis(
        jnp.pad(q, ((0, 0), (0, 1), (0, 0))), at, axis=1)[:, 0]
    residual = jnp.maximum(p_a - q_a, 0.0)
    resampled = jax.random.categorical(
        key_r, jnp.where(residual > 0, jnp.log(residual), NEG_INF),
        axis=-1).astype(jnp.int32)
    final_a = jnp.where(
        stochastic, resampled,
        jnp.take_along_axis(greedy_final, a[:, None], axis=1)[:, 0])
    return accept, jnp.broadcast_to(final_a[:, None], (b, s))


def parent_spec_verify(logits, drafts, draft_lens, temperature, top_p,
                       top_k, key, draft_probs):
    """``spec_verify(draft_probs=)``: the lines of it that branch ran."""
    b, s, vocab = logits.shape
    pos = jnp.arange(s)[None, :]
    in_draft = pos[:, :-1] < draft_lens[:, None]
    dsafe = jnp.clip(drafts, 0)
    stochastic = temperature > 0
    remove = (jax.nn.one_hot(dsafe, vocab, dtype=bool)
              & in_draft[..., None])
    remove = jnp.pad(remove, ((0, 0), (0, 1), (0, 0)))
    greedy_targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    greedy_final = jnp.argmax(
        jnp.where(remove, NEG_INF, logits), axis=-1).astype(jnp.int32)
    accept_greedy = (drafts == greedy_targets[:, :-1]) & in_draft
    accept, final = jax.lax.cond(
        jnp.any(stochastic),
        lambda: _parent_verify_proposal(
            logits, drafts, in_draft, draft_probs, temperature, top_p,
            top_k, key, accept_greedy, greedy_final),
        lambda: (accept_greedy, greedy_final))
    a = jnp.cumprod(accept.astype(jnp.int32), axis=-1).sum(axis=-1)
    drafts_padded = jnp.pad(drafts, ((0, 0), (0, 1)))
    return jnp.where(
        pos < a[:, None], drafts_padded,
        jnp.where(pos == a[:, None], final, -1)).astype(jnp.int32)


def parent_step(logits, module_logits, proposal, draft, has_draft,
                sampling, key):
    """``logits [B, 2, V]``, ``proposal [B, V]`` probabilities ->
    (tokens [B, 2], the next draft, the next proposal)."""
    temperature, top_p, top_k = sampling
    key_verify, key_draft = jax.random.split(key)
    out2 = parent_spec_verify(
        jnp.stack([logits[:, 0], logits[:, 1]], axis=1), draft[:, None],
        has_draft.astype(jnp.int32), temperature, top_p, top_k,
        key_verify, draft_probs=proposal[:, None])
    q = parent_sampling_probs(module_logits, temperature, top_p, top_k)
    draft = jnp.where(
        temperature > 0,
        jax.random.categorical(key_draft, jnp.log(q), axis=-1),
        jnp.argmax(module_logits, axis=-1)).astype(jnp.int32)
    return out2, draft, q


# ---- the program's form -----------------------------------------------------


def planes_step(logits, module_logits, proposal, draft, has_draft,
                sampling, key):
    """``logits [2, B, V]``, ``proposal [B, V]`` logits -> (tokens
    [B, 2], the next draft, None: the next proposal is
    ``module_logits`` as it stands, where the head wrote it)."""
    temperature, top_p, top_k = sampling
    key_verify, key_draft = jax.random.split(key)
    out2 = verify_proposal(
        logits, draft[:, None], has_draft.astype(jnp.int32), (proposal,),
        temperature, top_p, top_k, key_verify)
    return (out2, draw_proposal(module_logits, temperature, top_p, top_k,
                                key_draft), None)


FORMS = {"parent": (parent_step, 1), "planes": (planes_step, 0)}


def run(form: str, target, module, sampling, key, iterations: int):
    """``iterations`` of ``form`` in one program: (drafts offered,
    drafts accepted, a checksum of what was committed). ``target [2, B,
    V]`` is laid out as the form reads it; the module proposes from the
    same plane every iteration."""
    step, position_axis = FORMS[form]
    logits0 = jnp.moveaxis(target, 0, position_axis)
    rows = module.shape[0]
    origin = (0,) * 3

    def body(carry, step_key):
        logits, module_logits, proposal, draft, has_draft, seen = carry
        # A nudge that hangs on the last commit, in place: the planes
        # are the loop's own, so no pass over them is loop-invariant.
        nudge = 1e-6 * (seen[2] % 3).astype(jnp.float32)
        logits = logits.at[origin].add(nudge)
        module_logits = module_logits.at[origin[:2]].add(nudge)
        # The planes' proposal is the module's plane itself (in the
        # program the head writes a new one each iteration; a copy of
        # this one would be a pass the program does not make).
        out2, draft_next, proposal = step(
            logits, module_logits,
            module_logits if proposal is None else proposal, draft,
            has_draft, sampling, step_key)
        seen = seen + jnp.stack([
            jnp.sum(has_draft), jnp.sum(has_draft & (out2[:, 1] >= 0)),
            jnp.sum(jnp.clip(out2, 0)) % 1024]).astype(jnp.int32)
        return (logits, module_logits, proposal, draft_next,
                jnp.ones((rows,), bool), seen), None

    carry = (logits0, module,
             jnp.zeros_like(module) if form == "parent" else None,
             jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), bool),
             jnp.zeros((3,), jnp.int32))
    carry, _ = jax.lax.scan(body, carry, jax.random.split(key, iterations))
    return carry[-1]


def make_logits(key, rows: int, vocab: int):
    """(target [2, rows, vocab], module [rows, vocab]) float32."""
    k_target, k_own = jax.random.split(key)
    target = SPREAD * jax.random.normal(k_target, (2, rows, vocab),
                                        jnp.float32)
    own = SPREAD * jax.random.normal(k_own, (rows, vocab), jnp.float32)
    return target, MIX * target[0] + (1 - MIX ** 2) ** 0.5 * own


def sampling_of(rows: int, temperature: float):
    return (jnp.full((rows,), temperature, jnp.float32),
            jnp.ones((rows,), jnp.float32), jnp.zeros((rows,), jnp.int32))


def compile_for_v5e(out_dir: str, rows: int, vocab: int) -> dict:
    """Both forms' one iteration compiled for a described (not
    attached) v5e; the texts go to ``out_dir``. Returns each form's
    census (``plane_census``)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    pathlib.Path(out_dir).mkdir(parents=True, exist_ok=True)
    found = {}
    for form in FORMS:
        text = jax.jit(FORMS[form][0]).lower(
            *step_shapes(form, rows, vocab, chip)).compile().as_text()
        pathlib.Path(out_dir, f"{form}.txt").write_text(text)
        found[form] = plane_census(text, rows, vocab)
    return found


def step_shapes(form: str, rows: int, vocab: int, sharding=None):
    """One iteration's arguments, as shapes."""
    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    logits = [rows, vocab]
    logits.insert(FORMS[form][1], 2)
    return (shape(tuple(logits)), shape((rows, vocab)),
            shape((rows, vocab)), shape((rows,), jnp.int32),
            shape((rows,), bool),
            (shape((rows,)), shape((rows,)), shape((rows,), jnp.int32)),
            shape((2,), jnp.uint32))


def plane_census(text: str, rows: int, vocab: int) -> dict:
    """What a compiled iteration's text holds of the logits.

    ``pair_arrays``: every float32 array of ``rows x 2 x vocab``
    elements that an instruction makes or reads, as ``shape{layout}
    opcode`` with its count. The planes' form has one, the positions
    outermost under dense ``T(8,128)`` tiles, and only hands it on
    (``parameter``, ``bitcast``, ``get-tuple-element``); the parent's
    has ``[rows, 2, vocab]`` under ``T(2,128)`` tiles and reshapes,
    transposes, pads and gathers of it.
    ``plane_passes``: by computation (fused ones left out, a branch
    that sorts the vocabulary marked ``sorts``), the instructions that
    read or write a whole float32 ``[rows, vocab]`` plane or a pair of
    them: the passes an iteration makes over the vocabulary."""
    import re
    shape = re.compile(r"f32\[([\d,]+)\](\{[^}]*\})?")
    instruction = re.compile(
        r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?)\s([a-z][a-z\-]*)\((.*)")
    handed_on = ("parameter", "get-tuple-element", "tuple", "bitcast",
                 "constant", "copy-done")
    plane, pair = rows * vocab, 2 * rows * vocab

    def shapes(result):
        found = []
        for dims, layout in shape.findall(result):
            n = 1
            for d in dims.split(","):
                n *= int(d)
            found.append((n, f"f32[{dims}]{layout}"))
        return found

    census = {"pair_arrays": {}, "plane_passes": {}}
    computation, fused, sorts, made = None, False, set(), {}
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(", line)
        if head:
            computation = head.group(1)
            fused = computation.startswith("%fused_computation")
            continue
        m = instruction.match(line)
        if not m or computation is None:
            continue
        name, result, opcode, operands = m.groups()
        if opcode == "sort":
            sorts.add(computation)
        sizes = {n for n, _ in shapes(result)}
        made[(computation, name)] = sizes
        for n, text_of in shapes(result):
            if n == pair:
                by = census["pair_arrays"]
                key = f"{text_of} {opcode}"
                by[key] = by.get(key, 0) + 1
        for operand in re.findall(r"%[\w.\-]+", operands.split("),")[0]):
            sizes = sizes | made.get((computation, operand), set())
        if (not fused and opcode not in handed_on
                and sizes & {plane, pair}):
            by = census["plane_passes"]
            by.setdefault(computation, []).append(name.lstrip("%"))
    census["plane_passes"] = {
        name + (" sorts" if name in sorts else ""): passes
        for name, passes in census["plane_passes"].items()}
    return census


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=ROWS)
    ap.add_argument("--vocab", type=int, default=VOCAB)
    ap.add_argument("--temperature", type=float, default=TEMPERATURE)
    ap.add_argument("--iterations", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compile-for-v5e", metavar="DIR", default=None,
                    help="compile one iteration of each form for a "
                         "described v5e, write the texts there and "
                         "print the census; nothing runs")
    args = ap.parse_args()
    if args.compile_for_v5e:
        print(json.dumps(compile_for_v5e(args.compile_for_v5e, args.rows,
                                         args.vocab)))
        return
    target, module = make_logits(jax.random.PRNGKey(args.seed), args.rows,
                                 args.vocab)
    sampling = sampling_of(args.rows, args.temperature)
    device = jax.devices()[0]
    line = {"rows": args.rows, "vocab": args.vocab,
            "temperature": args.temperature,
            "iterations": args.iterations,
            "device": {"platform": device.platform,
                       "kind": device.device_kind}}
    for form in FORMS:
        program = jax.jit(run, static_argnames=("form", "iterations"))
        times = []
        for repeat in range(args.repeats + 1):      # the first compiles
            key = jax.random.PRNGKey(args.seed + 1 + repeat)
            start = time.perf_counter()
            seen = jax.block_until_ready(program(
                form, target, module, sampling, key, args.iterations))
            times.append(time.perf_counter() - start)
        offered, accepted, _ = (int(x) for x in seen)
        line[form] = {
            "ms_per_iteration": [round(1e3 * t / args.iterations, 4)
                                 for t in times[1:]],
            "compile_and_first_s": round(times[0], 2),
            "accept_rate": round(accepted / max(offered, 1), 4)}
    print(json.dumps(line))


if __name__ == "__main__":
    main()
