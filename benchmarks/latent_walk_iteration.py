"""The latent (MLA) decode walk alone: one sublayer's attention of a
deferred burst (the query's absorption, the Pallas walk of the row's
pages and its tail, the values' up-projection) at the two latent cells'
shapes, many calls inside ONE program.

    chiprun -- python3 benchmarks/latent_walk_iteration.py \
        --chunks 3,4,5,6,7 --out chiprun_out/pr45/walk.json
    python3 benchmarks/latent_walk_iteration.py --repo-root scratch/parent \
        --chunks 3,5                  # another checkout's form (the parent's)
    JAX_PLATFORMS=cpu python3 benchmarks/latent_walk_iteration.py \
        --tiny --interpret --calls 2 --repeats 1          # a rehearsal
    JAX_PLATFORMS=cpu python3 benchmarks/latent_walk_iteration.py \
        --compile-for-v5e /root/scratch/walk              # the text

``SHAPES`` are the calls the cells make (chipbench/configs): 160 rows, a
plane ``[1, pages, 512 + 64, 128]`` bfloat16 under a table of 34 pages;
LongCat's 64 heads at one position with a tail of 32 slots, GLM's verify
form (20 heads x 2 positions, a tail of 64) and its prediction module's
call (20 heads, one position). Row lengths are drawn as the cells'
closed traffic holds them in steady state: a prompt of 256-1024 tokens
and the elapsed part of an answer of 1024-3072 (256 to 4096 cached
tokens, 2 to 32 pages, about 1660 at the mean); ``--kv-len`` fixes one
length instead.

A call under 0.25 ms reads Python's dispatch (ROADMAP S18 (3)), so
``--calls`` of them run in one ``fori_loop``: each call's output nudges
the next call's query, and each call's table points into its own share
of the plane (``--planes`` shares), so no call is another's copy.
``--chunks`` runs the walk at those pages a link besides the file's own
rule ("rule") by replacing the rule the kernel's module reads at trace
time (``latent_pages_per_chunk``; in a checkout from before PR 45, the
K/V kernel's ``pages_per_chunk`` as that module imported it);
``--set _SLOTS=2,_GRANULE=12`` replaces the module's other integer
constants the same way (the slots the copies run ahead in, the widths
of a row's last link).

Prints one JSON line (and writes it to ``--out``): milliseconds a call
by shape and pages a link, and the device. On the CPU the times are the
interpreter's and say nothing of the chip (PERF.md section 6, PR 45).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import pathlib
import re
import sys
import time

# shape -> (heads, positions, dn, dr, rank, dv, tail slots)
SHAPES = {
    "longcat": (64, 1, 128, 64, 512, 128, 32),
    "glm-verify": (20, 2, 192, 64, 512, 256, 64),
    "glm-module": (20, 1, 192, 64, 512, 256, 64),
}
TINY = {"tiny": (4, 1, 16, 8, 24, 16, 4),
        "tiny-verify": (4, 2, 16, 8, 24, 16, 8)}
ROWS, PAGE, TABLE_PAGES = 160, 128, 34


def kernel_module(repo_root: "str | None"):
    """``ops/mla_attention_pallas.py`` of this checkout, or of the one
    at ``repo_root``."""
    root = (pathlib.Path(repo_root).resolve() if repo_root
            else pathlib.Path(__file__).resolve().parent.parent)
    sys.path.insert(0, str(root))
    module = importlib.import_module(
        "production_stack_tpu.ops.mla_attention_pallas")
    if root not in pathlib.Path(module.__file__).resolve().parents:
        raise RuntimeError(f"{module.__file__} is not under {root}")
    return module


def set_chunk(module, pages: "int | None") -> None:
    """Make every later trace of ``module``'s walk take ``pages`` a
    link (None: the module's own rule again)."""
    name = ("latent_pages_per_chunk"
            if hasattr(module, "latent_pages_per_chunk")
            else "pages_per_chunk")
    kept = module.__dict__.setdefault("_rule_as_written",
                                      getattr(module, name))
    setattr(module, name,
            kept if pages is None else lambda *a: min(pages, a[-1]))


def draw_lengths(key, rows: int, hi: int = 4096):
    """Cached tokens a row, as the closed traffic holds them in steady
    state: the prompt (a sixteenth to a quarter of ``hi``) and the
    elapsed part of the answer (a quarter to three quarters)."""
    import jax
    import jax.numpy as jnp
    k_prompt, k_answer, k_elapsed = jax.random.split(key, 3)
    prompt = jax.random.randint(k_prompt, (rows,), hi // 16, hi // 4 + 1)
    answer = jax.random.randint(k_answer, (rows,), hi // 4, 3 * hi // 4 + 1)
    elapsed = (jax.random.uniform(k_elapsed, (rows,)) * answer).astype(
        jnp.int32)
    return prompt + elapsed


def make_case(shape, rows: int, page: int, table_pages: int, planes: int,
              key, kv_len: "int | None" = None, dtype=None,
              as_shapes=None):
    """(q, plane, table, kv_lens, w_uk, w_uv, tail, q_positions) and the
    pages one share of the plane holds. With ``as_shapes`` (a sharding)
    nothing is drawn: the arguments are shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    heads, positions, dn, dr, rank, dv, slots = shape
    dtype = dtype or jnp.bfloat16
    keys = jax.random.split(key, 6)
    lens = (jnp.full((rows,), kv_len, jnp.int32) if kv_len is not None
            else jnp.minimum(draw_lengths(keys[0], rows,
                                          hi=(table_pages - 2) * page),
                             (table_pages - 1) * page))
    held = np.asarray(-(-lens // page))
    first = np.concatenate([[0], np.cumsum(held)[:-1]])
    share = int(held.sum())
    table = np.zeros((rows, table_pages), np.int32)
    for row in range(rows):
        table[row, :held[row]] = first[row] + np.arange(held[row])
    dims = {
        "q": (rows, positions, heads, dn + dr),
        "plane": (1, max(share, 1) * planes, rank + dr, page),
        "w_uk": (heads, dn, rank), "w_uv": (heads, rank, dv),
        "tail": (rows, slots, 1, rank + dr)}
    if as_shapes is not None:
        def shaped(dims, dtype=dtype):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=as_shapes)
        drawn = {name: shaped(d) for name, d in dims.items()}
        table, lens = shaped(table.shape, jnp.int32), shaped(
            lens.shape, jnp.int32)
        q_positions = shaped((rows, positions), jnp.int32)
    else:
        drawn = {name: (0.3 * jax.random.normal(k, d, jnp.float32)
                        ).astype(dtype)
                 for (name, d), k in zip(dims.items(), keys[1:])}
        table = jnp.asarray(table)
        # Each row a few slots into its burst: tail slots partly visible.
        q_positions = (lens + slots // 2 - positions)[:, None] + jnp.arange(
            positions, dtype=jnp.int32)
    return (drawn["q"], drawn["plane"], table, lens, drawn["w_uk"],
            drawn["w_uv"], drawn["tail"], q_positions), max(share, 1)


def sublayer(module, shape, interpret: bool):
    """One call as the model makes it (models/longcat_flash.py
    ``mla``), un-jitted so that a replaced chunk rule is read again."""
    heads, positions, dn, dr = shape[:4]
    scale = float(dn + dr) ** -0.5
    decode = module.latent_paged_decode_attention.__wrapped__
    verify = module.latent_paged_verify_attention.__wrapped__

    def call(q, plane, table, lens, w_uk, w_uv, tail, q_positions):
        if positions == 1:
            return decode(q[:, 0], plane, table, lens, w_uk, w_uv, scale,
                          tail=tail, q_positions=q_positions[:, 0],
                          interpret=interpret)[:, None]
        return verify(q, plane, table, lens, w_uk, w_uv, scale, tail=tail,
                      q_positions=q_positions, interpret=interpret)
    return call


def many_calls(call, calls: int, planes: int, share: int):
    """``calls`` calls in one program: (a checksum, the last output)."""
    import jax
    import jax.numpy as jnp

    def program(q, plane, table, lens, w_uk, w_uv, tail, q_positions):
        def body(i, carry):
            q, total = carry
            out = call(q, plane, table + (i % planes) * share, lens, w_uk,
                       w_uv, tail, q_positions)
            nudge = (1e-3 * out[..., :1]).astype(q.dtype)
            return q + nudge, total + jnp.sum(out.astype(jnp.float32))
        return jax.lax.fori_loop(0, calls, body,
                                 (q, jnp.zeros((), jnp.float32)))[1]
    return jax.jit(program)


def array_census(text: str, rows: int, query_rows: int, rank: int,
                 plane_elements: int) -> dict:
    """What a compiled call's text holds between the kernel and the
    up-projection. ``float32_state``: every float32 array of ``rows x
    R x rank`` or ``rows x R x 128`` elements (R the query's rows, as
    they are or padded to 16) an instruction makes: the softmax's
    state on its way through HBM. ``plane_sized``: every array of the
    plane's size that is not the parameter itself (a copy of the plane,
    or cached tokens expanded)."""
    instruction = re.compile(
        r"\s*(?:ROOT )?%[\w.\-]+ = (.*?)\s([a-z][a-z\-]*)\(")
    shape = re.compile(r"([a-z]+\d+)\[([\d,]+)\]")
    padded = -(-query_rows // 16) * 16
    state = {rows * r * lanes for r in (query_rows, padded)
             for lanes in (rank, 128)}
    census = {"float32_state": {}, "plane_sized": {}}
    for line in text.splitlines():
        m = instruction.match(line)
        if not m:
            continue
        result, opcode = m.groups()
        for dtype, dims in shape.findall(result):
            n = 1
            for d in dims.split(","):
                n *= int(d)
            key = f"{dtype}[{dims}] {opcode}"
            if dtype == "f32" and n in state:
                by = census["float32_state"]
                by[key] = by.get(key, 0) + 1
            if n == plane_elements and opcode not in (
                    "parameter", "get-tuple-element", "tuple", "bitcast"):
                by = census["plane_sized"]
                by[key] = by.get(key, 0) + 1
    return census


def compile_for_v5e(module, out_dir: str, shapes: dict, rows: int,
                    page: int, table_pages: int) -> dict:
    """Each shape's one call compiled for a described (not attached)
    v5e; the texts go to ``out_dir``. Returns each shape's census."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    pathlib.Path(out_dir).mkdir(parents=True, exist_ok=True)
    found = {}
    for name, shape in shapes.items():
        args, _ = make_case(shape, rows, page, table_pages, 1,
                            jax.random.PRNGKey(0), as_shapes=chip)
        text = jax.jit(sublayer(module, shape, False)).lower(
            *args).compile().as_text()
        pathlib.Path(out_dir, f"{name}.txt").write_text(text)
        plane = args[1].shape
        found[name] = array_census(
            text, rows, shape[0] * shape[1], shape[4],
            plane[1] * plane[2] * plane[3])
    return found


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=None,
                    help="comma-separated names of SHAPES (all of them)")
    ap.add_argument("--tiny", action="store_true",
                    help="the tests' widths, float32, pages of 16, 8 rows")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--page", type=int, default=None)
    ap.add_argument("--table-pages", type=int, default=None)
    ap.add_argument("--kv-len", type=int, default=None,
                    help="every row this long (default: drawn)")
    ap.add_argument("--chunks", default="rule",
                    help="comma-separated pages a link, or 'rule' (the "
                         "compile takes the first)")
    ap.add_argument("--calls", type=int, default=64)
    ap.add_argument("--planes", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--repo-root", default=None,
                    help="measure that checkout's kernel (the parent's)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--set", default="", metavar="NAME=INT[,...]",
                    help="replace integer constants of the kernel's "
                         "module before anything is traced (_SLOTS, "
                         "_GRANULE)")
    ap.add_argument("--compile-for-v5e", metavar="DIR", default=None,
                    help="compile one call of each shape for a described "
                         "v5e, write the texts there and print the "
                         "census; nothing runs")
    args = ap.parse_args(argv)
    known = TINY if args.tiny else SHAPES
    names = args.shapes.split(",") if args.shapes else list(known)
    args.shapes = {name: known[name] for name in names}
    args.rows = args.rows or (8 if args.tiny else ROWS)
    args.page = args.page or (16 if args.tiny else PAGE)
    args.table_pages = args.table_pages or (8 if args.tiny else TABLE_PAGES)
    args.chunks = [None if c == "rule" else int(c)
                   for c in args.chunks.split(",")]
    return args


def measure(module, args) -> dict:
    import jax
    import jax.numpy as jnp
    device = jax.devices()[0]
    line = {"rows": args.rows, "page": args.page,
            "table_pages": args.table_pages, "calls": args.calls,
            "repo_root": args.repo_root or ".",
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "ms_per_call": {}}
    for name, shape in args.shapes.items():
        case, share = make_case(
            shape, args.rows, args.page, args.table_pages, args.planes,
            jax.random.PRNGKey(args.seed), kv_len=args.kv_len,
            dtype=jnp.float32 if args.tiny else jnp.bfloat16)
        lens = case[3]
        line.setdefault("kv_lens", {})[name] = {
            "mean": float(lens.mean()), "min": int(lens.min()),
            "max": int(lens.max()), "pages": share}
        by_chunk = line["ms_per_call"][name] = {}
        for pages in args.chunks:
            set_chunk(module, pages)
            program = many_calls(sublayer(module, shape, args.interpret),
                                 args.calls, args.planes, share)
            times = []
            for _ in range(args.repeats + 1):       # the first compiles
                start = time.perf_counter()
                total = jax.block_until_ready(program(*case))
                times.append(time.perf_counter() - start)
            by_chunk["rule" if pages is None else str(pages)] = {
                "ms": [round(1e3 * t / args.calls, 4) for t in times[1:]],
                "compile_and_first_s": round(times[0], 2),
                "checksum": float(total)}
        set_chunk(module, None)
    return line


def main(argv=None) -> dict:
    args = parse_args(argv)
    module = kernel_module(args.repo_root)
    for item in filter(None, args.set.split(",")):
        name, value = item.split("=")
        if not isinstance(getattr(module, name), int):
            raise SystemExit(f"--set {name}: no integer constant of "
                             f"{module.__name__}")
        setattr(module, name, int(value))
    if args.compile_for_v5e:
        set_chunk(module, args.chunks[0])
        line = compile_for_v5e(module, args.compile_for_v5e, args.shapes,
                               args.rows, args.page, args.table_pages)
    else:
        line = measure(module, args)
    print(json.dumps(line))
    if args.out:
        path = pathlib.Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(line) + "\n")
    return line


if __name__ == "__main__":
    main()
