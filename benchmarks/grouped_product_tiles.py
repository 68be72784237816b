"""The routed experts alone (``ops/moe.py`` ``held_experts``: the sort,
the gather, the two grouped products and the sum) at the expert cells'
burst and prefill shapes and loads, many calls inside ONE program,
under several choices of the ``megablox`` kernel's tiles and of the
rows that go around it.

    chiprun -- python3 benchmarks/grouped_product_tiles.py \
        --tiles parent,2,3,4 --out chiprun_out/pr49/tiles.json
    python3 benchmarks/grouped_product_tiles.py --shapes lfm2,glm \
        --tiles 2048x512/1024x512,2048x512/whole-kx512   # the masked tile
    JAX_PLATFORMS=cpu python3 benchmarks/grouped_product_tiles.py \
        --tiny --interpret --calls 2 --repeats 1          # a rehearsal
    JAX_PLATFORMS=cpu python3 benchmarks/grouped_product_tiles.py \
        --compile-for-v5e --tiles parent,2,4              # Mosaic's verdict
    chiprun -- python3 benchmarks/grouped_product_tiles.py \
        --tiles rule --around every,rule --margin 1.25,1.5,2 \
        --out chiprun_out/pr54/around.json    # the whole call, by its rows

``SHAPES`` are the calls the cells' decode bursts make
(chipbench/configs, chipbench/workloads): the rows of a full burst
(GLM's verify form is 160 rows x 2 positions), the choices a row, the
router's width, the experts held, hidden and expert width; a name that
ends in ``-prefill`` is the call of that cell's widest prefill step
(``prefill-batch-size`` x ``prefill-chunk-size`` token places), run
``--calls`` / 8 times a program. A row's
choices are the ``top_k`` largest of Gumbel noise plus ``--skew`` times
a fixed normal draw an expert, and every call shifts the ids by one, so
the calls of a program do not hit the same experts; ``--fill`` is the
share of rows that are real. The line says what the draws came to
(``tokens_per_expert_mean``, ``_max`` and ``experts_hit``, the names of
the program's counters) so that they can be set beside a cell's.

A new configuration's widths: add a line to ``SHAPES`` (or pass
``--shape NAME=rows,top_k,router_width,held,hidden,expert_width``), run
``--compile-for-v5e`` here first (a tile that overflows the scoped VMEM
is refused there at no chip time), then the first command above with
``--shapes NAME``; the rule's choice is the ``rule`` entry of
``--tiles`` and the budget it was set by is ``ops/moe.py``
``_RHS_TILE_BYTES``.

``--around`` entries say which rows go around the two products
(``ops/moe.py`` ``expert_room``): ``rule`` (the module as it stands:
the room its shapes give) and ``every`` (all rows x choices, the call
without the router's width). ``--margin`` runs ``rule`` at those values
of ``_ROOM_MARGIN``; the line gives the ``room`` each ran under and, of
the calls' draws, the held pairs (``held_pairs_mean``, ``_max``: a call
whose held pairs pass the room takes one more chunk). PR 54's table
(PERF.md section 6) also timed a second way back to tokens, a gather
of each (token, choice)'s row by its place in the order, which lost at
every cell's shape and is not in the module.

``--tiles`` entries: ``rule`` (the module as it stands), ``parent``
(the constant tile before PR 49: 128, min(1024, k), min(512, n)), a
number (the rule at that many MiB a right-hand tile) or
``TKxTN/TKxTN`` (gate|up's and down's tiles by hand; ``whole-k`` and
``whole-n`` stand for the dimension). ``--part gate_up`` or ``down``
runs that product alone on rows already sorted.

A call under 0.25 ms reads Python's dispatch (ROADMAP S18 (3)), so
``--calls`` of them run in one ``fori_loop``, each call's output
nudging the next call's input. Prints one JSON line (and writes it to
``--out``): ms a call by shape and tiles beside ``bytes_ms`` (the
experts hit x an expert's bytes over 819 GB/s), the tiles and the grid
steps a visit. On the CPU the times are the interpreter's and say
nothing of the chip.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

# cell -> (rows, top_k, router width, experts held, hidden, expert width)
SHAPES = {
    "qwen3-next": (128, 10, 512, 128, 2048, 512),
    "longcat": (160, 12, 768, 16, 6144, 2048),
    "glm": (320, 4, 64, 64, 2048, 1536),
    "lfm2": (256, 4, 32, 8, 2048, 1792),
    "granite": (128, 10, 72, 18, 4096, 768),
    "k-exaone": (128, 8, 128, 8, 6144, 2048),
    "qwen3-next-prefill": (2048, 10, 512, 128, 2048, 512),
    "longcat-prefill": (2048, 12, 768, 16, 6144, 2048),
    "lfm2-prefill": (2048, 4, 32, 8, 2048, 1792),
    "granite-prefill": (1024, 10, 72, 18, 4096, 768),
    "k-exaone-prefill": (2048, 8, 128, 8, 6144, 2048),
}
TINY = {"tiny": (8, 2, 8, 4, 128, 256), "tiny-odd": (8, 2, 4, 4, 128, 160)}
HBM_BYTES_PER_S = 819e9          # chipbench/peaks.json, TPU v5 lite


def tile_rule(moe, spec: str, hidden: int, width: int):
    """The function ``spec`` puts in ``expert_tiles``' place for a
    shape of these widths."""
    if spec == "rule":
        return moe.expert_tiles
    if spec == "parent":
        return lambda k, n, itemsize: (128, min(1024, k), min(512, n))
    if "/" in spec:
        by_product = dict(zip(((hidden, 2 * width), (width, hidden)),
                              spec.split("/")))

        def by_hand(k, n, itemsize):
            tk, tn = by_product[k, n].split("x")
            return (128, k if tk == "whole-k" else int(tk),
                    n if tn == "whole-n" else int(tn))
        return by_hand
    budget, rule = int(float(spec) * (1 << 20)), moe.expert_tiles

    def at_budget(k, n, itemsize):
        was, moe._RHS_TILE_BYTES = moe._RHS_TILE_BYTES, budget
        try:
            return rule(k, n, itemsize)
        finally:
            moe._RHS_TILE_BYTES = was
    return at_budget


def draw_choices(key, shape, fill: float, skew: float):
    """(ids [rows, top_k] over the router's width, valid [rows])."""
    import jax
    import jax.numpy as jnp
    rows, top_k, router_width = shape[:3]
    k_noise, k_bias = jax.random.split(key)
    scores = (jax.random.gumbel(k_noise, (rows, router_width))
              + skew * jax.random.normal(k_bias, (router_width,)))
    ids = jax.lax.top_k(scores, top_k)[1].astype(jnp.int32)
    return ids, jnp.arange(rows) < round(fill * rows)


def load_of(ids, valid, shape, calls: int) -> dict:
    """What the program's counters would say of ``calls`` calls whose
    ids shift by one a call: means over the calls."""
    import numpy as np
    held, router_width = shape[3], shape[2]
    ids = np.asarray(ids)[np.asarray(valid)]
    loads = np.stack([np.bincount(((ids + i) % router_width).ravel(),
                                  minlength=router_width)[:held]
                      for i in range(calls)])
    return {"tokens_per_expert_mean": float(loads.mean()),
            "tokens_per_expert_max": float(loads.max(axis=1).mean()),
            "experts_hit": float((loads > 0).sum(axis=1).mean()),
            "held_pairs_mean": float(loads.sum(axis=1).mean()),
            "held_pairs_max": int(loads.sum(axis=1).max())}


def make_case(key, shape, fill, skew, dtype, as_shapes=None):
    """(x, weights, ids, valid, w_gate_up, w_down); with ``as_shapes``
    (a sharding) nothing is drawn: the arguments are shapes."""
    import jax
    import jax.numpy as jnp
    rows, top_k, _, held, hidden, width = shape
    dims = {"x": (rows, hidden), "w_gate_up": (held, hidden, 2 * width),
            "w_down": (held, width, hidden)}
    if as_shapes is not None:
        def shaped(dims, dtype=dtype):
            return jax.ShapeDtypeStruct(dims, dtype, sharding=as_shapes)
        return (shaped(dims["x"]), shaped((rows, top_k), jnp.float32),
                shaped((rows, top_k), jnp.int32), shaped((rows,), jnp.bool_),
                shaped(dims["w_gate_up"]), shaped(dims["w_down"]))
    keys = jax.random.split(key, 4)
    ids, valid = draw_choices(keys[0], shape, fill, skew)
    drawn = {name: (scale * jax.random.normal(k, d, jnp.float32)
                    ).astype(dtype)
             for (name, d), k, scale in zip(dims.items(), keys[1:],
                                            (1.0, 0.02, 0.02))}
    weights = jnp.full((rows, top_k), 1.0 / top_k, jnp.float32)
    return (drawn["x"], weights, ids, valid, drawn["w_gate_up"],
            drawn["w_down"])


def one_call(moe, shape, part: str, impl: str, around: str = "rule"):
    """x, ids (already shifted) -> one row a row of x: the routed
    experts as the models call them (``around`` says with which rows:
    the module's text), or one product alone on the rows as
    ``held_experts`` would hand them to it."""
    import jax.numpy as jnp
    rows, top_k, router_width, held, hidden, width = shape

    def call(x, weights, ids, valid, w_gate_up, w_down):
        if part == "experts":
            return moe.held_experts(
                x, weights, ids, w_gate_up, w_down, 0, valid=valid,
                impl=impl, router_width=(None if around == "every"
                                         else router_width))[0]
        load = jnp.zeros((held + 1,), jnp.int32).at[
            jnp.where((ids < held) & valid[:, None], ids, held).reshape(-1)
        ].add(1)[:held]
        pairs = jnp.repeat(x, top_k, axis=0)
        if part == "gate_up":
            return moe._grouped_dot(pairs, w_gate_up, load, impl)[::top_k]
        return moe._grouped_dot(jnp.resize(pairs, (rows * top_k, width)),
                                w_down, load, impl)[::top_k]
    return call


def many_calls(call, calls: int, router_width: int):
    """``calls`` calls in one program: a checksum."""
    import jax
    import jax.numpy as jnp

    def program(x, weights, ids, valid, w_gate_up, w_down):
        def body(i, carry):
            x, total = carry
            out = call(x, weights, (ids + i) % router_width, valid,
                       w_gate_up, w_down).astype(jnp.float32)
            nudge = 1e-3 * jnp.mean(out, axis=-1, keepdims=True)
            return x + nudge.astype(x.dtype), total + jnp.sum(out)
        return jax.lax.fori_loop(0, calls, body,
                                 (x, jnp.zeros((), jnp.float32)))[1]
    return jax.jit(program)


def described_v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=None,
                    help="comma-separated names of SHAPES (all of them)")
    ap.add_argument("--shape", action="append", default=[],
                    metavar="NAME=rows,top_k,router,held,hidden,width",
                    help="one more shape")
    ap.add_argument("--tiny", action="store_true",
                    help="the tests' widths in float32")
    ap.add_argument("--tiles", default="parent,rule")
    ap.add_argument("--around", default="rule",
                    help="comma-separated: rule, every")
    ap.add_argument("--margin", default=None,
                    help="comma-separated values of ops/moe.py "
                         "_ROOM_MARGIN (the module's)")
    ap.add_argument("--part", default="experts",
                    choices=("experts", "gate_up", "down"))
    ap.add_argument("--fill", type=float, default=1.0,
                    help="share of the rows that are real")
    ap.add_argument("--skew", type=float, default=0.0,
                    help="a fixed normal draw an expert, times this, "
                         "beside the Gumbel noise (0: an even router)")
    ap.add_argument("--calls", type=int, default=64)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--compile-for-v5e", action="store_true",
                    help="compile one call of each shape and tiles for a "
                         "described v5e and say which Mosaic refuses; "
                         "nothing runs")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    known = dict(TINY if args.tiny else SHAPES)
    for item in args.shape:
        name, dims = item.split("=")
        known[name] = tuple(int(d) for d in dims.split(","))
        if len(known[name]) != 6:
            raise SystemExit(f"--shape {item}: six numbers")
    names = (args.shapes.split(",") if args.shapes
             else list(known) if not args.shape
             else [item.split("=")[0] for item in args.shape])
    args.shapes = {name: known[name] for name in names}
    args.tiles = args.tiles.split(",")
    args.around = args.around.split(",")
    args.margin = ([float(m) for m in args.margin.split(",")]
                   if args.margin else [None])
    return args


def measure(moe, args) -> dict:
    import jax
    import jax.numpy as jnp
    device = jax.devices()[0]
    dtype = jnp.float32 if args.tiny else jnp.bfloat16
    itemsize = jnp.dtype(dtype).itemsize
    impl = "pallas-interpret" if args.interpret else "pallas"
    line = {"part": args.part, "calls": args.calls, "fill": args.fill,
            "skew": args.skew, "seed": args.seed,
            "device": {"platform": device.platform,
                       "kind": device.device_kind},
            "shapes": {}}
    chip = described_v5e() if args.compile_for_v5e else None
    kept = moe.expert_tiles, moe._ROOM_MARGIN
    for name, shape in args.shapes.items():
        rows, top_k, router_width, held, hidden, width = shape
        calls = (max(1, args.calls // 8) if name.endswith("-prefill")
                 else args.calls)
        case = make_case(jax.random.PRNGKey(args.seed), shape, args.fill,
                         args.skew, dtype, as_shapes=chip)
        expert_bytes = {"experts": 3 * hidden * width,
                        "gate_up": 2 * hidden * width,
                        "down": hidden * width}[args.part] * itemsize
        entry = line["shapes"][name] = {"shape": list(shape),
                                        "calls": calls, "tiles": {}}
        if chip is None:
            entry.update(load_of(case[2], case[3], shape, calls))
            entry["bytes_ms"] = round(
                1e3 * entry["experts_hit"] * expert_bytes / HBM_BYTES_PER_S,
                4)
        for spec, margin, around in itertools.product(
                args.tiles, args.margin, args.around):
            if around == "every" and margin != args.margin[0]:
                continue        # every row: no room, so no margin
            rule = tile_rule(moe, spec, hidden, width)
            gate_up = rule(hidden, 2 * width, itemsize)
            down = rule(width, hidden, itemsize)
            key = spec + (f":{around}" if around != "rule" else "") + (
                f"@{margin}" if margin is not None and around != "every"
                else "")
            found = entry["tiles"][key] = {
                "gate_up": list(gate_up), "down": list(down),
                "steps_per_visit":
                    moe.grid_steps(gate_up, hidden, 2 * width)
                    + moe.grid_steps(down, width, hidden)}
            moe.expert_tiles = rule
            if margin is not None:
                moe._ROOM_MARGIN = margin
            found["room"] = (None if around == "every" else moe.expert_room(
                rows, top_k, held, router_width))
            try:
                if chip is not None:
                    jax.jit(one_call(moe, shape, args.part, "pallas",
                                     around)).lower(*case).compile()
                    found["compiles"] = True
                    continue
                program = many_calls(
                    one_call(moe, shape, args.part, impl, around), calls,
                    router_width)
                times = []
                for _ in range(args.repeats + 1):   # the first compiles
                    start = time.perf_counter()
                    total = jax.block_until_ready(program(*case))
                    times.append(time.perf_counter() - start)
            except Exception as e:      # Mosaic's refusal, and the like
                found["error"] = str(e).strip().splitlines()[-1][:300]
                continue
            finally:
                moe.expert_tiles, moe._ROOM_MARGIN = kept
            found.update(
                ms=[round(1e3 * t / calls, 4) for t in times[1:]],
                compile_and_first_s=round(times[0], 2),
                checksum=float(total))
    return line


def main(argv=None) -> dict:
    args = parse_args(argv)
    from production_stack_tpu.ops import moe
    line = measure(moe, args)
    print(json.dumps(line))
    if args.out:
        path = pathlib.Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(line) + "\n")
    return line


if __name__ == "__main__":
    main()
