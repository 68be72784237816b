"""The run writer (``ops/attention.py`` ``write_run_to_pages``) against
the scatter (``write_to_pages``) at every cell's planes: equal bits,
and the device time of each.

    chiprun -- python3 benchmarks/page_write_check.py \
        --out chiprun_out/pr52/page_write_check.json
    python3 benchmarks/page_write_check.py --cells lfm2-8b-a1b-ep4,qwen2.5-3b
    JAX_PLATFORMS=cpu python3 benchmarks/page_write_check.py --interpret

For each configuration of ``chipbench/configs`` (all, or ``--cells``)
two cases are built from the file alone (the published widths and the
cell's ``server_flags``): ``flush``, every page plane the model has
([heads, --num-pages, width, --page-size], bfloat16) taking
``--max-num-seqs`` rows' tails of ``--decode-steps`` slots (twice that
where the family drafts) in one call, as a deferred burst ends; and
``chunk``, one layer's planes (K and V, or the one latent) taking
``--prefill-batch-size`` rows of ``--prefill-chunk-size`` tokens, as a
prefill step writes a layer. A row owns its pages, starts anywhere in
them and holds a drawn count of tokens (half the rows all of them, one
none); ``--seed`` draws.

Both writers run under ``jit`` with the planes donated, from the same
drawn planes. ``equal`` compares an exact hash of every plane's bits
outside page 0 (integer sums that wrap and do not round; page 0 is the
trash page, which the scatter fills with the pads); ``ms`` is the mean
of ``--calls`` calls after the first, ``ms_a_plane`` that over the
planes, ``first_s`` the first call with its compile. Prints one JSON
line a case, writes them all to ``--out``, and exits 1 if any case's
planes differ.

Without a TPU it exits 2 and measures nothing; ``--interpret`` is a dry
run of the script itself on whatever backend there is, at planes cut
to 16 lanes, 8 wide and two pages a row, float32, which says so in its
lines (``"interpret": true``) and whose times say nothing of the chip.
PERF.md section 6, PR 52.
"""

from __future__ import annotations

import argparse
import glob
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def cases_of(config_file: str, tiny: bool) -> dict:
    """``{"flush": (...), "chunk": (...)}``, each ``(planes, heads,
    pages, width, page_size, rows, tokens, table width)``."""
    from production_stack_tpu.engine import config as cfg
    with open(config_file) as f:
        hf = json.load(f)
    bench = hf.pop("chipbench")
    flags = bench["server_flags"]
    model = cfg.ModelConfig.from_hf_config(hf, bench["name"])
    held = model.page_cache
    layers = sum(not state for state in model.cache_entry_is_state)
    page, pages = flags["page-size"], flags["num-pages"]
    table = -(-flags["max-model-len"] // page)
    slots = flags["decode-steps"] * (2 if model.has_draft_module else 1)
    heads, width = held.heads, held.width
    cases = {"flush": (layers * held.planes, flags["max-num-seqs"], slots),
             "chunk": (held.planes, flags["prefill-batch-size"],
                       flags["prefill-chunk-size"])}
    if tiny:
        page, width, table = 16, 8, 6
        pages = 1 + 2 * flags["max-num-seqs"]
        cases = {name: (min(planes, 2), rows, min(t, 24))
                 for name, (planes, rows, t) in cases.items()}
    return {name: (planes, heads, pages, width, page, rows, t, table)
            for name, (planes, rows, t) in cases.items()}


def draw_rows(rng, pages, page, rows, t, table_width):
    """``(table, start, count)``: each row its own pages, as the
    allocator hands them out; the last row a pad row."""
    import numpy as np
    own = min(table_width, (pages - 1) // rows)
    table = np.zeros((rows, table_width), np.int32)
    table[:, :own] = 1 + rng.permutation(rows * own).reshape(rows, own)
    start = rng.integers(0, own * page - t + 1, rows)
    count = rng.integers(0, t + 1, rows)
    count[: rows // 2] = t
    table[-1], start[-1], count[-1] = 0, 0, 0
    return table, start.astype(np.int32), count.astype(np.int32)


def measure(case, seed: int, calls: int, dtype) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from production_stack_tpu.ops.attention import (
        write_run_to_pages,
        write_to_pages,
    )
    planes, heads, pages, width, page, rows, t, table_width = case
    table, start, count = (jnp.asarray(x) for x in draw_rows(
        np.random.default_rng(seed), pages, page, rows, t, table_width))
    slot = jnp.arange(t)[None]
    key = jax.random.PRNGKey(seed)
    news = tuple(
        jax.random.normal(jax.random.fold_in(key, i),
                          (rows, t, heads, width), dtype)
        for i in range(planes))

    def scatter(caches, news, table, start, count):
        at, valid = start[:, None] + slot, slot < count[:, None]
        return tuple(write_to_pages(c, n, table, at, valid)
                     for c, n in zip(caches, news))

    bits = jnp.uint16 if jnp.dtype(dtype).itemsize == 2 else jnp.uint32

    @jax.jit
    def plane_hash(plane):
        u = jax.lax.bitcast_convert_type(
            plane[:, 1:], bits).astype(jnp.uint32).reshape(-1)
        odd = jnp.arange(u.shape[0], dtype=jnp.uint32) * 2 + 1
        return jnp.sum(u * odd), jnp.sum(u ^ odd)

    found = {}
    for name, writer in (("scatter", scatter), ("run", write_run_to_pages)):
        caches = tuple(
            jax.random.normal(jax.random.fold_in(key, 1000 + i),
                              (heads, pages, width, page), dtype)
            for i in range(planes))
        before = [tuple(int(x) for x in plane_hash(c)) for c in caches]
        write = jax.jit(writer, donate_argnums=0)
        began = time.perf_counter()
        caches = jax.block_until_ready(
            write(caches, news, table, start, count))
        first = time.perf_counter() - began
        after = [tuple(int(x) for x in plane_hash(c)) for c in caches]
        began = time.perf_counter()
        for _ in range(calls):   # the same tokens to the same places
            caches = write(caches, news, table, start, count)
        jax.block_until_ready(caches)
        ms = 1e3 * (time.perf_counter() - began) / calls
        found[name] = {"ms": round(ms, 3),
                       "ms_a_plane": round(ms / planes, 4),
                       "first_s": round(first, 2), "hash": after,
                       "wrote": after != before}
        del caches
    equal = (found["scatter"].pop("hash") == found["run"].pop("hash")
             and found["scatter"]["wrote"] and found["run"]["wrote"])
    return {"planes": planes, "plane": [heads, pages, width, page],
            "plane_bytes": heads * pages * width * page
            * jnp.dtype(dtype).itemsize,
            "rows": rows, "tokens": t, "equal": equal, **found}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default=None,
                    help="comma-separated names of chipbench/configs "
                         "(default: all)")
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=52)
    ap.add_argument("--out", default="chiprun_out/pr52/page_write_check.json")
    ap.add_argument("--interpret", action="store_true",
                    help="no TPU: a dry run of this script at tiny "
                         "planes, float32, nothing of the chip timed")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.interpret:
        print(f"page_write_check: backend {jax.default_backend()!r} is "
              "no TPU; nothing measured (--interpret for a dry run)",
              file=sys.stderr)
        return 2
    files = sorted(glob.glob(str(ROOT / "chipbench/configs/*.json")))
    if args.cells:
        files = [f for f in files
                 if pathlib.Path(f).stem in args.cells.split(",")]
    device = jax.devices()[0]
    lines = []
    for config_file in files:
        for name, case in cases_of(config_file, not on_chip).items():
            line = {"cell": pathlib.Path(config_file).stem, "case": name,
                    "device": device.device_kind, "interpret": not on_chip,
                    **measure(case, args.seed, args.calls,
                              jnp.bfloat16 if on_chip else jnp.float32)}
            print(json.dumps(line), flush=True)
            lines.append(line)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(lines, indent=1) + "\n")
    return 0 if all(line["equal"] for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
