"""Share of the compute roofline reached by the prefill step program.
Per program, not per kernel.  Bound: compute (bf16 peak; under
weight-only int8 the products are still bf16).

Least time = ``roofline.prefill_flops`` of the prefill chunks that ran
in the profiler slice / the chip's bf16 peak.  Time taken = device time
of the ``_step_impl`` executions in the slice (the trace's ``XLA
Modules`` line).  The chunks are the engine spans' ``prefill_chunk``
events stamped inside the slice; the host clock places the slice's
edges to a few tenths of a second, so the operations are scaled by
executions in the trace over prefill step records in the slice.

Moves ``output_tok_s``: with ``--unified-step off`` a prefill step sits
between two decode bursts, so its device time is taken from every row
that is decoding.  No cell of ``BENCHMARK.json`` reports it yet (the
one cell bypasses prefill); the rehearsal cells do."""

from chipbench import roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

PROGRAM = "_step_impl"


def read(run):
    program = (run.trace or {}).get("programs", {}).get(PROGRAM)
    cell = run.cell
    if not program or not program["count"] or not cell.get("slice_unix"):
        return None
    lo, hi = cell["slice_unix"]
    chunks = [(e["start"], e["tokens"], e["last"])
              for span in run.spans.values() for e in span["events"]
              if e["event"] == "prefill_chunk" and lo <= e["ts"] < hi]
    steps = sum(1 for s in run.window_steps
                if s.get("kind") == "prefill" and lo <= s["ts"] < hi)
    if not chunks or not steps:
        return None
    cfg = cell["config_as_run"]
    flops = roofline.prefill_flops(cfg, chunks) * program["count"] / steps
    peak = roofline.peaks(cell["version"]["device_kind"])
    return 100.0 * flops / peak["bf16_flops_per_s"] / program["seconds"]
