"""Share of its roofline reached by the Mamba-2 recurrence of a
prefill step, the ``ssd_prefill`` scope of ``_step_impl``: the gather
of the rows' states, the chunk's matrix form, the scatter back.  Bound:
memory, by ``counts.ssd_prefill``'s count (the recurrence's own 5
operations a state element a token against the chip's bf16 peak, which
float32 products at the matrix unit's highest precision cannot reach;
a row's ``h`` in and out a chunk is the larger time at every chunk
length under 600 tokens), so the share flatters no one.

Least time = ``counts.ssd_prefill`` of the prompt chunks that ran in
the profiler slice (the engine spans' ``prefill_chunk`` events stamped
inside it, scaled as ``prefill_roofline`` scales them: executions in
the trace over prefill step records in the slice) x Mamba layers, over
the chip's peaks.  Time taken = the device seconds under the name in
the prefill step program.  None where no prefill fell in the slice,
the program has no such scope or the family's counts no such
function."""

from chipbench import family, hybrid_slice, roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "ssd_prefill"
PROGRAM = "_step_impl"


def read(run):
    seconds, events = hybrid_slice.scope_seconds(run.trace, SCOPE, PROGRAM)
    cell = run.cell
    if not events or not cell.get("slice_unix"):
        return None
    program = run.trace.get("programs", {}).get(PROGRAM)
    lo, hi = cell["slice_unix"]
    chunks = [e["tokens"] for span in run.spans.values()
              for e in span["events"]
              if e["event"] == "prefill_chunk" and lo <= e["ts"] < hi]
    steps = sum(1 for s in run.window_steps
                if s.get("kind") == "prefill" and lo <= s["ts"] < hi)
    if not chunks or not steps or not program or not program["count"]:
        return None
    cfg = cell["config_as_run"]
    counts = family.module("counts", cfg)
    if not hasattr(counts, "ssd_prefill"):
        return None
    flops, moved = counts.ssd_prefill(cfg, chunks)
    scale = program["count"] / steps * counts.num_mamba(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * scale, moved * scale,
        cell["version"]["device_kind"])
    return share
