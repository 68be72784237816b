"""Share of its roofline reached by a windowed layer's attention in a
prefill step, the ``swa_prefill`` scope of ``_step_impl``: the rows'
rings turned to position order, the step's own plane, the kernel under
its window term.  Bound: memory, by ``counts.swa_prefill``'s count (a
token's q, k, v and output are 36 864 B, 45 ns at the chip's peak,
against 4 x 64 heads x 128 x 128 operations over a window's keys, 21
ns): the kernel's products over a 256-token tile of which a third is
masked away, the ring's turn to position order and the step's own
plane all read as time over that floor, which they are.

Least time = ``counts.swa_prefill`` of the prompt chunks that ran in
the profiler slice (the engine spans' ``prefill_chunk`` events stamped
inside it, scaled as ``prefill_roofline`` scales them: executions in
the trace over prefill step records in the slice) x windowed layers,
over the chip's peaks.  Time taken = the device seconds under the name
in the prefill step program.  None where no prefill fell in the slice,
the program has no such scope or the family's counts no such
function."""

from chipbench import family, hybrid_slice, roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "swa_prefill"
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
    if not hasattr(counts, "swa_prefill"):
        return None
    flops, moved = counts.swa_prefill(cfg, chunks)
    scale = program["count"] / steps * counts.num_windowed(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * scale, moved * scale,
        cell["version"]["device_kind"])
    return share
