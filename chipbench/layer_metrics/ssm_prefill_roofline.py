"""Share of its roofline reached by the selective scan of a prefill
step, the ``ssm_prefill`` scope of ``_step_impl``: the gather of the
rows' states, the scan over the chunk's tokens, the scatter back.
Bound: memory by the chip's bf16 peak, which elementwise float32 work
cannot reach, so the share flatters no one.

Least time = ``counts.ssm_prefill`` of the prompt chunks that ran in
the profiler slice (the engine spans' ``prefill_chunk`` events stamped
inside it, scaled as ``prefill_roofline`` scales them: executions in
the trace over prefill step records in the slice) x Mamba layers, over
the chip's peaks.  Time taken = the device seconds under the name in
the prefill step program.  None where no prefill fell in the slice."""

from chipbench import family, hybrid_slice, roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "ssm_prefill"
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
    flops, moved = counts.ssm_prefill(cfg, chunks)
    scale = program["count"] / steps * counts.num_mamba(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * scale, moved * scale,
        cell["version"]["device_kind"])
    return share
