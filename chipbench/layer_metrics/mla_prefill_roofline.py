"""Share of its roofline reached by the latent attention (MLA) of a
prefill step, the ``mla_prefill`` scope of ``_step_impl``: the chunk's
latents written to their pages, then the chunk's queries against the
row's earlier latent in the pages and its own tokens, absorbed (the
decode step's form at T > 1), and the values' up-projection.  Bound:
compute at chunk sizes.

Least time = ``counts.mla_prefill`` of the prompt chunks that ran in
the profiler slice, each with the context before it (the engine spans'
``prefill_chunk`` events stamped inside it, scaled as
``prefill_roofline`` scales them: executions in the trace over prefill
step records in the slice) x sublayers, over the chip's peaks.  Time
taken = the device seconds under the name in the prefill step program.
None where no prefill fell in the slice, or the program has no such
name."""

from chipbench import family, hybrid_slice, roofline

LAYER = "model + ops"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"

SCOPE = "mla_prefill"
PROGRAM = "_step_impl"


def read(run):
    seconds, events = hybrid_slice.scope_seconds(run.trace, SCOPE, PROGRAM)
    cell = run.cell
    if not events or not cell.get("slice_unix"):
        return None
    program = run.trace.get("programs", {}).get(PROGRAM)
    lo, hi = cell["slice_unix"]
    chunks = [(e["start"], e["tokens"]) for span in run.spans.values()
              for e in span["events"]
              if e["event"] == "prefill_chunk" and lo <= e["ts"] < hi]
    steps = sum(1 for s in run.window_steps
                if s.get("kind") == "prefill" and lo <= s["ts"] < hi)
    if not chunks or not steps or not program or not program["count"]:
        return None
    cfg = cell["config_as_run"]
    counts = family.module("counts", cfg)
    flops, moved = counts.mla_prefill(cfg, chunks, steps)
    scale = program["count"] / steps * counts.num_sublayers(cfg)
    share, _ = roofline.kernel_roofline(
        seconds, flops * scale, moved * scale,
        cell["version"]["device_kind"])
    return share
