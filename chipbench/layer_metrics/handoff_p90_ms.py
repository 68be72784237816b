"""How late the event loop takes a turn's outputs: 90th percentile,
over the window's turns that emitted, of the record's ``handoff_ms``
(from the loop thread's entering ``emit`` until the event loop had put
the turn's last output on its stream).  The loop thread's own side of
the hand-over is ``emit_ms``; this is the other thread's."""

from chipbench.e2e import percentile

LAYER = "engine HTTP front"
UNIT = "ms"
MOVES = "output_tok_s"
SOURCE = "program_span"


def read(run):
    values = [s["handoff_ms"] for s in run.window_steps
              if s.get("emitted") and "handoff_ms" in s]
    return percentile(values, 90) if values else None
