"""The loop's period per decode token-step: median over the window's
decode-burst step records of (``host_ms + device_wait_ms``) / steps in
the burst."""

from chipbench.e2e import percentile

LAYER = "step programs"
UNIT = "ms"
MOVES = "output_tok_s"
SOURCE = "program_span"


def read(run):
    periods = [(s["host_ms"] + s["device_wait_ms"]) / s["window"]
               for s in run.window_steps
               if s.get("kind") == "decode" and s.get("window")]
    return percentile(periods, 50) if periods else None
