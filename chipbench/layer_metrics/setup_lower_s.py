"""Python on the loop thread that no cache saves: the sum of ``trace_s +
lower_s`` over the compile records stamped before the window (jax's
tracing of the step function and its lowering to StableHLO, as
``jax.monitoring`` published them while the program loaded)."""

from chipbench import setup_parts

LAYER = "step programs"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    loads = setup_parts.loads(run)
    if loads is None:
        return None
    return sum(r["trace_s"] + r["lower_s"] for r in loads)
