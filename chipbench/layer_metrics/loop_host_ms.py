"""What the server loop's thread does per decode turn while the device
is not what it waits for: median over the window's decode turns of the
turn's wall less its ``wait`` phase (and less ``idle``, in which the
thread is parked with nothing to serve), from the ``phases`` of the
``/debug/steps`` records.  Over the turn's wall this is what
``host_share`` was meant to be: the turn runs from the end of the one
before, so the hand-over of the outputs is inside it."""

from chipbench.host_phases import LOOP_PHASES, decode_turn_ms

LAYER = "engine loop + scheduler"
UNIT = "ms"
MOVES = "output_tok_s"
SOURCE = "program_span"


def read(run):
    return decode_turn_ms(run, LOOP_PHASES)
