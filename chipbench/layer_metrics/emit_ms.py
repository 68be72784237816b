"""Handing a decode turn's outputs to the event loop: median over the
window's decode turns of the phase ``emit`` of the ``/debug/steps``
records (one ``call_soon_threadsafe`` per token)."""

from chipbench.host_phases import decode_turn_ms

LAYER = "engine HTTP front"
UNIT = "ms"
MOVES = "output_tok_s"
SOURCE = "program_span"


def read(run):
    return decode_turn_ms(run, ("emit",))
