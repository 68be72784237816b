"""From a decode turn's start to its program enqueued: median over the
window's decode turns of the phases ``admit`` + ``plan`` + ``build`` +
``rng`` + ``dispatch`` of the ``/debug/steps`` records."""

from chipbench.host_phases import decode_turn_ms

LAYER = "engine loop + scheduler"
UNIT = "ms"
MOVES = "output_tok_s"
SOURCE = "program_span"


def read(run):
    return decode_turn_ms(
        run, ("admit", "plan", "build", "rng", "dispatch"))
