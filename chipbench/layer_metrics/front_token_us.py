"""What a token costs the event loop's thread: sum of ``front.cpu_ms``
over the window's turns of every kind, over the sum of their
``front.tokens`` (the tokens the streams' consumers took meanwhile), in
microseconds.  The thread's polls and probes are inside it: it is the
thread's CPU time a token served, not a consumer's alone.  Not read
from a program whose records have no ``front``."""

from chipbench.layer_metrics.front_cpu_share import front_turns

LAYER = "engine HTTP front"
UNIT = "us"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(run):
    turns = front_turns(run)
    tokens = sum(s["front"]["tokens"] for s in turns)
    if not tokens:
        return None
    return 1e3 * sum(s["front"]["cpu_ms"] for s in turns) / tokens
