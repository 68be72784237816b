"""The share of one interpreter that the event loop's thread used: sum
of ``front.cpu_ms`` (that thread's CPU clock between a turn's start and
its end) over the window's turns of every kind, over the sum of their
walls.  The turns are contiguous, so this is the thread's CPU time over
the time the turns cover.  Near 100 the front, not the chip, sets the
pace.  Not read from a program whose records have no ``front``."""

LAYER = "engine HTTP front"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def front_turns(run) -> list:
    """The window's turns that say what the event loop's thread's CPU
    clock read over them."""
    return [s for s in run.window_steps
            if "cpu_ms" in s.get("front", ()) and "t_start" in s]


def read(run):
    turns = front_turns(run)
    wall_ms = sum(s["t_end"] - s["t_start"] for s in turns) * 1e3
    if wall_ms <= 0:
        return None
    return 100.0 * sum(s["front"]["cpu_ms"] for s in turns) / wall_ms
