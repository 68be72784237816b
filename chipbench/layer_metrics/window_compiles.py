"""Step programs compiled inside the window: growth of
``/debug/compiles`` events between the window's start and its end.
Should be 0; anything else is set-up that leaked into the measurement."""

LAYER = "step programs"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    if not run.compiles:
        return None
    before, after = (sum(run.compiles[k]["events"].values())
                     for k in ("before", "after"))
    return after - before
