"""What the compile cache and the compiler cost: the sum of ``backend_s``
over the compile records stamped before the window (the stage jax times
as the backend's compile; a read from the persistent cache,
``cache_read_s``, is inside it).  The half-width prefill program
compiles on a thread beside the full width's load, so this sum can pass
the wall it took."""

from chipbench import setup_parts

LAYER = "step programs"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    loads = setup_parts.loads(run)
    if loads is None:
        return None
    return sum(r["backend_s"] for r in loads)
