"""Programs and probes the backend compiled though the persistent cache
was asked for them: compile records stamped before the window and
``boot.probe`` spans whose ``cache`` is ``miss``.  On a warm start the
ones left are the loads the cache never keeps."""

from chipbench import setup_parts

LAYER = "step programs"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    loads = setup_parts.loads(run)
    if loads is None:
        return None
    return sum(r.get("cache") == "miss"
               for r in loads + setup_parts.probes(run))
