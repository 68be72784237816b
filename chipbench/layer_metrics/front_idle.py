"""The device idle that falls while both of the interpreter's threads
want it: seconds of the profiler slice in which no operation ran on the
device, the server loop's thread was in a phase other than ``wait``,
and the event loop's thread was inside a ``server.*`` event, over the
slice's span (``front_phases.py``).  ``host_idle`` less this is idle
the loop thread explains alone.  Not read from a program without the
``server.consume`` events, nor where host threads stood in for the
device."""

from chipbench import front_phases

LAYER = "device"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"


def read(run):
    summary = front_phases.load(run)
    if (not summary or not summary.get("span_s")
            or not summary.get("engine_events") or summary.get("stand_in")
            or not summary["front_events"].get(front_phases.CONSUME)):
        return None
    return 100.0 * summary["idle_contended_s"] / summary["span_s"]
