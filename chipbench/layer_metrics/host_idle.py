"""The device idle that the host explains: seconds of the profiler
slice in which no operation ran on the device and the server loop's
thread was in a phase other than ``wait``, over the slice's span
(``host_phases.py``).  ``device_idle`` less this is idle under ``wait``
or under no phase at all.  Not read from a program without the phase
annotations, nor where host threads stood in for the device."""

from chipbench import host_phases

LAYER = "device"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"


def read(run):
    summary = host_phases.load(run)
    if (not summary or not summary.get("span_s")
            or not summary.get("engine_events") or summary.get("stand_in")):
        return None
    return 100.0 * host_phases.host_idle_s(summary) / summary["span_s"]
