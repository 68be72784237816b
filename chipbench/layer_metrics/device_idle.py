"""Share of the profiler slice in which no operation ran on the device:
1 - union of the device-op intervals / the slice's span, from
``reduce.py``.  Not read where host threads stood in for the device
(the CPU rehearsal)."""

LAYER = "device"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "device_trace"


def read(run):
    trace = run.trace
    if not trace or not trace.get("window_s") or trace.get("stand_in"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
