"""The engine loop's own share of a step: sum of ``host_ms`` over sum
of ``host_ms + device_wait_ms`` of the window's step records.  Host
time is time in which the loop did not wait for the device."""

LAYER = "engine loop + scheduler"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "program_span"


def read(run):
    host = sum(s["host_ms"] for s in run.window_steps)
    wait = sum(s["device_wait_ms"] for s in run.window_steps)
    return 100.0 * host / (host + wait) if host + wait > 0 else None
