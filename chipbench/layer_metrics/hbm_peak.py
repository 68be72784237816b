"""Peak device memory: ``peak_bytes_in_use`` of the fullest device, from
``/debug/memory`` after the window (the device's own
``memory_stats()``)."""

LAYER = "device"
UNIT = "GB"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(run):
    devices = (run.memory or {}).get("devices")
    if not devices:
        return None
    return max(d["peak_bytes_in_use"] for d in devices) / 1e9
