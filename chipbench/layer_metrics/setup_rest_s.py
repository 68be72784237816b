"""The rest of the server's side of set-up: from its process's start to
the window's first instant (``t0_unix - process_start_unix``), less
``setup_boot_s``, ``setup_lower_s``, ``setup_load_s``, the cell's ramp
and the half second after it.  The warm and check requests' own serving,
the router's start and the wait for the reference."""

from chipbench import setup_parts
from chipbench.layer_metrics import setup_boot_s, setup_load_s, setup_lower_s

LAYER = "engine loop + scheduler"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    parts = [reader.read(run)
             for reader in (setup_boot_s, setup_lower_s, setup_load_s)]
    if None in parts:
        return None
    whole = (run.cell["t0_unix"]
             - setup_parts.startup(run)["process_start_unix"])
    return (whole - sum(parts) - run.cell["traffic_params"]["ramp_s"]
            - setup_parts.START_IN_S)
