"""The kernels' lowering probes at start-up, all of them: span
``boot.probes`` of ``version.startup`` (inside ``setup_boot_s``; each
probed case is a ``boot.probe`` span with its kernel, its shape and what
its load was made of)."""

from chipbench import setup_parts

LAYER = "step programs"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return setup_parts.span_seconds(run, "boot.probes")
