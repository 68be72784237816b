"""The server's start, from the instant the kernel started its process
to its HTTP listener: span ``boot`` of ``version.startup`` (imports, the
backend's initialisation, the kernels' probes, the weights, the cache's
planes, the rest of the engine; ``/metrics``
``vllm:engine_startup_seconds{span}`` has each)."""

from chipbench import setup_parts

LAYER = "engine HTTP front"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return setup_parts.span_seconds(run, "boot")
