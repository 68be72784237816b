"""Fullest the paged cache got: maximum of ``vllm:gpu_cache_usage_perc``
polled each second of the window, as a percentage of the pages."""

LAYER = "paged cache"
UNIT = "%"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(run):
    return 100.0 * max(run.cache_usage) if run.cache_usage else None
