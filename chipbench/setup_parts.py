"""Set-up by part, for the six ``layer_metrics/setup_*.py`` readers.

What they share: the server's own timeline of its start
(``cell.json``'s ``version.startup``, which ``run.py`` fetched right
after ``/health``: spans ``boot`` and ``boot.*`` on the unix clock, from
the instant the kernel started the server's process) and the split of
every program load (``compiles.json``'s ``before``, the ledger at the
window's first instant: ``trace_s``, ``lower_s``, ``backend_s`` with a
``cache_read_s`` inside it, and ``cache``).  A server that gives no
``startup`` (one from before the timeline) gives every reader ``None``.

From the server's first instant to the window's:

    t0_unix - process_start_unix = setup_boot_s + setup_lower_s
        + setup_load_s + setup_rest_s + ramp_s + START_IN_S

``setup_probes_s`` is inside ``setup_boot_s``; ``setup_cache_misses``
is a count.  The run's ``setup_s`` is longer by ``run.py``'s own start
before it starts the server.
"""

# run.py opens the window this long after the ramp (``start_in_s``).
START_IN_S = 0.5


def startup(run):
    return (run.cell.get("version") or {}).get("startup")


def span_seconds(run, name):
    """Seconds under ``name`` (a span entered more than once is one
    sum); None without a timeline."""
    timeline = startup(run)
    if timeline is None:
        return None
    return sum(span["seconds"] or 0.0 for span in timeline["spans"]
               if span["name"] == name)


def loads(run):
    """The compile records stamped before the window; None without a
    timeline."""
    if startup(run) is None or not run.compiles:
        return None
    return [record for record in run.compiles["before"]["recent"]
            if record["ts"] < run.cell["t0_unix"]]


def probes(run):
    return [span for span in startup(run)["spans"]
            if span["name"] == "boot.probe"]


def main(argv=None) -> int:
    """``python3 -m chipbench.setup_parts <run directory> ...``: the six
    readers on a run's files, and both sides of the sum above."""
    import importlib
    import json
    import sys

    from chipbench.runfiles import RunFiles

    names = ("setup_boot_s", "setup_probes_s", "setup_lower_s",
             "setup_load_s", "setup_cache_misses", "setup_rest_s")
    for path in (sys.argv[1:] if argv is None else argv):
        run = RunFiles(path)
        out = {name: importlib.import_module(
            f"chipbench.layer_metrics.{name}").read(run) for name in names}
        if startup(run) is not None:
            out["ramp_s"] = run.cell["traffic_params"]["ramp_s"]
            out["parts_sum_s"] = (
                out["setup_boot_s"] + out["setup_lower_s"]
                + out["setup_load_s"] + out["setup_rest_s"]
                + out["ramp_s"] + START_IN_S)
            out["t0_less_process_start_s"] = (
                run.cell["t0_unix"] - startup(run)["process_start_unix"])
        print(json.dumps({"run": path, **out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
