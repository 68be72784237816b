"""How far a shift of phase can move a run's tokens per second: one
run's arrivals counted again with the window laid later and later.

    python3 chipbench/phase.py [--from <s>] <run directory> ...

A run directory (``.chipbench/runs/<cell>/``, or a copy of it) holds
``arrivals.json`` and ``cell.json``.  The window's origin is slid from
0 to +5.2 s (two of the decode-closed cell's cycles of 2.6 s) in steps
of 50 ms, and at each origin tokens per second are read twice: as
``e2e.output_tok_s`` has them, edges weighted, and by the plain count
over the window that the benchmark used before PR 25.  A closed loop
keeps its clients sending after the window, so the slid windows see the
same load; ``--from 25`` starts the slide 25 s into the window, where
the decode-closed cell's batch has filled (at 0 it has not: a window
laid later there leaves part of the fill behind, which moves either
reading by more than any phase).  Per run it prints, for each of the
two, the reading at the first origin, the median, the lowest and
highest, and peak to peak as a share of the median; then one JSON line
with both series.  Not a cell, and not run by the driver.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))

from chipbench import e2e  # noqa: E402
from chipbench.runfiles import RunFiles  # noqa: E402

SLIDE_S = 5.2
STEP_S = 0.05


def plain_tok_s(arrivals: list, seconds: float) -> float:
    """The count before PR 25: tokens that arrived in ``[0, seconds)``
    over ``seconds``, every token weighing the same."""
    return sum(n for t, n in arrivals if 0 <= t < seconds) / seconds


def slide(arrivals: list, seconds: float, estimator,
          start: float = 0.0) -> list:
    """``estimator``'s reading with the window's origin at ``start``,
    50 ms later, ... up to and including 5.2 s later."""
    origins = [start + k * STEP_S
               for k in range(round(SLIDE_S / STEP_S) + 1)]
    return [estimator([(t - origin, n) for t, n in arrivals], seconds)
            for origin in origins]


def peak_to_peak(series: list) -> float:
    """Highest less lowest, as a share of the median."""
    return (max(series) - min(series)) / statistics.median(series)


def main(argv: list) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--from", dest="start", type=float, default=0.0,
                        help="seconds into the window at which the "
                             "slide starts")
    parser.add_argument("run_dirs", nargs="+")
    args = parser.parse_args(argv)
    for run_dir in args.run_dirs:
        run = RunFiles(run_dir)
        if not run.arrivals or not run.cell:
            print(f"{run_dir}: no arrivals.json and cell.json of a run",
                  file=sys.stderr)
            return 1
        arrivals, seconds = run.arrivals, run.cell["seconds"]
        if arrivals[-1][0] < args.start + SLIDE_S + seconds:
            print(f"{run_dir}: the arrivals end before the last slid "
                  "window does", file=sys.stderr)
            return 1
        out = {"run": run_dir, "seed": run.cell["seed"], "seconds": seconds,
               "from_s": args.start, "slide_s": SLIDE_S, "step_s": STEP_S}
        print(f"{run_dir} (seed {run.cell['seed']}, window {seconds} s, "
              f"origin {args.start} s to +{SLIDE_S} s by {STEP_S} s)")
        for name, estimator in (("weighted", e2e.output_tok_s),
                                ("plain", plain_tok_s)):
            series = slide(arrivals, seconds, estimator, args.start)
            out[name] = [round(v, 3) for v in series]
            print(f"  {name:8s} first: {series[0]:.3f}  median "
                  f"{statistics.median(series):.3f}  lowest "
                  f"{min(series):.3f}  highest {max(series):.3f}  peak to "
                  f"peak {100 * peak_to_peak(series):.3f}%")
        print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
