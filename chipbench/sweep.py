"""Find a cell's knee, once, on the chip: one server, a few rates in
turn, the cell's own generator.

    python3 chipbench/sweep.py --workload <open-loop cell> \
        --rates 2,3.5,5,6.5,8 --seconds 30 --out chiprun_out/sweep

The knee is the highest rate at which the backlog does not grow over
the window: requests in flight at the window's end are no more than at
its middle (within a tenth of a second's arrivals), and the time to
first token has not left its plateau.  The cell's file then fixes 0.8 x
that rate as a number; the benchmark never searches.  Not a cell, and
not run by the driver.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))

from chipbench import e2e, run as bench_run  # noqa: E402
from chipbench.client import Load  # noqa: E402
from chipbench.procs import Procs  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--trace-rate", type=float, default=None,
                        help="take the traced side at this rate")
    parser.add_argument("--out", default="chiprun_out/sweep")
    args = parser.parse_args()
    cell = bench_run.find_cell(args.workload)
    config = bench_run.load_json(cell["config_file"])
    bench = config["chipbench"]
    run_dir = os.path.join(bench_run.STATE, "runs", "sweep")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    os.makedirs(args.out, exist_ok=True)
    procs = Procs(run_dir, bench_run.ROOT)
    rows = []
    try:
        reference = bench_run.Reference(cell, procs)
        engine_url, router_url, _, hf_config = bench_run.start_servers(
            cell, bench, procs, run_dir, trace=True)
        version = bench_run.require_device(engine_url, cell, bench)
        checked = bench_run.warm_and_check(cell, hf_config, bench,
                                           router_url, reference)
        traffic = importlib.import_module(
            f"chipbench.traffic.{cell['traffic_kind']}")
        for rate in map(float, args.rates.split(",")):
            params = dict(cell["traffic_params"], rate_per_s=rate)
            requests = traffic.plan(params, args.seconds, args.seed,
                                    hf_config["vocab_size"])
            load = Load(router_url, cell["config"], params, args.seconds,
                        cell["sampling"], start_in_s=params["ramp_s"] + 0.5)
            side = {}
            asyncio.run(bench_run.window(
                load, requests, traffic, engine_url, run_dir,
                rate == args.trace_rate, side))
            summary = e2e.summarize(load.records, load.arrivals, args.seconds)
            lasts = [r["last"] for r in load.records if r["last"]]
            row = {"rate_per_s": rate, **summary,
                   "in_flight_mid": e2e.in_flight(load.records,
                                                  args.seconds / 2),
                   "in_flight_end": e2e.in_flight(load.records,
                                                  args.seconds),
                   "drain_s": max(lasts) - args.seconds if lasts else None,
                   "compile_events":
                       side["compiles_after"]["events"],
                   "compile_seconds": side["compiles_after"]["seconds"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
            if side.get("steps"):
                with open(os.path.join(args.out, "steps.json"), "w") as f:
                    json.dump(side["steps"], f)
        memory = json.loads(bench_run.http(engine_url + "/debug/memory", 30))
    finally:
        procs.dump_tails(3000)
        procs.stop_all()
    with open(os.path.join(args.out, "sweep.json"), "w") as f:
        json.dump({"cell": cell["name"], "seconds": args.seconds,
                   "version": version, "reference": checked,
                   "memory": memory, "rows": rows}, f, indent=1)
    for name in ("engine.log", "router.log", "reference.log",
                 "spans.jsonl"):
        path = os.path.join(run_dir, name)
        if os.path.exists(path):
            shutil.copy(path, os.path.join(args.out, name))
    profile = os.path.join(run_dir, "profile")
    if os.path.isdir(profile):
        bench_run.reduce_trace(run_dir, procs, version["platform"])
        shutil.copy(os.path.join(run_dir, "trace_summary.json"), args.out)
        size = sum(os.path.getsize(os.path.join(d, f))
                   for d, _, files in os.walk(profile) for f in files)
        print(f"profile: {size} bytes", flush=True)
        if size < 40e6:  # what a call brings back is capped
            shutil.copytree(profile, os.path.join(args.out, "profile"),
                            dirs_exist_ok=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
