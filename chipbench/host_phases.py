"""What the host did while the device idled: a profiler slice's device
idle gaps, cut by the phase the server loop's thread was in.

    JAX_PLATFORMS=cpu python3 chipbench/host_phases.py \\
        <profile dir> <steps.json> <out.json> <platform>

The server loop's thread is always in one phase of one turn
(``production_stack_tpu/engine/tracing.py``, ``TURN_PHASES``).  While a
profiler slice runs, each phase is an event ``engine.<phase>`` inside
an event ``engine.turn``, both with ``step=<the turn record's step>``,
on that thread's line of ``/host:CPU``, and each delivery of a token to
its stream is an event ``server.stream_token`` on the event loop's
line.
They lie on the same clock as the device's operations, so an idle gap
of the device (``reduce.py``'s kind: a hole in the union of the ``XLA
Ops`` intervals) is cut by the phase events that overlap it; what no
phase covers is ``unattributed``.

Keys of the summary: ``span_s`` and ``idle_s`` as ``reduce.py`` has
them (``window_s`` and ``window_s - busy_s``, per device plane);
``idle_by_phase_s``; ``phase_s``, the seconds of each phase inside the
span; ``stream_busy_s``, the union of the ``server.stream_token``
events, and ``stream_busy_by_phase_s``, which phase the loop thread was
in meanwhile; ``turn_steps``, the turns that lie whole in the device's
span; ``clock_pairs`` instants that both clocks have: the start and the
end of each ``engine.turn`` event against ``t_start`` and ``t_end`` of
the record in ``steps.json`` with its ``step``, and for the turn that
the slice's start cut, and the one its end cut, the end of its last
phase event against ``t_end``, the start of its first against
``t_start``; ``clock_offset_ns`` is the median over them of the
record's instant less the event's on the profiler's clock (the plane
``Task Environment`` has the slice's start on the unix clock): how far
the records' clock and the profiler's disagree.  Nothing here depends
on it: idle is cut on the profiler's clock alone.  A slice of a program
without the annotations gives ``engine_events`` 0 and all idle
``unattributed``.

The per-layer readers (``layer_metrics/host_idle.py`` and the three
that read the turn records) come here: ``load(run)`` gives a run's
summary, from ``host_phases.json`` in the run directory or from a
child that writes it, since the benchmark's parent never imports jax.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import statistics
import subprocess
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import reduce  # noqa: E402
from chipbench.e2e import percentile  # noqa: E402

TURN, PHASE_PREFIX, STREAM = "engine.turn", "engine.", "server.stream_token"
UNATTRIBUTED = "unattributed"
# Phases in which the loop thread waits for the device; idle under any
# other phase is idle the host explains.
DEVICE_PHASES = ("wait",)
# What the loop thread does itself: every phase of TURN_PHASES but
# ``wait`` and ``idle`` (parked with nothing to serve).
LOOP_PHASES = ("admit", "plan", "build", "rng", "dispatch", "parse",
               "commit", "emit", "other")


def cut(intervals: list, phases: list) -> dict:
    """Seconds of ``intervals`` [(start_ns, end_ns)] under each label
    of ``phases`` [(start_ns, end_ns, label)], which are sorted and do
    not overlap one another."""
    starts = [p[0] for p in phases]
    out: dict = {}
    for s, e in intervals:
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(phases) and phases[i][0] < e:
            ps, pe, label = phases[i]
            shared = min(e, pe) - max(s, ps)
            if shared > 0:
                out[label] = out.get(label, 0) + shared
            i += 1
    return {k: v / 1e9 for k, v in out.items()}


def clip(intervals: list, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def summarize(planes: dict, host: dict, records: list) -> dict:
    """``planes`` as ``reduce.read_planes`` gives them; ``host``:
    {"phases": [(start_ns, end_ns, phase, step)], "turns": [(start_ns,
    end_ns, step)], "stream": [(start_ns, end_ns)], "start_unix_ns":
    int}; ``step`` is None on an event without it."""
    ops = [[(s, s + d) for _, s, d in p["ops"]] for p in planes.values()]
    ops = [o for o in ops if o]
    if not ops:
        return {"span_s": 0.0, "idle_s": 0.0, "engine_events": 0}
    lo = min(s for o in ops for s, _ in o)
    hi = max(e for o in ops for _, e in o)
    phases = sorted(p[:3] for p in host["phases"])
    stream = clip(host["stream"], lo, hi)
    idle_s, by_phase = 0.0, {}
    for intervals in ops:
        holes = reduce.gaps(intervals)
        idle_s += sum(e - s for s, e in holes) / 1e9
        for label, seconds in cut(holes, phases).items():
            by_phase[label] = by_phase.get(label, 0.0) + seconds
    n = len(ops)
    idle_s /= n
    by_phase = {k: v / n for k, v in by_phase.items()}
    by_phase[UNATTRIBUTED] = max(0.0, idle_s - sum(by_phase.values()))
    turns = [t for t in host["turns"] if lo <= t[0] and t[1] <= hi]
    offsets = clock_offsets(host, records)
    return {
        "span_s": (hi - lo) / 1e9,
        "idle_s": idle_s,
        "idle_by_phase_s": by_phase,
        "phase_s": cut([(lo, hi)], phases),
        "stream_busy_s": reduce.union_s(stream),
        "stream_busy_by_phase_s": cut(sorted(stream), phases),
        "clock_offset_ns": (round(statistics.median(offsets))
                            if offsets else None),
        "clock_pairs": len(offsets),
        "engine_events": len(phases) + len(host["turns"]),
        "turn_steps": sorted(step for _, _, step in turns),
    }


def clock_offsets(host: dict, records: list) -> list:
    """For each instant that a record and an event both have, the
    record's less the event's, in ns."""
    by_step = {r["step"]: r for r in records if "t_start" in r}
    whole = {step: (s, e) for s, e, step in host["turns"]}
    torn: dict = {}  # turns with phase events and no turn event
    for s, e, _, step in host["phases"]:
        if step is not None and step not in whole:
            first, last = torn.get(step, (s, e))
            torn[step] = (min(first, s), max(last, e))
    pairs = []
    for step, (s, e) in whole.items():
        if step in by_step:
            pairs += [(by_step[step]["t_start"], s),
                      (by_step[step]["t_end"], e)]
    for step, (s, e) in torn.items():
        if step not in by_step or not whole:
            continue
        if step < min(whole):    # began before the slice, ends in it
            pairs.append((by_step[step]["t_end"], e))
        elif step > max(whole):  # still open when the slice stopped
            pairs.append((by_step[step]["t_start"], s))
    return [unix_s * 1e9 - (host["start_unix_ns"] + ns)
            for unix_s, ns in pairs]


def read_host(path: str) -> dict:
    """The annotations of ``/host:CPU``, whatever thread they are on."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host = {"phases": [], "turns": [], "stream": [], "start_unix_ns": 0}
    for plane in data.planes:
        if plane.name == "Task Environment":
            host["start_unix_ns"] = int(dict(plane.stats).get(
                "profile_start_time", 0))
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                name = e.name
                if name == STREAM:
                    host["stream"].append((int(e.start_ns), int(e.end_ns)))
                elif name == TURN:
                    step = dict(e.stats).get("step")
                    if step is not None:
                        host["turns"].append(
                            (int(e.start_ns), int(e.end_ns), int(step)))
                elif name.startswith(PHASE_PREFIX):
                    host["phases"].append(
                        (int(e.start_ns), int(e.end_ns),
                         name[len(PHASE_PREFIX):],
                         dict(e.stats).get("step")))
    return host


def main(argv) -> int:
    profile_dir, steps_path, out, platform = argv
    paths = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        print(f"no .xplane.pb under {profile_dir}", file=sys.stderr)
        return 1
    try:
        planes = reduce.read_planes(paths[-1], platform)
    except reduce.NoDevicePlane as e:
        print(f"{paths[-1]}: {e}", file=sys.stderr)
        return 1
    records = []
    if os.path.exists(steps_path):
        with open(steps_path) as f:
            records = json.load(f)
        if isinstance(records, dict):  # /debug/steps as it was served
            records = records["steps"]
    summary = summarize(planes, read_host(paths[-1]), records)
    summary["stand_in"] = platform != "tpu"
    with open(out, "w") as f:
        json.dump(summary, f)
    return 0


# ---- for the readers -------------------------------------------------------


def load(run):
    """A run's summary, or None where there is no slice to read."""
    path = os.path.join(run.dir, "host_phases.json")
    if not os.path.exists(path):
        profile = os.path.join(run.dir, "profile")
        if not os.path.isdir(profile) or not run.cell:
            return None
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), profile,
             os.path.join(run.dir, "steps.json"), path,
             run.cell["version"]["platform"]],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
        if child.returncode != 0:
            return None
    with open(path) as f:
        return json.load(f)


def host_idle_s(summary: dict) -> float:
    return sum(v for k, v in summary["idle_by_phase_s"].items()
               if k != UNATTRIBUTED and k not in DEVICE_PHASES)


def decode_turn_ms(run, phases: tuple):
    """Median over the window's decode turns of the milliseconds in
    ``phases``; None where the step records carry no phases (a program
    without them)."""
    values = [sum(s["phases"].get(name, 0.0) for name in phases)
              for s in run.window_steps
              if s.get("kind") == "decode" and "phases" in s]
    return percentile(values, 50) if values else None


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
