"""What the event loop's thread did while the device idled: a profiler
slice's device idle gaps under the loop thread's own phases, cut by
whether the interpreter's other thread was busy meanwhile.

    JAX_PLATFORMS=cpu python3 chipbench/front_phases.py \\
        <profile dir> <out.json> <platform>

The server's process has two threads that run Python: the loop thread,
always in one ``engine.<phase>`` event (``host_phases.py``), and the
event loop's, which during a slice is inside an event
``server.stream_token`` while it puts a turn's outputs on their
streams, ``server.consume`` while a stream's consumer turns its tokens
into frames, and ``server.write`` for the synchronous part of each
socket write (``production_stack_tpu/engine/tracing.py``,
``FrontClock``).  They share one interpreter: while the event loop's
thread is inside one of the three, the loop thread can run Python only
in turns with it.  All of it lies on the profiler's clock, as the
device's operations do, and nothing here reads another.

Keys of the summary: ``span_s`` and ``idle_s`` as ``host_phases.py``
has them; ``front_events``, the events of each of the three names in
the slice; ``front_overlaps``, how many of them begin before the one
before them on their thread's line has ended (they never should);
``front_busy_s``, the union of the three inside the span;
``front_busy_by_phase_s``, which phase the loop thread was in
meanwhile; ``idle_contended_s``, the seconds in which no operation ran
on the device, the loop thread was in a phase other than ``wait``, and
the event loop's thread was inside a ``server.*`` event;
``idle_alone_s``, the same with the event loop's thread outside every
one.  The two sum to ``host_phases.py``'s ``host_idle_s`` of the same
slice.  ``load(run)`` gives a run's summary to the reader
(``layer_metrics/front_idle.py``), from ``front_phases.json`` in the
run directory or from a child that writes it, since the benchmark's
parent never imports jax.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import subprocess
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import reduce  # noqa: E402
from chipbench.host_phases import (  # noqa: E402
    DEVICE_PHASES, PHASE_PREFIX, STREAM, TURN, clip, cut)

CONSUME, WRITE = "server.consume", "server.write"
FRONT_EVENTS = (STREAM, CONSUME, WRITE)
CONTENDED, ALONE = "contended", "alone"


def merged(intervals: list) -> list:
    """The union of (start_ns, end_ns) intervals as sorted intervals
    that neither overlap nor touch."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def split(phases: list, busy: list) -> list:
    """``phases`` [(start_ns, end_ns, label)], sorted and apart, cut at
    the edges of ``busy`` (``merged``): [(start_ns, end_ns, CONTENDED
    or ALONE)], sorted and apart, covering what the phases cover."""
    starts = [b[0] for b in busy]
    out = []
    for ps, pe, _ in phases:
        at = ps
        i = max(0, bisect.bisect_right(starts, ps) - 1)
        while i < len(busy) and busy[i][0] < pe:
            bs, be = max(busy[i][0], ps), min(busy[i][1], pe)
            if be > bs:
                if bs > at:
                    out.append((at, bs, ALONE))
                out.append((bs, be, CONTENDED))
                at = be
            i += 1
        if pe > at:
            out.append((at, pe, ALONE))
    return out


def overlaps(lines: list) -> int:
    """Events that begin before the one before them on their line has
    ended; ``lines`` [[(start_ns, end_ns)]]."""
    return sum(b[0] < a[1] for events in map(sorted, lines)
               for a, b in zip(events, events[1:]))


def summarize(planes: dict, host: dict) -> dict:
    """``planes`` as ``reduce.read_planes`` gives them; ``host``:
    {"phases": [(start_ns, end_ns, phase)], "front_lines": [[(start_ns,
    end_ns)] a line that has any], "front_events": {name: count}}."""
    ops = [[(s, s + d) for _, s, d in p["ops"]] for p in planes.values()]
    ops = [o for o in ops if o]
    if not ops:
        return {"span_s": 0.0, "idle_s": 0.0, "engine_events": 0,
                "front_events": host["front_events"]}
    lo = min(s for o in ops for s, _ in o)
    hi = max(e for o in ops for _, e in o)
    phases = sorted(host["phases"])
    busy = merged(clip([event for events in host["front_lines"]
                        for event in events], lo, hi))
    own = split([p for p in phases if p[2] not in DEVICE_PHASES], busy)
    idle_s, shared = 0.0, {CONTENDED: 0.0, ALONE: 0.0}
    for intervals in ops:
        holes = reduce.gaps(intervals)
        idle_s += sum(e - s for s, e in holes) / 1e9
        for label, seconds in cut(holes, own).items():
            shared[label] += seconds
    n = len(ops)
    return {
        "span_s": (hi - lo) / 1e9,
        "idle_s": idle_s / n,
        "engine_events": len(phases),
        "front_events": host["front_events"],
        "front_overlaps": overlaps(host["front_lines"]),
        "front_busy_s": sum(e - s for s, e in busy) / 1e9,
        "front_busy_by_phase_s": cut(busy, phases),
        "idle_contended_s": shared[CONTENDED] / n,
        "idle_alone_s": shared[ALONE] / n,
    }


def read_host(path: str) -> dict:
    """The ``engine.<phase>`` events of ``/host:CPU`` and the three
    ``server.*`` names by the line they are on, read as
    ``host_phases.read_host`` reads them."""
    from jax.profiler import ProfileData
    host = {"phases": [], "front_lines": [],
            "front_events": dict.fromkeys(FRONT_EVENTS, 0)}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            on_line = []
            for e in line.events:
                name = e.name
                if name in host["front_events"]:
                    host["front_events"][name] += 1
                    on_line.append((int(e.start_ns), int(e.end_ns)))
                elif name.startswith(PHASE_PREFIX) and name != TURN:
                    host["phases"].append(
                        (int(e.start_ns), int(e.end_ns),
                         name[len(PHASE_PREFIX):]))
            if on_line:
                host["front_lines"].append(on_line)
    return host


def main(argv) -> int:
    profile_dir, out, platform = argv
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
    summary = summarize(planes, read_host(paths[-1]))
    summary["stand_in"] = platform != "tpu"
    with open(out, "w") as f:
        json.dump(summary, f)
    return 0


def load(run):
    """A run's summary, or None where there is no slice to read."""
    path = os.path.join(run.dir, "front_phases.json")
    if not os.path.exists(path):
        profile = os.path.join(run.dir, "profile")
        if not os.path.isdir(profile) or not run.cell:
            return None
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), profile, path,
             run.cell["version"]["platform"]],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
        if child.returncode != 0:
            return None
    with open(path) as f:
        return json.load(f)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
