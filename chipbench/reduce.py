"""From a profiler trace (``.xplane.pb``) to device busy time, time per
program, the heaviest device operations, the longest idle gaps, and
device time by name: by JAX name stack and by source file.

    JAX_PLATFORMS=cpu python3 chipbench/reduce.py <profile dir> <out.json> <platform>

A TPU's plane is ``/device:TPU:<n>``.  Its line ``XLA Ops`` holds one
event per operation that ran on the device (their union is the busy
time); its line ``XLA Modules`` holds one event per execution of a
jitted program, named after the jitted function
(``jit__decode_burst_impl(...)``); an execution cut by the slice's edge
is recorded short, which ``whole_execution_s`` allows for.  The window
is the span from the
first operation's start to the last one's end over all device planes;
busy time is averaged over the planes.  An idle gap is labelled by the
program that ran next: the gap is the time the device waited for that
program to be dispatched.

Each operation's event points at metadata of the plane (``xplane.py``)
that says where it came from.  ``scopes`` sums device seconds and
events by the name stack the operation was traced under, the traced
primitive last (``jit(_step_impl)/attn/named_kernel/pallas_call``: a
``jax.named_scope`` and a ``pallas_call``'s ``name`` are components of
it), so that a reader finds a kernel or a scope by the name the
program gave it (``roofline.scope_time``).  ``sources`` sums device
seconds by jitted program and by the file of the program's source the
operation came from, relative to the checkout, which needs no name in
the program.  Neither is cut at ten; an operation without the one or
the other is counted under ``NO_NAME`` / ``NO_SOURCE``, and a loop or
a conditional by its body's operations, like ``device_ops``.  A trace
that says neither (the rehearsal's stand-in) leaves both maps empty.

``<platform>`` is what the server said it runs on.  For ``tpu`` a trace
without a device plane that has an ``XLA Ops`` line is an error: the
reduction never reads host threads under a TPU's name.  Only for
``cpu`` (the rehearsal) do the XLA client's threads of ``/host:CPU``
stand in, so that the pipeline runs end to end; the summary then says
``stand_in`` and ``device_idle`` is not read from it.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from chipbench import xplane  # noqa: E402

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
NO_NAME, NO_SOURCE, NO_PROGRAM = "(no name)", "(no source)", "(no program)"


def union_s(intervals: list) -> float:
    """Seconds covered by the union of (start_ns, end_ns) intervals."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def gaps(intervals: list) -> list:
    """(start_ns, end_ns) of the holes in the union of ``intervals``."""
    out, end = [], None
    for s, e in sorted(intervals):
        if end is not None and s > end:
            out.append((end, s))
        end = e if end is None else max(end, e)
    return out


def program_name(event_name: str) -> str:
    """``jit__decode_burst_impl(1234)`` -> ``_decode_burst_impl``."""
    name = re.sub(r"\(.*$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """An op event is named by its whole HLO line (``%fusion.12 =
    bf16[...] fusion(...)``): keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def program_id(event_name: str):
    """``jit__decode_burst_impl(1234)`` -> 1234; None without one."""
    found = re.search(r"\((\d+)\)$", event_name)
    return int(found.group(1)) if found else None


def source_file(source: str, root: str = ROOT) -> str:
    """``/checkout/pkg/ops/x.py:57`` -> ``pkg/ops/x.py``: the line
    dropped, the path relative to ``root`` where it lies under it."""
    path = re.sub(r":\d+$", "", source)
    if not path:
        return NO_SOURCE
    if path.startswith(root + os.sep):
        return path[len(root) + 1:]
    return path


def whole_execution_s(durations: list) -> float:
    """Device time of one whole execution of a program.  An execution
    that the slice's edge cut short is recorded with what was seen of
    it, so executions shorter than half the longest are left out and
    the median of the rest is taken.  Right for a program of one fixed
    shape, whose executions take nearly the same time."""
    whole = sorted(d for d in durations if d >= max(durations) / 2)
    mid = len(whole) // 2
    return whole[mid] if len(whole) % 2 else (whole[mid - 1] + whole[mid]) / 2


def summarize(planes: dict) -> dict:
    """``planes``: {plane name: {"ops": [(name, start_ns, dur_ns)],
    "modules": [(name, start_ns, dur_ns)], and where the trace says
    it, in the order of "ops", "op_meta": [(name stack, source file,
    program)]}}."""
    starts = [s for p in planes.values() for _, s, _ in p["ops"]]
    ends = [s + d for p in planes.values() for _, s, d in p["ops"]]
    if not starts:
        return {"window_s": 0.0, "busy_s": 0.0, "planes": sorted(planes)}
    busy, op_time, programs, gap_list = [], {}, {}, []
    scopes, sources = {}, {}
    for plane in planes.values():
        intervals = [(s, s + d) for _, s, d in plane["ops"]]
        busy.append(union_s(intervals))
        meta = plane.get("op_meta")
        for index, (name, _, d) in enumerate(plane["ops"]):
            # A loop's own event spans its body's: count the body.
            if name.startswith(("while", "conditional")):
                continue
            op_time[name] = op_time.get(name, 0) + d
            if meta:
                scope, source, program = meta[index]
                entry = scopes.setdefault(scope, [0, 0])
                entry[0] += d
                entry[1] += 1
                by_file = sources.setdefault(program, {})
                by_file[source] = by_file.get(source, 0) + d
        modules = sorted((s, d, program_name(n))
                         for n, s, d in plane["modules"])
        for _, d, name in modules:
            entry = programs.setdefault(
                name, {"count": 0, "seconds": 0.0, "durations": []})
            entry["count"] += 1
            entry["seconds"] += d / 1e9
            entry["durations"].append(d / 1e9)
        for s, e in gaps(intervals):
            after = next((n for ms, _, n in modules if ms >= e - 1000),
                         "end of trace")
            gap_list.append((f"before {after}", (e - s) / 1e9))
    n = len(planes)
    by_label = {}
    for label, seconds in gap_list:
        by_label[label] = by_label.get(label, 0.0) + seconds
    return {
        "planes": sorted(planes),
        "window_s": (max(ends) - min(starts)) / 1e9,
        "busy_s": sum(busy) / n,
        "programs": {k: {"count": v["count"] / n,
                         "seconds": v["seconds"] / n,
                         "whole_s": whole_execution_s(v["durations"])}
                     for k, v in programs.items()},
        "device_ops": sorted(([k, v / 1e9 / n] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(([k, v / n] for k, v in by_label.items()),
                            key=lambda kv: -kv[1])[:10],
        "longest_gap_s": max((g for _, g in gap_list), default=0.0),
        "scopes": {k: {"seconds": ns / 1e9 / n, "count": count / n}
                   for k, (ns, count) in sorted(scopes.items())},
        "sources": {program: {k: ns / 1e9 / n
                              for k, ns in sorted(by_file.items())}
                    for program, by_file in sorted(sources.items())},
    }


class NoDevicePlane(Exception):
    pass


def read_planes(path: str, platform: str) -> dict:
    planes = {}
    if platform == "cpu":  # the rehearsal: host threads stand in
        from jax.profiler import ProfileData
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                ops = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for line in plane.lines
                       if line.name.startswith("tf_XLA")
                       for e in line.events]
                planes[plane.name] = {"ops": ops, "modules": []}
        return planes
    if platform != "tpu":
        raise NoDevicePlane(f"no reduction for platform {platform!r}")
    space = xplane.read_space(path)
    for plane in space.planes:
        if plane.name.startswith("/device:TPU"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            metadata = xplane.event_metadata(plane)
            modules = ([(metadata[i]["name"], s, d) for i, s, d in
                        xplane.line_events(lines[MODULES_LINE])]
                       if MODULES_LINE in lines else [])
            programs = {program_id(n): program_name(n)
                        for n, _, _ in modules}
            # What an operation is, worked out once for each and not
            # for each of its events.
            described = {
                i: (op_name(m["name"]),
                    (m.get("tf_op", "").rstrip(":") or NO_NAME,
                     source_file(m.get("source", "")),
                     programs.get(m.get("program_id"), NO_PROGRAM)))
                for i, m in metadata.items()}
            ops, op_meta = [], []
            for i, start, duration in xplane.line_events(lines[OPS_LINE]):
                name, meta = described[i]
                ops.append((name, start, duration))
                op_meta.append(meta)
            planes[plane.name] = {
                "ops": ops, "op_meta": op_meta,
                "modules": [(op_name(n), s, d) for n, s, d in modules]}
    if not planes:
        raise NoDevicePlane(
            f"no /device:TPU plane with an {OPS_LINE!r} line among "
            f"{[p.name for p in space.planes]}")
    return planes


def main(argv) -> int:
    profile_dir, out, platform = argv
    paths = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        print(f"no .xplane.pb under {profile_dir}", file=sys.stderr)
        return 1
    try:
        planes = read_planes(paths[-1], platform)
    except NoDevicePlane as e:
        print(f"{paths[-1]}: {e}", file=sys.stderr)
        return 1
    summary = summarize(planes)
    summary["stand_in"] = platform != "tpu"
    summary["trace_bytes"] = os.path.getsize(paths[-1])
    with open(out, "w") as f:
        json.dump(summary, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
