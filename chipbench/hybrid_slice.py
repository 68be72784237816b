"""What ran in a traced run's profiler slice, for the readers of a
hybrid model's kernels (recurrent layers beside paged attention, routed
experts): device seconds by name inside one program, the decode
token-steps the slice saw, and the means of the step records' counters
over the bursts stamped inside it.

A kernel's name (``jax.named_scope``) is a component of the name stack
of every operation traced under it, and the stack starts with
``jit(<program>)``; ``scope_seconds`` is ``roofline.scope_time`` kept to
one program, because the expert layer runs under one name in the decode
burst and in the prefill step and its bytes are counted differently in
the two.
"""

from __future__ import annotations

BURST_PREFIX = "_decode_burst"


def scope_seconds(trace: dict, scope: str, program_prefix: str) -> tuple:
    """Device seconds and events under ``scope`` in the programs whose
    name starts with ``program_prefix``; (0.0, 0) where nothing ran."""
    wanted = "/" + scope.strip("/") + "/"
    seconds = count = 0
    for stack, entry in (trace or {}).get("scopes", {}).items():
        head = stack.split("/", 1)[0]
        if (head.startswith(f"jit({program_prefix}")
                and wanted in "/" + stack + "/"):
            seconds += entry["seconds"]
            count += entry["count"]
    return seconds, count


def burst(run):
    """(program entry, decode-steps a burst) of the decode burst that
    ran in the slice, or None."""
    cell = run.cell
    if not run.trace or not cell.get("slice_unix"):
        return None
    programs = [p for name, p in run.trace.get("programs", {}).items()
                if name.startswith(BURST_PREFIX)]
    if not programs:
        return None
    steps = cell["config_as_run"]["chipbench"]["server_flags"]["decode-steps"]
    return max(programs, key=lambda p: p["seconds"]), steps


def token_steps(run) -> float:
    """Decode token-steps the slice saw: whole executions' worth of the
    burst's device seconds, times the steps of a burst."""
    found = burst(run)
    if found is None:
        return 0.0
    program, steps = found
    return program["seconds"] / program["whole_s"] * steps


def burst_means(run, *fields) -> dict:
    """Means over the decode-burst step records stamped inside the
    slice of the named fields, each over the records that carry it;
    None for a field none carries (a parent whose program lacks the
    counter)."""
    lo, hi = run.cell["slice_unix"]
    records = [s for s in run.window_steps
               if s.get("kind") == "decode" and lo <= s["ts"] < hi]
    out = {}
    for field in fields:
        values = [s[field] for s in records if field in s]
        out[field] = sum(values) / len(values) if values else None
    return out
