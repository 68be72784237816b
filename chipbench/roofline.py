"""The chip's peaks, the operations and bytes the algorithm needs, and
a named kernel's share of its roofline.

``cfg`` is a configuration file's content (the public ``config.json``
keys at the top, the benchmark's own under ``chipbench``).  What an
architecture needs is counted by the module its family names
(``chipbench/counts/<family>.py``, ``family.py``); ``decode_step_bytes``
and ``prefill_flops`` here hand on to it, so that ``decode_roofline``
and ``prefill_roofline`` mean the same thing in every family's cell.
Those two are per *program*: a decode token-step (one forward of one
token for every live row) is bound by memory, a prefill chunk by
compute.  A kernel's own share comes from the device seconds the trace
gives by name (``scope_time``) and the counts its family supplies for
it (``kernel_roofline``).
"""

from __future__ import annotations

import json
import os

from chipbench import family

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "chipbench/peaks.json")
    return table[device_kind]


def decode_step_bytes(cfg: dict, live_context_tokens: float) -> float:
    """Bytes one decode token-step must move with
    ``live_context_tokens`` of context over all live rows."""
    return family.module("counts", cfg).decode_step_bytes(
        cfg, live_context_tokens)


def prefill_flops(cfg: dict, chunks: list) -> float:
    """Floating-point operations of prefill chunks, each ``(start,
    tokens, last)``."""
    return family.module("counts", cfg).prefill_flops(cfg, chunks)


def scope_time(trace: dict, scope: str) -> tuple:
    """Device seconds and events of the operations traced under
    ``scope``, from ``reduce.py``'s ``scopes``: every name stack that
    has ``scope`` among its components (a ``jax.named_scope``, a
    ``pallas_call``'s ``name``, ``jit(<program>)``), or a run of them
    (``attn/named_kernel``).  (0.0, 0) where nothing ran under it."""
    wanted = "/" + scope.strip("/") + "/"
    seconds = count = 0
    for stack, entry in trace.get("scopes", {}).items():
        if wanted in "/" + stack + "/":
            seconds += entry["seconds"]
            count += entry["count"]
    return seconds, count


def kernel_roofline(seconds: float, flops: float, bytes_moved: float,
                    device_kind: str) -> tuple:
    """(share in %, ``"compute"`` or ``"memory"``): the least time the
    chip could take for ``flops`` operations (bf16 peak) and
    ``bytes_moved`` bytes to and from HBM, which is the larger of the
    two over their peaks, over the device ``seconds`` the kernel took;
    and which of the two bound it.  A share over 100% is an error, not
    a value: the operations or bytes are counted too high, or the
    seconds leave out part of the work."""
    if seconds <= 0:
        raise ValueError(f"a kernel that took {seconds} s has no roofline")
    peak = peaks(device_kind)
    compute_s = flops / peak["bf16_flops_per_s"]
    memory_s = bytes_moved / peak["hbm_bytes_per_s"]
    bound = "compute" if compute_s >= memory_s else "memory"
    share = 100.0 * max(compute_s, memory_s) / seconds
    if share > 100.0:
        raise ValueError(
            f"{share:.1f}% of the {bound} roofline: {flops:.4g} operations "
            f"and {bytes_moved:.4g} bytes cannot take {seconds:.4g} s on a "
            f"{device_kind}")
    return share, bound
