"""Device-level performance observatory (docs/observability.md).

Three ledgers the serving layer was previously blind to, all host-side
and allocation-free on the committed-token path:

- **compile ledger** — every jitted step program is wrapped in
  :class:`InstrumentedJit`; a growth of the executable cache between
  two calls is a compile event, recorded with its kind, wall time and
  the ``(rows, W)`` shape key that triggered it. A recompile storm
  shows up on the dashboard within one scrape instead of only in a
  slow test.
- **HBM memory ledger** — an always-available analytic breakdown of
  device bytes from the engine config (weights from the actual param
  tree, KV pages + int8 scale tensors from the page math, step
  buffers), plus ``device.memory_stats()`` where the backend supports
  it. The int8 capacity-expansion math (docs/kv_quantization.md) is a
  live gauge here instead of a config-time log line.
- **step-time / MFU ledger** — per-kind device-wait seconds and
  useful tokens processed, turned into an analytic model-FLOPs
  utilization figure against a per-device peak-FLOPs table (or the
  ``--device-peak-flops`` override). Unknown devices report MFU 0
  rather than a guessed peak.

Everything is plain-Python counter arithmetic on the single step
thread: no device transfers, no jax imports at call time, and every
hook is behind an ``observatory is None`` guard so the byte-identical
greedy parity tests can pin zero overhead.
"""

from __future__ import annotations

import collections
import statistics
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

# Peak bf16 matmul FLOP/s per chip, read by the engine's MFU gauge.
# Prefix-matched against ``device.device_kind``; an unknown device
# (including CPU) resolves to 0.0 so the gauge reads 0 instead of
# lying.
PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def resolve_peak_flops(device_kind: Optional[str],
                       override: float = 0.0) -> float:
    """Per-chip peak FLOP/s: explicit override wins, then the device
    table, then 0.0 (honest "unknown")."""
    if override and override > 0:
        return float(override)
    if device_kind:
        lowered = device_kind.lower()
        for k, v in PEAK_FLOPS_BY_DEVICE_KIND.items():
            if lowered.startswith(k.lower()):
                return v
    return 0.0


class PerfObservatory:
    """Host-side device-performance ledgers for one model runner.

    Single-writer by construction (the engine step thread); readers
    (the /metrics handler, debug endpoints) only see monotone counter
    snapshots, so no locking is needed.
    """

    def __init__(self, config, *, param_count: int = 0,
                 params_bytes: int = 0,
                 device_kind: Optional[str] = None,
                 compile_ring_size: int = 128):
        self.config = config
        self.param_count = int(param_count)
        self.params_bytes = int(params_bytes)
        self.device_kind = device_kind or ""
        self.peak_flops = resolve_peak_flops(
            self.device_kind,
            float(getattr(config, "device_peak_flops", 0.0) or 0.0))
        # Dense decoder forward pass: ~2 FLOPs per parameter per token.
        self.flops_per_token = 2.0 * self.param_count

        # ---- compile ledger ------------------------------------------
        self._compile_events: Dict[str, int] = {}
        self._compile_seconds: Dict[str, float] = {}
        self._cache_sizes: Dict[str, int] = {}
        self._jits: Dict[str, Any] = {}
        self._compile_ring: Deque[Dict[str, Any]] = collections.deque(
            maxlen=compile_ring_size)

        # ---- step / MFU ledger ---------------------------------------
        self._device_seconds: Dict[str, float] = {}
        self._tokens: Dict[str, int] = {}
        self.device_seconds_total = 0.0
        self.tokens_total = 0
        # Bounded per-kind ring of recent step durations; its medians
        # feed vllm:engine_step_time_median_seconds{kind} and the
        # router-side drift sentinel (obs/drift.py).
        self._step_durations: Dict[str, Deque[float]] = {}
        self._step_ring_size = 512

        # ---- attention-impl info ledger ------------------------------
        self._attention_impls: Dict[str, str] = {}

    # ---- compile ledger --------------------------------------------------

    def register_jit(self, kind: str, fn: Any) -> None:
        """Zero-init a program kind at wrap time so the gauges exist
        (at 0) before the first dispatch, and keep the jit handle for
        live executable-cache-size reads."""
        self._compile_events.setdefault(kind, 0)
        self._compile_seconds.setdefault(kind, 0.0)
        self._cache_sizes.setdefault(kind, 0)
        self._jits[kind] = fn

    def on_compile(self, kind: str,
                   key: Optional[Tuple[int, ...]],
                   seconds: float, cache_size: int) -> None:
        self._compile_events[kind] = self._compile_events.get(kind, 0) + 1
        self._compile_seconds[kind] = (
            self._compile_seconds.get(kind, 0.0) + float(seconds))
        self._cache_sizes[kind] = int(cache_size)
        self._compile_ring.append({
            "kind": kind,
            "key": list(key) if key is not None else None,
            "seconds": round(float(seconds), 6),
            "cache_size": int(cache_size),
            "ts": time.time(),
        })

    def compile_events_by_kind(self) -> Dict[str, int]:
        return dict(self._compile_events)

    def compile_seconds_by_kind(self) -> Dict[str, float]:
        return dict(self._compile_seconds)

    def compile_events_total(self, kind: Optional[str] = None) -> int:
        if kind is not None:
            return self._compile_events.get(kind, 0)
        return sum(self._compile_events.values())

    def executable_cache_sizes(self) -> Dict[str, int]:
        """Live per-kind executable-cache sizes, read from the jit
        handles where the runtime exposes ``_cache_size`` and falling
        back to the last compile-time observation otherwise."""
        sizes: Dict[str, int] = {}
        for kind, tracked in self._cache_sizes.items():
            fn = self._jits.get(kind)
            size_fn = getattr(fn, "_cache_size", None)
            if callable(size_fn):
                try:
                    sizes[kind] = int(size_fn())
                    continue
                except Exception:
                    pass
            sizes[kind] = tracked
        return sizes

    def recent_compiles(self, limit: int = 32) -> List[Dict[str, Any]]:
        items = list(self._compile_ring)
        if limit >= 0:
            items = items[-limit:]
        return items

    def compile_report(self, limit: int = 32) -> Dict[str, Any]:
        return {
            "events": self.compile_events_by_kind(),
            "seconds": {k: round(v, 6)
                        for k, v in self._compile_seconds.items()},
            "executable_cache_sizes": self.executable_cache_sizes(),
            "recent": self.recent_compiles(limit),
        }

    # ---- HBM memory ledger -----------------------------------------------

    def hbm_bytes(self) -> Dict[str, int]:
        """Analytic device-byte breakdown. ``kv_pages`` + ``kv_scales``
        equals ``num_pages * page_size * kv_bytes_per_token`` exactly
        (the post-expansion int8 budget), and ``weights`` is the exact
        leaf-sum of the sharded param tree."""
        model = self.config.model
        cache = self.config.cache
        sched = self.config.scheduler
        pages = model.page_cache
        slots = pages.planes * pages.entries * pages.heads
        tokens = cache.num_pages * cache.page_size
        if cache.resolved_kv_dtype() == "int8":
            kv_pages = slots * tokens * pages.width  # int8 data
            kv_scales = slots * tokens * 4  # f32 per-slot scales
        else:
            import jax.numpy as jnp
            itemsize = jnp.dtype(model.jax_dtype).itemsize
            kv_pages = slots * tokens * pages.width * itemsize
            kv_scales = 0
        rows = sched.max_num_seqs + sched.prefill_batch_size
        width = sched.prefill_chunk_size
        # Step-buffer estimate: one f32 logits plane plus the i32
        # token/descriptor blocks for the widest mixed batch.
        step_buffers = rows * model.vocab_size * 4 + rows * width * 4
        out = {
            "weights": int(self.params_bytes),
            "kv_pages": int(kv_pages),
            "kv_scales": int(kv_scales),
            "step_buffers": int(step_buffers),
        }
        if cache.num_state_slots:
            # Recurrent-state pools of the linear-attention layers
            # (the trash slot included).
            out["recurrent_state"] = int(
                (cache.num_state_slots + 1)
                * model.recurrent_state_bytes())
        return out

    def memory_report(self) -> Dict[str, Any]:
        analytic = self.hbm_bytes()
        report: Dict[str, Any] = {
            "analytic": analytic,
            "total_analytic_bytes": sum(analytic.values()),
            "kv_cache_dtype": self.config.cache.resolved_kv_dtype(),
            "num_pages": self.config.cache.num_pages,
            "page_size": self.config.cache.page_size,
            "param_count": self.param_count,
        }
        import jax
        devices = jax.local_devices()
        stats = devices[0].memory_stats()  # backend-dependent: None on CPU
        if stats:
            report["device"] = {
                k: int(v) for k, v in stats.items()
                if isinstance(v, (int, float))}
            # Every local device, so a sharded engine shows its
            # weights and pages spread out rather than on device 0.
            report["devices"] = [
                {"id": d.id, **{k: int(d.memory_stats().get(k, 0))
                                for k in ("bytes_in_use",
                                          "peak_bytes_in_use")}}
                for d in devices]
        return report

    # ---- step-time / MFU ledger ------------------------------------------

    def on_step(self, kind: str, device_s: float, tokens: int) -> None:
        self._device_seconds[kind] = (
            self._device_seconds.get(kind, 0.0) + float(device_s))
        self._tokens[kind] = self._tokens.get(kind, 0) + int(tokens)
        self.device_seconds_total += float(device_s)
        self.tokens_total += int(tokens)
        ring = self._step_durations.get(kind)
        if ring is None:
            ring = self._step_durations[kind] = collections.deque(
                maxlen=self._step_ring_size)
        ring.append(float(device_s))

    def device_seconds_by_kind(self) -> Dict[str, float]:
        return dict(self._device_seconds)

    def step_time_medians(self) -> Dict[str, float]:
        """Median recent step duration per kind (seconds). Computed
        over the bounded ring, so it tracks the *current* regime
        rather than the lifetime mean the cumulative counters give."""
        out: Dict[str, float] = {}
        for kind, ring in self._step_durations.items():
            if ring:
                out[kind] = statistics.median(ring)
        return out

    def tokens_by_kind(self) -> Dict[str, int]:
        return dict(self._tokens)

    def mfu(self) -> float:
        """Useful-token MFU: committed/processed tokens (prefill chunk
        tokens + emitted decode tokens) against the peak — rejected
        speculative drafts and pad rows count as lost utilization,
        which is the operationally interesting number. 0.0 when the
        device peak is unknown."""
        if (self.peak_flops <= 0 or self.device_seconds_total <= 0
                or self.tokens_total <= 0):
            return 0.0
        achieved = self.flops_per_token * self.tokens_total
        return achieved / self.device_seconds_total / self.peak_flops

    # ---- attention-impl info ledger --------------------------------------

    def set_attention_impl(self, phase: str, impl: str) -> None:
        self._attention_impls[phase] = impl

    def attention_impls(self) -> Dict[str, str]:
        return dict(self._attention_impls)


class InstrumentedJit:
    """Transparent wrapper around one jitted step program.

    Detects compile events as growth of the executable cache between
    two calls (compilation is synchronous inside ``__call__`` even
    under async dispatch, so the wall-clock delta on a growing call is
    trace+compile time). The owner's ``observatory`` attribute is
    looked up at call time: set it to ``None`` and every call is a
    plain passthrough — the parity tests pin that path.

    ``_cache_size`` and attribute access forward to the wrapped jit so
    existing introspection (bench warmup, tests) keeps working.
    """

    def __init__(self, kind: str, fn: Any, owner: Any):
        self.kind = kind
        self.fn = fn
        self._owner = owner
        obs = getattr(owner, "observatory", None)
        if obs is not None:
            obs.register_jit(kind, fn)

    def __call__(self, *args, **kwargs):
        obs = getattr(self._owner, "observatory", None)
        size_fn = getattr(self.fn, "_cache_size", None)
        if obs is None or size_fn is None:
            return self.fn(*args, **kwargs)
        before = size_fn()
        t0 = time.perf_counter()
        out = self.fn(*args, **kwargs)
        after = size_fn()
        if after != before:
            key: Optional[Tuple[int, ...]] = None
            # args[3] is the tokens block for every step program —
            # its (rows, W) shape is the bucket key that compiled.
            if len(args) > 3 and hasattr(args[3], "shape"):
                key = tuple(int(d) for d in args[3].shape)
            obs.on_compile(self.kind, key,
                           time.perf_counter() - t0, after)
        return out

    def _cache_size(self) -> int:
        size_fn = getattr(self.fn, "_cache_size", None)
        return int(size_fn()) if callable(size_fn) else 0

    def __getattr__(self, name):
        return getattr(self.fn, name)
