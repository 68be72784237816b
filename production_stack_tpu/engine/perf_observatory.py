"""Device-level performance observatory (docs/observability.md).

Three ledgers the serving layer was previously blind to, all host-side
and allocation-free on the committed-token path:

- **compile ledger** — every jitted step program is wrapped in
  :class:`InstrumentedJit`; a growth of the executable cache between
  two calls is a compile event, recorded with its kind, wall time and
  the ``(rows, W)`` shape key that triggered it, and with what the
  load was made of as ``jax.monitoring`` published it meanwhile
  (:func:`listen_for_loads`): Python tracing, lowering, the backend's
  compile, a read from the persistent cache inside it, and whether
  that cache had the executable. A recompile storm shows up on the
  dashboard within one scrape instead of only in a slow test.
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
import threading
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


# ---- what a program load is made of ----------------------------------------

# The seconds of a load by part, as a compile record, a ``boot.probe``
# span and ``compile_report()["parts"]`` carry them. ``cache_read_s``
# is inside ``backend_s``: jax asks the persistent cache from within
# the stage it times as the backend's compile.
LOAD_PARTS = ("trace_s", "lower_s", "backend_s", "cache_read_s")

_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _Heard(threading.local):
    """What this thread has heard since it last took: ``[end, what,
    value]`` on ``perf_counter``'s clock, and the profiler events of
    the stages under way, the outermost first."""

    def __init__(self):
        self.entries: List[list] = []
        self.marks: List[Any] = []


_heard = _Heard()
_annotate: Any = None  # jax.profiler.TraceAnnotation once listening


def _hear_stage_start(event: str, value: float, **kwargs: Any) -> None:
    """jax says a stage begins (the scalar that precedes a duration):
    it is a profiler event from here to its duration,
    ``engine.load.trace|lower|backend``, on the thread that runs it."""
    part = _STAGES.get(event)
    if part is not None:
        mark = _annotate("engine.load." + part[:-2],
                         fun=str(kwargs.get("fun_name")))
        mark.__enter__()
        _heard.marks.append(mark)


def _hear_duration(event: str, duration: float, **kwargs: Any) -> None:
    heard = _heard
    part = _STAGES.get(event)
    if part is not None:
        if heard.marks:
            heard.marks.pop().__exit__(None, None, None)
        if heard.marks:
            # A stage inside another on this thread (a jitted helper
            # traced inside the step's trace or inside a kernel's
            # lowering, hundreds a kernel) is in the outer's seconds:
            # the parts of a load stay disjoint.
            return
    elif event == _CACHE_READ and len(heard.marks) <= 1:
        part = "cache_read_s"
    else:
        return
    heard.entries.append([time.perf_counter(), part, duration])
    if len(heard.entries) > 256:  # a thread that loads and never takes
        del heard.entries[:128]


def _hear_event(event: str, **kwargs: Any) -> None:
    # Asked of the cache from inside the outermost backend stage: a
    # load nested in another's stage is the outer's.
    if ((event == _CACHE_ASKED or event == _CACHE_HIT)
            and len(_heard.marks) <= 1):
        _heard.entries.append([time.perf_counter(), event, 1])


_listening = False


def listen_for_loads() -> None:
    """Registers the listeners, once a process however many runners it
    builds, before the first compile. They run only when jax traces,
    lowers, compiles or reads its cache: a call that finds its
    executable hears nothing and allocates nothing."""
    global _listening, _annotate
    if _listening:
        return
    _listening = True
    from jax import monitoring, profiler
    _annotate = profiler.TraceAnnotation
    monitoring.register_scalar_listener(_hear_stage_start)
    monitoring.register_event_duration_secs_listener(_hear_duration)
    monitoring.register_event_listener(_hear_event)


def take_load_split(since: float) -> Dict[str, Any]:
    """What the calling thread heard since ``since`` (``perf_counter``)
    as a record's split: the seconds by part and ``cache``, ``hit``
    where the persistent cache gave every executable that was asked of
    it, ``miss`` where the backend compiled one it was asked for,
    ``none`` where it was asked nothing (all was in the process, or
    there is no cache). What was heard before ``since`` belongs to
    nobody who will ask, and goes too."""
    split: Dict[str, Any] = dict.fromkeys(LOAD_PARTS, 0.0)
    asked = hits = 0
    for end, what, value in _heard.entries:
        if end < since:
            continue
        if what == _CACHE_ASKED:
            asked += 1
        elif what == _CACHE_HIT:
            hits += 1
        else:
            split[what] += value
    _heard.entries.clear()
    split["cache"] = ("none" if not asked
                      else "hit" if hits >= asked else "miss")
    return split


def add_load_splits(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
    """One load heard on two threads (lowered on one, compiled on
    another) as one split."""
    out: Dict[str, Any] = {p: a[p] + b[p] for p in LOAD_PARTS}
    caches = {a["cache"], b["cache"]} - {"none"}
    out["cache"] = ("none" if not caches
                    else "miss" if "miss" in caches else "hit")
    return out


def rounded_split(split: Dict[str, Any]) -> Dict[str, Any]:
    return {**{p: round(split[p], 6) for p in LOAD_PARTS},
            "cache": split["cache"]}


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
        self._compile_parts: Dict[str, Dict[str, float]] = {}
        self._cache_results = {"hit": 0, "miss": 0}
        self._jits: Dict[str, Any] = {}
        self._compile_ring: Deque[Dict[str, Any]] = collections.deque(
            maxlen=compile_ring_size)
        # (kind, key) -> (seconds, split) of a program brought up
        # ahead of its first call (load_ahead), until that call's
        # record takes them.
        self._ahead: Dict[tuple, tuple] = {}

        # ---- step / MFU ledger ---------------------------------------
        self._device_seconds: Dict[str, float] = {}
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
        self._compile_parts.setdefault(
            kind, dict.fromkeys(LOAD_PARTS, 0.0))
        self._jits[kind] = fn

    def load_ahead(self, kind: str, key: Tuple[int, ...],
                   seconds: float, split: Dict[str, Any]) -> None:
        """A program of ``kind`` and shape ``key`` was lowered and
        compiled before its first call (the half-width prefill
        program, model_runner._load_step_program): that call's record
        is the program's, and takes these seconds and this split."""
        self._ahead[(kind, tuple(key))] = (float(seconds), split)

    def on_compile(self, kind: str,
                   key: Optional[Tuple[int, ...]],
                   seconds: float, split: Dict[str, Any]) -> None:
        ahead = self._ahead.pop((kind, key), None)
        if ahead is not None:
            seconds += ahead[0]
            split = add_load_splits(ahead[1], split)
        self._compile_events[kind] = self._compile_events.get(kind, 0) + 1
        self._compile_seconds[kind] = (
            self._compile_seconds.get(kind, 0.0) + float(seconds))
        parts = self._compile_parts.setdefault(
            kind, dict.fromkeys(LOAD_PARTS, 0.0))
        for part in LOAD_PARTS:
            parts[part] += split[part]
        if split["cache"] != "none":
            self._cache_results[split["cache"]] += 1
        self._compile_ring.append({
            "kind": kind,
            "key": list(key) if key is not None else None,
            "seconds": round(float(seconds), 6),
            "ts": time.time(),
            **rounded_split(split),
        })

    def compile_events_by_kind(self) -> Dict[str, int]:
        return dict(self._compile_events)

    def compile_seconds_by_kind(self) -> Dict[str, float]:
        return dict(self._compile_seconds)

    def compile_events_total(self, kind: Optional[str] = None) -> int:
        if kind is not None:
            return self._compile_events.get(kind, 0)
        return sum(self._compile_events.values())

    def compile_parts_by_kind(self) -> Dict[str, Dict[str, float]]:
        """The compile seconds of each kind by part (LOAD_PARTS)."""
        return {kind: dict(parts)
                for kind, parts in self._compile_parts.items()}

    def cache_results(self) -> Dict[str, int]:
        """Program loads the persistent cache answered (``hit``) and
        loads the backend compiled though it was asked (``miss``)."""
        return dict(self._cache_results)

    def executable_cache_sizes(self) -> Dict[str, int]:
        """Live per-kind executable-cache sizes, read from the jit
        handles (0 where the runtime exposes no ``_cache_size``, and
        then InstrumentedJit counts no compile either)."""
        sizes: Dict[str, int] = {}
        for kind, fn in self._jits.items():
            size_fn = getattr(fn, "_cache_size", None)
            sizes[kind] = int(size_fn()) if callable(size_fn) else 0
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
            "parts": {kind: {p: round(v, 6) for p, v in parts.items()}
                      for kind, parts in self._compile_parts.items()},
            "cache": dict(self._cache_results),
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


def program_key(args: tuple, kwargs: dict) -> Optional[Tuple[int, ...]]:
    """The ``(rows, W)`` that names the step program a call compiled.
    ``args[3]`` is the tokens block of every step program: ``[rows,
    W]`` for a prefill, verify or unified step, ``[rows]`` for a
    single decode step (W = 1), and ``[rows, 1]`` for a burst, plain or
    drafting, whose program is one a ``num_steps``: its W is the steps
    it runs."""
    if len(args) <= 3 or not hasattr(args[3], "shape"):
        return None
    shape = tuple(int(d) for d in args[3].shape)
    steps = kwargs.get("num_steps")
    if steps is not None:
        return (shape[0], int(steps))
    return shape if len(shape) > 1 else shape + (1,)


class InstrumentedJit:
    """Transparent wrapper around one jitted step program.

    Detects compile events as growth of the executable cache between
    two calls (compilation is synchronous inside ``__call__`` even
    under async dispatch, so the wall-clock delta on a growing call is
    trace+compile time, and what the thread heard of jax's stages
    meanwhile is its split). The owner's ``observatory`` attribute is
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
            obs.on_compile(self.kind, program_key(args, kwargs),
                           time.perf_counter() - t0, take_load_split(t0))
        return out

    def _cache_size(self) -> int:
        size_fn = getattr(self.fn, "_cache_size", None)
        return int(size_fn()) if callable(size_fn) else 0

    def __getattr__(self, name):
        return getattr(self.fn, name)
