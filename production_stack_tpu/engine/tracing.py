"""Engine-side request tracing: per-request event timelines, a step
flight recorder, and JSON span lines in the same format family as
``router/tracing.py`` (docs/observability.md).

The router's span stops at the proxy boundary; this module picks the
request up inside the engine, keyed by the router's ``x-request-id``
header, and records the lifecycle events aggregate histograms
structurally cannot show for one request: enqueue, ``AWAITING_KV``
park/restore, each prefill chunk, first token, preemption, offload
restore, handoff ship, finish reason. Two sinks:

- an optional JSON-line span log (``--request-span-log``; ``-`` logs
  via the process logger) emitting one ``{"span": "engine_request"}``
  line per finished request, mergeable with the router's
  ``{"span": "request"}`` lines by ``python -m
  production_stack_tpu.traceview``;
- an always-on (when a tracer is installed) flight recorder: bounded
  rings of recent request timelines and per-step records, served at
  ``/debug/trace/{request_id}`` and ``/debug/steps``.

Under the server loop a step record is one *turn* of that loop: the
loop thread is always in exactly one of ``TURN_PHASES``, the record
carries the milliseconds spent in each between ``t_start`` and
``t_end``, and turns are contiguous, so nothing the loop thread does
falls between two records. A turn runs from the end of one program's
commit to the end of the next's, and the loop dispatches first
(docs/async_pipeline.md, "The served loop"): the outputs of the turn
before are made and handed over behind this turn's dispatch, so they
are in this turn's ``commit`` and ``emit``, in its ``emitted`` and its
``handoff_ms``, and ``handover`` says so (``behind``; ``flushed`` where
they went at once because no program followed). Beside each phase's
wall the record has the thread's CPU clock over it (``cpu``): a
phase's wall less its CPU is the time the thread was kept off a core (the clock is the host's: where
it advances in ticks of 10 ms, as on the v5e hosts, read sums over many
turns, not one record). The interpreter has a second
thread, the event loop, which turns the tokens into frames and writes
the sockets: every turn closes what that thread's CPU clock read
between the turn's ``t_start`` and ``t_end``, and the tokens its
consumers took meanwhile, into the record's ``front`` (``FrontClock``,
``tracer.front``). With an
annotation factory (the server hands over
``jax.profiler.TraceAnnotation``) each phase and the turn around it
are also profiler events on the loop thread, and each delivery, each
wake of a stream's consumer and each socket write an event on the
event loop's thread, so a profiler slice shows what both threads of
the host did while the device idled. A turn far slower than its
kind's recent median logs one ``slow turn`` WARNING.

Before the first turn there is the start (``StartupTimeline``): the
thread that builds the engine is always in exactly one ``boot.*`` span
of ``STARTUP_SPANS``, from the instant the kernel started the process
to the HTTP listener, on the unix clock; ``/version`` carries it
(``startup``) and ``/metrics`` its sums
(docs/observability.md, "Why is a start slow?").

Concurrency: the engine's device loop, the asyncio handlers, and the
drain path all touch the tracer. Every mutation is a GIL-atomic dict
or ``deque(maxlen=...)`` operation — no lock is taken on the step or
token path. The front's token count is a plain attribute that the
event loop's thread alone writes and the loop thread reads once a
turn. The module is stdlib-only (no JAX, no aiohttp) so the fake
engine reuses it verbatim; it binds no event loop, and its records
have no ``front``.

Disabled cost: the engine holds ``tracer = None`` unless a tracer is
explicitly installed; every emission site is behind an ``is None``
check, so the disabled hot path allocates no span objects at all, and
the front's sites (``AsyncEngine.front`` is None then) do nothing.
With a tracer and no profiler slice the loop thread pays two clock
reads a phase switch and one read of the other thread's CPU clock a
turn (a thread's CPU clock is a system call: 0.5 us on a plain kernel,
6 us on the v5e hosts' sandboxed one, PERF.md PR 39), and the event
loop's thread reads no clock: an addition a wake and ``is None`` checks.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import statistics
import threading
import time
import types
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)

# The start of the process where the kernel does not say it
# (process_start_unix): this module is imported on the way to main().
_IMPORTED_UNIX = time.time()

# The closed vocabulary of engine span event names. The staticcheck
# ``span-contract`` rule holds this tuple, every string literal passed
# to ``EngineTracer.event`` / ``EngineSpan.event`` across the package,
# and the event table in docs/observability.md in three-way agreement.
SPAN_EVENTS = (
    "enqueue",
    "awaiting_kv_park",
    "awaiting_kv_restore",
    "offload_restore",
    "prefill_chunk",
    "first_token",
    "preempt",
    "preempt_offload",
    "qos_shed",
    "handoff_ship",
    "profiler_start",
    "profiler_stop",
    "checkpoint_ship",
    "resume_restore",
    "migrate_ship",
    "watchdog_trip",
    "crash_respawn",
    "autotune_decision",
    "finish",
)

# The closed vocabulary of what the server loop's thread can be doing.
# ``EngineTracer.phase`` takes one of these; the profiler annotation of
# a phase is ``engine.<name>`` inside ``engine.turn``. tests/
# test_turn_phases.py holds this tuple, every literal passed to
# ``*.phase(...)`` across the package and the table in
# docs/observability.md in agreement.
TURN_PHASES = (
    "idle",      # parked: no request, nothing in flight
    "admit",     # draining the submit queue into the scheduler
    "plan",      # scheduler.plan_step / plan_ahead, lock wait included
    "build",     # host arrays for the program, its sampling key among them
    "dispatch",  # until the jitted call returns
    "wait",      # blocked on the device's results
    "parse",     # device arrays to Python lists
    "commit",    # scheduler and sequence updates; the last turn's outputs
    "emit",      # one call that hands a turn's outputs to the event loop
    "other",     # autotuner tick, back-off waits, the rest
)

# The closed vocabulary of a start's spans (StartupTimeline). ``boot``
# is the whole; ``boot.probe`` is one probed case inside
# ``boot.probes``; every other is a child of ``boot``, and the thread
# that builds the engine is in exactly one of them at any instant, so
# they tile ``boot`` from the process's start to the listener. The
# profiler annotation of a span is ``engine.<name>``. tests/
# test_startup_timeline.py holds this tuple, every literal passed to
# ``*.enter(...)`` / ``*.within(...)`` across the package and the
# table in docs/observability.md in agreement.
STARTUP_SPANS = (
    "boot",                # the process's start -> the listener is up
    "boot.imports",        # the process's start -> main() entered
    "boot.claim_devices",  # the backend's initialisation
    "boot.probes",         # the kernels' lowering probes, all of them
    "boot.probe",          # one probed case
    "boot.weights",        # init or checkpoint read, quantise, shard
    "boot.cache",          # the page planes and the state pool
    "boot.tokenizer",      # a model directory's tokenizer, its imports
    "boot.engine",         # the rest: arguments, scheduler, the tracer
    "boot.listen",         # web.run_app -> on_startup
)

# Phases in which the loop thread is off the CPU because that is what
# the phase is: blocked on the device, or parked with nothing to serve.
# In every other phase the thread has work, and wall less CPU there is
# time it was kept from it.
PARKED_PHASES = ("wait", "idle")

# A turn is slow when its wall (less ``idle``) is more than
# SLOW_TURN_FACTOR times the median of the last SLOW_TURN_HISTORY turns
# of its kind and at least SLOW_TURN_MIN_S longer; a kind says nothing
# before it has SLOW_TURN_MIN_HISTORY turns. ``handoff_ms`` is judged
# the same way.
SLOW_TURN_HISTORY = 32
SLOW_TURN_MIN_HISTORY = 8
SLOW_TURN_FACTOR = 2.0
SLOW_TURN_MIN_S = 1.0


def _ms(a: Optional[float], b: Optional[float]) -> Optional[float]:
    if a is None or b is None:
        return None
    return round((b - a) * 1e3, 2)


def process_start_unix() -> Optional[float]:
    """When the kernel started this process, on the unix clock, to the
    hundredth of a second that ``/proc`` keeps: the age of the process
    is the machine's uptime less the start time of ``/proc/self/stat``
    (field 22, in clock ticks since the machine came up). None where
    that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            # After the command's closing bracket (a command may hold
            # spaces): field 3 is the first there, field 22 the 20th.
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None
    return time.time() - age if age >= 0 else None


class StartupTimeline:
    """A start's spans, in memory: ``name``, ``parent``, ``t_start``
    (unix), ``seconds`` (None while open) and small fields.

    ``boot`` opens at the process's start and ``boot.imports`` fills it
    up to this object's creation (the top of ``server.main()``). From
    there the creating thread is in ``boot.engine`` unless it has said
    otherwise: ``enter(name)`` closes the span it is in and opens
    ``name`` at the same clock read, ``within(name)`` does so for a
    block and goes back, so the children of ``boot`` are contiguous and
    ``boot`` has no time of its own. ``probe()`` is one ``boot.probe``
    inside ``boot.probes``; ``ready()`` closes the last child and
    ``boot``. One thread writes; ``/version`` and ``/metrics`` read
    after ``ready()``. A runner built with no timeline handed to it
    makes its own, which nobody reads: a few dozen clock reads.
    """

    def __init__(self, annotate: Optional[Callable[..., Any]] = None,
                 clock: Callable[[], float] = time.time,
                 process_start: Optional[float] = None):
        self.annotate = annotate
        self._clock = clock
        now = clock()
        if process_start is None:
            process_start = process_start_unix() or _IMPORTED_UNIX
        self.process_start_unix = min(process_start, now)
        self.ready_unix: Optional[float] = None
        self.spans: List[Dict[str, Any]] = []
        self._marks: List[Any] = []
        self._boot = self._open("boot", self.process_start_unix)
        self._close(self._open("boot.imports", self.process_start_unix),
                    now)
        self._current = self._open("boot.engine", now, live=True)

    def _open(self, name: str, now: float, live: bool = False,
              **fields: Any) -> Dict[str, Any]:
        """``live``: the span opens now and not in the past, so it can
        be a profiler event too."""
        parent = {"boot": None, "boot.probe": "boot.probes"}.get(
            name, "boot")
        span = {"name": name, "parent": parent, "t_start": now,
                "seconds": None, **fields}
        self.spans.append(span)
        if live and self.annotate is not None:
            mark = self.annotate("engine." + name)
            mark.__enter__()
            self._marks.append(mark)
        return span

    def _close(self, span: Dict[str, Any], now: float,
               live: bool = False) -> None:
        span["seconds"] = now - span["t_start"]
        if live and self._marks:
            self._marks.pop().__exit__(None, None, None)

    def enter(self, name: str, **fields: Any) -> str:
        """The building thread goes over to ``name`` (a child of
        ``boot``); returns the span's name it was in. After
        ``ready()`` a start has no more spans."""
        old = self._current["name"]
        if self.ready_unix is None:
            now = self._clock()
            self._close(self._current, now, live=True)
            self._current = self._open(name, now, live=True, **fields)
        return old

    @contextlib.contextmanager
    def within(self, name: str, **fields: Any):
        """``name`` for a block, then back to the span before; yields
        the span, for the fields that are known at its end."""
        old = self.enter(name, **fields)
        try:
            yield self._current
        finally:
            self.enter(old)

    @contextlib.contextmanager
    def probe(self, **fields: Any):
        """One ``boot.probe``: inside ``boot.probes``, which it enters
        for its own length where the thread is elsewhere."""
        with (contextlib.nullcontext()
              if self._current["name"] == "boot.probes"
              else self.within("boot.probes")):
            span = self._open("boot.probe", self._clock(), live=True,
                              **fields)
            try:
                yield span
            finally:
                self._close(span, self._clock(), live=True)

    def ready(self) -> None:
        """The listener is up: the last child and ``boot`` end here."""
        if self.ready_unix is not None:
            return
        now = self.ready_unix = self._clock()
        self._close(self._current, now, live=True)
        self._close(self._boot, now)
        self._boot.update(process_start_unix=self.process_start_unix,
                          ready_unix=now)

    def seconds_by_span(self) -> Dict[str, float]:
        """Closed seconds by name, ``boot`` and its children (a name
        entered more than once is one sum)."""
        out: Dict[str, float] = {}
        for span in self.spans:
            if span["seconds"] is not None and span["parent"] in (
                    None, "boot"):
                out[span["name"]] = (out.get(span["name"], 0.0)
                                     + span["seconds"])
        return out

    def to_dict(self) -> Dict[str, Any]:
        """``/version``'s ``startup``: the spans so far."""
        def rounded(span):
            return {k: round(v, 6) if isinstance(v, float) else v
                    for k, v in span.items()}
        return {"process_start_unix": round(self.process_start_unix, 6),
                "ready_unix": (None if self.ready_unix is None
                               else round(self.ready_unix, 6)),
                "spans": [rounded(s) for s in self.spans]}


class EngineSpan:
    """One request's event timeline inside a single engine process."""

    __slots__ = ("seq_id", "request_id", "role", "start_ts", "events",
                 "summary")

    def __init__(self, seq_id: str, request_id: Optional[str],
                 role: str = "both"):
        self.seq_id = seq_id
        self.request_id = request_id
        self.role = role
        self.start_ts = time.time()
        self.events: List[Dict[str, Any]] = []
        self.summary: Dict[str, Any] = {}

    def event(self, name: str, **fields: Any) -> None:
        record: Dict[str, Any] = {"event": name,
                                  "ts": round(time.time(), 6)}
        if fields:
            record.update(fields)
        self.events.append(record)

    def to_dict(self) -> Dict[str, Any]:
        data: Dict[str, Any] = {
            "span": "engine_request",
            "request_id": self.request_id,
            "seq_id": self.seq_id,
            "role": self.role,
            "arrival_ts": round(self.start_ts, 6),
        }
        data.update(self.summary)
        data["events"] = self.events
        return data

    def to_json(self) -> str:
        return json.dumps(self.to_dict())


class _SpanSink:
    """Line-buffered JSON-line sink, same contract as the router's
    SpanLogger: path ``-`` routes through the process logger."""

    def __init__(self, path: str):
        self._lock = threading.Lock()
        self._fh = None
        if path != "-":
            self._fh = open(path, "a", buffering=1)

    def emit(self, line: str) -> None:
        if self._fh is None:
            logger.info("engine-span %s", line)
            return
        with self._lock:
            self._fh.write(line + "\n")


class FrontClock:
    """What the event loop's thread did, counted on that thread: its
    CPU clock, and the tokens that the streams' consumers (detokeniser,
    stop scanner, one frame a token) took, added a wake and not a
    token. ``tokens`` only grows; ``close_interval()`` gives the loop
    thread what both grew by since it last asked. Inside a profiler
    slice (``annotate`` set) a consumer's wake is one ``server.consume``
    event and a socket write's synchronous part one ``server.write``
    event; neither spans a point where its coroutine yields, so at
    most one is open at any instant. Outside a slice the thread reads
    no clock for this.
    """

    def __init__(self):
        self.tokens = 0
        # The annotation factory of the newest hand-over: set by each
        # delivery, so the consumers it wakes and their writes are
        # events of the slice that the turn was handed over in.
        self.annotate: Optional[Callable[..., Any]] = None
        self._cpu_clock: Optional[int] = None
        self._mark: Any = None
        # The loop thread's: what close_interval() last read. None
        # until bind().
        self._last: Optional[tuple] = None

    def bind(self) -> None:
        """Called once, on the event loop's thread, before the loop
        thread starts: from here on the turns' records have ``front``.
        Where the platform gives no clock of another thread's CPU time
        ``cpu_ms`` is absent from it."""
        if hasattr(time, "pthread_getcpuclockid"):
            self._cpu_clock = time.pthread_getcpuclockid(
                threading.get_ident())
        self._last = (self.cpu_s(), self.tokens)

    def cpu_s(self) -> Optional[float]:
        """The bound thread's CPU clock, from any thread; None where
        there is none to read (not bound, no such call, thread gone)."""
        if self._cpu_clock is None:
            return None
        try:
            return time.clock_gettime(self._cpu_clock)
        except OSError:
            return None

    # -- the event loop's thread --------------------------------------------

    def consume_begin(self) -> None:
        """A consumer has an output in hand: inside a slice a
        ``server.consume`` event is open until ``consume_end()``,
        which comes before anything that can yield."""
        if self.annotate is not None:
            self._mark = self.annotate("server.consume")
            self._mark.__enter__()

    def consume_end(self) -> None:
        if self._mark is not None:
            self._mark.__exit__(None, None, None)
            self._mark = None

    def wake_done(self, tokens: int) -> None:
        """The consumer's stream is empty again (or it is leaving)."""
        self.consume_end()
        self.tokens += tokens

    @types.coroutine
    def write(self, writing):
        """Inside a slice only: awaits ``writing``, a coroutine that
        writes to a socket, with one ``server.write`` event around its
        synchronous part: up to where it returns or, with a transport
        over its high-water mark, first suspends to drain. Parked, the
        coroutine is nobody's."""
        with self.annotate("server.write"):
            try:
                step = writing.send(None)
            except StopIteration as done:
                return done.value
        try:
            while True:  # ``yield from`` for a coroutine already begun
                try:
                    sent = yield step
                except BaseException as thrown:
                    step = writing.throw(thrown)
                else:
                    step = writing.send(sent)
        except StopIteration as done:
            return done.value

    # -- the loop thread ----------------------------------------------------

    def close_interval(self) -> Optional[Dict[str, Any]]:
        """What the event loop's thread did since the last call (or
        since ``bind()``), as the record's ``front``; None unbound."""
        last = self._last
        if last is None:
            return None
        now = self._last = (self.cpu_s(), self.tokens)
        front: Dict[str, Any] = {}
        if last[0] is not None and now[0] is not None:
            front["cpu_ms"] = round((now[0] - last[0]) * 1e3, 3)
        front["tokens"] = now[1] - last[1]
        return front


class EngineTracer:
    """Per-request timelines + step flight recorder for one engine.

    Installed on ``LLMEngine.tracer`` (and mirrored onto
    ``Scheduler.tracer``); every caller guards with ``is None`` so an
    engine without a tracer pays nothing.
    """

    def __init__(self, span_log_path: Optional[str] = None,
                 ring_size: int = 256, step_ring_size: int = 512,
                 role: str = "both",
                 annotate: Optional[Callable[..., Any]] = None):
        self.role = role
        self._live: Dict[str, EngineSpan] = {}
        self._ring: deque = deque(maxlen=max(1, int(ring_size)))
        self._steps: deque = deque(maxlen=max(1, int(step_ring_size)))
        self._next_step = 0
        self._sink = (_SpanSink(span_log_path)
                      if span_log_path else None)
        # Turn state, touched by the server loop's thread only.
        # ``_phase`` is None until start_turns(): an engine stepped
        # without the server loop records steps as before.
        self.annotate = annotate
        self._phase: Optional[str] = None
        self._phase_t = 0.0
        self._cpu_t = 0.0
        self._turn_t = 0.0
        self._acc: Dict[str, float] = {}
        self._cpu: Dict[str, float] = {}
        # The loop thread's wall less CPU outside PARKED_PHASES, summed
        # with its sign over every closed turn (on a CPU clock that
        # advances in ticks a phase's difference is as often below
        # zero as above, and only the sum is true), and the largest
        # that sum has been, which never decreases:
        # vllm:engine_loop_offcpu_seconds_total.
        self._offcpu_s = 0.0
        self.loop_offcpu_s = 0.0
        # The event loop's side of the turns (AsyncEngine.start binds
        # it to its thread).
        self.front = FrontClock()
        self._turn_step = 0
        # The record of the turn under way: made when the turn opens,
        # so that a hand-over inside it has where to stamp, and
        # ``_open`` once a step has been accounted into it.
        self._record: Dict[str, Any] = {}
        self._open: Optional[Dict[str, Any]] = None
        self._closed_kind: Optional[str] = None
        # How the outputs of the turn before went ("behind" this
        # turn's dispatch or "flushed" at once), for this turn's
        # record, and how this turn's own went where they are gone
        # already, for the next.
        self._handover: Optional[str] = None
        self._own_handover: Optional[str] = None
        self._marks: List[Any] = []
        self._compiles_seen = 0
        # perf_counter() + _unix0 is the records' clock: t_end - t_start
        # is then exactly the sum of the phases.
        self._unix0 = time.time() - time.perf_counter()
        self._walls: Dict[str, deque] = {}
        self._handoffs: Dict[str, deque] = {}

    # -- request timeline ---------------------------------------------------

    def start(self, seq_id: str, request_id: Optional[str] = None,
              **fields: Any) -> None:
        span = EngineSpan(seq_id, request_id, role=self.role)
        span.event("enqueue", **fields)
        self._live[seq_id] = span

    def event(self, seq_id: str, name: str, **fields: Any) -> None:
        span = self._live.get(seq_id)
        if span is not None:
            span.event(name, **fields)

    def finish(self, seq_id: str, reason: Optional[str] = None, *,
               arrival_ts: Optional[float] = None,
               first_scheduled_ts: Optional[float] = None,
               first_token_ts: Optional[float] = None,
               finish_ts: Optional[float] = None,
               prompt_tokens: Optional[int] = None,
               output_tokens: Optional[int] = None) -> None:
        """Finalizes a live span: appends the terminal event, derives
        the phase durations, emits the JSON line, and moves the span
        into the flight-recorder ring. Idempotent per seq_id (abort
        and the finished-output drain can race to it)."""
        span = self._live.pop(seq_id, None)
        if span is None:
            return
        span.event("finish", reason=reason)
        arrival = arrival_ts if arrival_ts is not None else span.start_ts
        end = finish_ts if finish_ts is not None else time.time()
        span.summary = {
            "finish_reason": reason,
            "prompt_tokens": prompt_tokens,
            "output_tokens": output_tokens,
            "queue_ms": _ms(arrival, first_scheduled_ts),
            "ttft_ms": _ms(arrival, first_token_ts),
            "decode_ms": _ms(first_token_ts, end),
            "latency_ms": _ms(arrival, end),
        }
        self._ring.append(span)
        if self._sink is not None:
            self._sink.emit(span.to_json())

    # -- step flight recorder -----------------------------------------------

    def on_step(self, **fields: Any) -> None:
        record: Dict[str, Any] = {"step": self._next_step,
                                  "ts": round(time.time(), 6)}
        self._next_step += 1
        record.update(fields)
        if self._phase is None:
            self._steps.append(record)
            return
        # Under the server loop the record is the turn's: end_turn()
        # completes it and puts it into the ring. One left open (the
        # step raised after its accounting) goes in as it is.
        if self._open is not None:
            self._steps.append(self._open)
            self._record = {}
        # Into the turn's record, beside what a hand-over has stamped
        # there already (the event loop may be stamping it now: both
        # sides only store keys of their own).
        self._record.update(record)
        self._open = self._record

    # -- turns of the server loop -------------------------------------------

    def _mark(self, name: str) -> None:
        # ``step`` joins the event to the record this turn writes.
        mark = self.annotate("engine." + name, step=self._turn_step)
        mark.__enter__()
        self._marks.append(mark)

    def _open_turn(self) -> None:
        """A turn starts where the last ended, in ``other``."""
        self._turn_step = self._next_step
        self._record = {"step": self._next_step}
        if self.annotate is not None:
            self._mark("turn")
            self._mark("other")

    def start_turns(self, compiles: int = 0) -> None:
        """The server loop's first act: from here on its thread is
        always in one phase of one turn. ``compiles`` is the compile
        ledger's total so far, start-up's, which is no turn's."""
        self._phase = "other"
        self._phase_t = self._turn_t = time.perf_counter()
        self._cpu_t = time.thread_time()
        self._compiles_seen = compiles
        self._open_turn()

    def phase(self, name: str) -> Optional[str]:
        """The loop thread goes over to ``name`` (one of TURN_PHASES);
        the time since the last switch is the old phase's. Returns the
        old phase, or None where no server loop keeps turns."""
        old = self._phase
        if old is None or old == name:
            return old
        self._close_phase(name)
        if self._marks:
            self._marks.pop().__exit__(None, None, None)
            self._mark(name)
        return old

    def _close_phase(self, name: str) -> None:
        now, cpu = time.perf_counter(), time.thread_time()
        old = self._phase
        self._acc[old] = self._acc.get(old, 0.0) + now - self._phase_t
        self._cpu[old] = self._cpu.get(old, 0.0) + cpu - self._cpu_t
        self._phase_t, self._cpu_t = now, cpu
        self._phase = name

    def end_turn(self, emitted: int,
                 compiles: int = 0) -> Optional[Dict[str, Any]]:
        """The turn ends here if it accounted a step: its record gets
        ``t_start``, ``t_end``, ``phases`` (ms, summing to the wall),
        ``cpu`` (the thread's CPU clock over the same phases),
        ``front`` (what the event loop's thread did meanwhile, where
        one is bound), ``emitted`` (the outputs handed over inside it),
        ``handover`` (how the outputs of the turn before went:
        ``behind`` this turn's dispatch or ``flushed`` at once; absent
        where that turn had none) and, where the compile ledger's
        total ``compiles`` grew during it, ``compiles``; it goes into
        the ring and is returned. A turn without a step (nothing
        planned) goes on."""
        record = self._open
        if record is None:
            return None
        self._open = None
        self._closed_kind = record.get("kind")
        if self._handover is not None:
            record["handover"] = self._handover
        self._handover, self._own_handover = self._own_handover, None
        self._close_phase("other")
        while self._marks:
            self._marks.pop().__exit__(None, None, None)
        phases, self._acc = self._acc, {}
        cpu, self._cpu = self._cpu, {}
        t_start, self._turn_t = self._turn_t, self._phase_t
        record["t_start"] = round(self._unix0 + t_start, 6)
        record["t_end"] = round(self._unix0 + self._turn_t, 6)
        record["phases"] = {k: round(v * 1e3, 3)
                            for k, v in phases.items()}
        record["cpu"] = {k: round(v * 1e3, 3) for k, v in cpu.items()}
        self._offcpu_s += sum(wall - cpu[k] for k, wall in phases.items()
                              if k not in PARKED_PHASES)
        self.loop_offcpu_s = max(self.loop_offcpu_s, self._offcpu_s)
        front = self.front.close_interval()
        if front is not None:
            record["front"] = front
        record["emitted"] = emitted
        if compiles > self._compiles_seen:
            record["compiles"] = compiles - self._compiles_seen
        self._compiles_seen = compiles
        self._steps.append(record)
        # Parked without work is no part of a stall, nor its name.
        idle = phases.pop("idle", 0.0)
        wall = self._turn_t - t_start - idle
        median = self._slow(self._walls, record.get("kind"), wall)
        if median is not None:
            name = max(phases, key=phases.get)
            logger.warning(
                "slow turn: %s %.1f ms against a median of %.1f ms, "
                "most of it in %s (%.1f ms): %s", record.get("kind"),
                wall * 1e3, median * 1e3, name, phases[name] * 1e3,
                json.dumps(record))
        self._open_turn()
        return record

    def handoff_stamp(self, behind: Optional[bool] = None
                      ) -> Optional[Callable[[], None]]:
        """For the loop thread, as it enters ``emit``: what the event
        loop is to call once it has delivered the outputs, or None
        where no server loop keeps turns. The record is the one of the
        turn under way, which end_turn() closes later; the outputs are
        those of the step it has accounted, else of the turn before,
        and ``behind`` says whether they go behind a dispatch or at
        once (None: outputs of no turn, a refusal's)."""
        if self._phase is None:
            return None
        own = self._open is not None
        kind = self._open.get("kind") if own else self._closed_kind
        if behind is not None:
            order = "behind" if behind else "flushed"
            if own:
                self._own_handover = order
            else:
                self._handover = order
        return functools.partial(self.on_handoff, self._record,
                                 time.perf_counter(), kind)

    def on_handoff(self, record: Dict[str, Any], emit_start: float,
                   kind: Optional[str] = None) -> None:
        """Runs on the event loop as the last act of the call that
        delivered a turn's outputs: ``handoff_ms`` is how long after
        the loop thread entered ``emit`` the event loop had put the
        last of them on its stream. The streams' consumers run after
        it and are not in it. The loop thread may still be filling or
        closing the record: both sides only store keys of their own.
        ``kind`` is the kind of the turn whose outputs they are (the
        record's own where not given), which is whose history a late
        one is judged against."""
        taken = time.perf_counter() - emit_start
        record["handoff_ms"] = round(taken * 1e3, 3)
        if kind is None:
            kind = record.get("kind")
        median = self._slow(self._handoffs, kind, taken)
        if median is not None:
            logger.warning(
                "slow turn: %s handoff_ms %.1f against a median of "
                "%.1f ms, the event loop was late: %s",
                kind, taken * 1e3, median * 1e3, json.dumps(record))

    @staticmethod
    def _slow(history: Dict[str, deque], kind: Optional[str],
              seconds: float) -> Optional[float]:
        """The median ``seconds`` is slow against, else None; either
        way ``seconds`` joins its kind's history."""
        past = history.setdefault(
            str(kind), deque(maxlen=SLOW_TURN_HISTORY))
        median = None
        if len(past) >= SLOW_TURN_MIN_HISTORY:
            median = statistics.median(past)
            if not (seconds > SLOW_TURN_FACTOR * median
                    and seconds >= median + SLOW_TURN_MIN_S):
                median = None
        past.append(seconds)
        return median

    def recent_steps(self, limit: int = 100) -> List[Dict[str, Any]]:
        steps = list(self._steps)
        if limit > 0:
            steps = steps[-limit:]
        return steps

    # -- lookup -------------------------------------------------------------

    def lookup(self, trace_id: str) -> Optional[Dict[str, Any]]:
        """All recorded timelines for one ``x-request-id`` (or engine
        seq id) — live spans first, then the ring, oldest first."""
        spans = [span for span in
                 list(self._live.values()) + list(self._ring)
                 if trace_id in (span.seq_id, span.request_id)]
        if not spans:
            return None
        return {"request_id": trace_id,
                "spans": [s.to_dict() for s in spans]}
