"""OpenAI-compatible HTTP front end for the TPU engine.

This is the process the Helm chart's engine pods run (the counterpart of
``vllm serve`` in reference deployment-vllm-multi.yaml:57-103). Surface:

  POST /v1/chat/completions | /v1/completions   (stream + non-stream)
  GET  /v1/models | /health | /version
  GET  /metrics  -- vLLM exposition names the router scrapes
                    (reference engine_stats.py:46-55):
                    vllm:num_requests_running, vllm:num_requests_waiting,
                    vllm:gpu_cache_usage_perc, vllm:gpu_prefix_cache_hit_rate

Threading model: the device loop runs in one dedicated thread (JAX
dispatch is blocking); HTTP handlers submit requests through a
thread-safe queue and receive per-token deltas via asyncio queues.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import json
import queue
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from aiohttp import web

from production_stack_tpu.engine.config import (
    AutotuneConfig,
    bench_1b_model_config,
    CacheConfig,
    EngineConfig,
    KVEconConfig,
    LoRAConfig,
    ModelConfig,
    OffloadConfig,
    ParallelConfig,
    QoSConfig,
    SchedulerConfig,
    tiny_model_config,
)
from production_stack_tpu.kvecon.summary import (
    PrefixSummaryTracker,
    routable_text,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.perf_observatory import LOAD_PARTS
from production_stack_tpu.engine.tracing import StartupTimeline
from production_stack_tpu.qos import (
    parse_priority,
    Priority,
    PRIORITY_HEADER,
    PRIORITY_NAMES,
    priority_name,
    shed_counter_dict,
    shed_retry_after_s,
    SPEC_OFF_HEADER,
)
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.engine.tokenizer import (
    get_tokenizer,
    render_chat_prompt,
)
from production_stack_tpu.utils.log import init_logger
from production_stack_tpu.version import __version__

logger = init_logger(__name__)


# Consecutive failed engine steps after which /health answers 503.
STEP_FAILURE_LIMIT = 3


def slice_options():
    """What a /debug/profiler slice records: the device's planes and
    the host's TraceAnnotations (``engine.*`` on the loop thread;
    ``server.stream_token``, ``server.consume`` and ``server.write``
    on the event loop's) but no Python frames. The Python tracer
    writes an event per call on every thread: it doubled the hand-over
    it was there to measure, made a slice of 8 s 100 MB, and stopping
    it held the interpreter for tens of seconds (PERF.md, PR 26 and
    PR 28)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    return options


class AsyncEngine:
    """Background-thread engine loop with asyncio streaming outputs."""

    def __init__(self, engine: LLMEngine):
        self.engine = engine
        self._submit_q: "queue.Queue" = queue.Queue()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._streams: Dict[str, asyncio.Queue] = {}
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="engine-loop"
        )
        self._started = threading.Event()
        # Wakes the loop out of its idle/backoff waits the moment new
        # work arrives (submit/abort) instead of serving out a fixed
        # sleep — cuts TTFT for requests that land on an idle engine.
        self._wakeup = threading.Event()
        self.uptime_start = time.time()
        # Step watchdog (docs/crash_recovery.md): wall-clock start of
        # the step currently executing on the device thread, None
        # between steps. The asyncio /health handler reads it — a hung
        # device program blocks this thread, not the event loop.
        self._step_started: Optional[float] = None
        # Self-tuning (docs/autotuning.md): the EngineServer installs
        # an Autotuner here; the loop ticks it between steps so
        # controllers touch scheduler/config state from the same
        # thread that reads it. None = no tuning.
        self.autotuner = None
        # Steps that raised since the last one that did not; /health
        # turns 503 at STEP_FAILURE_LIMIT so the router's prober
        # rotates out a replica whose device programs keep failing.
        self.consecutive_step_failures = 0
        # The profiler endpoints set this to the tracer's annotation
        # factory while a slice runs: each delivery of a turn's
        # outputs to their streams is then one ``server.stream_token``
        # event on the event loop's thread, and each wake of a
        # stream's consumer and each socket write that follow it a
        # ``server.consume`` / ``server.write`` event (``front`` keeps
        # the factory of the newest delivery). None outside a slice.
        self.stream_annotation = None
        # The event loop's side of the turn records (engine/tracing.py
        # FrontClock): the tracer's, once start() has bound it to the
        # event loop's thread; None without a tracer.
        self.front = None

    def current_step_s(self) -> float:
        """Seconds the in-flight engine step has been running
        (0.0 when no step is executing)."""
        started = self._step_started
        if started is None:
            return 0.0
        return time.time() - started

    def start(self, loop: asyncio.AbstractEventLoop) -> None:
        """Runs on the event loop's thread."""
        self._loop = loop
        tracer = self.engine.tracer
        if tracer is not None:
            tracer.front.bind()
            self.front = tracer.front
        self._thread.start()
        self._started.set()

    def _run(self) -> None:
        from production_stack_tpu.engine.engine import StepOutput
        self._started.wait()
        # Turn phases (engine/tracing.py TURN_PHASES): with a tracer
        # this thread is always in one named phase, and each pass that
        # accounts a step closes one turn record.
        tracer = self.engine.tracer
        obs = getattr(self.engine.runner, "observatory", None)
        if tracer is not None:
            tracer.start_turns(
                compiles=obs.compile_events_total() if obs else 0)
        # Outputs handed over since the last record closed.
        emitted = 0
        while True:
            # Drain submissions (non-blocking when engine has work).
            block = not self.engine.has_work()
            if tracer is not None:
                tracer.phase("idle" if block else "admit")
            try:
                item = self._submit_q.get(
                    block=block, timeout=1.0 if block else None
                )
            except queue.Empty:
                item = None
            if item is not None:
                if tracer is not None:
                    tracer.phase("admit")
                seq_id = item["seq_id"]
                try:
                    if item.get("kind") == "handoff":
                        # Disagg decode role: park until the shipped
                        # KV is reachable (engine.add_handoff).
                        self.engine.add_handoff(
                            item["prompt"], item["first_token"],
                            item["sampling"], seq_id=seq_id,
                            request_id=item.get("request_id"),
                        )
                    elif item.get("kind") == "resume":
                        # Mid-stream failover: park until the
                        # checkpointed KV is reachable, or recompute
                        # from the journal (engine.add_resume).
                        self.engine.add_resume(
                            item["tokens"], item["prior"],
                            item["sampling"], seq_id=seq_id,
                            request_id=item.get("request_id"),
                        )
                    else:
                        self.engine.add_request(
                            item["prompt"], item["sampling"],
                            seq_id=seq_id,
                            lora_name=item.get("lora_name"),
                            handoff_prefill=item.get(
                                "handoff_prefill", False),
                            request_id=item.get("request_id"),
                            priority=item.get("priority"),
                            spec_off=item.get("spec_off", False),
                        )
                except Exception as e:
                    # Queue full / invalid request: fail THIS request,
                    # never the engine loop.
                    logger.warning("Rejecting %s: %s", seq_id, e)
                    self._hand_over([StepOutput(
                        seq_id=seq_id, new_token=None, finished=True,
                        finish_reason="abort",
                    )])
                continue  # admit as many as possible before stepping
            if tracer is not None:
                tracer.phase("other")
            if self.autotuner is not None:
                try:
                    self.autotuner.maybe_tick()
                except Exception:
                    logger.exception("autotune tick failed")
            if not self.engine.has_work():
                continue
            # The turn dispatches first (docs/async_pipeline.md, "The
            # served loop"): plan, build and enqueue the next program
            # from the committed state; behind it, while the device
            # runs, what the turn before still owes its streams; then
            # wait, parse and commit what the next plan reads.
            self._step_started = time.time()
            try:
                enqueued = self.engine.begin_step()
                handed = self._hand_over_owed(behind=enqueued is not None)
                if enqueued is not None:
                    self.engine.finish_step(enqueued)
            except Exception as e:
                logger.exception("Engine step failed: %s", e)
                self.consecutive_step_failures += 1
                if tracer is not None:
                    tracer.phase("other")
                # The sequences that step touched end here with a
                # terminal 'abort' instead of being retried forever,
                # behind whatever earlier turns still owed them.
                self._hand_over(self.engine.abort_after_step_failure())
                # Interruptible backoff: a new submission or abort
                # wakes the loop immediately instead of serving out
                # the full 50 ms.
                self._wakeup.wait(0.05)
                self._wakeup.clear()
                continue
            finally:
                self._step_started = None
            self.consecutive_step_failures = 0
            if enqueued is None and not handed:
                # Planner produced no executable work (e.g. transient
                # KV-cache starvation, or an async dispatch that owes
                # nothing yet): don't busy-spin, but let new arrivals
                # cut the wait short.
                if tracer is not None:
                    tracer.phase("other")
                self._wakeup.wait(0.002)
                self._wakeup.clear()
            if not self.engine.more_to_run():
                # Nothing is owed to an idle engine: where no program
                # follows, the turn's own outputs go now.
                handed += self._hand_over_owed(behind=False)
            emitted += handed
            if tracer is not None and tracer.end_turn(
                    emitted=emitted,
                    compiles=obs.compile_events_total() if obs else 0):
                emitted = 0

    def _hand_over_owed(self, behind: bool) -> int:
        """The deferred half of the engine's commits so far, made and
        handed over, ``behind`` the dispatch of the next program or at
        once; returns how many outputs that was."""
        outputs = self.engine.take_owed()
        if outputs:
            tracer = self.engine.tracer
            if tracer is None:
                self._hand_over(outputs)
            else:
                tracer.phase("emit")
                self._hand_over(outputs, tracer.handoff_stamp(behind))
        return len(outputs)

    def _hand_over(self, outputs, stamp=None) -> None:
        """The loop thread's side: one cross-thread call for all of
        ``outputs``, whatever their number, and back to the chip. The
        streams are fed on the event loop while the next program runs."""
        if outputs:
            # The annotation is bound now: the slice may have ended by
            # the time the event loop gets to the callback.
            self._loop.call_soon_threadsafe(
                self._deliver, outputs, self.stream_annotation, stamp)

    def _deliver(self, outputs, annotate, stamp) -> None:
        """The event loop's side: each output onto its stream, in the
        engine's order, so a stream's tokens stay in order and its
        finish comes last. A stream that was finished or aborted
        meanwhile is gone from ``_streams`` and its outputs dropped.
        Feeds no accumulator of the front's: it hands the consumers
        it wakes the slice's annotation factory (None outside one)."""
        if self.front is not None:
            self.front.annotate = annotate
        with (contextlib.nullcontext() if annotate is None
              else annotate("server.stream_token")):
            for out in outputs:
                stream = self._streams.get(out.seq_id)
                if stream is not None:
                    stream.put_nowait(out)
        if stamp is not None:
            stamp()

    async def submit(self, prompt: List[int], sampling: SamplingParams,
                     lora_name: Optional[str] = None,
                     handoff_prefill: bool = False,
                     request_id: Optional[str] = None,
                     priority: Optional[int] = None,
                     spec_off: bool = False,
                     ) -> tuple[str, asyncio.Queue]:
        seq_id = f"seq-{uuid.uuid4().hex[:16]}"
        stream: asyncio.Queue = asyncio.Queue()
        self._streams[seq_id] = stream
        self._submit_q.put({
            "kind": "request", "prompt": prompt, "sampling": sampling,
            "seq_id": seq_id, "lora_name": lora_name,
            "handoff_prefill": handoff_prefill,
            "request_id": request_id,
            "priority": priority, "spec_off": spec_off,
        })
        self._wakeup.set()
        return seq_id, stream

    async def submit_handoff(self, prompt: List[int], first_token: int,
                             sampling: SamplingParams,
                             request_id: Optional[str] = None,
                             ) -> tuple[str, asyncio.Queue]:
        """Submit a disagg handoff descriptor's sequence
        (docs/disaggregation.md); the stream carries tokens FROM THE
        SECOND onward — the caller already has the first."""
        seq_id = f"seq-{uuid.uuid4().hex[:16]}"
        stream: asyncio.Queue = asyncio.Queue()
        self._streams[seq_id] = stream
        self._submit_q.put({
            "kind": "handoff", "prompt": prompt,
            "first_token": first_token, "sampling": sampling,
            "seq_id": seq_id, "request_id": request_id,
        })
        self._wakeup.set()
        return seq_id, stream

    async def submit_resume(self, tokens: List[int], prior: int,
                            sampling: SamplingParams,
                            request_id: Optional[str] = None,
                            ) -> tuple[str, asyncio.Queue]:
        """Submit a crashed stream's resume journal
        (docs/crash_recovery.md); the stream carries only NEW tokens —
        the journaled context is replayed by the handler."""
        seq_id = f"seq-{uuid.uuid4().hex[:16]}"
        stream: asyncio.Queue = asyncio.Queue()
        self._streams[seq_id] = stream
        self._submit_q.put({
            "kind": "resume", "tokens": tokens, "prior": prior,
            "sampling": sampling, "seq_id": seq_id,
            "request_id": request_id,
        })
        self._wakeup.set()
        return seq_id, stream

    def finish_stream(self, seq_id: str) -> None:
        self._streams.pop(seq_id, None)

    def abort(self, seq_id: str) -> None:
        self.engine.abort_request(seq_id)
        self.finish_stream(seq_id)
        self._wakeup.set()  # freed capacity: let the planner retry


# ---- request handling ------------------------------------------------------


def _sampling_from_body(body: dict, max_model_len: int,
                        vocab_size: "int | None" = None
                        ) -> SamplingParams:
    max_tokens = body.get("max_tokens")
    if max_tokens is None:
        max_tokens = body.get("max_completion_tokens")
    if max_tokens is None:
        max_tokens = 256  # OpenAI default; 0 is invalid, not "unset"
    # JSON null must fall back to the OpenAI defaults, not to 0.
    temperature = body.get("temperature")
    top_p = body.get("top_p")
    top_k = body.get("top_k")
    stop = body.get("stop")
    if stop is None:
        stop_strings = []
    elif isinstance(stop, str):
        stop_strings = [stop]
    else:
        stop_strings = [str(s) for s in stop][:4]  # OpenAI caps at 4
    presence = body.get("presence_penalty")
    frequency = body.get("frequency_penalty")
    repetition = body.get("repetition_penalty")  # vLLM extension
    # Chat API: logprobs is a bool + top_logprobs an int; legacy
    # completions API: logprobs is the top-k int itself.
    lp_req = body.get("logprobs")
    lp_top = int(body.get("top_logprobs") or 0)
    if isinstance(lp_req, bool):
        if not lp_req and lp_top > 0:
            raise ValueError(
                "'top_logprobs' is only allowed when 'logprobs' is "
                "enabled")
        lp_flag = lp_req
    elif lp_req is None:
        lp_flag = lp_top > 0
    else:
        lp_flag, lp_top = True, int(lp_req)
    # OpenAI logit_bias: {"<token_id>": bias} with string keys (JSON
    # object keys) and bias in [-100, 100], at most 300 entries.
    raw_bias = body.get("logit_bias")
    logit_bias = None
    if raw_bias:
        if not isinstance(raw_bias, dict):
            raise ValueError("logit_bias must be an object mapping "
                             "token ids to bias values")
        if len(raw_bias) > 300:
            raise ValueError("logit_bias supports at most 300 entries")
        logit_bias = {}
        for k, v in raw_bias.items():
            try:
                tid = int(k)
                bv = float(v)
            except (TypeError, ValueError):
                raise ValueError(
                    f"logit_bias entries must map integer token ids "
                    f"to numbers (got {k!r}: {v!r})")
            if not (-100.0 <= bv <= 100.0):
                raise ValueError(
                    f"logit_bias values must be in [-100, 100], got "
                    f"{bv} for token {tid}")
            if vocab_size is not None and not (0 <= tid < vocab_size):
                # Reject like every other out-of-range param — a
                # silently dropped ban (wrong tokenizer assumed) would
                # succeed while doing nothing.
                raise ValueError(
                    f"logit_bias token id {tid} is outside the model "
                    f"vocabulary (size {vocab_size})")
            logit_bias[tid] = bv
    params = SamplingParams(
        max_tokens=min(int(max_tokens), max_model_len),
        temperature=1.0 if temperature is None else float(temperature),
        top_p=1.0 if top_p is None else float(top_p),
        top_k=0 if top_k is None else int(top_k),
        stop_strings=stop_strings,
        presence_penalty=0.0 if presence is None else float(presence),
        frequency_penalty=(0.0 if frequency is None
                           else float(frequency)),
        repetition_penalty=(1.0 if repetition is None
                            else float(repetition)),
        ignore_eos=bool(body.get("ignore_eos", False)),
        seed=None if body.get("seed") is None else int(body["seed"]),
        logprobs=lp_flag,
        top_logprobs=lp_top,
        logit_bias=logit_bias,
        min_tokens=int(body.get("min_tokens") or 0),
        guided=_guided_from_body(body),
        # A block-diffusion model's (docs/block_diffusion.md; the
        # names of the published generate.py). The engine makes them
        # whole from the model's defaults, or refuses them for a model
        # that generates left to right (LLMEngine.check_sampling).
        denoising_steps=(None if body.get("denoising_steps") is None
                         else int(body["denoising_steps"])),
        remasking_strategy=(
            None if body.get("remasking_strategy") is None
            else str(body["remasking_strategy"])),
        confidence_threshold=(
            None if body.get("confidence_threshold") is None
            else float(body["confidence_threshold"])),
    )
    _validate_sampling(params)
    return params


def _guided_from_body(body: dict) -> "str | None":
    """OpenAI ``response_format`` -> guided mode ('json' or None)."""
    rf = body.get("response_format")
    if rf is None:
        return None
    if not isinstance(rf, dict) or "type" not in rf:
        raise ValueError(
            "response_format must be an object with a 'type' field")
    kind = rf["type"]
    if kind == "text":
        return None
    if kind == "json_object":
        return "json"
    raise ValueError(
        f"unsupported response_format type {kind!r} "
        "(supported: 'text', 'json_object')")


def _validate_sampling(p: SamplingParams) -> None:
    """Reject out-of-range sampling params with ValueError (the caller
    maps it to HTTP 400, matching OpenAI/vLLM behavior) instead of
    letting them reach the device, where e.g. repetition_penalty=0
    divides logits and emits NaN garbage with a 200."""
    if p.max_tokens < 1:
        raise ValueError("max_tokens must be at least 1")
    if not (0.0 <= p.temperature <= 2.0):
        raise ValueError(
            f"temperature must be in [0, 2], got {p.temperature}")
    if not (0.0 < p.top_p <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {p.top_p}")
    if p.top_k < 0:
        raise ValueError(
            f"top_k must be a non-negative integer, got {p.top_k}")
    if not (-2.0 <= p.presence_penalty <= 2.0):
        raise ValueError(
            f"presence_penalty must be in [-2, 2], got "
            f"{p.presence_penalty}")
    if not (-2.0 <= p.frequency_penalty <= 2.0):
        raise ValueError(
            f"frequency_penalty must be in [-2, 2], got "
            f"{p.frequency_penalty}")
    if p.repetition_penalty <= 0.0:
        raise ValueError(
            f"repetition_penalty must be a positive number, got "
            f"{p.repetition_penalty}")
    if not (0 <= p.top_logprobs <= 20):
        raise ValueError(
            f"top_logprobs must be in [0, 20], got {p.top_logprobs}")
    if not (0 <= p.min_tokens <= p.max_tokens):
        raise ValueError(
            f"min_tokens must be in [0, max_tokens], got "
            f"{p.min_tokens} with max_tokens {p.max_tokens}")


def _sampling_to_wire(p: SamplingParams) -> dict:
    """SamplingParams -> JSON-safe dict for a handoff descriptor."""
    d = dict(vars(p))
    if d.get("logit_bias"):
        # JSON object keys are strings; _sampling_from_wire restores
        # the int token ids.
        d["logit_bias"] = {str(k): v
                           for k, v in d["logit_bias"].items()}
    return d


def _sampling_from_wire(d: dict) -> SamplingParams:
    """Inverse of _sampling_to_wire; unknown keys are dropped so a
    newer prefill engine can hand off to an older decode engine."""
    d = dict(d)
    lb = d.get("logit_bias")
    if lb:
        d["logit_bias"] = {int(k): float(v) for k, v in lb.items()}
    allowed = {f.name for f in dataclasses.fields(SamplingParams)}
    return SamplingParams(**{k: v for k, v in d.items()
                             if k in allowed})


class _StopStringScanner:
    """Incremental OpenAI ``stop``-sequence detection on decoded text.

    Stop sequences are a TEXT contract: a stop string can span token
    boundaries, so it cannot be evaluated on token ids in the engine.
    The scanner holds back the last ``max(len(stop)) - 1`` characters
    of the stream; on a hit it emits only the text before the stop
    (OpenAI semantics: the stop sequence itself is not returned) and
    flags ``stopped`` so the caller aborts the engine sequence.
    """

    def __init__(self, stops):
        self.stops = [s for s in stops if s]
        self.hold = (max(len(s) for s in self.stops) - 1
                     if self.stops else 0)
        self.buf = ""
        self.stopped = False

    def feed(self, delta: str) -> str:
        if self.stopped or not delta:
            return ""
        if not self.stops:
            return delta
        self.buf += delta
        hit = -1
        for s in self.stops:
            j = self.buf.find(s)
            if j != -1 and (hit == -1 or j < hit):
                hit = j
        if hit != -1:
            self.stopped = True
            out, self.buf = self.buf[:hit], ""
            return out
        if len(self.buf) > self.hold:
            cut = len(self.buf) - self.hold
            out, self.buf = self.buf[:cut], self.buf[cut:]
            return out
        return ""

    def flush(self) -> str:
        """Emit any held-back tail once the stream ends unstopped."""
        if self.stopped:
            return ""
        out, self.buf = self.buf, ""
        return out


def _usage(prompt_len: int, completion_len: int) -> dict:
    return {
        "prompt_tokens": prompt_len,
        "completion_tokens": completion_len,
        "total_tokens": prompt_len + completion_len,
    }


class EngineServer:
    def __init__(self, engine: LLMEngine, served_model_name: str,
                 pooling: str = "last",
                 profile_dir: Optional[str] = None,
                 chat_template: Optional[str] = None,
                 drain_exit_timeout_s: float = 0.0,
                 build_id: str = ""):
        self.async_engine = AsyncEngine(engine)
        self.engine = engine
        # The start's spans (engine/tracing.py StartupTimeline), the
        # runner's: main()'s where the process began there. /version
        # and /metrics read it; build_app's on_startup closes it.
        self.startup = (getattr(getattr(engine, "runner", None),
                                "startup", None) or StartupTimeline())
        self.model_name = served_model_name
        self.tokenizer = engine.tokenizer
        self.pooling = pooling
        self._embedder = None
        self._embed_lock = asyncio.Lock()
        self.profile_dir = profile_dir
        # The one /debug/profiler slice: None, "running", or
        # "stopping" while its trace is being written.
        self._profiling: Optional[str] = None
        # Synthetic span id for the active profiler capture window, so
        # the capture shows up in traceview next to the requests it
        # overlapped (docs/observability.md).
        self._profiler_span_id: Optional[str] = None
        # Jinja source overriding the model's chat template (vLLM's
        # --chat-template; a path is read by main()).
        self.chat_template = chat_template
        # Zero-loss drain (docs/fleet.md): once POST /drain flips this,
        # new admissions get 503+Retry-After (the resilience layer's
        # retryable-rejection semantics) while in-flight generation
        # requests run to completion untouched.
        self.draining = False
        self.drain_exit_timeout_s = drain_exit_timeout_s
        # Rolling upgrades (docs/fleet.md): --build-id labels the
        # running revision in /health and /version so the rollout
        # controller can verify which build a replica actually runs.
        # A migrate-mode drain flips migrate_drain: checkpointed
        # streams are cut right after a checkpoint frame so the router
        # resumes them on a new-revision replica instead of waiting
        # for multi-minute streams to finish here.
        self.build_id = build_id
        self.migrate_drain = False
        self._active_generations = 0
        self._drain_exit_task: Optional[asyncio.Task] = None
        # QoS graceful shedding (docs/qos.md): per-priority-class count
        # of requests turned away with 429 at the shed gate. Rendered
        # as vllm:qos_shed_total{class=...} on /metrics.
        self.qos_shed_counts = shed_counter_dict()
        # Step watchdog (docs/crash_recovery.md): latched once per hung
        # step so the trip is logged/span-evented once, not per probe.
        self._watchdog_tripped = False
        # Cluster KV economy (docs/kv_economy.md): decayed hot-prefix
        # tracker behind GET /kv/summary. Observed from the request
        # text at admission (O(prompt) hashing, no per-step cost); the
        # router's KVStateAwarePolicy hashes the same text domain so
        # the chain hashes line up.
        kve = getattr(engine.config, "kvecon", None) or KVEconConfig()
        self.kv_summary = PrefixSummaryTracker(
            top_k=kve.summary_top_k, admit_hits=kve.admit_hits,
            ttl_s=kve.ttl_s)
        # Topology observability (docs/parallelism.md): which slice
        # this process's first local device belongs to, resolved once
        # (jax.devices() order is stable for the process lifetime).
        self._slice_id_cache: Optional[int] = None
        # Self-tuning controllers (docs/autotuning.md). Constructed
        # unconditionally — maybe_tick() is a cheap no-op in 'off'
        # mode — so /autotune/status always answers and flipping the
        # mode needs no re-wiring.
        from production_stack_tpu.autotune import (
            Autotuner, build_engine_controllers,
            observatory_drift_flags)
        at = (getattr(engine.config, "autotune", None)
              or AutotuneConfig())
        try:
            controllers = build_engine_controllers(self, at)
            drift_flags = observatory_drift_flags(engine.runner)
        except AttributeError:
            # Stub engines (tests) lack the scheduler/metrics surface
            # the catalog reads; they still get a live, empty
            # autotuner so /autotune/status answers.
            controllers, drift_flags = [], None
        self.autotuner = Autotuner(
            at, controllers,
            tracer=getattr(engine, "tracer", None),
            drift_flags=drift_flags)
        self.async_engine.autotuner = self.autotuner

    def _slice_id(self) -> int:
        if self._slice_id_cache is None:
            try:
                from production_stack_tpu.parallel.topology import (
                    discover_topology,
                )
                import jax
                topo = discover_topology(
                    num_slices=self.engine.config.parallel.num_slices)
                self._slice_id_cache = topo.slice_of(
                    jax.local_devices()[0])
            except Exception:
                self._slice_id_cache = 0
        return self._slice_id_cache

    # -- decoding helpers ---------------------------------------------------

    def _delta_decoder(self):
        """Incremental detokenizer: feed token ids, get new text.

        ``push(tok)`` returns newly-decoded text (holding back a tail
        that may be an incomplete UTF-8/BPE run); ``push(None,
        flush=True)`` force-emits whatever is still held back (stream
        end).
        """
        tokens: List[int] = []
        base = 0  # tokens[:base] are already emitted

        def push(token_id: Optional[int], flush: bool = False) -> str:
            nonlocal base
            if token_id is not None:
                tokens.append(token_id)
            # Decode only the pending tail (O(1) per token, not O(n)).
            tail = self.tokenizer.decode(tokens[base:])
            if not flush and tail.endswith("�"):
                return ""  # likely an incomplete UTF-8/BPE run
            base = len(tokens)
            return tail

        return push

    # -- handlers -----------------------------------------------------------

    @staticmethod
    async def _json_body(request: web.Request) -> dict:
        try:
            body = await request.json()
        except Exception:
            raise web.HTTPBadRequest(
                text='{"error": {"message": "Request body is not valid '
                     'JSON"}}',
                content_type="application/json",
            )
        if not isinstance(body, dict):
            raise web.HTTPBadRequest(
                text='{"error": {"message": "Request body must be a '
                     'JSON object"}}',
                content_type="application/json",
            )
        return body

    async def chat_completions(self, request: web.Request):
        body = await self._json_body(request)
        messages = body.get("messages")
        if not isinstance(messages, list):
            return web.json_response(
                {"error": {"message": "'messages' must be a list"}},
                status=400,
            )
        prompt = render_chat_prompt(self.tokenizer, messages,
                                    chat_template=self.chat_template)
        self.kv_summary.observe_text(routable_text(body))
        return await self._generate_response(
            request, body, prompt, chat=True
        )

    async def completions(self, request: web.Request):
        body = await self._json_body(request)
        if body.get("suffix"):
            return web.json_response(
                {"error": {"message": "'suffix' (insertion) is not "
                                      "supported",
                           "type": "invalid_request_error"}},
                status=400,
            )
        prompt_in = body.get("prompt", "")
        if isinstance(prompt_in, list) and prompt_in and isinstance(
                prompt_in[0], int):
            prompt = list(prompt_in)
            prompt_text = None  # token-array prompt: decode for echo
        elif isinstance(prompt_in, list):
            prompt_text = "".join(prompt_in)
            prompt = self.tokenizer.encode(prompt_text)
        else:
            prompt_text = str(prompt_in)
            prompt = self.tokenizer.encode(prompt_text)
        self.kv_summary.observe_text(routable_text(body))
        return await self._generate_response(
            request, body, prompt, chat=False, prompt_text=prompt_text
        )

    def _qos_admit(self, request: web.Request):
        """Parse the request's QoS class and apply the shed gate.

        Returns ``(priority, spec_off, rejection)``. An unparseable
        ``x-priority`` header is the caller's bug -> 400. Under queue
        pressure (waiting depth at or past ``qos.shed_threshold`` of
        ``--max-queue-len``) non-interactive classes are turned away
        with an honest ``429 + Retry-After`` BEFORE they enter the
        engine queue — never a silent drop, never a 5xx; interactive
        requests are always admitted (the queue-full reject in
        ``Scheduler.add`` remains the hard backstop). Retry-After is
        queue_depth / running-slots (one request per slot-second is
        the deliberately pessimistic service-rate proxy; docs/qos.md).
        """
        raw = request.headers.get(PRIORITY_HEADER)
        if raw is None:
            priority = Priority(self.engine.default_priority)
        else:
            try:
                priority = parse_priority(raw)
            except ValueError as e:
                return None, False, web.json_response(
                    {"error": {"message": str(e),
                               "type": "invalid_request_error"}},
                    status=400,
                )
        spec_off = request.headers.get(SPEC_OFF_HEADER) == "1"
        qos = self.engine.config.qos
        max_queue = self.engine.config.scheduler.max_queue_len
        depth = self.engine.scheduler.num_waiting
        if (priority != Priority.INTERACTIVE
                and depth >= qos.shed_threshold * max_queue):
            retry_after = shed_retry_after_s(
                depth, max(1.0, float(self.engine.scheduler.num_running)))
            self.qos_shed_counts[priority_name(priority)] += 1
            return priority, spec_off, web.json_response(
                {"error": {"message": (
                    f"engine overloaded ({depth} requests waiting); "
                    f"{priority_name(priority)} requests are being "
                    f"shed — retry after {retry_after}s"),
                    "type": "overloaded_error"}},
                status=429, headers={"Retry-After": str(retry_after)},
            )
        return priority, spec_off, None

    async def _generate_response(self, request: web.Request, body: dict,
                                 prompt: List[int], chat: bool,
                                 prompt_text: Optional[str] = None):
        priority, spec_off, rejection = self._qos_admit(request)
        if rejection is not None:
            return rejection
        try:
            sampling = _sampling_from_body(
                body, self.engine.config.scheduler.max_model_len,
                vocab_size=self.engine.config.model.vocab_size,
            )
            self.engine.check_sampling(sampling)
        except (ValueError, TypeError) as e:
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error"}},
                status=400,
            )
        stream_mode = bool(body.get("stream", False))
        created = int(time.time())
        rid = ("chatcmpl-" if chat else "cmpl-") + uuid.uuid4().hex[:16]

        max_prompt = self.engine.config.scheduler.max_model_len - 1
        if len(prompt) > max_prompt:
            return web.json_response(
                {"error": {"message": (
                    f"Prompt is {len(prompt)} tokens; maximum is "
                    f"{max_prompt} (max_model_len "
                    f"{self.engine.config.scheduler.max_model_len})"
                ), "type": "invalid_request_error"}},
                status=400,
            )

        # A request addressed to a registered adapter name runs with
        # that adapter; anything else runs the base model (the router
        # already filtered by served model name).
        requested = body.get("model")
        lora_name = (requested
                     if requested in self.engine.lora_names() else None)
        # Adapter-addressed requests echo the adapter name (vLLM does
        # the same so per-model client accounting stays correct).
        response_model = lora_name or self.model_name

        n = body.get("n")
        try:
            # 0 is invalid, not "default": only JSON null/absent means 1.
            n = 1 if n is None else int(n)
        except (TypeError, ValueError):
            n = -1
        if not 1 <= n <= 16:
            return web.json_response(
                {"error": {"message": "'n' must be an integer in "
                                      "[1, 16]",
                           "type": "invalid_request_error"}},
                status=400,
            )

        # Legacy /v1/completions best_of: generate best_of candidates
        # server-side, return the n with the highest mean token
        # logprob (the OpenAI contract; chat has no best_of).
        best_of = n
        if not chat and body.get("best_of") is not None:
            try:
                best_of = int(body["best_of"])
            except (TypeError, ValueError):
                best_of = -1
            if not n <= best_of <= 16:
                return web.json_response(
                    {"error": {"message": "'best_of' must be an "
                                          "integer in [n, 16]",
                               "type": "invalid_request_error"}},
                    status=400,
                )
            if stream_mode and best_of > n:
                return web.json_response(
                    {"error": {"message": "'best_of' > n cannot be "
                                          "streamed",
                               "type": "invalid_request_error"}},
                    status=400,
                )
        echo = bool(body.get("echo")) and not chat
        if echo and sampling.logprobs:
            return web.json_response(
                {"error": {"message": "'echo' with 'logprobs' (prompt "
                                      "logprobs) is not supported",
                           "type": "invalid_request_error"}},
                status=400,
            )
        # Echo returns the ORIGINAL prompt string when the client sent
        # text (decode(encode(s)) need not round-trip: special-token
        # text, sentencepiece normalization); token-array prompts are
        # decoded.
        echo_text = ""
        if echo:
            echo_text = (prompt_text if prompt_text is not None
                         else self.tokenizer.decode(prompt))

        candidates = best_of
        # Capture BEFORE the internal force below: legacy forms like
        # integer logprobs:0 or bare top_logprobs parse to
        # sampling.logprobs=True while bool(body["logprobs"]) is
        # falsy.
        requested_lp = sampling.logprobs
        if candidates > n and not sampling.logprobs:
            # Candidate ranking needs per-token logprobs internally;
            # the response omits them unless the client asked.
            sampling = dataclasses.replace(sampling, logprobs=True)

        # ``n`` choices = n engine sequences sharing one prompt; the
        # prefix cache makes the shared prompt prefill nearly free
        # after the first, and continuous batching decodes them as
        # ordinary batch rows. A seeded request derives per-choice
        # seeds (seed + i): seeded randomness is a pure function of
        # (seed, position), so identical seeds would make all n
        # choices byte-identical.
        def choice_sampling(i):
            if candidates == 1 or sampling.seed is None:
                return sampling
            return dataclasses.replace(sampling,
                                       seed=sampling.seed + i)

        trace_id = request.headers.get("x-request-id")
        subs = [await self.async_engine.submit(
            prompt, choice_sampling(i), lora_name=lora_name,
            request_id=trace_id, priority=int(priority),
            spec_off=spec_off)
            for i in range(candidates)]

        def legacy_lp(lps):
            """lp_json entries -> the legacy /v1/completions shape."""
            if not lps:
                return None
            return {
                "tokens": [e["token"] for e in lps],
                "token_logprobs": [e["logprob"] for e in lps],
                "top_logprobs": [
                    {t["token"]: t["logprob"]
                     for t in e["top_logprobs"]}
                    for e in lps],
            }

        def lp_json(token_id, entry):
            """One position in OpenAI chat logprobs.content form."""
            slp, tops = entry
            txt = self.tokenizer.decode([token_id])
            return {
                "token": txt, "logprob": slp,
                "bytes": list(txt.encode("utf-8", "replace")),
                "top_logprobs": [
                    {"token": self.tokenizer.decode([tid]),
                     "logprob": tlp}
                    for tid, tlp in tops
                ],
            }

        async def consume_choice(seq_id, stream, on_delta=None,
                                 on_idle=None):
            """Drain one sequence's stream with stop-string scanning.

            Returns (text, n_tokens, finish_reason, lp_content);
            ``on_delta(text, lp_entries)`` (streaming mode) is awaited
            per emitted text delta — lp_entries carries the logprob
            positions consumed since the previous emit (the
            detokenizer may buffer partial UTF-8, so text deltas and
            token positions align only at emit points).
            ``on_idle()`` is awaited whenever the stream has nothing
            more to hand over at once: a decode burst's tokens arrive
            together (AsyncEngine._hand_over), and the streaming side
            puts their frames on the wire in one write there.

            With a tracer, each wake (from ``stream.get()`` returning
            until the stream is empty again) feeds the front's
            ``tokens``, streaming or not: one addition a wake, whatever
            its tokens. Inside a profiler slice the wake is one
            ``server.consume`` event, closed before anything that can
            yield (engine/tracing.py FrontClock).

            Logprob entries are released by CHARACTER accounting: a
            token's entry joins logprobs.content only once its decoded
            text has fully left the stop-string hold-back buffer, so a
            stop hit drops the entries of every (partially) truncated
            token — held-back runs included — and the content list
            always aligns with the returned text.
            """
            decoder = self._delta_decoder()
            scanner = _StopStringScanner(sampling.stop_strings)
            pieces: List[str] = []
            lp_content: List[dict] = []
            lp_queue: List[tuple] = []  # (entry, fed-chars watermark)
            fed_chars = 0
            emitted_chars = 0
            n_tokens = 0
            finish_reason = "stop"
            front = self.async_engine.front
            awake, counted = False, 0

            def release_entries():
                ready = []
                while (lp_queue and lp_queue[0][1] is not None
                       and lp_queue[0][1] <= emitted_chars):
                    ready.append(lp_queue.pop(0)[0])
                lp_content.extend(ready)
                return ready

            def queue_entry(entry, token_text):
                # A token the detokenizer buffered (zero visible
                # chars) can't be char-aligned on its own: its bytes
                # surface inside a LATER feed's text, so it inherits
                # that feed's watermark.
                lp_queue.append(
                    [entry, fed_chars if token_text else None])

            def settle_watermarks():
                for item in lp_queue:
                    if item[1] is None:
                        item[1] = fed_chars

            async def emit(text):
                nonlocal emitted_chars
                emitted_chars += len(text)
                ready = release_entries()
                if not text and not ready:
                    return
                if on_delta is not None:
                    # Streaming: deltas go straight to the wire; never
                    # buffer the whole completion in memory.
                    await on_delta(text, ready)
                elif text:
                    pieces.append(text)

            try:
                while True:
                    if stream.empty():
                        if awake:
                            awake = False
                            front.wake_done(n_tokens - counted)
                            counted = n_tokens
                        if on_idle is not None:
                            await on_idle()
                    out = await stream.get()
                    if front is not None and not awake:
                        awake = True
                        front.consume_begin()
                    if out.new_token is not None:
                        n_tokens += 1
                        token_text = decoder(out.new_token)
                        fed_chars += len(token_text)
                        if token_text:
                            settle_watermarks()
                        if out.logprobs is not None:
                            queue_entry(
                                lp_json(out.new_token, out.logprobs),
                                token_text)
                        await emit(scanner.feed(token_text))
                        if scanner.stopped:
                            # Text-level stop hit: the engine doesn't
                            # know about it, so cut generation here.
                            self.async_engine.abort(seq_id)
                            finish_reason = "stop"
                            break
                    if out.finished:
                        finish_reason = out.finish_reason or "stop"
                        tail = decoder(None, flush=True)
                        fed_chars += len(tail)
                        settle_watermarks()
                        await emit(scanner.feed(tail))
                        await emit(scanner.flush())
                        if scanner.stopped:
                            # The stop landed in the final flush: the
                            # engine's reason (e.g. length) is
                            # superseded by the text-level stop.
                            finish_reason = "stop"
                        break
            finally:
                if awake:
                    front.wake_done(n_tokens - counted)
                self.async_engine.finish_stream(seq_id)
            return ("".join(pieces), n_tokens, finish_reason,
                    lp_content)

        if not stream_mode:
            tasks = [asyncio.ensure_future(consume_choice(sid, stream))
                     for sid, stream in subs]
            try:
                results = await asyncio.gather(*tasks)
            except BaseException:
                # One choice failed or the request was cancelled:
                # cancel the sibling consumers (gather leaves them
                # running) and stop every engine sequence.
                for t in tasks:
                    t.cancel()
                for sid, _ in subs:
                    self.async_engine.abort(sid)
                await asyncio.gather(*tasks, return_exceptions=True)
                raise
            if candidates > n:
                # Rank by mean token logprob; ties keep earlier
                # candidates. The extra candidates' tokens still count
                # toward usage (they were generated).
                def mean_lp(r):
                    lps = r[3]
                    if not lps:
                        return float("-inf")
                    return (sum(e["logprob"] for e in lps)
                            / len(lps))
                ranked = sorted(range(candidates),
                                key=lambda i: -mean_lp(results[i]))
                total_tokens = sum(r[1] for r in results)
                results = [results[i] for i in ranked[:n]]
                if not requested_lp:
                    sampling = dataclasses.replace(
                        sampling, logprobs=False)
            else:
                total_tokens = sum(r[1] for r in results)
            if chat:
                choices = [{
                    "index": i,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": finish,
                    "logprobs": ({"content": lps}
                                 if sampling.logprobs else None),
                } for i, (text, _, finish, lps)
                  in enumerate(results)]
                payload = {
                    "id": rid, "object": "chat.completion",
                    "created": created, "model": response_model,
                    "choices": choices,
                    "usage": _usage(len(prompt), total_tokens),
                }
            else:
                choices = [{
                    "index": i, "text": echo_text + text,
                    "finish_reason": finish,
                    "logprobs": (legacy_lp(lps)
                                 if sampling.logprobs else None),
                } for i, (text, _, finish, lps)
                  in enumerate(results)]
                payload = {
                    "id": rid, "object": "text_completion",
                    "created": created, "model": response_model,
                    "choices": choices,
                    "usage": _usage(len(prompt), total_tokens),
                }
            return web.json_response(payload)

        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        })
        await resp.prepare(request)

        def sse(payload: dict) -> bytes:
            return f"data: {json.dumps(payload)}\n\n".encode()

        def chunk(index: int, delta: Optional[str],
                  finish: Optional[str], first: bool = False,
                  lps=None) -> dict:
            if chat:
                d: Dict[str, Any] = {}
                if first:
                    d["role"] = "assistant"
                if delta:
                    d["content"] = delta
                choice = {"index": index, "delta": d,
                          "finish_reason": finish}
                if sampling.logprobs:
                    choice["logprobs"] = (
                        {"content": lps} if lps else None)
                obj = "chat.completion.chunk"
            else:
                choice = {"index": index, "text": delta or "",
                          "finish_reason": finish}
                if sampling.logprobs:
                    choice["logprobs"] = legacy_lp(lps)
                obj = "text_completion"
            return {"id": rid, "object": obj, "created": created,
                    "model": response_model, "choices": [choice]}

        write_lock = asyncio.Lock()
        completion_tokens = [0] * n
        # Mid-stream crash safety (docs/crash_recovery.md): single-
        # choice plain streams relay the engine's latest resume
        # descriptor as an SSE comment frame — invisible to SSE
        # clients, stripped and remembered by the router for a
        # /v1/resume re-submission if this process dies. Multi-choice,
        # logprobs and echo streams carry wire state one descriptor
        # cannot reconstruct, so they stream without a safety net.
        relay_ckpt = (self.engine.config.checkpoint_interval_tokens > 0
                      and candidates == 1 and not sampling.logprobs
                      and not echo)

        def ckpt_frame(ckpt: dict) -> bytes:
            desc = {
                "version": 1,
                "request_id": trace_id,
                "response_id": rid,
                "created": created,
                "chat": chat,
                "model": response_model,
                "kv_dtype":
                    self.engine.config.cache.resolved_kv_dtype(),
                "sampling": _sampling_to_wire(sampling),
            }
            desc.update(ckpt)
            return f": checkpoint {json.dumps(desc)}\n\n".encode()

        async def stream_choice(index, seq_id, stream):
            # Frames of this choice not yet on the wire. One socket
            # write a frame was over half of what the event loop spent
            # on a token, and the loop is what bounds a full batch of
            # short steps (PERF.md, PR 38): the frames of one hand-over
            # go out together once the stream has no more to give.
            pending: List[bytes] = []
            front = self.async_engine.front

            async def flush():
                if pending:
                    frames = b"".join(pending)
                    pending.clear()
                    async with write_lock:
                        if front is None or front.annotate is None:
                            await resp.write(frames)
                        else:
                            # Inside a slice: a ``server.write`` event
                            # around the write's synchronous part.
                            await front.write(resp.write(frames))

            async def on_delta(text, lps):
                pending.append(sse(chunk(index, text, None, lps=lps)))
                if not relay_ckpt:
                    return
                if front is None:
                    return await relay()
                # Inside the consumer's wake, and the writes can park:
                # its ``server.consume`` event is closed meanwhile.
                front.consume_end()
                try:
                    await relay()
                finally:
                    front.consume_begin()

            async def relay():
                # A resume descriptor follows the frame it describes.
                await flush()
                ckpt = self.engine.take_checkpoint(seq_id)
                if ckpt is None:
                    return
                async with write_lock:
                    await resp.write(ckpt_frame(ckpt))
                    if self.migrate_drain:
                        # Migrate-mode drain (docs/fleet.md): the frame
                        # just written is the full resume state, so cut
                        # the connection abruptly — a clean EOF would
                        # read as a finished stream, while an abrupt
                        # close makes the router resume it on another
                        # replica byte-exactly.
                        tracer = self.engine.tracer
                        if tracer is not None:
                            tracer.event(seq_id, "migrate_ship")
                        # In-band marker: the router's config watcher
                        # polls too slowly to classify this cut as a
                        # migration on its own.
                        await resp.write(b": migrating\n\n")
                        if request.transport is not None:
                            request.transport.close()

            _, n_toks, finish_reason, _ = await consume_choice(
                seq_id, stream, on_delta=on_delta, on_idle=flush)
            completion_tokens[index] = n_toks
            pending.append(sse(chunk(index, None, finish_reason)))
            await flush()

        tasks = [asyncio.ensure_future(stream_choice(i, sid, stream))
                 for i, (sid, stream) in enumerate(subs)]
        try:
            if chat:
                # Under the lock: the stream_choice tasks are already
                # scheduled, and a content delta must never overtake
                # its choice's role chunk.
                async with write_lock:
                    for i in range(n):
                        await resp.write(sse(chunk(i, None, None,
                                                   first=True)))
            elif echo_text:
                async with write_lock:
                    for i in range(n):
                        await resp.write(sse(chunk(i, echo_text,
                                                   None)))
            await asyncio.gather(*tasks)
            stream_opts = body.get("stream_options")
            if (isinstance(stream_opts, dict)
                    and stream_opts.get("include_usage")):
                # OpenAI stream_options.include_usage: one final chunk
                # with empty choices and the aggregate usage.
                await resp.write(sse({
                    "id": rid,
                    "object": ("chat.completion.chunk" if chat
                               else "text_completion"),
                    "created": created, "model": response_model,
                    "choices": [],
                    "usage": _usage(len(prompt),
                                    sum(completion_tokens)),
                }))
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        except BaseException:
            # Disconnect or failure on one choice: cancel the sibling
            # stream tasks BEFORE aborting (abort pops their streams,
            # and a consumer still waiting on a popped stream would
            # block forever), then reap them.
            for t in tasks:
                t.cancel()
            for sid, _ in subs:
                self.async_engine.abort(sid)
            await asyncio.gather(*tasks, return_exceptions=True)
            raise
        return resp

    # -- disaggregated serving (docs/disaggregation.md) ---------------------

    async def disagg_prefill(self, request: web.Request):
        """POST /v1/disagg/prefill: run the prompt through the normal
        chunked-prefill path, ship the committed KV pages to the
        offload tiers (push-on-prefill-done) and return the handoff
        descriptor a decode-role engine resumes from. The first
        sampled token rides the descriptor — it is never recomputed.

        Any engine can serve this (the role gates routing, not
        capability); without an offload tier the descriptor ships zero
        pages and the decode side recomputes (degraded, still exact).
        """
        body = await self._json_body(request)
        messages = body.get("messages")
        chat = isinstance(messages, list)
        if chat:
            prompt = render_chat_prompt(
                self.tokenizer, messages,
                chat_template=self.chat_template)
        else:
            prompt_in = body.get("prompt", "")
            if (isinstance(prompt_in, list) and prompt_in
                    and isinstance(prompt_in[0], int)):
                prompt = list(prompt_in)
            elif isinstance(prompt_in, list):
                prompt = self.tokenizer.encode("".join(prompt_in))
            else:
                prompt = self.tokenizer.encode(str(prompt_in))
        try:
            sampling = _sampling_from_body(
                body, self.engine.config.scheduler.max_model_len,
                vocab_size=self.engine.config.model.vocab_size,
            )
            self.engine.check_sampling(sampling)
        except (ValueError, TypeError) as e:
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error"}},
                status=400,
            )
        if (sampling.guided is not None or sampling.logprobs
                or body.get("model") in self.engine.lora_names()):
            # Monolithic-only features: guided automaton state and
            # first-token logprobs do not transfer across a handoff,
            # and adapter cache salts are process-local. The router
            # never disagg-routes these; a direct caller gets 400.
            return web.json_response(
                {"error": {"message": (
                    "request cannot be disaggregated (guided "
                    "decoding, logprobs and LoRA adapters are "
                    "monolithic-only)"),
                    "type": "invalid_request_error"}},
                status=400,
            )
        max_prompt = self.engine.config.scheduler.max_model_len - 1
        if len(prompt) > max_prompt:
            return web.json_response(
                {"error": {"message": (
                    f"Prompt is {len(prompt)} tokens; maximum is "
                    f"{max_prompt}"),
                    "type": "invalid_request_error"}},
                status=400,
            )
        seq_id, stream = await self.async_engine.submit(
            prompt, sampling, handoff_prefill=True,
            request_id=request.headers.get("x-request-id"))
        try:
            out = await stream.get()
        finally:
            self.async_engine.finish_stream(seq_id)
        if out.new_token is None and out.finish_reason == "abort":
            return web.json_response(
                {"error": {"message":
                           "prefill engine rejected the request"}},
                status=503, headers={"Retry-After": "1"},
            )
        info = (self.engine.take_handoff_info(seq_id)
                or {"num_pages": 0, "kv_bytes": 0, "page_keys": []})
        descriptor = {
            "version": 1,
            "request_id": seq_id,
            "chat": chat,
            "model": self.model_name,
            "token_ids": list(prompt),
            "first_token": out.new_token,
            # Non-None when the first token already finished the
            # request (stop/length): the decode side then emits that
            # single token and never touches its engine.
            "finish_reason": (out.finish_reason
                              if out.finish_reason != "handoff"
                              else None),
            "kv_dtype": self.engine.config.cache.resolved_kv_dtype(),
            "page_keys": info["page_keys"],
            "num_pages": info["num_pages"],
            "kv_bytes": info["kv_bytes"],
            "sampling": _sampling_to_wire(sampling),
        }
        return web.json_response({"descriptor": descriptor})

    async def disagg_handoff(self, request: web.Request):
        """POST /v1/disagg/handoff: resume decoding from a prefill
        engine's descriptor. Emits OpenAI chunks (or one JSON
        completion), starting with the descriptor's first sampled
        token; the engine restores the shipped KV pages (AWAITING_KV)
        or degrades to recompute — the request always completes."""
        body = await self._json_body(request)
        desc = body.get("descriptor")
        if not isinstance(desc, dict):
            return web.json_response(
                {"error": {"message": "'descriptor' object is "
                                      "required"}}, status=400)
        token_ids = desc.get("token_ids")
        first_token = desc.get("first_token")
        if (not isinstance(token_ids, list)
                or not all(isinstance(t, int) for t in token_ids)
                or not isinstance(first_token, int)):
            return web.json_response(
                {"error": {"message": "descriptor missing "
                                      "token_ids/first_token"}},
                status=400)
        my_dtype = self.engine.config.cache.resolved_kv_dtype()
        desc_dtype = desc.get("kv_dtype") or my_dtype
        if desc_dtype != my_dtype:
            # 409: this pod can NEVER restore those pages (tier keys
            # are dtype-namespaced) — the router stops retrying the
            # decode pool and falls back to a monolithic recompute.
            return web.json_response(
                {"error": {"message": (
                    f"handoff KV not restorable here (descriptor "
                    f"kv_dtype {desc_dtype!r}, engine "
                    f"{my_dtype!r})")}},
                status=409)
        try:
            sampling = _sampling_from_wire(desc.get("sampling") or {})
        except Exception as e:
            return web.json_response(
                {"error": {"message":
                           f"bad descriptor sampling: {e}"}},
                status=400)
        chat = bool(desc.get("chat", True))
        stream_mode = bool(body.get("stream", False))
        created = int(time.time())
        rid = (("chatcmpl-" if chat else "cmpl-")
               + uuid.uuid4().hex[:16])
        finish_hint = desc.get("finish_reason")
        seq_id: Optional[str] = None
        stream: Optional[asyncio.Queue] = None
        if not finish_hint and sampling.max_tokens > 1:
            seq_id, stream = await self.async_engine.submit_handoff(
                token_ids, first_token, sampling,
                request_id=request.headers.get("x-request-id"))
        # Peek the first engine event so a rejected submission (queue
        # full) surfaces as a retryable 503, not a stream that aborts
        # after the headers already went out.
        first_out = None
        if stream is not None:
            first_out = await stream.get()
            if (first_out.finished and first_out.new_token is None
                    and first_out.finish_reason == "abort"):
                self.async_engine.finish_stream(seq_id)
                return web.json_response(
                    {"error": {"message":
                               "decode engine rejected the handoff"}},
                    status=503, headers={"Retry-After": "1"},
                )

        async def produce(on_text):
            """Decode + stop-scan the token stream (first token from
            the descriptor, rest from the engine); returns
            (completion_tokens, finish_reason)."""
            decoder = self._delta_decoder()
            scanner = _StopStringScanner(sampling.stop_strings)
            n_tokens = 1
            try:
                await on_text(scanner.feed(decoder(first_token)))
                if scanner.stopped:
                    if seq_id is not None:
                        self.async_engine.abort(seq_id)
                    return n_tokens, "stop"
                if stream is None:
                    tail = scanner.feed(decoder(None, flush=True))
                    await on_text(tail + scanner.flush())
                    return n_tokens, finish_hint or "length"
                out = first_out
                while True:
                    if out.new_token is not None:
                        n_tokens += 1
                        await on_text(
                            scanner.feed(decoder(out.new_token)))
                        if scanner.stopped:
                            self.async_engine.abort(seq_id)
                            return n_tokens, "stop"
                    if out.finished:
                        finish = out.finish_reason or "stop"
                        tail = scanner.feed(decoder(None, flush=True))
                        await on_text(tail + scanner.flush())
                        return (n_tokens,
                                "stop" if scanner.stopped else finish)
                    out = await stream.get()
            finally:
                if seq_id is not None:
                    self.async_engine.finish_stream(seq_id)

        if not stream_mode:
            pieces: List[str] = []

            async def collect(t):
                if t:
                    pieces.append(t)

            try:
                n_tokens, finish = await produce(collect)
            except BaseException:
                if seq_id is not None:
                    self.async_engine.abort(seq_id)
                raise
            text = "".join(pieces)
            if chat:
                choice = {"index": 0,
                          "message": {"role": "assistant",
                                      "content": text},
                          "finish_reason": finish}
                obj = "chat.completion"
            else:
                choice = {"index": 0, "text": text,
                          "finish_reason": finish}
                obj = "text_completion"
            return web.json_response({
                "id": rid, "object": obj, "created": created,
                "model": self.model_name, "choices": [choice],
                "usage": _usage(len(token_ids), n_tokens),
            })

        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        })
        await resp.prepare(request)

        def sse(payload: dict) -> bytes:
            return f"data: {json.dumps(payload)}\n\n".encode()

        def chunk(delta: Optional[str], finish: Optional[str],
                  first: bool = False) -> dict:
            if chat:
                d: Dict[str, Any] = {}
                if first:
                    d["role"] = "assistant"
                if delta:
                    d["content"] = delta
                choice = {"index": 0, "delta": d,
                          "finish_reason": finish}
                obj = "chat.completion.chunk"
            else:
                choice = {"index": 0, "text": delta or "",
                          "finish_reason": finish}
                obj = "text_completion"
            return {"id": rid, "object": obj, "created": created,
                    "model": self.model_name, "choices": [choice]}

        async def emit(t):
            if t:
                await resp.write(sse(chunk(t, None)))

        try:
            if chat:
                await resp.write(sse(chunk(None, None, first=True)))
            _, finish = await produce(emit)
            await resp.write(sse(chunk(None, finish)))
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        except BaseException:
            if seq_id is not None:
                self.async_engine.abort(seq_id)
            raise
        return resp

    async def resume(self, request: web.Request):
        """POST /v1/resume: continue a stream whose engine died
        mid-generation (docs/crash_recovery.md). The body carries the
        checkpoint descriptor the dead engine attached to its SSE
        stream plus ``delivered_text_chars`` — how much content text
        the router already forwarded to the client. The journaled
        context parks in ``AWAITING_KV`` (restore the checkpointed
        pages, or recompute from the token journal on a miss); the
        handler replays the journal through the same detokenizer +
        stop-scanner pipeline the dead engine ran, skips the
        already-delivered characters, and streams the rest — for
        greedy sampling the concatenated client stream is
        byte-identical to an uninterrupted run."""
        body = await self._json_body(request)
        desc = body.get("descriptor")
        if not isinstance(desc, dict):
            return web.json_response(
                {"error": {"message": "'descriptor' object is "
                                      "required"}}, status=400)
        token_ids = desc.get("tokens")
        output_tokens = desc.get("output_tokens")
        if (not isinstance(token_ids, list) or not token_ids
                or not all(isinstance(t, int) for t in token_ids)
                or not isinstance(output_tokens, int)
                or not 0 < output_tokens < len(token_ids)):
            return web.json_response(
                {"error": {"message": "descriptor missing "
                                      "tokens/output_tokens"}},
                status=400)
        my_dtype = self.engine.config.cache.resolved_kv_dtype()
        desc_dtype = desc.get("kv_dtype") or my_dtype
        if desc_dtype != my_dtype:
            # 409: this pod can NEVER restore those pages (tier keys
            # are dtype-namespaced) — the router must pick a
            # same-dtype replacement or accept a recompute elsewhere.
            return web.json_response(
                {"error": {"message": (
                    f"checkpoint KV not restorable here (descriptor "
                    f"kv_dtype {desc_dtype!r}, engine "
                    f"{my_dtype!r})")}},
                status=409)
        try:
            sampling = _sampling_from_wire(desc.get("sampling") or {})
        except Exception as e:
            return web.json_response(
                {"error": {"message":
                           f"bad descriptor sampling: {e}"}},
                status=400)
        if sampling.guided is not None:
            return web.json_response(
                {"error": {"message": "guided streams cannot be "
                                      "resumed"}}, status=400)
        try:
            delivered = int(body.get("delivered_text_chars") or 0)
        except (TypeError, ValueError):
            delivered = -1
        if delivered < 0:
            return web.json_response(
                {"error": {"message": "delivered_text_chars must be "
                                      "a non-negative integer"}},
                status=400)
        chat = bool(desc.get("chat", True))
        stream_mode = bool(body.get("stream", True))
        # The original stream's identity: resumed chunks must carry
        # the SAME id/created/model for the concatenated stream to be
        # byte-identical to an uninterrupted run.
        rid = (desc.get("response_id")
               or ("chatcmpl-" if chat else "cmpl-")
               + uuid.uuid4().hex[:16])
        created = int(desc.get("created") or time.time())
        response_model = desc.get("model") or self.model_name
        prompt_len = len(token_ids) - output_tokens
        output_ids = token_ids[prompt_len:]

        seq_id, stream = await self.async_engine.submit_resume(
            token_ids, output_tokens, sampling,
            request_id=request.headers.get("x-request-id"))
        # Peek the first engine event so a rejected submission (queue
        # full / draining race) surfaces as a retryable 503, not a
        # stream that aborts after the headers went out.
        first_out = await stream.get()
        if (first_out.finished and first_out.new_token is None
                and first_out.finish_reason == "abort"):
            self.async_engine.finish_stream(seq_id)
            return web.json_response(
                {"error": {"message":
                           "engine rejected the resume"}},
                status=503, headers={"Retry-After": "1"},
            )

        async def produce(on_text):
            """Replay the journal through a fresh detokenizer + stop
            scanner (rebuilding the dead engine's exact text state),
            skip the already-delivered chars, then stream new tokens.
            Returns (completion_tokens, finish_reason)."""
            decoder = self._delta_decoder()
            scanner = _StopStringScanner(sampling.stop_strings)
            n_tokens = output_tokens
            skip = delivered

            async def put(text):
                nonlocal skip
                if not text:
                    return
                if skip:
                    if len(text) <= skip:
                        skip -= len(text)
                        return
                    text = text[skip:]
                    skip = 0
                await on_text(text)

            try:
                for tok in output_ids:
                    await put(scanner.feed(decoder(tok)))
                    if scanner.stopped:
                        self.async_engine.abort(seq_id)
                        return n_tokens, "stop"
                out = first_out
                while True:
                    if out.new_token is not None:
                        n_tokens += 1
                        await put(scanner.feed(decoder(out.new_token)))
                        if scanner.stopped:
                            self.async_engine.abort(seq_id)
                            return n_tokens, "stop"
                    if out.finished:
                        finish = out.finish_reason or "stop"
                        tail = scanner.feed(decoder(None, flush=True))
                        await put(tail + scanner.flush())
                        return (n_tokens,
                                "stop" if scanner.stopped else finish)
                    out = await stream.get()
            finally:
                self.async_engine.finish_stream(seq_id)

        if not stream_mode:
            pieces: List[str] = []

            async def collect(t):
                if t:
                    pieces.append(t)

            try:
                n_tokens, finish = await produce(collect)
            except BaseException:
                self.async_engine.abort(seq_id)
                raise
            text = "".join(pieces)
            if chat:
                choice = {"index": 0,
                          "message": {"role": "assistant",
                                      "content": text},
                          "finish_reason": finish}
                obj = "chat.completion"
            else:
                choice = {"index": 0, "text": text,
                          "finish_reason": finish}
                obj = "text_completion"
            return web.json_response({
                "id": rid, "object": obj, "created": created,
                "model": response_model, "choices": [choice],
                "usage": _usage(prompt_len, n_tokens),
            })

        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        })
        await resp.prepare(request)

        def sse(payload: dict) -> bytes:
            return f"data: {json.dumps(payload)}\n\n".encode()

        def chunk(delta: Optional[str],
                  finish: Optional[str]) -> dict:
            # Shape-identical to the monolithic stream's chunk() (no
            # role chunk — the dead engine already delivered it).
            if chat:
                d: Dict[str, Any] = {}
                if delta:
                    d["content"] = delta
                choice = {"index": 0, "delta": d,
                          "finish_reason": finish}
                obj = "chat.completion.chunk"
            else:
                choice = {"index": 0, "text": delta or "",
                          "finish_reason": finish}
                obj = "text_completion"
            return {"id": rid, "object": obj, "created": created,
                    "model": response_model, "choices": [choice]}

        def ckpt_frame(ckpt: dict) -> bytes:
            # Keep checkpointing on the resumed leg too, so a second
            # crash resumes again (the descriptor identity fields are
            # carried forward from the original stream).
            new_desc = {
                "version": 1,
                "request_id": desc.get("request_id"),
                "response_id": rid,
                "created": created,
                "chat": chat,
                "model": response_model,
                "kv_dtype": my_dtype,
                "sampling": _sampling_to_wire(sampling),
            }
            new_desc.update(ckpt)
            return f": checkpoint {json.dumps(new_desc)}\n\n".encode()

        async def emit(t):
            if t:
                await resp.write(sse(chunk(t, None)))
            ckpt = self.engine.take_checkpoint(seq_id)
            if ckpt is not None:
                await resp.write(ckpt_frame(ckpt))
                if self.migrate_drain:
                    # Migrate-mode drain cuts resumed legs too — a
                    # stream can hop replicas more than once during a
                    # rolling upgrade (docs/fleet.md).
                    tracer = self.engine.tracer
                    if tracer is not None:
                        tracer.event(seq_id, "migrate_ship")
                    # Same in-band migration marker as the original
                    # stream leg.
                    await resp.write(b": migrating\n\n")
                    if request.transport is not None:
                        request.transport.close()

        try:
            _, finish = await produce(emit)
            await resp.write(sse(chunk(None, finish)))
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
        except BaseException:
            self.async_engine.abort(seq_id)
            raise
        return resp

    async def embeddings(self, request: web.Request):
        """OpenAI /v1/embeddings over the served model's hidden states."""
        from production_stack_tpu.engine.embeddings import (
            parse_embedding_input,
        )
        body = await self._json_body(request)
        try:
            token_lists = parse_embedding_input(
                body.get("input"), self.tokenizer,
                max_len=self.engine.config.scheduler.max_model_len,
            )
        except ValueError as e:
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error"}},
                status=400,
            )
        try:
            await self._ensure_embedder()
        except NotImplementedError as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=501,
            )
        # One embed batch on-device at a time; compute off the event
        # loop so token streaming stays live.
        async with self._embed_lock:
            vectors = await asyncio.to_thread(
                self._embedder.embed_batch, token_lists
            )
        n_tokens = sum(len(t) for t in token_lists)
        return web.json_response({
            "object": "list",
            "model": self.model_name,
            "data": [
                {"object": "embedding", "index": i,
                 "embedding": vec.tolist()}
                for i, vec in enumerate(vectors)
            ],
            "usage": {"prompt_tokens": n_tokens,
                      "total_tokens": n_tokens},
        })

    async def _ensure_embedder(self):
        from production_stack_tpu.engine.embeddings import Embedder
        if self._embedder is None:
            if self.engine.runner.bridge is not None:
                # Multihost: lazy construction would launch a
                # collective program workers never mirror (they only
                # enter embedders built at startup by main()), so the
                # slice would deadlock on the first request.
                raise NotImplementedError(
                    "embeddings unavailable: this multihost slice was "
                    "started without an embedder (unsupported "
                    "architecture or quantized weights)"
                )
            self._embedder = Embedder(
                self.engine.config.model,
                self.engine.runner.params,
                max_len=self.engine.config.scheduler.max_model_len,
                pooling=self.pooling,
            )
            self.engine.runner.embedder = self._embedder
        return self._embedder

    async def _pair_scores(self, query: str, documents: List[str]):
        """Bi-encoder relevance: cosine of pooled embeddings (the
        engine-side backend for the router's /score and /rerank proxy
        paths, reference main_router.py:42-84)."""
        import numpy as np
        embedder = await self._ensure_embedder()
        max_len = self.engine.config.scheduler.max_model_len
        token_lists = [self.tokenizer.encode(query)[:max_len]] + [
            self.tokenizer.encode(d)[:max_len] for d in documents
        ]
        for ids in token_lists:
            if not ids:
                raise ValueError("texts must not be empty")
        async with self._embed_lock:
            vectors = await asyncio.to_thread(
                embedder.embed_batch, token_lists
            )
        q_vec, d_vecs = vectors[0], vectors[1:]
        # Embeddings are L2-normalized: dot == cosine.
        scores = d_vecs @ q_vec
        n_tokens = sum(len(t) for t in token_lists)
        return [float(s) for s in scores], n_tokens

    async def score(self, request: web.Request):
        """/v1/score: relevance of text_2 document(s) to text_1."""
        body = await self._json_body(request)
        text_1 = body.get("text_1") or body.get("query")
        text_2 = body.get("text_2") or body.get("documents")
        if not isinstance(text_1, str) or text_2 is None:
            return web.json_response(
                {"error": {"message": "'text_1' (string) and 'text_2' "
                                      "(string or list) are required"}},
                status=400,
            )
        docs = [text_2] if isinstance(text_2, str) else list(text_2)
        try:
            scores, n_tokens = await self._pair_scores(text_1, docs)
        except ValueError as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=400)
        except NotImplementedError as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=501)
        return web.json_response({
            "id": "score-" + uuid.uuid4().hex[:16],
            "object": "list",
            "model": self.model_name,
            "data": [
                {"object": "score", "index": i, "score": s}
                for i, s in enumerate(scores)
            ],
            "usage": {"prompt_tokens": n_tokens,
                      "total_tokens": n_tokens},
        })

    async def rerank(self, request: web.Request):
        """/v1/rerank: order documents by relevance to the query."""
        body = await self._json_body(request)
        query = body.get("query")
        documents = body.get("documents")
        if not isinstance(query, str) or not isinstance(documents, list):
            return web.json_response(
                {"error": {"message": "'query' (string) and 'documents'"
                                      " (list of strings) are required"}},
                status=400,
            )
        try:
            scores, n_tokens = await self._pair_scores(
                query, [str(d) for d in documents])
        except ValueError as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=400)
        except NotImplementedError as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=501)
        order = sorted(range(len(scores)), key=lambda i: -scores[i])
        top_n = body.get("top_n")
        if isinstance(top_n, int) and top_n > 0:
            order = order[:top_n]
        return web.json_response({
            "id": "rerank-" + uuid.uuid4().hex[:16],
            "model": self.model_name,
            "usage": {"total_tokens": n_tokens},
            "results": [
                {
                    "index": i,
                    "document": {"text": documents[i]},
                    "relevance_score": scores[i],
                }
                for i in order
            ],
        })

    async def models(self, request: web.Request):
        created = int(self.async_engine.uptime_start)
        data = [{
            "id": self.model_name, "object": "model",
            "created": created,
            "owned_by": "production-stack-tpu",
        }]
        # LoRA adapters are addressable models (vLLM behavior).
        for name in self.engine.lora_names():
            data.append({
                "id": name, "object": "model", "created": created,
                "owned_by": "production-stack-tpu",
                "parent": self.model_name,
            })
        return web.json_response({"object": "list", "data": data})

    async def health(self, request: web.Request):
        # ``role`` feeds the router's role-aware discovery
        # (router/service_discovery.py probes it; absent on older
        # engines -> treated as "both"). ``draining`` makes the active
        # health prober fail the endpoint out of routing while its
        # in-flight streams finish (docs/fleet.md); the fleet manager
        # polls ``active_requests`` to know when a SIGTERM is loss-free.
        # getattr: older configs (and test stubs) predate the watchdog.
        def reply(status: str, http_status: int = 200, **extra):
            return web.json_response({
                "status": status, **extra,
                "role": self.engine.config.engine_role,
                "draining": self.draining,
                "active_requests": self._active_generations,
                "build_id": self.build_id,
            }, status=http_status)

        wd = getattr(self.engine.config, "step_watchdog_s", 0.0)
        if wd > 0:
            stuck = self.async_engine.current_step_s()
            if stuck > wd:
                # A wedged device step stalls every queued request; a
                # 503 makes the router's prober rotate the replica out
                # (docs/crash_recovery.md).
                self._note_watchdog_trip(stuck)
                return reply("watchdog", 503,
                             stuck_step_s=round(stuck, 3))
            self._watchdog_tripped = False
        failures = self.async_engine.consecutive_step_failures
        if failures >= STEP_FAILURE_LIMIT:
            # Device programs keep raising: same remedy.
            return reply("step_failures", 503,
                         consecutive_step_failures=failures)
        return reply("ok")

    def _note_watchdog_trip(self, stuck: float) -> None:
        if self._watchdog_tripped:
            return
        self._watchdog_tripped = True
        logger.error("Step watchdog tripped: step running for %.3fs "
                     "(limit %.3fs); /health now 503",
                     stuck, self.engine.config.step_watchdog_s)
        tracer = self.engine.tracer
        if tracer is not None:
            # Synthetic span (profiler-capture pattern) so the trip is
            # visible in traceview next to the requests it stalled.
            sid = f"watchdog-{uuid.uuid4().hex[:12]}"
            tracer.start(sid, prompt_tokens=0)
            tracer.event(sid, "watchdog_trip", step_s=round(stuck, 3))
            tracer.finish(sid, reason="watchdog")

    # -- zero-loss drain (docs/fleet.md) ------------------------------------

    def _drain_rejection(self) -> Optional[web.Response]:
        if not self.draining:
            return None
        return web.json_response(
            {"error": {"message": "engine is draining; retry on "
                                  "another replica"}},
            status=503, headers={"Retry-After": "1"},
        )

    def _guarded(self, handler):
        """Wrap a generation handler: reject while draining, count the
        request as in-flight otherwise. The counter — not the engine's
        queue depth alone — gates drain-exit, because a stream keeps
        writing after its last engine step."""
        async def wrapped(request: web.Request):
            rejection = self._drain_rejection()
            if rejection is not None:
                return rejection
            self._active_generations += 1
            try:
                return await handler(request)
            finally:
                self._active_generations -= 1
        return wrapped

    async def drain(self, request: web.Request):
        """POST /drain: flip to DRAINING. New admissions are rejected
        with 503+Retry-After (the router retries them on another
        replica); everything already admitted finishes normally. With
        ``{"exit": true}`` the process exits clean once idle — the path
        the fleet manager uses so it never has to SIGKILL an engine
        that still has running sequences."""
        body: dict = {}
        if request.can_read_body:
            try:
                body = await request.json()
            except Exception:
                body = {}
        already = self.draining
        self.draining = True
        if body.get("migrate"):
            # Migrate-mode drain (docs/fleet.md): cut checkpointed
            # streams at their next checkpoint frame so the router
            # resumes them elsewhere instead of waiting them out.
            self.migrate_drain = True
        if not already:
            logger.info("Drain requested: rejecting new admissions, "
                        "%d generation request(s) in flight",
                        self._active_generations)
        if body.get("exit") and self._drain_exit_task is None:
            self._drain_exit_task = asyncio.ensure_future(
                self._exit_when_idle())
        stats = self.engine.stats()
        return web.json_response({
            "status": "draining",
            "active_requests": self._active_generations,
            "running": stats["num_requests_running"],
            "waiting": stats["num_requests_waiting"],
        })

    async def _exit_when_idle(self) -> None:
        """Wait for every in-flight generation to finish, then stop the
        process via SIGTERM (aiohttp's run_app shuts down gracefully on
        it). --drain-exit-timeout-s bounds the wait; 0 waits forever —
        the fleet manager applies its own deadline instead."""
        import os
        import signal
        deadline = (time.time() + self.drain_exit_timeout_s
                    if self.drain_exit_timeout_s > 0 else None)
        while (self._active_generations > 0
               or self.engine.has_work()):
            if deadline is not None and time.time() >= deadline:
                logger.warning(
                    "Drain exit timeout (%.1fs) with %d request(s) "
                    "still in flight; exiting anyway",
                    self.drain_exit_timeout_s, self._active_generations)
                break
            await asyncio.sleep(0.05)
        logger.info("Drain complete; exiting")
        os.kill(os.getpid(), signal.SIGTERM)

    async def profiler_start(self, request: web.Request):
        """Start a JAX profiler trace (view in TensorBoard/XProf).

        SURVEY.md §5: the reference has no tracing subsystem; the TPU
        engine adds profiler hooks as the aux-parity extension.
        """
        import jax
        trace_dir = request.query.get(
            "dir", self.profile_dir or "/tmp/jax-trace")
        if self._profiling:
            return web.json_response(
                {"error": {"message": "profiler already running"}},
                status=409,
            )
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=slice_options())
        self._profiling = "running"
        tracer = self.engine.tracer
        if tracer is not None:
            self.async_engine.stream_annotation = tracer.annotate
            sid = f"prof-{uuid.uuid4().hex[:12]}"
            self._profiler_span_id = sid
            tracer.start(
                sid,
                request_id=request.headers.get("x-request-id"),
                prompt_tokens=0)
            tracer.event(sid, "profiler_start", dir=trace_dir)
        return web.json_response({"status": "started",
                                  "dir": trace_dir})

    async def profiler_stop(self, request: web.Request):
        """Stop the slice; answers once the trace is on disk. Writing
        it takes seconds, so it runs off the event loop: streams keep
        flowing and requests keep being admitted meanwhile, and a
        start or a second stop that arrives then gets its 409."""
        import jax
        if self._profiling != "running":
            return web.json_response(
                {"error": {"message": "profiler not running"}},
                status=409,
            )
        self._profiling = "stopping"
        self.async_engine.stream_annotation = None
        try:
            await asyncio.get_running_loop().run_in_executor(
                None, jax.profiler.stop_trace)
        finally:
            self._profiling = None
        tracer = self.engine.tracer
        sid, self._profiler_span_id = self._profiler_span_id, None
        if tracer is not None and sid is not None:
            tracer.event(sid, "profiler_stop")
            tracer.finish(sid, reason="profiler",
                          arrival_ts=time.time())
        return web.json_response({"status": "stopped"})

    async def debug_trace(self, request: web.Request):
        """GET /debug/trace/{request_id}: the flight recorder's event
        timeline for one request, looked up by router x-request-id or
        engine seq id (docs/observability.md)."""
        tracer = self.engine.tracer
        if tracer is None:
            return web.json_response(
                {"error": {"message": "tracing disabled"}}, status=404)
        found = tracer.lookup(request.match_info["request_id"])
        if found is None:
            return web.json_response(
                {"error": {"message": "no trace for that id (expired "
                                      "from the ring or never seen)"}},
                status=404)
        return web.json_response(found)

    async def debug_steps(self, request: web.Request):
        """GET /debug/steps[?limit=N]: most recent per-step flight
        recorder records, oldest first."""
        tracer = self.engine.tracer
        if tracer is None:
            return web.json_response(
                {"error": {"message": "tracing disabled"}}, status=404)
        try:
            limit = int(request.query.get("limit", "100"))
        except ValueError:
            return web.json_response(
                {"error": {"message": "limit must be an integer"}},
                status=400)
        return web.json_response(
            {"steps": tracer.recent_steps(limit=limit)})

    async def debug_compiles(self, request: web.Request):
        """GET /debug/compiles[?limit=N]: the device performance
        observatory's compile ledger — per-kind event/seconds
        counters and the seconds by part of a load, how the
        persistent cache answered, live executable-cache sizes and
        the bounded ring of recent compiles with their (rows, W)
        shape keys and each one's split (docs/observability.md)."""
        obs = getattr(self.engine.runner, "observatory", None)
        if obs is None:
            return web.json_response(
                {"error": {"message": "observatory disabled"}},
                status=404)
        try:
            limit = int(request.query.get("limit", "32"))
        except ValueError:
            return web.json_response(
                {"error": {"message": "limit must be an integer"}},
                status=400)
        return web.json_response(obs.compile_report(limit=limit))

    async def debug_memory(self, request: web.Request):
        """GET /debug/memory: the observatory's HBM ledger — analytic
        per-category breakdown (always available) plus
        device.memory_stats() where the backend supports it."""
        obs = getattr(self.engine.runner, "observatory", None)
        if obs is None:
            return web.json_response(
                {"error": {"message": "observatory disabled"}},
                status=404)
        return web.json_response(obs.memory_report())

    async def version(self, request: web.Request):
        # The device is named by the process that holds it, so a smoke
        # or a benchmark never infers it from logs (chip_smoke.py);
        # kv_writes says which decode burst the runner compiled, and
        # conv_tails, for a family whose recurrent layers hold a
        # convolution's tail, whether that burst carries the tails
        # ("burst") or each step goes to the slot pool ("step").
        import jax
        devices = jax.devices()
        runner = self.engine.runner
        obs = getattr(runner, "observatory", None)
        config = self.engine.config
        deferred = config.scheduler.deferred_kv_writes
        conv_tails = ({"conv_tails": "burst" if deferred else "step"}
                      if config.model.family.conv_tail else {})
        # What a sequence holds of the state pool, over all recurrent
        # layers, for a family that keeps such a state.
        state = ({"state_bytes_per_sequence":
                  config.model.recurrent_state_bytes()}
                 if config.model.has_recurrent_state else {})
        # What a page holds: K and V planes, or one latent a token an
        # entry stored once; and the bytes a committed token costs.
        kv = {"kv": ("latent" if config.model.has_latent_cache
                     else "pair"),
              "kv_bytes_per_token":
                  config.cache.kv_bytes_per_token(config.model)}
        # The tiles the routed experts' two grouped products take at
        # this model's widths, and the grid steps a visit (ops/moe.py
        # expert_tiles: static a shape, so stated once, not counted);
        # and the rows that go around and through those products a
        # chunk (expert_room: null where every row goes through), for
        # the burst and for each shape a prefill step is compiled at.
        import jax.numpy as jnp
        from production_stack_tpu.engine.model_runner import prefill_shapes
        from production_stack_tpu.ops.moe import (
            expert_layer_tiles,
            expert_room,
        )
        model = config.model

        def room(rows: int, tokens: int):
            return expert_room(rows * tokens, model.num_experts_per_tok,
                               model.num_experts, model.router_width)
        experts = ({
            "expert_tiles": expert_layer_tiles(
                model.hidden_size, model.moe_intermediate_size,
                jnp.dtype(model.jax_dtype).itemsize),
            "expert_room": {
                "burst": room(runner.decode_width,
                              model.block_length
                              or (2 if config.scheduler.draft_module
                                  else 1)),
                "prefill": {f"{rows}x{tokens}": room(rows, tokens)
                            for rows, tokens in prefill_shapes(
                                runner.prefill_width,
                                config.scheduler.prefill_chunk_size)}}}
            if model.num_experts else {})
        # The kinds of layer of a family whose attention layers are not
        # all alike, and the window of the windowed ones, whose K/V is a
        # ring in the state pool and never pages.
        window = ({"layer_types": list(model.layer_types),
                   "sliding_window": model.sliding_window}
                  if model.sliding_window else {})
        return web.json_response({
            "version": __version__,
            "build_id": self.build_id,
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "num_devices": len(devices),
            "attention_impl": (obs.attention_impls()
                               if obs is not None else {}),
            "kv_writes": "deferred" if deferred else "eager",
            # How a run of tokens a row (a prefill chunk, a burst's
            # tail) goes to its pages: page by page in the plane's own
            # layout ("in_place": per-layer plain planes,
            # ops/attention.write_run_to_pages), or by the scatter one
            # token takes everywhere ("scatter": the stacked cache,
            # int8 pages).
            "page_writes": (
                "in_place" if runner.cache_layout == "per_layer"
                and not runner.kv_quantized else "scatter"),
            # What proposes drafts: the model's own prediction module
            # inside the burst ("module", with its depth and the most
            # tokens an iteration commits), the prompt-lookup proposer
            # ("prompt_lookup"), or nothing.
            "drafts": (
                {"by": "module",
                 "layers": config.model.num_nextn_predict_layers,
                 "tokens_per_iteration": 2}
                if config.scheduler.draft_module else
                {"by": "prompt_lookup",
                 "k": config.scheduler.speculative_k}
                if config.scheduler.speculative_k > 0 else
                {"by": "none"}),
            **conv_tails,
            **state,
            **experts,
            **window,
            "family": config.model.architecture,
            **kv,
            # A family that generates by diffusion over blocks: the
            # block, the defaults a request may replace, and what a
            # burst is planned at (docs/block_diffusion.md).
            **({"block_diffusion": {
                "block_length": model.block_length,
                "mask_token_id": model.mask_token_id,
                "denoising_steps": model.diffusion_steps,
                "remasking_strategy": model.diffusion_remasking,
                "confidence_threshold":
                    model.diffusion_confidence_threshold,
                "burst_passes": config.scheduler.decode_steps,
                "burst_blocks": runner.burst_blocks}}
               if model.block_length else {}),
            # The start by span, from the process's first instant to
            # the listener, on the unix clock (engine/tracing.py
            # STARTUP_SPANS; docs/observability.md, "Why is a start
            # slow?").
            "startup": self.startup.to_dict(),
        })

    async def kv_summary_handler(self, request: web.Request):
        """Cluster KV economy (docs/kv_economy.md): the engine's live
        KV state for the router's KVStateAwarePolicy — hot prefix
        chains (text-domain blake2b, decayed hit counts), free-page
        headroom, and the KV storage dtype. Served from host-side
        tracker/counter state only; never touches the device."""
        cm = self.engine.cache_manager
        return web.json_response({
            "hot_chains": [[h, v]
                           for h, v in self.kv_summary.snapshot()],
            "free_pages": cm.num_free_pages,
            "total_pages": cm.config.num_pages - 1,
            "kv_dtype": self.engine.config.cache.resolved_kv_dtype(),
            "top_k": self.kv_summary.top_k,
        })

    async def metrics(self, request: web.Request):
        stats = self.engine.stats()
        lines = []
        for name, value in (
            ("vllm:num_requests_running",
             stats["num_requests_running"]),
            ("vllm:num_requests_waiting",
             stats["num_requests_waiting"]),
            ("vllm:gpu_cache_usage_perc",
             stats["gpu_cache_usage_perc"]),
            ("vllm:gpu_prefix_cache_hit_rate",
             stats["gpu_prefix_cache_hit_rate"]),
        ):
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {float(value)}")
        lines.append("# TYPE vllm:num_preemptions_total counter")
        lines.append("vllm:num_preemptions_total "
                     f"{float(stats['num_preemptions_total'])}")
        # The full prefill steps that plan_step's chains put before a
        # burst, the prefill steps run at the half width
        # (model_runner.prefill_shape), then the hybrid models' figures
        # (docs/observability.md): the recurrent-state
        # pool beside the pages, prefix hits declined for want of a
        # state, and the held experts' load over the last decode
        # dispatch. Zeros for a model with neither.
        for name, kind in (
                ("vllm:engine_prefill_chained_steps_total", "counter"),
                ("vllm:engine_prefill_narrow_steps_total", "counter"),
                ("vllm:engine_state_slots_used", "gauge"),
                ("vllm:engine_state_slots_total", "gauge"),
                ("vllm:engine_prefix_declined_tokens_total", "counter"),
                ("vllm:engine_moe_tokens_per_expert_max", "gauge"),
                ("vllm:engine_moe_tokens_per_expert_mean", "gauge"),
                ("vllm:engine_moe_held_choice_share", "gauge"),
                ("vllm:engine_moe_zero_choice_share", "gauge")):
            lines.append(f"# TYPE {name} {kind}")
            lines.append(f"{name} {float(stats[name[5:]])}")
        # KV quantization telemetry: page budget after any int8
        # expansion, worst-case KV bytes written per decode step, and
        # the storage dtype as a labeled one-hot gauge so dashboards
        # can group pods by KV format.
        lines.append("# TYPE vllm:engine_kv_cache_page_capacity gauge")
        lines.append("vllm:engine_kv_cache_page_capacity "
                     f"{float(stats['engine_kv_cache_page_capacity'])}")
        lines.append("# TYPE vllm:engine_kv_bytes_per_decode_step gauge")
        lines.append(
            "vllm:engine_kv_bytes_per_decode_step "
            f"{float(stats['engine_kv_bytes_per_decode_step'])}")
        kv_dtype = self.engine.config.cache.resolved_kv_dtype()
        lines.append("# TYPE vllm:engine_kv_cache_dtype gauge")
        lines.append("vllm:engine_kv_cache_dtype{kv_dtype=\""
                     f"{kv_dtype}\"}} 1.0")
        # Disaggregated serving (docs/disaggregation.md): per-role
        # request counters, KV bytes shipped on handoffs, and the
        # AWAITING_KV admission depth.
        lines.append("# TYPE vllm:disagg_prefill_requests_total "
                     "counter")
        lines.append("vllm:disagg_prefill_requests_total "
                     f"{float(stats['disagg_prefill_requests_total'])}")
        lines.append("# TYPE vllm:disagg_decode_requests_total "
                     "counter")
        lines.append("vllm:disagg_decode_requests_total "
                     f"{float(stats['disagg_decode_requests_total'])}")
        lines.append("# TYPE vllm:disagg_kv_bytes_shipped_total "
                     "counter")
        lines.append("vllm:disagg_kv_bytes_shipped_total "
                     f"{float(stats['disagg_kv_bytes_shipped_total'])}")
        lines.append("# TYPE vllm:disagg_awaiting_kv_requests gauge")
        lines.append("vllm:disagg_awaiting_kv_requests "
                     f"{float(stats['disagg_awaiting_kv_requests'])}")
        # Cluster KV economy (docs/kv_economy.md): summary breadth and
        # headroom mirror GET /kv/summary; the cluster counters come
        # from the remote-tier client (0 until an offload remote is
        # configured — the scrape surface stays stable either way).
        cm = self.engine.cache_manager
        lines.append("# TYPE vllm:kv_summary_hot_chains gauge")
        lines.append("vllm:kv_summary_hot_chains "
                     f"{float(self.kv_summary.hot_count())}")
        lines.append("# TYPE vllm:kv_free_page_headroom gauge")
        lines.append("vllm:kv_free_page_headroom "
                     f"{float(cm.num_free_pages)}")
        lines.append("# TYPE vllm:kv_total_pages gauge")
        lines.append("vllm:kv_total_pages "
                     f"{float(cm.config.num_pages - 1)}")
        ostats = (self.engine.offload.stats()
                  if self.engine.offload is not None else {})
        lines.append("# TYPE vllm:kv_cluster_hits_total counter")
        lines.append("vllm:kv_cluster_hits_total "
                     f"{float(ostats.get('cluster_hits', 0.0))}")
        lines.append("# TYPE vllm:kv_cluster_misses_total counter")
        lines.append("vllm:kv_cluster_misses_total "
                     f"{float(ostats.get('cluster_misses', 0.0))}")
        lines.append("# TYPE vllm:kv_cluster_admissions_total counter")
        lines.append("vllm:kv_cluster_admissions_total "
                     f"{float(ostats.get('cluster_admissions', 0.0))}")
        lines.append("# TYPE vllm:kv_cluster_rejections_total counter")
        lines.append("vllm:kv_cluster_rejections_total "
                     f"{float(ostats.get('cluster_rejections', 0.0))}")
        # Zero-loss drain (docs/fleet.md): 1 while new admissions are
        # rejected and in-flight sequences finish.
        lines.append("# TYPE vllm:engine_draining gauge")
        lines.append(f"vllm:engine_draining {float(self.draining)}")
        # Self-tuning (docs/autotuning.md): controllers allowed to
        # act, latched guardrail freezes, live knob values, and
        # cumulative decision counts (applied + shadow).
        at = self.autotuner
        lines.append("# TYPE vllm:autotune_active_controllers gauge")
        lines.append("vllm:autotune_active_controllers "
                     f"{float(at.active_count())}")
        lines.append("# TYPE vllm:autotune_frozen gauge")
        for name, frozen in sorted(at.frozen_flags().items()):
            lines.append("vllm:autotune_frozen{controller=\""
                         f"{name}\"}} {float(frozen)}")
        lines.append("# TYPE vllm:autotune_knob_value gauge")
        for name, value in sorted(at.knob_values().items()):
            lines.append("vllm:autotune_knob_value{controller=\""
                         f"{name}\"}} {float(value)}")
        lines.append("# TYPE vllm:autotune_decisions_total counter")
        for name, count in sorted(at.decisions_total.items()):
            lines.append("vllm:autotune_decisions_total{controller=\""
                         f"{name}\"}} {float(count)}")
        # QoS under overload (docs/qos.md): per-class shed counts from
        # the 429 gate and per-outcome preemption counts (did the
        # victim's KV pages ship to the offload tier, or will the
        # victim recompute from scratch?).
        lines.append("# TYPE vllm:qos_shed_total counter")
        for cls, count in sorted(self.qos_shed_counts.items()):
            lines.append("vllm:qos_shed_total{class=\""
                         f"{cls}\"}} {float(count)}")
        lines.append("# TYPE vllm:preempt_offload_total counter")
        for outcome, count in sorted(
                self.engine.scheduler.preempt_offload_outcomes.items()):
            lines.append("vllm:preempt_offload_total{outcome=\""
                         f"{outcome}\"}} {float(count)}")
        # Device performance observatory (docs/observability.md):
        # compile ledger, HBM breakdown, step-time/MFU, and the
        # resolved attention impls as a labeled one-hot info gauge
        # (the silent-XLA-fallback alarm).
        obs = getattr(self.engine.runner, "observatory", None)
        if obs is not None:
            lines.append("# TYPE vllm:engine_compile_events_total "
                         "counter")
            for kind, count in sorted(
                    obs.compile_events_by_kind().items()):
                lines.append(
                    "vllm:engine_compile_events_total{kind=\""
                    f"{kind}\"}} {float(count)}")
            lines.append("# TYPE vllm:engine_compile_seconds_total "
                         "counter")
            for kind, secs in sorted(
                    obs.compile_seconds_by_kind().items()):
                lines.append(
                    "vllm:engine_compile_seconds_total{kind=\""
                    f"{kind}\"}} {float(secs)}")
            # What the loads were made of, the probes' among them (a
            # ``backend`` second may be a ``cache_read`` second too:
            # jax reads its cache inside the stage it times as the
            # backend's), and how the persistent cache answered.
            probes = [s for s in self.startup.spans
                      if s["name"] == "boot.probe"]
            parts = obs.compile_parts_by_kind().values()
            lines.append("# TYPE vllm:engine_compile_part_seconds_total "
                         "counter")
            for part in LOAD_PARTS:
                lines.append(
                    "vllm:engine_compile_part_seconds_total{part=\""
                    f"{part[:-2]}\"}} "
                    f"{sum(p[part] for p in (*parts, *probes))}")
            lines.append("# TYPE vllm:engine_compile_cache_total "
                         "counter")
            for result, count in obs.cache_results().items():
                count += sum(s["cache"] == result for s in probes)
                lines.append(
                    "vllm:engine_compile_cache_total{result=\""
                    f"{result}\"}} {float(count)}")
            lines.append("# TYPE vllm:engine_startup_seconds gauge")
            for span, secs in self.startup.seconds_by_span().items():
                lines.append("vllm:engine_startup_seconds{span=\""
                             f"{span}\"}} {secs}")
            lines.append("# TYPE vllm:engine_executable_cache_size "
                         "gauge")
            for kind, size in sorted(
                    obs.executable_cache_sizes().items()):
                lines.append(
                    "vllm:engine_executable_cache_size{kind=\""
                    f"{kind}\"}} {float(size)}")
            lines.append("# TYPE vllm:engine_hbm_bytes gauge")
            for category, nbytes in sorted(obs.hbm_bytes().items()):
                lines.append("vllm:engine_hbm_bytes{category=\""
                             f"{category}\"}} {float(nbytes)}")
            lines.append(
                "# TYPE vllm:engine_step_device_seconds_total counter")
            for kind, secs in sorted(
                    obs.device_seconds_by_kind().items()):
                lines.append(
                    "vllm:engine_step_device_seconds_total{kind=\""
                    f"{kind}\"}} {float(secs)}")
            lines.append(
                "# TYPE vllm:engine_step_time_median_seconds gauge")
            for kind, med in sorted(obs.step_time_medians().items()):
                lines.append(
                    "vllm:engine_step_time_median_seconds{kind=\""
                    f"{kind}\"}} {float(med)}")
            lines.append("# TYPE vllm:engine_mfu gauge")
            lines.append(f"vllm:engine_mfu {float(obs.mfu())}")
            lines.append("# TYPE vllm:engine_attention_impl gauge")
            for phase, impl in sorted(obs.attention_impls().items()):
                lines.append("vllm:engine_attention_impl{phase=\""
                             f"{phase}\",impl=\"{impl}\"}} 1.0")
        # The interpreter's two threads (docs/observability.md, "Is
        # the front the wall?"): the event loop thread's CPU clock, and
        # the loop thread's wall less CPU while it had work to do.
        tracer = self.engine.tracer
        if tracer is not None:
            front_cpu_s = tracer.front.cpu_s()
            if front_cpu_s is not None:
                lines.append(
                    "# TYPE vllm:engine_front_cpu_seconds_total counter")
                lines.append("vllm:engine_front_cpu_seconds_total "
                             f"{front_cpu_s}")
            lines.append(
                "# TYPE vllm:engine_loop_offcpu_seconds_total counter")
            lines.append("vllm:engine_loop_offcpu_seconds_total "
                         f"{tracer.loop_offcpu_s}")
        # Topology observability (docs/parallelism.md): the mesh the
        # engine actually runs on, which slice this process owns, and
        # per-slice liveness from the multihost bridge (a dead host
        # names ONE slice here instead of indicting the whole pool).
        lines.append("# TYPE vllm:engine_mesh_shape gauge")
        mesh = getattr(self.engine.runner, "mesh", None)
        par = self.engine.config.parallel
        axis_sizes = (dict(zip(mesh.axis_names, mesh.devices.shape))
                      if mesh is not None else
                      {"dp": 1, "pp": par.pipeline_parallel_size,
                       "sp": par.context_parallel_size,
                       "tp": par.tensor_parallel_size})
        for axis in ("dp", "pp", "sp", "tp"):
            lines.append("vllm:engine_mesh_shape{axis=\""
                         f"{axis}\"}} "
                         f"{float(axis_sizes.get(axis, 1))}")
        lines.append("# TYPE vllm:engine_slice_id gauge")
        lines.append(
            f"vllm:engine_slice_id {float(self._slice_id())}")
        lines.append("# TYPE vllm:engine_slice_live gauge")
        bridge = getattr(self.engine.runner, "bridge", None)
        if bridge is not None:
            live_map = bridge.check_liveness()
        else:
            live_map = {self._slice_id(): True}
        for slice_id, live in sorted(live_map.items()):
            lines.append("vllm:engine_slice_live{slice=\""
                         f"{slice_id}\"}} {float(live)}")
        # vLLM-parity request-latency histograms + token counters.
        lines.extend(self.engine.metrics.render())
        lines.append("")
        return web.Response(text="\n".join(lines),
                            content_type="text/plain")

    async def autotune_status(self, request: web.Request
                              ) -> web.Response:
        """Self-tuning introspection (docs/autotuning.md): mode,
        cadence, and per-controller knob/clamp/frozen/decision
        state."""
        return web.json_response(self.autotuner.status())

    async def autotune_reset(self, request: web.Request
                             ) -> web.Response:
        """Operator reset for guardrail freezes: unlatch one
        controller ({"controller": name}) or all (empty body)."""
        try:
            body = await request.json()
        except Exception:
            body = {}
        name = (body or {}).get("controller")
        cleared = self.autotuner.reset(name)
        return web.json_response({"reset": cleared})

    def build_app(self) -> web.Application:
        app = web.Application(client_max_size=1024 ** 3)
        app.router.add_post("/v1/chat/completions",
                            self._guarded(self.chat_completions))
        app.router.add_post("/v1/completions",
                            self._guarded(self.completions))
        app.router.add_post("/v1/disagg/prefill",
                            self._guarded(self.disagg_prefill))
        app.router.add_post("/v1/disagg/handoff",
                            self._guarded(self.disagg_handoff))
        app.router.add_post("/v1/resume", self._guarded(self.resume))
        app.router.add_post("/drain", self.drain)
        app.router.add_post("/v1/embeddings", self.embeddings)
        app.router.add_post("/v1/score", self.score)
        app.router.add_post("/score", self.score)
        app.router.add_post("/v1/rerank", self.rerank)
        app.router.add_post("/rerank", self.rerank)
        app.router.add_get("/v1/models", self.models)
        app.router.add_get("/health", self.health)
        app.router.add_get("/version", self.version)
        app.router.add_get("/metrics", self.metrics)
        app.router.add_get("/kv/summary", self.kv_summary_handler)
        app.router.add_get("/autotune/status", self.autotune_status)
        app.router.add_post("/autotune/reset", self.autotune_reset)
        app.router.add_post("/debug/profiler/start", self.profiler_start)
        app.router.add_post("/debug/profiler/stop", self.profiler_stop)
        app.router.add_get("/debug/trace/{request_id}", self.debug_trace)
        app.router.add_get("/debug/steps", self.debug_steps)
        app.router.add_get("/debug/compiles", self.debug_compiles)
        app.router.add_get("/debug/memory", self.debug_memory)

        async def on_startup(app):
            self.async_engine.start(asyncio.get_event_loop())
            self.startup.ready()

        app.on_startup.append(on_startup)
        return app


# ---- CLI -------------------------------------------------------------------


def _resolve_deferred_kv(args, model_config) -> bool:
    """--deferred-kv-writes auto|on|off -> bool.

    'auto' defers decode KV writes to one batched flush per burst
    where the capability guards pass (model_runner rejects an
    ineligible explicit 'on' loudly). The default rests on a builder's
    capture, not a driver's number; ROADMAP D5 owns the comparison."""
    if args.deferred_kv_writes == "on":
        return True
    if args.deferred_kv_writes == "off":
        return False
    from production_stack_tpu.engine.model_runner import (
        deferred_kv_eligible,
    )
    return deferred_kv_eligible(
        model_config.architecture, args.decode_steps,
        args.pipeline_parallel_size, args.context_parallel_size,
        args.speculative_k)


def _resolve_draft_module(args, model_config, deferred: bool) -> bool:
    """--draft-module auto|on|off -> bool.

    'auto' drafts with the model's own multi-token-prediction module
    wherever it can: the family declares one, the checkpoint's
    configuration keeps one (num_nextn_predict_layers >= 1) and the
    deferred burst, inside which it drafts, is served. 'on' where it
    cannot is a start-up error (engine/config.py); 'off' serves the
    model without the module: no weights, no cache entry."""
    if args.draft_module == "on":
        return True
    if args.draft_module == "off":
        return False
    return (deferred and model_config.has_draft_module
            and args.speculative_k == 0)


def _resolve_async_scheduling(args) -> bool:
    """--async-scheduling auto|on|off -> bool.

    'auto' enables the overlapped plan/dispatch/complete pipeline
    (docs/async_pipeline.md) for pure single-host single-step decode
    serving: multi-step bursts and speculative decoding already
    amortize the host round trip on device, so 'auto' keeps the
    pipeline off there, and the multihost step bridge broadcasts
    host-resident payloads. An explicit 'on' is legal alongside
    bursts and --speculative-k (docs/unified_step.md
    §dissolved-rules): bursts run as synchronous pipeline breaks and
    verify steps reconcile through the assume-1 stale-drop path. A
    prefill-role engine (docs/disaggregation.md) has no decode steps
    to overlap, so 'auto' resolves off and an explicit 'on' is
    legal but inert."""
    if args.async_scheduling == "on":
        return True
    if args.async_scheduling == "off":
        return False
    if getattr(args, "engine_role", "both") == "prefill":
        return False
    from production_stack_tpu.engine.model_runner import (
        async_scheduling_eligible,
    )
    return async_scheduling_eligible(
        args.decode_steps, args.speculative_k,
        distributed=args.distributed)


def _resolve_unified_step(args, model_config=None) -> bool:
    """--unified-step auto|on|off -> bool.

    'auto' enables the unified ragged step (docs/unified_step.md) —
    prefill chunks admitted into decode steps as one fixed-shape
    mixed batch — wherever it can run: single-host, a monolithic
    engine role. An explicit 'on' outside that envelope fails loudly
    at runner init (model_runner.unified_step_eligible)."""
    if args.unified_step == "on":
        return True
    if args.unified_step == "off":
        return False
    if model_config is not None and (model_config.has_recurrent_state
                                     or model_config.has_latent_cache
                                     or model_config.block_length):
        # The ragged rows have no path for a recurrent state or for a
        # latent plane (engine/config.py refuses an explicit 'on').
        return False
    from production_stack_tpu.engine.model_runner import (
        unified_step_eligible,
    )
    return unified_step_eligible(
        distributed=args.distributed,
        engine_role=getattr(args, "engine_role", "both"))


def build_engine_from_args(args, startup=None) -> tuple[LLMEngine, str]:
    """``startup``: main()'s timeline of the start (engine/tracing.py
    StartupTimeline); a checkpoint's read and the runner's probes,
    weights and cache are spans of it, the rest ``boot.engine``."""
    if startup is None:
        startup = StartupTimeline()
    mesh = None
    if args.model in ("tiny-llama", "tiny-opt"):
        model_config = tiny_model_config(args.model.split("-")[1])
        params = None
        # bench (not byte) tokenizer: random-weight greedy ids land
        # uniformly in the 512 vocab, and ByteTokenizer.decode drops
        # ids >= 256 — streaming clients would lose those deltas.
        # vocab_size threaded from the model so vocab-sized consumers
        # agree with what the engine can emit.
        from production_stack_tpu.engine.tokenizer import BenchTokenizer
        tokenizer = BenchTokenizer(model_config.vocab_size)
        served_name = args.served_model_name or args.model
    elif args.model == "bench-1b":
        # The 1B-class geometry (config.bench_1b_model_config),
        # random weights + bench tokenizer: lets chip_smoke.py drive
        # the real HTTP server at full width without a checkpoint on
        # disk. The bench tokenizer (not byte): random-weight greedy
        # tokens are almost surely >= 256, which ByteTokenizer.decode
        # drops — streaming clients would see zero non-empty deltas
        # (no TTFT signal, gen_tokens 0).
        model_config = bench_1b_model_config()
        params = None
        from production_stack_tpu.engine.tokenizer import BenchTokenizer
        tokenizer = BenchTokenizer(model_config.vocab_size)
        served_name = args.served_model_name or args.model
    else:
        from production_stack_tpu.engine.weights import (
            load_model_config,
            load_weights,
        )
        model_config = load_model_config(args.model)
        if args.dtype:
            model_config.dtype = args.dtype
        with startup.within("boot.weights"):
            params = (None if args.random_weights
                      else load_weights(args.model, model_config))
        with startup.within("boot.tokenizer"):
            tokenizer = get_tokenizer(args.tokenizer or args.model)
        served_name = args.served_model_name or args.model
    model_config.quantization = args.quantization
    model_config.attention_impl = args.attention_impl

    if (args.tensor_parallel_size > 1
            or args.pipeline_parallel_size > 1
            or args.context_parallel_size > 1
            or args.num_slices > 1):
        from production_stack_tpu.parallel.mesh import build_mesh
        from production_stack_tpu.parallel.topology import (
            parse_placement,
        )
        mesh = build_mesh(
            tensor_parallel_size=args.tensor_parallel_size,
            pipeline_parallel_size=args.pipeline_parallel_size,
            context_parallel_size=args.context_parallel_size,
            num_slices=args.num_slices,
            placement=parse_placement(args.mesh_placement),
        )

    deferred_kv = _resolve_deferred_kv(args, model_config)
    config = EngineConfig(
        model=model_config,
        cache=CacheConfig(
            page_size=args.page_size,
            num_pages=args.num_pages,
            enable_prefix_caching=not args.disable_prefix_caching,
            cache_layout=args.cache_layout,
            kv_cache_dtype=args.kv_cache_dtype,
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=args.max_num_seqs,
            max_model_len=args.max_model_len,
            prefill_chunk_size=args.prefill_chunk_size,
            prefill_batch_size=args.prefill_batch_size,
            decode_steps=args.decode_steps,
            deferred_kv_writes=deferred_kv,
            draft_module=_resolve_draft_module(args, model_config,
                                               deferred_kv),
            speculative_k=args.speculative_k,
            speculative_min_match=args.speculative_min_match,
            async_scheduling=_resolve_async_scheduling(args),
            unified_step=_resolve_unified_step(args, model_config),
            max_queue_len=args.max_queue_len,
        ),
        parallel=ParallelConfig(
            tensor_parallel_size=args.tensor_parallel_size,
            pipeline_parallel_size=args.pipeline_parallel_size,
            context_parallel_size=args.context_parallel_size,
            long_prefill_threshold=args.long_prefill_threshold,
            num_slices=args.num_slices,
            mesh_placement=args.mesh_placement,
        ),
        offload=OffloadConfig(
            enable=args.enable_kv_offload or bool(args.kv_remote_url),
            host_pool_bytes=args.kv_host_pool_bytes,
            remote_url=args.kv_remote_url,
        ),
        lora=LoRAConfig(
            enable=args.enable_lora or bool(args.lora_modules),
            max_loras=args.max_loras,
            max_lora_rank=args.max_lora_rank,
        ),
        qos=QoSConfig(
            default_priority=args.default_priority,
            preempt_to_offload=args.preempt_to_offload == "on",
            shed_threshold=args.shed_threshold,
        ),
        kvecon=KVEconConfig(
            summary_top_k=args.kv_summary_top_k,
            admit_hits=args.kv_admit_hits,
            ttl_s=args.kv_ttl_s,
            watermark_high=args.kv_watermark_high,
            watermark_low=args.kv_watermark_low,
        ),
        autotune=AutotuneConfig(
            mode=args.autotune,
            interval_s=args.autotune_interval_s,
            dead_band=args.autotune_dead_band,
            controllers=args.autotune_controllers,
            freeze_window_s=args.autotune_freeze_window_s,
            burn_threshold=args.autotune_burn_threshold,
            target_itl_ms=args.autotune_target_itl_ms,
            min_spec_k=args.autotune_min_spec_k,
            min_checkpoint_interval_tokens=(
                args.autotune_min_checkpoint_interval_tokens),
            max_checkpoint_interval_tokens=(
                args.autotune_max_checkpoint_interval_tokens),
            min_shed_threshold=args.autotune_min_shed_threshold,
        ),
        seed=args.seed,
        engine_role=args.engine_role,
        handoff_timeout_s=args.handoff_timeout_s,
        device_peak_flops=args.device_peak_flops,
        checkpoint_interval_tokens=args.checkpoint_interval_tokens,
        step_watchdog_s=args.step_watchdog_s,
    )
    engine = LLMEngine(config, mesh=mesh, params=params,
                       tokenizer=tokenizer, startup=startup)
    for module in args.lora_modules or []:
        name, _, path = module.partition("=")
        if not path:
            raise ValueError(
                f"--lora-modules entries must be name=path, got {module!r}"
            )
        engine.register_lora(path, name=name)
    if args.request_span_log or args.trace_ring_size > 0:
        # Server default: flight recorder on (ring > 0), span log off.
        # Library/tests constructing LLMEngine directly keep
        # engine.tracer None — zero tracing cost there.
        import jax

        from production_stack_tpu.engine.tracing import EngineTracer
        engine.tracer = EngineTracer(
            span_log_path=args.request_span_log,
            ring_size=max(1, args.trace_ring_size),
            role=args.engine_role,
            # The loop's turn phases as profiler events; a no-op
            # while no /debug/profiler slice runs.
            annotate=jax.profiler.TraceAnnotation,
        )
    return engine, served_name


def parse_args(argv=None):
    parser = argparse.ArgumentParser(prog="tpu-engine")
    parser.add_argument("--model", default="tiny-llama",
                        help="HF model dir, or tiny-llama/tiny-opt")
    parser.add_argument("--served-model-name", default=None)
    parser.add_argument("--tokenizer", default=None)
    parser.add_argument("--random-weights", action="store_true")
    parser.add_argument("--dtype", default=None,
                        choices=[None, "bfloat16", "float32", "float16"])
    parser.add_argument("--attention-impl", default="auto",
                        choices=["auto", "xla", "pallas",
                                 "pallas-interpret"],
                        help="auto = the Pallas decode and prefill "
                             "kernels where each compiles, else XLA "
                             "(model_runner)")
    parser.add_argument("--quantization", default="none",
                        choices=["none", "int8"],
                        help="Weight-only quantization (halves weight "
                             "HBM traffic on the decode path)")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--page-size", type=int, default=16)
    parser.add_argument("--num-pages", type=int, default=512)
    parser.add_argument("--kv-cache-dtype", default="auto",
                        choices=["auto", "bf16", "int8"],
                        help="KV page storage dtype. 'auto'/'bf16' "
                             "store pages in the model dtype; 'int8' "
                             "quantizes pages with per-slot per-head "
                             "scales and expands the page budget "
                             "~2x at the same HBM bytes "
                             "(docs/kv_quantization.md)")
    parser.add_argument("--cache-layout", default="auto",
                        choices=["auto", "stacked", "per_layer"],
                        help="KV cache HBM layout: auto "
                             "(per_layer unless pp/sp), one "
                             "stacked [L,...] array, or a tuple of "
                             "per-layer buffers (engine/config.py "
                             "CacheConfig)")
    parser.add_argument("--max-num-seqs", type=int, default=8)
    parser.add_argument("--max-model-len", type=int, default=2048)
    parser.add_argument("--prefill-chunk-size", type=int, default=512)
    parser.add_argument("--prefill-batch-size", type=int, default=4)
    parser.add_argument("--decode-steps", type=int, default=1,
                        help="Decode iterations fused per compiled "
                             "program (K tokens per host round-trip)")
    parser.add_argument("--speculative-k", type=int, default=0,
                        help="Draft-free speculative decoding: propose "
                             "up to K tokens per row via prompt lookup "
                             "and verify K+1 positions in one pass "
                             "(docs/speculative.md). 0 = off. Draft-"
                             "less steps fall back to the --decode-"
                             "steps burst; incompatible with "
                             "--deferred-kv-writes on")
    parser.add_argument("--speculative-min-match", type=int, default=2,
                        help="Minimum n-gram match length before the "
                             "prompt-lookup proposer drafts")
    parser.add_argument("--async-scheduling", default="auto",
                        choices=["auto", "on", "off"],
                        help="Overlapped async execution pipeline: "
                             "plan + dispatch decode step N+1 before "
                             "step N's tokens are read back, hiding "
                             "host work behind the device step "
                             "(docs/async_pipeline.md). 'auto' "
                             "enables it for single-host single-step "
                             "decode (off under --decode-steps > 1, "
                             "--speculative-k > 0, --distributed)")
    parser.add_argument("--unified-step", default="auto",
                        choices=["auto", "on", "off"],
                        help="Unified ragged step: admit prefill "
                             "chunks into decode steps as one fixed-"
                             "shape mixed batch instead of "
                             "alternating whole steps "
                             "(docs/unified_step.md). 'auto' enables "
                             "it for single-host monolithic serving "
                             "(off under pp/sp sharding, "
                             "--distributed, a disagg --engine-role)")
    parser.add_argument("--deferred-kv-writes", default="auto",
                        choices=["auto", "on", "off"],
                        help="Defer decode KV writes to one batched "
                             "flush per burst. 'auto' enables it "
                             "when eligible (llama, mistral, qwen2, "
                             "qwen3_next, jamba, lfm2_moe, longcat_flash, "
                             "glm4_moe_lite, granitemoehybrid, "
                             "exaone_moe; decode-steps "
                             "> 1, no pp/sp); /version "
                             "says which "
                             "is served (kv_writes)")
    parser.add_argument("--draft-module", default="auto",
                        choices=["auto", "on", "off"],
                        help="Draft with the model's own multi-token-"
                             "prediction module inside the deferred "
                             "burst: an iteration verifies one draft a "
                             "row and commits one or two tokens "
                             "(docs/speculative.md). 'auto' drafts "
                             "where the family declares a module, the "
                             "checkpoint's configuration keeps one "
                             "(num_nextn_predict_layers >= 1) and KV "
                             "writes are deferred; 'off' serves the "
                             "model without the module; /version says "
                             "which is served (drafts)")
    parser.add_argument("--tensor-parallel-size", type=int, default=1)
    parser.add_argument("--pipeline-parallel-size", type=int, default=1,
                        help="Layer stages over the pp mesh axis "
                             "(serving-path pipeline parallelism)")
    parser.add_argument("--context-parallel-size", type=int, default=1,
                        help="Sequence shards over the sp mesh axis: "
                             "long prompts prefill in one ring-"
                             "attention dispatch "
                             "(parallel/context_serving.py)")
    parser.add_argument("--long-prefill-threshold", type=int,
                        default=None,
                        help="Prompt length (tokens) that takes the "
                             "context-parallel prefill path (default "
                             "2 x prefill-chunk-size)")
    parser.add_argument("--num-slices", type=int, default=0,
                        help="Force the device topology into N equal "
                             "contiguous slices (CPU harness / "
                             "override); 0 auto-discovers ICI or "
                             "process grouping (parallel/topology.py)")
    parser.add_argument("--mesh-placement", default="auto",
                        help="Per-axis mesh placement as 'axis=ici' / "
                             "'axis=any' pairs (comma separated); "
                             "'auto' keeps tp/sp inside one ICI "
                             "domain and lets dp/pp cross slices")
    parser.add_argument("--disable-prefix-caching", action="store_true")
    parser.add_argument("--enable-lora", action="store_true",
                        help="Enable multi-LoRA adapter serving")
    parser.add_argument("--lora-modules", nargs="*", default=None,
                        metavar="NAME=PATH",
                        help="PEFT adapter dirs to serve by name")
    parser.add_argument("--max-loras", type=int, default=8)
    parser.add_argument("--max-lora-rank", type=int, default=16)
    parser.add_argument("--pooling", default="last",
                        choices=["last", "mean"],
                        help="/v1/embeddings pooling mode")
    parser.add_argument("--chat-template", default=None,
                        help="Jinja chat template source or file path, "
                             "overriding the model's own template")
    parser.add_argument("--profile-dir", "--profiler-dir",
                        dest="profile_dir", default=None,
                        help="Default output dir for "
                             "/debug/profiler/start traces "
                             "(--profiler-dir is an alias)")
    parser.add_argument("--device-peak-flops", type=float, default=0.0,
                        help="Per-chip peak FLOP/s for the "
                             "observatory's vllm:engine_mfu gauge; 0 "
                             "resolves from the device-kind table "
                             "(unknown devices report MFU 0)")
    parser.add_argument("--request-span-log", default=None,
                        help="Emit one JSON engine-span line per "
                             "finished request to this path ('-' = "
                             "the engine log). Same span family as "
                             "the router's --request-span-log; stitch "
                             "with python -m "
                             "production_stack_tpu.traceview "
                             "(docs/observability.md)")
    parser.add_argument("--trace-ring-size", type=int, default=256,
                        help="Flight-recorder depth: recent request "
                             "timelines kept for /debug/trace/{id} "
                             "and step records for /debug/steps. "
                             "0 disables the recorder (and, with no "
                             "--request-span-log, all tracing)")
    parser.add_argument("--compilation-cache-dir", default=None,
                        help="Persistent XLA compilation cache (point "
                             "at the PVC so pod restarts skip "
                             "recompilation). Ignored when "
                             "JAX_COMPILATION_CACHE_DIR is set; default "
                             "<checkout>/.jax_cache")
    # Multi-host slice serving (jax.distributed; parallel/distributed.py).
    # On GKE TPU slices the three values auto-detect — pass none of them.
    parser.add_argument("--distributed", action="store_true",
                        help="Join a jax.distributed multi-host slice")
    parser.add_argument("--coordinator-address", default=None)
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)
    parser.add_argument("--enable-kv-offload", action="store_true",
                        help="HBM->host-RAM KV offload tier")
    parser.add_argument("--kv-host-pool-bytes", type=int,
                        default=2 * 1024 ** 3)
    parser.add_argument("--kv-remote-url", default=None,
                        help="Remote shared KV cache server URL")
    parser.add_argument("--max-queue-len", type=int, default=1024,
                        help="Waiting-queue depth before submissions "
                             "are rejected (scheduler backpressure)")
    parser.add_argument("--seed", type=int, default=0,
                        help="Base RNG seed for sampled requests "
                             "without a per-request seed")
    parser.add_argument("--engine-role", default="both",
                        choices=["prefill", "decode", "both"],
                        help="Disaggregated serving role "
                             "(docs/disaggregation.md): 'prefill' "
                             "computes prompt KV and hands off via "
                             "the offload wire, 'decode' resumes "
                             "handoffs, 'both' (default) serves "
                             "monolithically. Advertised via /health "
                             "for role-aware routing")
    parser.add_argument("--default-priority", default="batch",
                        choices=list(PRIORITY_NAMES),
                        help="QoS class assumed for requests without "
                             "an x-priority header (docs/qos.md). "
                             "Priority orders waiting-queue admission "
                             "and picks preemption victims "
                             "(lowest class, newest arrival first)")
    parser.add_argument("--preempt-to-offload", default="on",
                        choices=["on", "off"],
                        help="Under KV page pressure, ship a preempted "
                             "victim's committed pages to the "
                             "configured offload tier and restore "
                             "them on re-admission instead of "
                             "recomputing (docs/qos.md). Inert "
                             "without --enable-kv-offload or "
                             "--kv-remote-url")
    parser.add_argument("--shed-threshold", type=float, default=0.95,
                        help="Fraction of --max-queue-len at which "
                             "non-interactive requests are shed with "
                             "429 + Retry-After instead of queued "
                             "(docs/qos.md); interactive requests are "
                             "never shed by this gate")
    parser.add_argument("--handoff-timeout-s", type=float, default=30.0,
                        help="How long a decode-role engine holds a "
                             "handoff in AWAITING_KV waiting for an "
                             "unreachable offload tier before "
                             "degrading to full recompute")
    parser.add_argument("--drain-exit-timeout-s", type=float,
                        default=0.0,
                        help="After POST /drain {\"exit\": true}, the "
                             "longest the server waits for in-flight "
                             "requests before exiting anyway (0 = "
                             "wait forever; the fleet manager applies "
                             "its own drain deadline)")
    parser.add_argument("--build-id", type=str, default="",
                        help="Opaque build/revision label reported in "
                             "/health and /version; the fleet rollout "
                             "controller uses it to verify which "
                             "revision a replica runs (docs/fleet.md)")
    parser.add_argument("--checkpoint-interval-tokens", type=int,
                        default=0,
                        help="Every N generated tokens, ship a "
                             "streaming sequence's committed KV pages "
                             "to the offload tier and attach a resume "
                             "descriptor to the SSE stream so the "
                             "router can resume it on another engine "
                             "after a crash (0 disables; "
                             "docs/crash_recovery.md)")
    parser.add_argument("--step-watchdog-s", type=float, default=0.0,
                        help="Seconds a single engine step may run "
                             "before /health flips to 503 so the "
                             "router's prober rotates the hung "
                             "replica out (0 disables)")
    # Cluster KV economy (docs/kv_economy.md): the GET /kv/summary
    # hot-chain tracker and the offload tier's watermark hysteresis.
    parser.add_argument("--kv-summary-top-k", type=int, default=64,
                        help="Hot prefix chains advertised at "
                             "GET /kv/summary for KV-state-aware "
                             "routing (docs/kv_economy.md)")
    parser.add_argument("--kv-admit-hits", type=int, default=2,
                        help="Decayed hit count a prefix chain needs "
                             "before the summary advertises it")
    parser.add_argument("--kv-ttl-s", type=float, default=900.0,
                        help="Seconds an idle prefix chain stays in "
                             "the summary tracker (0 disables TTL)")
    parser.add_argument("--kv-watermark-high", type=float, default=1.0,
                        help="Host KV pool fill fraction that triggers "
                             "LRU eviction (1.0 = legacy exact-"
                             "capacity behavior)")
    parser.add_argument("--kv-watermark-low", type=float, default=1.0,
                        help="Fill fraction the host KV pool drains "
                             "down to once the high watermark trips")
    # Self-tuning controllers (docs/autotuning.md).
    parser.add_argument("--autotune", default="off",
                        choices=["off", "shadow", "on"],
                        help="Self-tuning controllers: off, shadow "
                             "(compute + span-log decisions without "
                             "applying), or on (close the loop)")
    parser.add_argument("--autotune-interval-s", type=float,
                        default=2.0,
                        help="Seconds between controller ticks")
    parser.add_argument("--autotune-dead-band", type=float,
                        default=0.05,
                        help="Relative dead-band: drop proposals "
                             "within this fraction of the current "
                             "knob value")
    parser.add_argument("--autotune-controllers", default="all",
                        help="Comma-separated controller allowlist "
                             "(spec_k,prefill_budget,kvecon,"
                             "checkpoint_interval,qos_shed) or 'all'")
    parser.add_argument("--autotune-freeze-window-s", type=float,
                        default=30.0,
                        help="Guardrail blame window: freeze "
                             "controllers that applied a decision "
                             "this recently when perf drift flips "
                             "or 5m burn rises")
    parser.add_argument("--autotune-burn-threshold", type=float,
                        default=1.0,
                        help="5m SLO burn rate at/above which a rise "
                             "trips the guardrail")
    parser.add_argument("--autotune-target-itl-ms", type=float,
                        default=50.0,
                        help="Decode ITL p99 target the prefill-"
                             "budget controller steers toward")
    parser.add_argument("--autotune-min-spec-k", type=int, default=1,
                        help="Floor for the per-sequence speculative "
                             "draft cap (ceiling is --speculative-k)")
    parser.add_argument("--autotune-min-checkpoint-interval-tokens",
                        type=int, default=64,
                        help="Floor for the tuned checkpoint "
                             "interval")
    parser.add_argument("--autotune-max-checkpoint-interval-tokens",
                        type=int, default=4096,
                        help="Ceiling for the tuned checkpoint "
                             "interval")
    parser.add_argument("--autotune-min-shed-threshold", type=float,
                        default=0.5,
                        help="Floor for the tuned QoS shed gate "
                             "(ceiling is --shed-threshold)")
    return parser.parse_args(argv)


def _load_chat_template(args) -> Optional[str]:
    """--chat-template accepts inline Jinja source or a file path."""
    import os
    if not args.chat_template:
        return None
    if os.path.exists(args.chat_template):
        with open(args.chat_template) as f:
            source = f.read()
    else:
        source = args.chat_template
    # Fail fast on a broken template: a render failure at request time
    # silently falls back to the model's template (tokenizer.py), which
    # an operator who set the flag should learn at startup instead.
    import jinja2
    jinja2.Template(source).render(
        messages=[{"role": "user", "content": "probe"}],
        add_generation_prompt=True,
    )
    return source


def _claim_devices(args, startup) -> None:
    """Initialize the JAX backend now, so a device that cannot serve
    what was asked is a start-up error with a reason. A chip belongs
    to one process at a time and nothing here pins a process to a
    device: every engine process claims every chip of its host, so a
    second engine on the same host (or a parent that already touched
    JAX) cannot start (README "One process per chip"). The start's
    ``boot.claim_devices`` span (``startup``)."""
    import os

    import jax
    with startup.within("boot.claim_devices") as span:
        try:
            devices = jax.devices()
        except RuntimeError as e:
            raise SystemExit(
                "tpu-engine: cannot claim the accelerator: " + str(e)
                + "\nA chip belongs to one process at a time — is "
                "another engine, benchmark or Python session holding "
                "it?") from e
        platform = devices[0].platform
        span.update(platform=platform, devices=len(devices))
    logger.info("Devices: %d x %s (%s)", len(devices),
                devices[0].device_kind, platform)
    if platform == "cpu" and "cpu" not in os.environ.get(
            "JAX_PLATFORMS", ""):
        # JAX falls back to the CPU when it finds no accelerator (or
        # another process holds it); serving from that fallback would
        # look like a working, very slow TPU engine.
        raise SystemExit(
            "tpu-engine: JAX found no accelerator and fell back to "
            "the CPU. Serving on the CPU is for tests; ask for it "
            "with JAX_PLATFORMS=cpu.")
    if platform == "cpu" and args.attention_impl == "pallas":
        raise SystemExit(
            "tpu-engine: --attention-impl pallas needs a TPU (Mosaic "
            "compiles for no other backend); on the CPU use "
            "pallas-interpret.")


def main(argv=None) -> None:
    # The start's timeline, from the process's first instant: what
    # follows until the listener is in one of its spans.
    import jax
    startup = StartupTimeline(annotate=jax.profiler.TraceAnnotation)
    args = parse_args(argv)
    # Persistent executable cache: a restarted pod (weight PVC + this
    # cache) resumes serving without the cold-compile wait.
    from production_stack_tpu.utils.compile_cache import (
        configure_compile_cache,
    )
    logger.info("Compilation cache: %s",
                configure_compile_cache(args.compilation_cache_dir))
    if args.distributed:
        from production_stack_tpu.parallel.distributed import (
            MultihostStepBridge,
            init_distributed,
            is_coordinator,
        )
        if args.enable_kv_offload or args.kv_remote_url:
            raise ValueError(
                "KV offload tiers are host-0-local state and are not "
                "yet supported in multi-host mode"
            )
        if args.context_parallel_size > 1:
            # Fail at startup, not on the first long prompt: sp
            # prefill payloads are not mirrored over the step bridge
            # yet (model_runner.dispatch_sp_prefill), and a mid-serving
            # NotImplementedError would wedge the worker hosts.
            raise ValueError(
                "--context-parallel-size > 1 is not yet supported "
                "with --distributed (single-host sp only)"
            )
        init_distributed(args.coordinator_address, args.num_processes,
                         args.process_id)
        engine, served_name = build_engine_from_args(args, startup)
        # Size the liveness ledger from the discovered topology so a
        # dead host's missing acks name one slice on /metrics.
        from production_stack_tpu.parallel.topology import (
            discover_topology,
        )
        topo = discover_topology(num_slices=args.num_slices)
        bridge = MultihostStepBridge(engine.runner,
                                     num_slices=topo.num_slices)
        # Build the embedder on EVERY host now: embed programs run
        # collectives over the global mesh, so workers must be able to
        # mirror KIND_EMBED payloads — a host-0-only lazy build would
        # deadlock the slice on the first /v1/embeddings request.
        try:
            from production_stack_tpu.engine.embeddings import Embedder
            embedder = Embedder(
                engine.config.model, engine.runner.params,
                max_len=engine.config.scheduler.max_model_len,
                pooling=args.pooling,
            )
            engine.runner.embedder = embedder
        except NotImplementedError as e:
            logger.info("embeddings/score/rerank disabled on this "
                        "slice: %s", e)
            embedder = None
        if not is_coordinator():
            # Workers never serve HTTP; they mirror host 0's steps.
            bridge.worker_loop()
            return
        engine.runner.bridge = bridge
        server = EngineServer(engine, served_name, pooling=args.pooling,
                          profile_dir=args.profile_dir,
                          chat_template=_load_chat_template(args),
                          drain_exit_timeout_s=args.drain_exit_timeout_s,
                          build_id=args.build_id)
        if embedder is not None:
            embedder.bridge = bridge
            server._embedder = embedder
        logger.info("tpu-engine %s (multihost coordinator) serving %s "
                    "on %s:%d", __version__, served_name, args.host,
                    args.port)
        startup.enter("boot.listen")
        try:
            web.run_app(server.build_app(), host=args.host,
                        port=args.port, print=None)
        finally:
            bridge.shutdown()
        return
    _claim_devices(args, startup)
    engine, served_name = build_engine_from_args(args, startup)
    server = EngineServer(engine, served_name, pooling=args.pooling,
                          profile_dir=args.profile_dir,
                          chat_template=_load_chat_template(args),
                          drain_exit_timeout_s=args.drain_exit_timeout_s,
                          build_id=args.build_id)
    logger.info("tpu-engine %s serving %s on %s:%d",
                __version__, served_name, args.host, args.port)
    startup.enter("boot.listen")
    web.run_app(server.build_app(), host=args.host, port=args.port,
                print=None)


if __name__ == "__main__":
    main()
