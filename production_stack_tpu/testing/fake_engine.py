"""Fake serving engine for router tests and perf rigs.

Capability parity with reference src/tests/perftest/fake-openai-server.py:
an OpenAI-compatible HTTP server that streams chat-completion chunks at a
configurable tokens/sec rate (``--speed``) after a configurable first-token
delay (``--ttft``), and exposes a synthetic vLLM-style ``/metrics``
exposition — so the full router stack can be exercised with zero TPUs.

Fault injection (for resilience tests): ``--fault MODE`` at startup or
``POST /fault {"mode": MODE}`` at runtime, with MODE one of

- ``error500``       every API request answers 500 ( /health too )
- ``hang``           accept the connection, never send a response
- ``slow_first_token``  first token delayed by ``--fault-ttft`` seconds
- ``abort_mid_stream``  stream a couple of chunks, then drop the socket
- ``crash``          chaos (docs/crash_recovery.md): SIGKILL the whole
                     process after ``--crash-after-tokens`` streamed
                     tokens — the rawest mid-stream death, no FIN, no
                     terminating chunk. Only sane for subprocess fakes
                     (fleet pools, chaos tests); an in-process fake
                     would kill the test runner.
- ``hang_step``      a wedged device step: streams stall mid-response
                     without closing, and /health answers 503
                     ``{"status": "watchdog"}`` like the real server's
                     ``--step-watchdog-s`` trip.
- ``unhealthy``      API keeps working but /health answers 500
- ``kv_missing``     disagg: a prefill-role fake emits descriptors whose
                     pages are unavailable; a decode-role fake answers
                     409 to every handoff (KV never restorable here)
- ``overload``       QoS (docs/qos.md): the fake is "saturated" — it
                     keeps serving ``interactive`` requests but answers
                     429 + Retry-After to every other priority class,
                     counting them in ``vllm:qos_shed_total{class=...}``
                     and emitting a ``qos_shed`` span event. With
                     ``--priority-aware`` the class comes from the
                     request's ``x-priority`` header; without it every
                     request is treated as the deployment default
                     (batch), i.e. everything is shed.
- ``slow_ttft``      SLO-breach timing fault (docs/observability.md):
                     first token delayed by an extra ``--slow-ttft-s``
                     seconds — the stream still completes cleanly, so
                     router-side SLO ledger / slow-archive tests see a
                     breaching-but-successful request
- ``slow_itl``       SLO-breach timing fault: every streamed token
                     takes ``--slow-itl-s`` seconds instead of
                     ``1/speed``
- ``degrade_new_revision``  rollout-canary fault bundle
                     (docs/fleet.md): slow_ttft AND slow_itl at once
                     while /health stays green — the shape of a bad
                     build that boots fine but serves badly, which
                     only the rollout judge's bake-window scoring
                     catches
- ``null``/absent    healthy (clears a previously set fault)

Disaggregation (docs/disaggregation.md): ``--role prefill|decode|both``
is reported in ``/health`` for the router's role discovery, and the
fakes serve ``/v1/disagg/prefill`` (returns a handoff descriptor) and
``/v1/disagg/handoff`` (streams from a descriptor) with output
byte-identical to the monolithic fake endpoints.

Fleet-manager support (docs/fleet.md), mirroring the real engine server:

- ``POST /drain`` flips DRAINING — new admissions answer 503 +
  Retry-After while in-flight streams finish byte-identically; with
  ``{"exit": true}`` the process exits clean once idle.
- ``POST /gauges`` injects deterministic load-gauge values (waiting
  depth, cache usage) into ``/metrics`` so autoscaler tests can drive
  SLO signals without real load.

Connection refusal needs no mode: point the router at an unbound port.

Run: ``python -m production_stack_tpu.testing.fake_engine --port 9001``
"""

from __future__ import annotations

import argparse
import asyncio
import json
import time
import uuid
from typing import Optional

from aiohttp import web

# Stdlib-only by design (no JAX, no engine imports beyond it): the fake
# reuses the real engine's tracer so router-side stitching tests see
# genuine {"span": "engine_request"} lines without a TPU.
from production_stack_tpu.engine.tracing import (
    EngineTracer,
    StartupTimeline,
)
from production_stack_tpu.version import __version__
from production_stack_tpu.kvecon.summary import (
    chain_text,
    expected_hit_blocks,
    routable_text,
    TOKENS_PER_BLOCK,
)
from production_stack_tpu.qos import (
    DEFAULT_PRIORITY,
    parse_priority,
    Priority,
    PRIORITY_HEADER,
    priority_name,
    shed_counter_dict,
)


FAULT_MODES = (
    "error500", "hang", "slow_first_token", "abort_mid_stream", "crash",
    "hang_step", "unhealthy", "kv_missing", "overload",
    "slow_ttft", "slow_itl", "degrade_new_revision",
)

ENGINE_ROLES = ("prefill", "decode", "both")

# endpoint-contract markers (staticcheck/analyzers/endpoint_contract.py):
# every real-server route is mirrored here or listed below with the
# reason the fake cannot (or need not) fake it. Both directions are
# linted — a stale or redundant entry is itself a finding.
FAKE_ENGINE_EXEMPT = {
    "POST /v1/embeddings":
        "pooling endpoints run a real model forward (hidden-state "
        "pooling); router tests exercise generation routing, and a "
        "fabricated embedding vector would only test the fabrication",
    "POST /v1/score":
        "cross-encoder scoring needs a real forward pass — see "
        "POST /v1/embeddings",
    "POST /score":
        "alias of /v1/score — same real-forward dependency",
    "POST /v1/rerank":
        "rerank is score over N candidates — same real-forward "
        "dependency",
    "POST /rerank":
        "alias of /v1/rerank — same real-forward dependency",
    "POST /debug/profiler/start":
        "drives the live JAX profiler on the device; meaningless "
        "without a TPU and never routed through the router",
    "POST /debug/profiler/stop":
        "paired with /debug/profiler/start — same device dependency",
    "POST /kv/batch_get":
        "cache-server route: tests run the real CacheServer app "
        "in-process (it has no device dependency) instead of faking it",
    "PUT /kv/{key}":
        "cache-server route — real CacheServer runs in-process for "
        "tests",
    "HEAD /kv/{key}":
        "cache-server route — real CacheServer runs in-process for "
        "tests",
    "GET /kv/{key}":
        "cache-server route — real CacheServer runs in-process for "
        "tests",
    "GET /stats":
        "cache-server route — real CacheServer runs in-process for "
        "tests",
}

# Routes only the fake serves: test hooks with no real-server twin.
FAKE_ONLY_ROUTES = {
    "POST /fault": "fault-injection hook for resilience tests",
    "POST /gauges": "injects deterministic load-gauge values so "
                    "autoscaler tests can drive SLO signals",
    "POST /kv/summary": "lets KV-economy tests plant the hot-chain "
                        "snapshot the GET serves",
    "GET /cluster/status": "single-fake stand-in for the ROUTER's "
                           "fleet rollup (router/app.py serves the "
                           "real one) so stacktop render tests run "
                           "without a router",
    "POST /autotune/knobs": "plants knob values / frozen flags the "
                            "fake reports in /metrics and "
                            "/cluster/status, so router and fleet "
                            "self-tuning tests run without a real "
                            "engine's controller loop",
}


class FakeEngineState:
    def __init__(self, model: str, speed: float, ttft: float,
                 max_tokens_default: int = 32,
                 fault: Optional[str] = None, fault_ttft: float = 5.0,
                 role: str = "both", priority_aware: bool = False,
                 max_concurrency: int = 0,
                 checkpoint_interval: int = 0,
                 crash_after_tokens: int = 4,
                 kv_hot_capacity: int = 128,
                 kv_total_pages: int = 512,
                 build_id: str = ""):
        self.model = model
        self.speed = speed  # tokens per second
        self.ttft = ttft  # seconds before first token
        self.max_tokens_default = max_tokens_default
        self.running = 0
        self.waiting = 0
        self.total_served = 0
        self.fault = fault  # one of FAULT_MODES or None
        self.fault_ttft = fault_ttft  # slow_first_token delay
        # SLO-breach timing faults (docs/observability.md): extra
        # first-token delay / per-token cadence under the slow_ttft /
        # slow_itl fault modes.
        self.slow_ttft_s = 0.75
        self.slow_itl_s = 0.2
        self.requests_received = 0  # API hits incl. faulted ones
        self.role = role  # reported in /health for role discovery
        self.disagg_prefills = 0  # descriptors emitted
        self.disagg_decodes = 0  # handoffs streamed
        self.draining = False  # POST /drain flips; 503s new admissions
        # Migrate-mode drain (fleet rollouts, docs/fleet.md): in-flight
        # checkpointed streams are cut at their next checkpoint
        # boundary so the router resumes them on a live replica instead
        # of waiting out multi-minute generations.
        self.migrate_drain = False
        # Build revision reported in /version and /health so rollout
        # tests and bench can assert revision membership.
        self.build_id = build_id
        self.cache_usage = None  # POST /gauges override; None = derived
        # QoS (docs/qos.md): when priority-aware the fake reads the
        # x-priority header; the overload fault sheds non-interactive
        # classes and these counters back vllm:qos_shed_total.
        self.priority_aware = priority_aware
        self.qos_shed_counts = shed_counter_dict()
        # Capacity model (--max-concurrency): > 0 = that many
        # decode slots; excess requests QUEUE (waiting gauge rises,
        # TTFT inflates) exactly like a saturated pod — without it the
        # fake serves unlimited concurrency and overload is invisible.
        self.max_concurrency = max_concurrency
        self._slots: Optional[asyncio.Semaphore] = None
        # Crash recovery (docs/crash_recovery.md): with a checkpoint
        # interval set, streams carry ``: checkpoint {json}`` comment
        # frames every N tokens and /v1/resume continues a broken
        # stream from a descriptor; the crash fault SIGKILLs the
        # process after this many streamed tokens.
        self.checkpoint_interval = checkpoint_interval
        self.crash_after_tokens = crash_after_tokens
        self.stream_resumes = 0
        # Real EngineTracer (engine/tracing.py): fakes emit the same
        # engine-span lines and serve /debug/trace/{id} as the real
        # server. None disables tracing entirely.
        self.tracer: Optional[EngineTracer] = None
        # The start's spans (engine/tracing.py StartupTimeline): the
        # fake has no device to claim and nothing to probe, so its
        # start is ``boot.imports``, ``boot.engine`` and
        # ``boot.listen``.
        self.startup = StartupTimeline()
        # Cluster KV economy (docs/kv_economy.md): capped LRU hot set
        # of text-domain prefix chain hashes — the fake's stand-in for
        # "which prefixes have live KV here". The CAP matters: a fake
        # with unbounded memory would make every routing policy look
        # prefix-perfect, so pinning too many distinct prefixes on one
        # replica must thrash, exactly like a real page budget.
        self.kv_hot_capacity = kv_hot_capacity
        self.kv_total_pages = kv_total_pages
        self.kv_hot: "dict[int, float]" = {}  # chain_hash -> hits
        self.prefix_hit_tokens = 0
        self.prefix_query_tokens = 0
        # POST /kv/summary overrides (None = derived from kv_hot).
        self.kv_summary_override: Optional[dict] = None
        # Self-tuning (docs/autotuning.md): the fake has no controller
        # loop — POST /autotune/knobs plants these, and they surface in
        # /metrics, /autotune/status and /cluster/status exactly where
        # the real server reports its live controllers.
        self.autotune_mode = "off"
        self.autotune_knobs: "dict[str, float]" = {}
        self.autotune_frozen: "dict[str, bool]" = {}
        self.autotune_decisions: "dict[str, float]" = {}

    def autotune_active(self) -> int:
        if self.autotune_mode != "on":
            return 0
        return sum(1 for name in self.autotune_knobs
                   if not self.autotune_frozen.get(name))

    def observe_prefix(self, body: dict) -> float:
        """Score the request against the hot set (fraction of prompt
        blocks with 'live KV'), then fold its chains in with LRU
        eviction at the capacity cap. Returns the hit fraction."""
        text = routable_text(body)
        if not text:
            return 0.0
        chains = chain_text(text)
        if not chains:
            return 0.0
        hit = expected_hit_blocks(chains, self.kv_hot)
        self.prefix_hit_tokens += hit * TOKENS_PER_BLOCK
        self.prefix_query_tokens += len(chains) * TOKENS_PER_BLOCK
        now = time.monotonic()
        for h in chains:
            self.kv_hot.pop(h, None)  # re-insert = move to MRU end
            self.kv_hot[h] = now
        while len(self.kv_hot) > self.kv_hot_capacity:
            self.kv_hot.pop(next(iter(self.kv_hot)))
        return hit / len(chains)

    def prefix_hit_rate(self) -> float:
        if self.prefix_query_tokens == 0:
            return 0.0
        return self.prefix_hit_tokens / self.prefix_query_tokens

    def kv_summary_payload(self) -> dict:
        if self.kv_summary_override is not None:
            return self.kv_summary_override
        hot = sorted(self.kv_hot.items(), key=lambda kv: -kv[1])
        return {
            "hot_chains": [[h, 1.0] for h, _ in hot],
            "free_pages": max(
                0, self.kv_total_pages - len(self.kv_hot)
                - self.running),
            "total_pages": self.kv_total_pages,
            "kv_dtype": "bf16",
        }

    def slot_sem(self) -> Optional[asyncio.Semaphore]:
        # Lazily created so the semaphore binds to the serving loop.
        if self.max_concurrency > 0 and self._slots is None:
            self._slots = asyncio.Semaphore(self.max_concurrency)
        return self._slots


def _request_priority(state: FakeEngineState,
                      request: web.Request) -> Priority:
    """Priority class of a request: the x-priority header when the fake
    is --priority-aware (malformed values fall back to the default, the
    fake never 400s on it), else the deployment default."""
    if not state.priority_aware:
        return DEFAULT_PRIORITY
    raw = request.headers.get(PRIORITY_HEADER)
    if not raw:
        return DEFAULT_PRIORITY
    try:
        return parse_priority(raw)
    except ValueError:
        return DEFAULT_PRIORITY


async def _apply_api_fault(state: FakeEngineState,
                           request: web.Request) -> Optional[web.Response]:
    """Returns an error response (or hangs) per the active fault mode;
    None when the request should proceed normally."""
    if state.draining:
        # Zero-loss drain: mirror the real engine server's retryable
        # rejection — the router fails the request over to a live
        # replica (never a client-visible 5xx).
        return web.json_response(
            {"error": {"message": "engine is draining; retry on "
                                  "another replica"}},
            status=503, headers={"Retry-After": "1"},
        )
    if state.fault == "overload":
        # Saturated-but-healthy: interactive traffic still flows, every
        # other class gets the same honest 429 + Retry-After the real
        # engine's shed gate produces (never a 5xx, never a drop).
        pri = _request_priority(state, request)
        if pri != Priority.INTERACTIVE:
            state.qos_shed_counts[priority_name(pri)] += 1
            if state.tracer is not None:
                seq_id = f"shed-{uuid.uuid4().hex[:12]}"
                state.tracer.start(
                    seq_id,
                    request_id=request.headers.get("x-request-id"),
                    prompt_tokens=0)
                state.tracer.event(seq_id, "qos_shed",
                                   priority=priority_name(pri),
                                   retry_after_s=1)
                state.tracer.finish(seq_id, reason="shed",
                                    arrival_ts=time.time())
            return web.json_response(
                {"error": {"message": "engine overloaded (injected); "
                                      "retry later",
                           "type": "overloaded_error"}},
                status=429, headers={"Retry-After": "1"},
            )
    if state.fault == "error500":
        return web.json_response(
            {"error": {"message": "injected fault", "type": "server_error"}},
            status=500,
        )
    if state.fault == "hang":
        await asyncio.sleep(3600)
        return web.json_response({"error": "hang elapsed"}, status=500)
    if state.fault == "slow_first_token":
        await asyncio.sleep(state.fault_ttft)
    return None


def _echo_headers(request: web.Request) -> dict:
    """Echo the router's x-request-id so clients (and tests) can
    correlate a response with its /debug/trace/{id} timeline."""
    trace_id = request.headers.get("x-request-id")
    return {"x-request-id": trace_id} if trace_id else {}


def _sse(payload: dict) -> bytes:
    return f"data: {json.dumps(payload)}\n\n".encode()


def _chunk(request_id: str, model: str, text: Optional[str],
           finish: Optional[str] = None, role: Optional[str] = None) -> dict:
    delta = {}
    if role:
        delta["role"] = role
    if text is not None:
        delta["content"] = text
    return {
        "id": request_id,
        "object": "chat.completion.chunk",
        "created": int(time.time()),
        "model": model,
        "choices": [
            {"index": 0, "delta": delta, "finish_reason": finish}
        ],
    }


def _ckpt_frame(request_id: str, model: str, n_tokens: int,
                done: int) -> bytes:
    """SSE comment frame carrying the fake's resume descriptor — same
    in-band relay channel the real engine uses; invisible to SSE
    clients, captured (and stripped) by the router."""
    desc = {
        "version": 1,
        "fake": True,
        "response_id": request_id,
        "chat": True,
        "model": model,
        "kv_dtype": "bf16",
        "n_tokens": n_tokens,
        "output_tokens": done,
        "sampling": {"max_tokens": n_tokens},
    }
    return f": checkpoint {json.dumps(desc)}\n\n".encode()


def _sigkill_self() -> None:
    # The rawest mid-stream death: no FIN, no terminating chunk.
    import os
    import signal
    os.kill(os.getpid(), signal.SIGKILL)


async def chat_completions(request: web.Request) -> web.StreamResponse:
    state: FakeEngineState = request.app["state"]
    state.requests_received += 1
    fault_resp = await _apply_api_fault(state, request)
    if fault_resp is not None:
        return fault_resp
    body = await request.json()
    n_tokens = int(
        body.get("max_tokens")
        or body.get("max_completion_tokens")
        or state.max_tokens_default
    )
    stream = bool(body.get("stream", False))
    request_id = f"chatcmpl-{uuid.uuid4().hex[:16]}"
    model = body.get("model", state.model)
    # KV economy TTFT model (docs/kv_economy.md): prefill time scales
    # with the cold fraction of the prompt — a prefix already hot on
    # this replica skips its share of --ttft, so routing policies that
    # land repeat prefixes on the same pod measurably win.
    hit_frac = state.observe_prefix(body)
    ttft_eff = state.ttft * (1.0 - 0.9 * hit_frac)
    # SLO-breach timing faults: breach-but-succeed, so the router's
    # SLO ledger classifies a completed request as bad and captures
    # its exemplar (docs/observability.md). degrade_new_revision is
    # both at once — a bad build that boots healthy but serves badly.
    if state.fault in ("slow_ttft", "degrade_new_revision"):
        ttft_eff += state.slow_ttft_s
    tok_delay = (state.slow_itl_s
                 if state.fault in ("slow_itl", "degrade_new_revision")
                 else 1.0 / state.speed)
    words = [f"tok{i} " for i in range(n_tokens)]
    tracer, arrival = state.tracer, time.time()
    if tracer is not None:
        tracer.start(request_id,
                     request_id=request.headers.get("x-request-id"),
                     prompt_tokens=8)

    sem = state.slot_sem()
    if sem is not None:
        state.waiting += 1
        try:
            await sem.acquire()
        finally:
            state.waiting -= 1
    state.running += 1
    try:
        await asyncio.sleep(ttft_eff)
        first_ts = time.time()
        if tracer is not None:
            tracer.event(request_id, "prefill_chunk",
                         start=0, tokens=8, last=True)
            tracer.event(request_id, "first_token", token=0)
        if not stream:
            await asyncio.sleep(n_tokens * tok_delay)
            state.total_served += 1
            if tracer is not None:
                tracer.finish(request_id, reason="stop",
                              arrival_ts=arrival,
                              first_scheduled_ts=arrival,
                              first_token_ts=first_ts,
                              finish_ts=time.time(),
                              prompt_tokens=8, output_tokens=n_tokens)
            return web.json_response({
                "id": request_id,
                "object": "chat.completion",
                "created": int(time.time()),
                "model": model,
                "choices": [{
                    "index": 0,
                    "message": {"role": "assistant",
                                "content": "".join(words)},
                    "finish_reason": "stop",
                }],
                "usage": {
                    "prompt_tokens": 0,
                    "completion_tokens": n_tokens,
                    "total_tokens": n_tokens,
                },
            }, headers=_echo_headers(request))
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            **_echo_headers(request),
        })
        await resp.prepare(request)
        await resp.write(_sse(_chunk(request_id, model, None,
                                     role="assistant")))
        for i, word in enumerate(words):
            if state.fault == "abort_mid_stream" and i >= 2:
                # A couple of chunks are downstream; now drop the socket
                # without a terminating chunk or [DONE].
                if tracer is not None:
                    tracer.finish(request_id, reason="abort",
                                  arrival_ts=arrival,
                                  first_token_ts=first_ts)
                if request.transport is not None:
                    request.transport.close()
                return resp
            if (state.fault == "crash"
                    and i >= state.crash_after_tokens):
                _sigkill_self()
            if state.fault == "hang_step":
                # A wedged device step: the stream stalls open while
                # /health reports the watchdog trip.
                await asyncio.sleep(3600)
            await asyncio.sleep(tok_delay)
            await resp.write(_sse(_chunk(request_id, model, word)))
            if (state.checkpoint_interval > 0
                    and (i + 1) % state.checkpoint_interval == 0):
                await resp.write(_ckpt_frame(request_id, model,
                                             n_tokens, i + 1))
                if state.migrate_drain and i + 1 < n_tokens:
                    # Migrate-mode drain cut (docs/fleet.md): the
                    # checkpoint just shipped; dropping the socket
                    # abruptly (no FIN handshake semantics a client
                    # would read as completion) makes the router
                    # resume the stream byte-exactly on a live
                    # replica instead of waiting this one out.
                    if tracer is not None:
                        tracer.event(request_id, "migrate_ship",
                                     tokens_done=i + 1)
                        tracer.finish(request_id, reason="migrate",
                                      arrival_ts=arrival,
                                      first_token_ts=first_ts,
                                      prompt_tokens=8,
                                      output_tokens=i + 1)
                    # In-band marker so the router classifies this cut
                    # as a migration even before its dynamic-config
                    # watcher observes the migrating list.
                    await resp.write(b": migrating\n\n")
                    if request.transport is not None:
                        request.transport.close()
                    return resp
        await resp.write(_sse(_chunk(request_id, model, None,
                                     finish="stop")))
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        state.total_served += 1
        if tracer is not None:
            tracer.finish(request_id, reason="stop",
                          arrival_ts=arrival,
                          first_scheduled_ts=arrival,
                          first_token_ts=first_ts,
                          finish_ts=time.time(),
                          prompt_tokens=8, output_tokens=n_tokens)
        return resp
    finally:
        state.running -= 1
        if sem is not None:
            sem.release()


async def completions(request: web.Request) -> web.Response:
    state: FakeEngineState = request.app["state"]
    state.requests_received += 1
    fault_resp = await _apply_api_fault(state, request)
    if fault_resp is not None:
        return fault_resp
    body = await request.json()
    n_tokens = int(body.get("max_tokens") or state.max_tokens_default)
    hit_frac = state.observe_prefix(body)
    sem = state.slot_sem()
    if sem is not None:
        state.waiting += 1
        try:
            await sem.acquire()
        finally:
            state.waiting -= 1
    state.running += 1
    try:
        # Same SLO-breach timing faults as chat_completions: the whole
        # body is delayed by the faulted ttft + per-token cadence.
        ttft_eff = state.ttft * (1.0 - 0.9 * hit_frac)
        if state.fault in ("slow_ttft", "degrade_new_revision"):
            ttft_eff += state.slow_ttft_s
        tok_delay = (state.slow_itl_s
                     if state.fault in ("slow_itl",
                                        "degrade_new_revision")
                     else 1.0 / state.speed)
        await asyncio.sleep(ttft_eff + n_tokens * tok_delay)
        state.total_served += 1
        return web.json_response({
            "id": f"cmpl-{uuid.uuid4().hex[:16]}",
            "object": "text_completion",
            "created": int(time.time()),
            "model": body.get("model", state.model),
            "choices": [{
                "index": 0,
                "text": " ".join(f"tok{i}" for i in range(n_tokens)),
                "finish_reason": "length",
            }],
            "usage": {"prompt_tokens": 0, "completion_tokens": n_tokens,
                      "total_tokens": n_tokens},
        })
    finally:
        state.running -= 1
        if sem is not None:
            sem.release()


async def disagg_prefill(request: web.Request) -> web.Response:
    """Fake prefill hop: returns a handoff descriptor without doing any
    work. Under the ``kv_missing`` fault the descriptor is poisoned
    (``pages_available: false``) so a well-behaved decode fake 409s it."""
    state: FakeEngineState = request.app["state"]
    state.requests_received += 1
    fault_resp = await _apply_api_fault(state, request)
    if fault_resp is not None:
        return fault_resp
    body = await request.json()
    n_tokens = int(
        body.get("max_tokens")
        or body.get("max_completion_tokens")
        or state.max_tokens_default
    )
    chat = isinstance(body.get("messages"), list)
    seq_id = f"disagg-{uuid.uuid4().hex[:16]}"
    tracer, arrival = state.tracer, time.time()
    if tracer is not None:
        tracer.start(seq_id,
                     request_id=request.headers.get("x-request-id"),
                     prompt_tokens=8)
    await asyncio.sleep(state.ttft)
    state.disagg_prefills += 1
    state.total_served += 1
    available = state.fault != "kv_missing"
    if tracer is not None:
        first_ts = time.time()
        tracer.event(seq_id, "prefill_chunk",
                     start=0, tokens=8, last=True)
        tracer.event(seq_id, "first_token", token=0)
        tracer.event(seq_id, "handoff_ship",
                     num_pages=1 if available else 0,
                     kv_bytes=4096 if available else 0)
        tracer.finish(seq_id, reason="handoff", arrival_ts=arrival,
                      first_scheduled_ts=arrival, first_token_ts=first_ts,
                      finish_ts=first_ts, prompt_tokens=8,
                      output_tokens=1)
    return web.json_response({"descriptor": {
        "version": 1,
        "request_id": seq_id,
        "chat": chat,
        "model": body.get("model", state.model),
        "token_ids": [0] * 8,
        "first_token": 0,
        "finish_reason": None,
        "kv_dtype": "bf16",
        "page_keys": ["fake-page-0"] if available else [],
        "num_pages": 1 if available else 0,
        "kv_bytes": 4096 if available else 0,
        "pages_available": available,
        "sampling": {"max_tokens": n_tokens},
    }}, headers=_echo_headers(request))


async def disagg_handoff(request: web.Request) -> web.StreamResponse:
    """Fake decode hop: streams the same token text the monolithic fake
    endpoints produce, resuming from a prefill fake's descriptor.
    Answers 409 for poisoned descriptors or under its own
    ``kv_missing`` fault — the router must fall back monolithically."""
    state: FakeEngineState = request.app["state"]
    state.requests_received += 1
    fault_resp = await _apply_api_fault(state, request)
    if fault_resp is not None:
        return fault_resp
    body = await request.json()
    desc = body.get("descriptor") or {}
    if state.fault == "kv_missing" or not desc.get("pages_available", True):
        return web.json_response(
            {"error": {"message": "handoff KV not restorable here"}},
            status=409,
        )
    n_tokens = int(
        (desc.get("sampling") or {}).get("max_tokens")
        or state.max_tokens_default
    )
    stream = bool(body.get("stream", False))
    chat = bool(desc.get("chat", True))
    model = desc.get("model", state.model)
    request_id = f"chatcmpl-{uuid.uuid4().hex[:16]}"
    words = [f"tok{i} " for i in range(n_tokens)]
    tracer, arrival = state.tracer, time.time()
    if tracer is not None:
        tracer.start(request_id,
                     request_id=request.headers.get("x-request-id"),
                     prompt_tokens=len(desc.get("token_ids") or []))
        tracer.event(request_id, "awaiting_kv_park")
        tracer.event(request_id, "awaiting_kv_restore",
                     waited_ms=0.0, outcome="ready")
        tracer.event(request_id, "first_token",
                     token=int(desc.get("first_token") or 0))

    def _finish_span(reason: str) -> None:
        if tracer is not None:
            tracer.finish(request_id, reason=reason, arrival_ts=arrival,
                          first_scheduled_ts=arrival,
                          first_token_ts=arrival, finish_ts=time.time(),
                          prompt_tokens=len(desc.get("token_ids") or []),
                          output_tokens=n_tokens)

    state.running += 1
    state.disagg_decodes += 1
    try:
        if not stream:
            await asyncio.sleep(n_tokens / state.speed)
            state.total_served += 1
            _finish_span("stop")
            if chat:
                return web.json_response({
                    "id": request_id,
                    "object": "chat.completion",
                    "created": int(time.time()),
                    "model": model,
                    "choices": [{
                        "index": 0,
                        "message": {"role": "assistant",
                                    "content": "".join(words)},
                        "finish_reason": "stop",
                    }],
                    "usage": {
                        "prompt_tokens": 0,
                        "completion_tokens": n_tokens,
                        "total_tokens": n_tokens,
                    },
                }, headers=_echo_headers(request))
            return web.json_response({
                "id": f"cmpl-{uuid.uuid4().hex[:16]}",
                "object": "text_completion",
                "created": int(time.time()),
                "model": model,
                "choices": [{
                    "index": 0,
                    "text": " ".join(f"tok{i}" for i in range(n_tokens)),
                    "finish_reason": "length",
                }],
                "usage": {"prompt_tokens": 0,
                          "completion_tokens": n_tokens,
                          "total_tokens": n_tokens},
            }, headers=_echo_headers(request))
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            **_echo_headers(request),
        })
        await resp.prepare(request)
        await resp.write(_sse(_chunk(request_id, model, None,
                                     role="assistant")))
        for i, word in enumerate(words):
            if state.fault == "abort_mid_stream" and i >= 2:
                _finish_span("abort")
                if request.transport is not None:
                    request.transport.close()
                return resp
            await asyncio.sleep(1.0 / state.speed)
            await resp.write(_sse(_chunk(request_id, model, word)))
        await resp.write(_sse(_chunk(request_id, model, None,
                                     finish="stop")))
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        state.total_served += 1
        _finish_span("stop")
        return resp
    finally:
        state.running -= 1


async def resume(request: web.Request) -> web.StreamResponse:
    """POST /v1/resume stub (docs/crash_recovery.md): regenerate the
    deterministic token text from the descriptor, skip what the router
    already delivered, and stream the rest — no role chunk, same
    response id — so the concatenated client stream matches an
    uninterrupted run. Keeps the checkpoint cadence (and the crash
    fault) active, so a resumed stream can crash and resume again."""
    state: FakeEngineState = request.app["state"]
    state.requests_received += 1
    fault_resp = await _apply_api_fault(state, request)
    if fault_resp is not None:
        return fault_resp
    body = await request.json()
    desc = body.get("descriptor") or {}
    if not desc.get("fake"):
        return web.json_response(
            {"error": {"message": "descriptor did not come from a "
                                  "fake engine"}}, status=400)
    delivered = int(body.get("delivered_text_chars") or 0)
    n_tokens = int(desc.get("n_tokens") or state.max_tokens_default)
    model = desc.get("model", state.model)
    request_id = (desc.get("response_id")
                  or f"chatcmpl-{uuid.uuid4().hex[:16]}")
    words = [f"tok{i} " for i in range(n_tokens)]
    state.stream_resumes += 1
    state.running += 1
    tracer, arrival = state.tracer, time.time()
    if tracer is not None:
        tracer.start(request_id,
                     request_id=request.headers.get("x-request-id"),
                     prompt_tokens=8)
        tracer.event(request_id, "resume_restore",
                     prior_tokens=int(desc.get("output_tokens") or 0))
    try:
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            **_echo_headers(request),
        })
        await resp.prepare(request)
        pos = 0
        emitted = 0
        for i, word in enumerate(words):
            end = pos + len(word)
            if end <= delivered:
                pos = end
                continue
            text = word if pos >= delivered else word[delivered - pos:]
            pos = end
            if (state.fault == "crash"
                    and emitted >= state.crash_after_tokens):
                _sigkill_self()
            if state.fault == "hang_step":
                await asyncio.sleep(3600)
            await asyncio.sleep(1.0 / state.speed)
            await resp.write(_sse(_chunk(request_id, model, text)))
            emitted += 1
            if (state.checkpoint_interval > 0
                    and (i + 1) % state.checkpoint_interval == 0):
                await resp.write(_ckpt_frame(request_id, model,
                                             n_tokens, i + 1))
                if state.migrate_drain and i + 1 < n_tokens:
                    # Same migrate cut as chat_completions: a resumed
                    # stream can migrate onward mid-roll.
                    if tracer is not None:
                        tracer.event(request_id, "migrate_ship",
                                     tokens_done=i + 1)
                        tracer.finish(request_id, reason="migrate",
                                      arrival_ts=arrival,
                                      prompt_tokens=8,
                                      output_tokens=i + 1)
                    # Same in-band migration marker as the original
                    # stream leg.
                    await resp.write(b": migrating\n\n")
                    if request.transport is not None:
                        request.transport.close()
                    return resp
        await resp.write(_sse(_chunk(request_id, model, None,
                                     finish="stop")))
        await resp.write(b"data: [DONE]\n\n")
        await resp.write_eof()
        state.total_served += 1
        if tracer is not None:
            tracer.finish(request_id, reason="stop",
                          arrival_ts=arrival,
                          first_scheduled_ts=arrival,
                          first_token_ts=arrival,
                          finish_ts=time.time(),
                          prompt_tokens=8, output_tokens=n_tokens)
        return resp
    finally:
        state.running -= 1


async def models(request: web.Request) -> web.Response:
    state: FakeEngineState = request.app["state"]
    return web.json_response({
        "object": "list",
        "data": [{
            "id": state.model, "object": "model",
            "created": int(time.time()), "owned_by": "fake-engine",
        }],
    })


async def health(request: web.Request) -> web.Response:
    state: FakeEngineState = request.app["state"]
    if state.fault in ("error500", "unhealthy"):
        return web.json_response({"status": "injected fault"}, status=500)
    if state.fault == "hang_step":
        # Same contract as the real server's --step-watchdog-s trip:
        # the prober rotates the wedged replica out on this 503.
        return web.json_response({
            "status": "watchdog",
            "stuck_step_s": 3600.0,
            "role": state.role,
            "draining": state.draining,
            "active_requests": state.running,
            "build_id": state.build_id,
        }, status=503)
    if state.fault == "hang":
        await asyncio.sleep(3600)
    return web.json_response({
        "status": "ok",
        "role": state.role,
        "draining": state.draining,
        "active_requests": state.running,
        "build_id": state.build_id,
    })


async def drain(request: web.Request) -> web.Response:
    """POST /drain: same contract as the real engine server — reject
    new admissions 503+Retry-After, finish in-flight streams, and with
    ``{"exit": true}`` exit the process once idle."""
    state: FakeEngineState = request.app["state"]
    body: dict = {}
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:
            body = {}
    state.draining = True
    if body.get("migrate"):
        state.migrate_drain = True
    if body.get("exit"):
        async def exit_when_idle():
            import os
            import signal
            while state.running > 0:
                await asyncio.sleep(0.02)
            os.kill(os.getpid(), signal.SIGTERM)
        asyncio.ensure_future(exit_when_idle())
    return web.json_response({
        "status": "draining",
        "active_requests": state.running,
        "running": state.running,
        "waiting": state.waiting,
    })


async def set_gauges(request: web.Request) -> web.Response:
    """POST /gauges: deterministic load-gauge injection for autoscaler
    tests — drive the SLO signals the fleet manager scrapes without
    generating real load. {"waiting": 12, "cache_usage": 0.95};
    null/absent clears an override."""
    state: FakeEngineState = request.app["state"]
    body = await request.json()
    if "waiting" in body:
        state.waiting = int(body["waiting"] or 0)
    if "cache_usage" in body:
        state.cache_usage = (None if body["cache_usage"] is None
                             else float(body["cache_usage"]))
    return web.json_response({
        "waiting": state.waiting,
        "cache_usage": state.cache_usage,
    })


async def set_fault(request: web.Request) -> web.Response:
    """Runtime fault control: POST /fault {"mode": "error500" | null}."""
    state: FakeEngineState = request.app["state"]
    body = await request.json()
    mode = body.get("mode")
    if mode is not None and mode not in FAULT_MODES:
        return web.json_response(
            {"error": f"unknown fault mode {mode!r}; "
                      f"one of {list(FAULT_MODES)}"},
            status=400,
        )
    state.fault = mode
    if "fault_ttft" in body:
        state.fault_ttft = float(body["fault_ttft"])
    if "slow_ttft_s" in body:
        state.slow_ttft_s = float(body["slow_ttft_s"])
    if "slow_itl_s" in body:
        state.slow_itl_s = float(body["slow_itl_s"])
    return web.json_response({"fault": state.fault})


async def debug_trace(request: web.Request) -> web.Response:
    """GET /debug/trace/{request_id}: same flight-recorder lookup the
    real engine server exposes (docs/observability.md)."""
    state: FakeEngineState = request.app["state"]
    if state.tracer is None:
        return web.json_response(
            {"error": {"message": "tracing disabled"}}, status=404)
    found = state.tracer.lookup(request.match_info["request_id"])
    if found is None:
        return web.json_response(
            {"error": {"message": "no trace for that id"}}, status=404)
    return web.json_response(found)


async def kv_summary(request: web.Request) -> web.Response:
    """GET /kv/summary: same schema as the real engine server
    (docs/kv_economy.md), derived from the fake's capped hot set —
    or from a POST /kv/summary override."""
    state: FakeEngineState = request.app["state"]
    return web.json_response(state.kv_summary_payload())


async def set_kv_summary(request: web.Request) -> web.Response:
    """POST /kv/summary: pin the summary payload for router tests
    ({"hot_chains": [[hash, hits], ...], "free_pages": N,
    "total_pages": N, "kv_dtype": "bf16"}); null body/empty object
    clears the override back to derived state."""
    state: FakeEngineState = request.app["state"]
    body = await request.json()
    state.kv_summary_override = body or None
    return web.json_response(state.kv_summary_payload())


async def set_autotune_knobs(request: web.Request) -> web.Response:
    """POST /autotune/knobs: plant the self-tuning state this fake
    reports — {"mode": "on", "knobs": {"spec_k": 4}, "frozen":
    {"spec_k": true}, "decisions": {"spec_k": 12}} — each key optional,
    merged into current state; {"clear": true} resets everything.
    Echoes the resulting state (same shape as GET /autotune/status)."""
    state: FakeEngineState = request.app["state"]
    body = await request.json()
    if body.get("clear"):
        state.autotune_mode = "off"
        state.autotune_knobs = {}
        state.autotune_frozen = {}
        state.autotune_decisions = {}
    if "mode" in body:
        state.autotune_mode = str(body["mode"])
    for name, val in (body.get("knobs") or {}).items():
        state.autotune_knobs[str(name)] = float(val)
    for name, val in (body.get("frozen") or {}).items():
        state.autotune_frozen[str(name)] = bool(val)
    for name, val in (body.get("decisions") or {}).items():
        state.autotune_decisions[str(name)] = float(val)
    return await autotune_status(request)


async def autotune_status(request: web.Request) -> web.Response:
    """GET /autotune/status: same shape as the real server's handler,
    fed from the planted knob/frozen/decision state."""
    state: FakeEngineState = request.app["state"]
    return web.json_response({
        "mode": state.autotune_mode,
        "interval_s": 2.0,
        "active_controllers": state.autotune_active(),
        "controllers": [
            {"name": name,
             "knob": state.autotune_knobs[name],
             "lo": 0.0, "hi": 0.0,
             "frozen": bool(state.autotune_frozen.get(name)),
             "decisions": int(state.autotune_decisions.get(name, 0)),
             "applied": int(state.autotune_decisions.get(name, 0))}
            for name in sorted(state.autotune_knobs)
        ],
    })


async def autotune_reset(request: web.Request) -> web.Response:
    """POST /autotune/reset: operator unfreeze, same contract as the
    real server — optional {"controller": name} limits the reset."""
    state: FakeEngineState = request.app["state"]
    target = None
    if request.can_read_body:
        try:
            target = (await request.json()).get("controller")
        except Exception:
            target = None
    if target is None:
        cleared = [k for k, v in sorted(state.autotune_frozen.items())
                   if v]
        state.autotune_frozen = {}
    else:
        cleared = ([target]
                   if state.autotune_frozen.pop(target, False) else [])
    return web.json_response({"reset": cleared})


async def metrics(request: web.Request) -> web.Response:
    state: FakeEngineState = request.app["state"]
    cache_usage = (state.cache_usage if state.cache_usage is not None
                   else min(1.0, state.running / 16))
    kvs = state.kv_summary_payload()
    text = "\n".join([
        "# TYPE vllm:num_requests_running gauge",
        f"vllm:num_requests_running {float(state.running)}",
        "# TYPE vllm:num_requests_waiting gauge",
        f"vllm:num_requests_waiting {float(state.waiting)}",
        "# TYPE vllm:num_requests_total counter",
        f"vllm:num_requests_total {float(state.total_served)}",
        "# TYPE vllm:gpu_prefix_cache_hit_rate gauge",
        "vllm:gpu_prefix_cache_hit_rate "
        f"{float(state.prefix_hit_rate())}",
        "# TYPE vllm:gpu_cache_usage_perc gauge",
        f"vllm:gpu_cache_usage_perc {float(cache_usage)}",
        # Cluster KV economy (docs/kv_economy.md): mirrors the real
        # server's summary gauges; the cluster counters stay 0 (the
        # fake has no offload tier) to keep the scrape surface stable.
        "# TYPE vllm:kv_summary_hot_chains gauge",
        f"vllm:kv_summary_hot_chains {float(len(kvs['hot_chains']))}",
        "# TYPE vllm:kv_free_page_headroom gauge",
        f"vllm:kv_free_page_headroom {float(kvs['free_pages'])}",
        "# TYPE vllm:kv_total_pages gauge",
        f"vllm:kv_total_pages {float(kvs['total_pages'])}",
        "# TYPE vllm:kv_cluster_hits_total counter",
        "vllm:kv_cluster_hits_total 0.0",
        "# TYPE vllm:kv_cluster_misses_total counter",
        "vllm:kv_cluster_misses_total 0.0",
        "# TYPE vllm:kv_cluster_admissions_total counter",
        "vllm:kv_cluster_admissions_total 0.0",
        "# TYPE vllm:kv_cluster_rejections_total counter",
        "vllm:kv_cluster_rejections_total 0.0",
        "# TYPE vllm:engine_draining gauge",
        f"vllm:engine_draining {float(state.draining)}",
        # Self-tuning (docs/autotuning.md): planted via
        # POST /autotune/knobs — same families as the real server.
        "# TYPE vllm:autotune_active_controllers gauge",
        "vllm:autotune_active_controllers "
        f"{float(state.autotune_active())}",
        "# TYPE vllm:autotune_frozen gauge",
        *(
            "vllm:autotune_frozen{controller=\"" f"{name}\"}} "
            f"{float(bool(frozen))}"
            for name, frozen in sorted(state.autotune_frozen.items())
        ),
        "# TYPE vllm:autotune_knob_value gauge",
        *(
            "vllm:autotune_knob_value{controller=\"" f"{name}\"}} "
            f"{float(value)}"
            for name, value in sorted(state.autotune_knobs.items())
        ),
        "# TYPE vllm:autotune_decisions_total counter",
        *(
            "vllm:autotune_decisions_total{controller=\"" f"{name}\"}} "
            f"{float(count)}"
            for name, count in sorted(state.autotune_decisions.items())
        ),
        "# TYPE vllm:qos_shed_total counter",
        *(
            "vllm:qos_shed_total{class=\"" f"{cls}\"}} {float(count)}"
            for cls, count in sorted(state.qos_shed_counts.items())
        ),
        # Device performance observatory (docs/observability.md):
        # static deterministic values so router-side scrape/re-export
        # tests run without JAX.
        "# TYPE vllm:engine_compile_events_total counter",
        'vllm:engine_compile_events_total{kind="step"} 3.0',
        'vllm:engine_compile_events_total{kind="unified"} 1.0',
        "# TYPE vllm:engine_compile_seconds_total counter",
        'vllm:engine_compile_seconds_total{kind="step"} 1.25',
        'vllm:engine_compile_seconds_total{kind="unified"} 0.5',
        "# TYPE vllm:engine_executable_cache_size gauge",
        'vllm:engine_executable_cache_size{kind="step"} 3.0',
        'vllm:engine_executable_cache_size{kind="unified"} 1.0',
        "# TYPE vllm:engine_hbm_bytes gauge",
        'vllm:engine_hbm_bytes{category="weights"} 1048576.0',
        'vllm:engine_hbm_bytes{category="kv_pages"} 524288.0',
        'vllm:engine_hbm_bytes{category="kv_scales"} 0.0',
        'vllm:engine_hbm_bytes{category="step_buffers"} 65536.0',
        "# TYPE vllm:engine_step_device_seconds_total counter",
        'vllm:engine_step_device_seconds_total{kind="decode"} 2.5',
        # Step-time medians (drift sentinel, obs/drift.py): static
        # values matching observability/perf_baseline.json, so an
        # unmodified fake reads as "no drift".
        "# TYPE vllm:engine_step_time_median_seconds gauge",
        'vllm:engine_step_time_median_seconds{kind="decode"} 0.025',
        'vllm:engine_step_time_median_seconds{kind="prefill"} 0.5',
        "# TYPE vllm:engine_mfu gauge",
        "vllm:engine_mfu 0.37",
        "# TYPE vllm:engine_attention_impl gauge",
        'vllm:engine_attention_impl{phase="decode",impl="xla"} 1.0',
        'vllm:engine_attention_impl{phase="prefill",impl="xla"} 1.0',
        "",
    ])
    return web.Response(text=text, content_type="text/plain")


async def cluster_status(request: web.Request) -> web.Response:
    """GET /cluster/status: a /cluster/status-shaped snapshot with
    this fake as the only server — built through the same
    obs.cluster_status rollup the router uses, so stacktop render
    tests exercise the real payload shape without a router."""
    from types import SimpleNamespace

    from production_stack_tpu.obs.cluster_status import build_snapshot

    state: FakeEngineState = request.app["state"]
    kvs = state.kv_summary_payload()
    cache_usage = (state.cache_usage if state.cache_usage is not None
                   else min(1.0, state.running / 16))
    stats = SimpleNamespace(
        num_running_requests=state.running,
        num_queuing_requests=state.waiting,
        kv_usage_perc=float(cache_usage),
        kv_cache_hit_rate=state.prefix_hit_rate(),
        engine_draining=float(state.draining),
        kv_summary_hot_chains=float(len(kvs["hot_chains"])),
        kv_free_page_headroom=float(kvs["free_pages"]),
        kv_total_pages=float(kvs["total_pages"]),
        kv_summary_time=time.time(),
        qos_shed_by_class=dict(state.qos_shed_counts),
        compile_events_by_kind={"step": 3.0, "unified": 1.0},
        engine_mfu=0.37,
        hbm_bytes_by_category={"weights": 1048576.0,
                               "kv_pages": 524288.0,
                               "kv_scales": 0.0,
                               "step_buffers": 65536.0},
        step_time_median_by_kind={"decode": 0.025, "prefill": 0.5},
        autotune_active_controllers=float(state.autotune_active()),
        autotune_frozen_by_controller={
            k: float(bool(v))
            for k, v in state.autotune_frozen.items()},
        autotune_knob_by_controller=dict(state.autotune_knobs),
    )
    url = f"http://{request.host}"
    ep = SimpleNamespace(url=url, model_name=state.model,
                         role=state.role)
    return web.json_response(
        build_snapshot({url: stats}, endpoints=[ep],
                       healthy={url: state.fault not in
                                ("error500", "unhealthy")}))


async def debug_compiles(request: web.Request) -> web.Response:
    """GET /debug/compiles[?limit=N]: deterministic compile-ledger
    payload matching the real server's shape (engine/server.py
    debug_compiles)."""
    try:
        limit = int(request.query.get("limit", "32"))
    except ValueError:
        return web.json_response(
            {"error": {"message": "limit must be an integer"}},
            status=400)
    def split(trace_s, lower_s, backend_s, cache_read_s, cache):
        return {"trace_s": trace_s, "lower_s": lower_s,
                "backend_s": backend_s, "cache_read_s": cache_read_s,
                "cache": cache}

    recent = [
        {"kind": "step", "key": [4, 16], "seconds": 0.4, "ts": 0.0,
         **split(0.1, 0.1, 0.15, 0.05, "hit")},
        {"kind": "step", "key": [4, 32], "seconds": 0.45, "ts": 1.0,
         **split(0.1, 0.1, 0.2, 0.0, "miss")},
        {"kind": "step", "key": [8, 32], "seconds": 0.4, "ts": 2.0,
         **split(0.1, 0.1, 0.15, 0.05, "hit")},
        {"kind": "unified", "key": [12, 32], "seconds": 0.5, "ts": 3.0,
         **split(0.15, 0.1, 0.2, 0.1, "hit")},
    ]
    return web.json_response({
        "events": {"step": 3, "unified": 1},
        "seconds": {"step": 1.25, "unified": 0.5},
        "parts": {kind: {part: round(sum(r[part] for r in recent
                                         if r["kind"] == kind), 6)
                         for part in ("trace_s", "lower_s", "backend_s",
                                      "cache_read_s")}
                  for kind in ("step", "unified")},
        "cache": {"hit": 3, "miss": 1},
        "executable_cache_sizes": {"step": 3, "unified": 1},
        "recent": recent[-limit:] if limit >= 0 else recent,
    })


async def version(request: web.Request) -> web.Response:
    """GET /version: the identity fields of the real server's reply
    (the package version — the fake IS this package — and the
    deployed build id for rollout membership checks) and the start's
    spans, from the same class as the real server's. The real server
    also names its device and attention impls; the fake has none."""
    state: FakeEngineState = request.app["state"]
    return web.json_response({"version": __version__,
                              "build_id": state.build_id,
                              "startup": state.startup.to_dict()})


async def debug_steps(request: web.Request) -> web.Response:
    """GET /debug/steps[?limit=N]: the fake's flight recorder (same
    EngineTracer class as the real engine), same 404/400 contract as
    engine/server.py debug_steps."""
    state: FakeEngineState = request.app["state"]
    if state.tracer is None:
        return web.json_response(
            {"error": {"message": "tracing disabled"}}, status=404)
    try:
        limit = int(request.query.get("limit", "100"))
    except ValueError:
        return web.json_response(
            {"error": {"message": "limit must be an integer"}},
            status=400)
    return web.json_response(
        {"steps": state.tracer.recent_steps(limit=limit)})


async def debug_memory(request: web.Request) -> web.Response:
    """GET /debug/memory: deterministic HBM-ledger payload matching
    the real server's shape (engine/server.py debug_memory)."""
    analytic = {"weights": 1048576, "kv_pages": 524288,
                "kv_scales": 0, "step_buffers": 65536}
    return web.json_response({
        "analytic": analytic,
        "total_analytic_bytes": sum(analytic.values()),
        "kv_cache_dtype": "bf16",
        "num_pages": 512,
        "page_size": 16,
        "param_count": 524288,
    })


def build_fake_engine(model: str = "fake/model", speed: float = 100.0,
                      ttft: float = 0.02, fault: Optional[str] = None,
                      fault_ttft: float = 5.0, role: str = "both",
                      span_log: Optional[str] = None,
                      trace_ring: int = 256,
                      priority_aware: bool = False,
                      max_concurrency: int = 0,
                      checkpoint_interval: int = 0,
                      crash_after_tokens: int = 4,
                      kv_hot_capacity: int = 128,
                      kv_total_pages: int = 512,
                      build_id: str = "") -> web.Application:
    state = FakeEngineState(model=model, speed=speed, ttft=ttft,
                            fault=fault, fault_ttft=fault_ttft,
                            role=role, priority_aware=priority_aware,
                            max_concurrency=max_concurrency,
                            checkpoint_interval=checkpoint_interval,
                            crash_after_tokens=crash_after_tokens,
                            kv_hot_capacity=kv_hot_capacity,
                            kv_total_pages=kv_total_pages,
                            build_id=build_id)
    if span_log or trace_ring > 0:
        # Same default as the real server: flight recorder on, span
        # log only when a path is given.
        state.tracer = EngineTracer(span_log_path=span_log,
                                    ring_size=max(1, trace_ring),
                                    role=role)
    app = web.Application()
    app["state"] = state
    app.router.add_post("/v1/chat/completions", chat_completions)
    app.router.add_post("/v1/completions", completions)
    app.router.add_post("/v1/disagg/prefill", disagg_prefill)
    app.router.add_post("/v1/disagg/handoff", disagg_handoff)
    app.router.add_post("/v1/resume", resume)
    app.router.add_get("/v1/models", models)
    app.router.add_get("/health", health)
    app.router.add_get("/version", version)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/kv/summary", kv_summary)
    app.router.add_post("/kv/summary", set_kv_summary)
    app.router.add_get("/autotune/status", autotune_status)
    app.router.add_post("/autotune/reset", autotune_reset)
    app.router.add_post("/autotune/knobs", set_autotune_knobs)
    app.router.add_get("/cluster/status", cluster_status)
    app.router.add_get("/debug/trace/{request_id}", debug_trace)
    app.router.add_get("/debug/steps", debug_steps)
    app.router.add_get("/debug/compiles", debug_compiles)
    app.router.add_get("/debug/memory", debug_memory)
    app.router.add_post("/fault", set_fault)
    app.router.add_post("/drain", drain)
    app.router.add_post("/gauges", set_gauges)

    async def on_startup(app):
        state.startup.ready()

    state.startup.enter("boot.listen")
    app.on_startup.append(on_startup)
    return app


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description="Fake OpenAI engine")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=9001)
    parser.add_argument("--model", default="fake/model")
    parser.add_argument("--speed", type=float, default=100.0,
                        help="tokens per second")
    parser.add_argument("--ttft", type=float, default=0.02,
                        help="seconds before first token")
    parser.add_argument("--fault", default=None, choices=FAULT_MODES,
                        help="start with this fault mode active")
    parser.add_argument("--fault-ttft", type=float, default=5.0,
                        help="slow_first_token injected delay (seconds)")
    parser.add_argument("--slow-ttft-s", type=float, default=0.75,
                        help="slow_ttft fault: extra first-token "
                             "delay (seconds)")
    parser.add_argument("--slow-itl-s", type=float, default=0.2,
                        help="slow_itl fault: per-token cadence "
                             "(seconds) replacing 1/speed")
    parser.add_argument("--role", default="both", choices=ENGINE_ROLES,
                        help="engine role reported in /health "
                             "(disaggregated-serving discovery)")
    parser.add_argument("--priority-aware", action="store_true",
                        help="honor the x-priority request header "
                             "(QoS tests; docs/qos.md) — the overload "
                             "fault then sheds only non-interactive "
                             "classes")
    parser.add_argument("--max-concurrency", type=int, default=0,
                        help="decode-slot capacity model: requests "
                             "beyond this many queue (TTFT inflates) "
                             "instead of running concurrently; 0 = "
                             "unlimited")
    parser.add_argument("--span-log", default=None,
                        help="Emit engine-span JSON lines to this "
                             "path ('-' = the process log), same "
                             "format as the real engine server's "
                             "--request-span-log")
    parser.add_argument("--checkpoint-interval-tokens", type=int,
                        default=0,
                        help="Attach a resume descriptor to streams "
                             "every N tokens, like the real engine's "
                             "flag (docs/crash_recovery.md)")
    parser.add_argument("--crash-after-tokens", type=int, default=4,
                        help="With the crash fault: SIGKILL self after "
                             "this many streamed tokens")
    parser.add_argument("--kv-hot-capacity", type=int, default=128,
                        help="Capped LRU hot-prefix set size behind "
                             "GET /kv/summary (docs/kv_economy.md) — "
                             "pinning more distinct prefixes than this "
                             "on one fake thrashes, like a real page "
                             "budget")
    parser.add_argument("--kv-total-pages", type=int, default=512,
                        help="total_pages reported by GET /kv/summary")
    parser.add_argument("--build-id", default="",
                        help="Build revision reported in /version and "
                             "/health, like the real engine's flag — "
                             "rollout tests assert revision membership "
                             "with it (docs/fleet.md)")
    args = parser.parse_args(argv)
    app = build_fake_engine(args.model, args.speed, args.ttft,
                            fault=args.fault, fault_ttft=args.fault_ttft,
                            role=args.role, span_log=args.span_log,
                            priority_aware=args.priority_aware,
                            max_concurrency=args.max_concurrency,
                            checkpoint_interval=(
                                args.checkpoint_interval_tokens),
                            crash_after_tokens=args.crash_after_tokens,
                            kv_hot_capacity=args.kv_hot_capacity,
                            kv_total_pages=args.kv_total_pages,
                            build_id=args.build_id)
    app["state"].slow_ttft_s = args.slow_ttft_s
    app["state"].slow_itl_s = args.slow_itl_s
    web.run_app(app, host=args.host, port=args.port, print=None)


if __name__ == "__main__":
    main()
