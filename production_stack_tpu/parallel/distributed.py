"""Multi-host serving: jax.distributed runtime + step-plan broadcast.

The reference's engines scale across hosts with vLLM's NCCL/Ray stack,
provisioned by the chart (/dev/shm, GPU resources —
deployment-vllm-multi.yaml:84-87,226-233). The TPU equivalent is JAX's
multi-controller model: every host of a slice runs this same program,
``jax.distributed.initialize`` wires the slice together, and jitted
steps over a global ``Mesh`` execute SPMD with XLA collectives riding
ICI (intra-slice) / DCN (inter-slice).

Serving needs one extra piece the SPMD model doesn't give us: the
scheduler (request queue, page allocator) lives only on host 0, but
every host must dispatch the SAME device program each step. The
``MultihostStepBridge`` closes that gap: host 0 authors a step payload
(numpy arrays) and broadcasts it; followers run a receive-execute
loop. All hosts then enter the same compiled step with identical
inputs, so the device programs line up without any per-step consensus
protocol.

The bridge speaks through a pluggable *endpoint* (docs/parallelism.md
§bridge-protocol): ``JaxBroadcastEndpoint`` rides
``multihost_utils.broadcast_one_to_all`` on a real multi-process
deployment, and ``FakeTransport`` provides in-process queue-backed
endpoints so tier-1 tests exercise the exact publish/receive/execute
sequence — including the template structural check and follower step
ordering — without spawning processes. Per-slice liveness
(``SliceLiveness``) rides the same plumbing: followers ack each
executed step (fake transport) or the collective's completion marks
everyone live (real transport — a dead host would hang the
broadcast, which the step watchdog surfaces), so a dead host names
ONE slice on /metrics instead of indicting the whole pool.
"""

from __future__ import annotations

import copy
import threading
import time
from typing import Dict, List, Optional

import numpy as np

import jax

from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)

KIND_SHUTDOWN = 0
KIND_PREFILL = 1
KIND_DECODE = 2
KIND_EMBED = 3  # /v1/embeddings|score|rerank batches (engine/embeddings.py)
KIND_SPEC = 4  # speculative verify step (docs/speculative.md)
KIND_UNIFIED = 5  # mixed ragged step (docs/unified_step.md)


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Join the jax.distributed runtime.

    On GKE TPU slices all arguments auto-detect from the TPU metadata;
    explicit values support bare-metal/CPU rigs (the reference's
    bare-metal flow analogue, run_production_stack/).
    """
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
    logger.info("jax.distributed up: process %d/%d, %d local / %d "
                "global devices", jax.process_index(),
                jax.process_count(), jax.local_device_count(),
                jax.device_count())


def is_coordinator() -> bool:
    return jax.process_index() == 0


# ---- liveness ----------------------------------------------------------


class SliceLiveness:
    """Per-slice liveness ledger: a slice is live while at least one
    of its hosts has been seen within ``timeout_s``.

    Fed by follower acks (fake transport) or collective completion
    (real transport). The point of keying on SLICES rather than the
    pool: when a host dies, /metrics names the one slice to drain and
    replace — the rest of the fleet stays green.
    """

    def __init__(self, num_slices: int = 1, timeout_s: float = 10.0):
        self.num_slices = max(1, int(num_slices))
        self.timeout_s = timeout_s
        now = time.monotonic()
        self._last: Dict[int, float] = {
            i: now for i in range(self.num_slices)}
        self._lock = threading.Lock()

    def heartbeat(self, slice_id: int,
                  now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        with self._lock:
            if slice_id in self._last:
                self._last[slice_id] = max(self._last[slice_id], now)

    def mark_all(self, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        with self._lock:
            for i in self._last:
                self._last[i] = max(self._last[i], now)

    def snapshot(self, now: Optional[float] = None) -> Dict[int, bool]:
        """slice_id -> live?"""
        now = time.monotonic() if now is None else now
        with self._lock:
            return {i: (now - t) <= self.timeout_s
                    for i, t in sorted(self._last.items())}

    def dead_slices(self, now: Optional[float] = None) -> List[int]:
        return [i for i, live in self.snapshot(now).items()
                if not live]


# ---- transports --------------------------------------------------------


def _template_mismatch(template, value) -> Optional[str]:
    """Structural diff between a receive template and the payload that
    actually arrived: None when they agree, else a reason string. The
    real broadcast enforces this implicitly (shape-mismatched
    collectives corrupt or hang); the fake transport enforces it
    loudly so tier-1 catches template drift."""
    if isinstance(template, dict) or isinstance(value, dict):
        if not (isinstance(template, dict) and isinstance(value, dict)):
            return (f"kind mismatch: template {type(template).__name__}"
                    f" vs payload {type(value).__name__}")
        if set(template) != set(value):
            missing = sorted(set(template) - set(value))
            extra = sorted(set(value) - set(template))
            return f"key drift: missing={missing} extra={extra}"
        for k in template:
            why = _template_mismatch(template[k], value[k])
            if why is not None:
                return f"{k}: {why}"
        return None
    t, v = np.asarray(template), np.asarray(value)
    if t.shape != v.shape:
        return f"shape {t.shape} vs {v.shape}"
    if t.dtype != v.dtype:
        return f"dtype {t.dtype} vs {v.dtype}"
    return None


class JaxBroadcastEndpoint:
    """Real transport: host 0's value reaches every process via
    ``multihost_utils.broadcast_one_to_all``. The broadcast is a
    collective, so its completion doubles as an all-hosts-alive
    signal (``collective`` = True)."""

    collective = True

    @property
    def process_index(self) -> int:
        return jax.process_index()

    @property
    def num_processes(self) -> int:
        return jax.process_count()

    def broadcast(self, value):
        from jax.experimental import multihost_utils
        return multihost_utils.broadcast_one_to_all(value)

    def ack(self, seq: int) -> None:
        # The collective already synchronized every process; there is
        # no (and no need for a) backchannel.
        del seq

    def drain_acks(self):
        return []


class FakeTransport:
    """In-process stand-in for the multi-host broadcast: one queue per
    follower, plus a shared ack queue back to the publisher.

    ``endpoint(i)`` hands out the per-process view; endpoint 0
    publishes, endpoints 1..N-1 receive in their own threads. Tier-1
    tests drive the REAL bridge code (publish/worker_loop) over this,
    so follower step ordering, template agreement, and dead-follower
    detection are all pinned without subprocesses.
    """

    def __init__(self, num_processes: int):
        import queue
        if num_processes < 2:
            raise ValueError("FakeTransport needs >= 2 processes")
        self.num_processes = num_processes
        self._queues = [queue.Queue() for _ in range(num_processes)]
        self._acks: "queue.Queue" = queue.Queue()

    def endpoint(self, process_index: int) -> "_FakeEndpoint":
        return _FakeEndpoint(self, process_index)


class _FakeEndpoint:
    collective = False

    def __init__(self, transport: FakeTransport, process_index: int):
        self._transport = transport
        self.process_index = process_index
        self.num_processes = transport.num_processes
        # Follower receive timeout: generous enough for slow CI, small
        # enough that a wedged test fails instead of hanging forever.
        self.recv_timeout_s = 30.0

    def broadcast(self, value):
        if self.process_index == 0:
            for q in self._transport._queues[1:]:
                q.put(copy.deepcopy(value))
            return value
        item = self._transport._queues[self.process_index].get(
            timeout=self.recv_timeout_s)
        why = _template_mismatch(value, item)
        if why is not None:
            raise ValueError(
                f"follower {self.process_index} payload does not "
                f"match its receive template ({why}) — the "
                f"(kind, t, flags) header no longer derives the "
                f"payload shapes")
        return item

    def ack(self, seq: int) -> None:
        self._transport._acks.put(
            (self.process_index, seq, time.monotonic()))

    def drain_acks(self):
        import queue
        out = []
        while True:
            try:
                out.append(self._transport._acks.get_nowait())
            except queue.Empty:
                return out


class MultihostStepBridge:
    """Host-0 -> followers broadcast of per-step device-program inputs.

    Protocol per step: a fixed [kind, t_bucket, flags] int32 header,
    then the payload pytree whose array shapes are a pure function of
    (kind, t_bucket, flags) and the engine config — so followers can
    always offer a matching zero-filled structure to the endpoint's
    ``broadcast``. ``flags`` carries the presence of the optional
    per-request inputs (penalties, seeding, logprobs) whose keys are
    request-dependent rather than config-dependent, and the one row
    width that is the plan's (a prefill step at half the rows).

    Rank 0 owns scheduling; followers mirror its dispatch sequence
    exactly. ``endpoint`` defaults to the real jax.distributed
    broadcast; tier-1 hands in ``FakeTransport`` endpoints.
    ``num_slices`` sizes the liveness ledger — processes map to
    slices contiguously (process grouping is slice-major, matching
    parallel/topology.py's device order).
    """

    FLAG_PENALTIES = 1
    FLAG_SEEDING = 2
    FLAG_LOGPROBS = 4
    FLAG_BIAS = 8
    FLAG_SUPPRESS = 16
    FLAG_GUIDED = 32
    # A prefill step at half the rows (model_runner.prefill_shape).
    FLAG_NARROW = 64

    def __init__(self, runner, endpoint=None, num_slices: int = 1,
                 liveness_timeout_s: float = 10.0):
        self.runner = runner
        self.endpoint = (endpoint if endpoint is not None
                         else JaxBroadcastEndpoint())
        self.num_slices = max(1, int(num_slices))
        self.liveness = SliceLiveness(self.num_slices,
                                      liveness_timeout_s)
        # Monotone per-publish sequence number; follower acks echo the
        # sequence they executed, so ordering bugs surface as stale
        # acks rather than silent divergence.
        self._seq = 0
        # Host 0 publishes from two threads (engine device loop:
        # prefill/decode; embed worker threads: KIND_EMBED). Followers
        # consume one strict header/payload/execute sequence, and XLA
        # collective programs must launch in the same order on every
        # process — so each publish+execute pair must be atomic.
        self.lock = threading.Lock()

    def slice_of_process(self, process_index: int) -> int:
        """Contiguous process -> slice mapping (slice-major hosts)."""
        n = max(1, getattr(self.endpoint, "num_processes", 1))
        return min(self.num_slices - 1,
                   process_index * self.num_slices // n)

    # -- shapes --------------------------------------------------------------

    def _payload_template(self, kind: int, t: int,
                          flags: int = 0) -> Dict[str, np.ndarray]:
        r = self.runner
        if kind == KIND_EMBED:
            # Embed batches have their own (batch_width, token-bucket)
            # geometry; every host built the same Embedder at startup.
            return {
                "tokens": np.zeros((r.embedder.batch_width, t),
                                   np.int32),
                "lengths": np.zeros((r.embedder.batch_width,),
                                    np.int32),
            }
        if kind == KIND_PREFILL:
            b, tt = r.prefill_width, t
            if flags & self.FLAG_NARROW:
                b = -(-b // 2)
        elif kind == KIND_SPEC:
            # Verify steps score t = speculative_k + 1 positions per
            # decode slot; t is static per engine config so the shape
            # is derivable from the header.
            b, tt = r.decode_width, t
        elif kind == KIND_UNIFIED:
            # Mixed ragged step (docs/unified_step.md): decode and
            # prefill rows share one [R, W] block; W rides the header
            # and the row count / draft span are config-static.
            b, tt = r.unified_rows, t
        else:
            b, tt = r.decode_width, 1
        template = {
            "tokens": np.zeros((b, tt), np.int32),
            "positions": np.zeros((b, tt), np.int32),
            "valid": np.zeros((b, tt), bool),
            "page_table": np.zeros((b, r.max_pages_per_seq), np.int32),
            "kv_lens": np.zeros((b,), np.int32),
            "last_index": np.zeros((b,), np.int32),
            "temperature": np.zeros((b,), np.float32),
            "top_p": np.zeros((b,), np.float32),
            "top_k": np.zeros((b,), np.int32),
            "rng": np.zeros((2,), np.uint32),
        }
        if kind == KIND_SPEC:
            # Draft tokens per row (-1 padded) + true draft lengths;
            # the acceptance rule runs in-graph (ops/sampling.py).
            template["drafts"] = np.zeros((b, t - 1), np.int32)
            template["draft_lens"] = np.zeros((b,), np.int32)
        if kind == KIND_UNIFIED:
            # Every unified row carries the draft span (zero-length
            # for prefill/plain-decode rows); width is config-static.
            template["drafts"] = np.zeros(
                (b, r.unified_span - 1), np.int32)
            template["draft_lens"] = np.zeros((b,), np.int32)
        if kind == KIND_DECODE and t > 1:
            # Decode bursts carry per-row lifecycle state
            # (model_runner.dispatch_burst); STOP_SET_WIDTH is fixed so
            # this shape is derivable from the (kind, t) header alone.
            from production_stack_tpu.engine.model_runner import (
                STOP_SET_WIDTH,
            )
            template["active"] = np.zeros((b,), bool)
            template["budgets"] = np.zeros((b,), np.int32)
            template["stop_tokens"] = np.zeros(
                (b, STOP_SET_WIDTH), np.int32)
        if r.lora_registry is not None:
            template["lora_ids"] = np.zeros((b,), np.int32)
        if flags & self.FLAG_PENALTIES:
            v = r.config.model.vocab_size
            template["pen_counts"] = np.zeros((b, v), np.int32)
            template["pen_prompt_mask"] = np.zeros((b, v), bool)
            template["pen_presence"] = np.zeros((b,), np.float32)
            template["pen_frequency"] = np.zeros((b,), np.float32)
            template["pen_repetition"] = np.zeros((b,), np.float32)
        if flags & self.FLAG_SEEDING:
            template["seed_rows"] = np.zeros((b,), np.int32)
            template["seed_on"] = np.zeros((b,), bool)
            template["seed_emitted"] = np.zeros((b,), np.int32)
        if flags & self.FLAG_BIAS:
            template["logit_bias"] = np.zeros(
                (b, r.config.model.vocab_size), np.float32)
        if flags & self.FLAG_SUPPRESS:
            from production_stack_tpu.engine.model_runner import (
                STOP_SET_WIDTH,
            )
            template["sup_ids"] = np.zeros(
                (b, STOP_SET_WIDTH), np.int32)
            template["sup_rem"] = np.zeros((b,), np.int32)
        if flags & self.FLAG_GUIDED:
            # Followers hold identical automaton tables (built eagerly
            # at engine init — engine.py); only the per-row states
            # ride the broadcast.
            template["fsm_state"] = np.zeros((b,), np.int32)
        return template

    # -- host 0 --------------------------------------------------------------

    def payload_flags(self, kind: int,
                      payload: Dict[str, np.ndarray]) -> int:
        """The header's third word: which optional inputs the payload
        carries and whether a prefill step runs at half the rows —
        with (kind, t), all a follower needs to offer its shapes."""
        flags = 0
        if (kind == KIND_PREFILL
                and len(payload["tokens"]) < self.runner.prefill_width):
            flags |= self.FLAG_NARROW
        if "pen_prompt_mask" in payload:
            flags |= self.FLAG_PENALTIES
        if "seed_rows" in payload:
            flags |= self.FLAG_SEEDING
        if payload.get("want_logprobs"):
            flags |= self.FLAG_LOGPROBS
        if "logit_bias" in payload:
            flags |= self.FLAG_BIAS
        if "sup_ids" in payload:
            flags |= self.FLAG_SUPPRESS
        if "fsm_state" in payload:
            flags |= self.FLAG_GUIDED
        return flags

    def publish(self, kind: int, t: int,
                payload: Dict[str, np.ndarray]) -> None:
        flags = self.payload_flags(kind, payload)
        header = np.asarray([kind, t, flags], np.int32)
        self.endpoint.broadcast(header)
        if kind != KIND_SHUTDOWN:
            # want_logprobs is a static python flag, carried in the
            # header (a non-array leaf can't ride the broadcast).
            arrays = {k: v for k, v in payload.items()
                      if k != "want_logprobs"}
            self.endpoint.broadcast(arrays)
        self._seq += 1
        if self.endpoint.collective:
            # broadcast_one_to_all returning means every process
            # participated — the strongest liveness signal available
            # without a backchannel.
            self.liveness.mark_all()
        else:
            # The publisher's own slice is trivially alive.
            self.liveness.heartbeat(self.slice_of_process(
                self.endpoint.process_index))
            self.pump_acks()

    def pump_acks(self) -> None:
        """Fold follower acks into the per-slice liveness ledger."""
        for process_index, _seq, when in self.endpoint.drain_acks():
            self.liveness.heartbeat(
                self.slice_of_process(process_index), when)

    def check_liveness(self) -> Dict[int, bool]:
        """Current slice_id -> live map (drains pending acks first).
        The /metrics per-slice gauges render exactly this."""
        self.pump_acks()
        return self.liveness.snapshot()

    def shutdown(self) -> None:
        """Release followers from their receive loop."""
        with self.lock:
            self.publish(KIND_SHUTDOWN, 0, {})

    # -- followers -----------------------------------------------------------

    def worker_loop(self) -> None:
        """Receive-execute loop for processes > 0. Returns on
        shutdown. Each executed step is acked with its sequence
        number so host 0's liveness ledger sees this process's slice
        making progress."""
        pid = self.endpoint.process_index
        logger.info("follower %d entering step loop", pid)
        seq = 0
        while True:
            header = self.endpoint.broadcast(np.zeros((3,), np.int32))
            kind, t, flags = (int(header[0]), int(header[1]),
                              int(header[2]))
            if kind == KIND_SHUTDOWN:
                logger.info("follower %d shutting down", pid)
                return
            payload = self.endpoint.broadcast(
                self._payload_template(kind, t, flags)
            )
            payload = {k: np.asarray(v) for k, v in payload.items()}
            if flags & self.FLAG_LOGPROBS:
                payload["want_logprobs"] = True
            self.runner.execute_payload(kind, payload, t)
            seq += 1
            self.endpoint.ack(seq)
