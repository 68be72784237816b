"""LLMEngine: ties scheduler + cache manager + model runner together.

Synchronous core (one ``step()`` = one compiled device program) with a
``generate()`` convenience for tests/benchmarks; the HTTP server
(engine/server.py) drives the same core from a background thread and
streams per-token outputs through asyncio queues.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
import uuid
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.kv_cache import PagedCacheManager
from production_stack_tpu.engine.model_runner import ModelRunner
from production_stack_tpu.engine.scheduler import Scheduler, StepPlan
from production_stack_tpu.engine.sequence import (
    SamplingParams,
    Sequence,
    SequenceState,
)
from production_stack_tpu.engine.tokenizer import (
    BaseTokenizer,
    get_tokenizer,
)
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)


@dataclass
class StepOutput:
    seq_id: str
    new_token: Optional[int]
    finished: bool
    finish_reason: Optional[str]
    # (sampled_logprob, [(token_id, logprob), ...]) when the request
    # asked for logprobs; None otherwise.
    logprobs: Optional[tuple] = None


@dataclass
class CommittedRow:
    """A decode row's tokens as the planner's half of the commit left
    them (``Scheduler.commit_decode_tokens``): what the deferred half
    makes the row's StepOutputs from. ``finished`` and
    ``finish_reason`` are the row's as the commit decided them, not as
    an abort between the two halves may have changed them since."""
    seq: Sequence
    tokens: List[int]
    logprobs: Optional[list]
    finished: bool
    finish_reason: Optional[str]
    now: float


@dataclass
class EnqueuedStep:
    """One device program between LLMEngine.begin_step, which planned,
    built and enqueued it, and finish_step, which waits for it."""
    plan: StepPlan
    handle: object  # the runner's: result() is the one blocking read
    commit: Callable  # (plan, result): the planner's half of the commit
    t0: float  # the turn's start, before the plan
    enqueue_s: float  # inside the runner, up to the enqueue


class LLMEngine:
    def __init__(self, config: EngineConfig, mesh=None, params=None,
                 tokenizer: Optional[BaseTokenizer] = None,
                 startup=None):
        self.config = config
        self.tokenizer = tokenizer or get_tokenizer(None)
        self.cache_manager = PagedCacheManager(config.cache)
        sp_threshold = None
        if config.parallel.context_parallel_size > 1:
            sp_threshold = (config.parallel.long_prefill_threshold
                            or 2 * config.scheduler.prefill_chunk_size)
        # Guided JSON decoding (engine/guided.py): built EAGERLY for
        # byte-range tokenizers so multihost workers hold identical
        # tables before the first guided payload arrives (a lazy
        # host-0-only build would desync the step broadcast). HF
        # subword tokenizers: None — the server rejects
        # response_format json_object for them with a 400.
        self.guided_fsm = None
        from production_stack_tpu.engine.tokenizer import ByteTokenizer
        if isinstance(self.tokenizer, ByteTokenizer):
            from production_stack_tpu.engine.guided import build_json_fsm
            self.guided_fsm = build_json_fsm(self.tokenizer)
        self.scheduler = Scheduler(
            config.scheduler, config.cache, self.cache_manager,
            sp_threshold=sp_threshold,
            guided_advance=self._guided_advance,
        )
        # ``startup``: the server's timeline of the start
        # (engine/tracing.py StartupTimeline), the runner's to fill.
        self.runner = ModelRunner(config, mesh=mesh, params=params,
                                  startup=startup)
        if self.guided_fsm is not None:
            self.runner.set_guided_tables(self.guided_fsm)
        self.sequences: Dict[str, Sequence] = {}
        # QoS (docs/qos.md): priority class for requests that don't
        # carry an explicit one.
        from production_stack_tpu.qos import parse_priority
        self.default_priority = int(
            parse_priority(config.qos.default_priority))
        self._lock = threading.Lock()
        from production_stack_tpu.engine.metrics import EngineMetrics
        self.metrics = EngineMetrics()
        self.metrics.moe_held_experts = config.model.num_experts
        # Overlapped async pipeline state (docs/async_pipeline.md):
        # at most ONE dispatched-but-unread decode step. ``_idle_mark``
        # timestamps the moment the device drained its queue so the
        # next dispatch can account the idle gap — the quantity the
        # pipeline exists to shrink.
        self._in_flight = None
        self._idle_mark: Optional[float] = None
        # The deferred half of the commits so far (take_owed): ready
        # StepOutputs and CommittedRows, in the engine's order. The
        # server loop takes them behind its next dispatch
        # (docs/async_pipeline.md, "The served loop").
        self._owed: list = []
        # The program begin_step enqueued and finish_step has not read.
        self._enqueued: Optional[EnqueuedStep] = None
        # Row/spec detail for the step about to be accounted, staged
        # by the execute helpers for the flight recorder (tracer set
        # only); drained by _account_step.
        self._step_note: Optional[dict] = None
        # (kind, useful tokens) for the step about to be accounted,
        # staged by the execute helpers for the device performance
        # observatory's step/MFU ledger; drained by _account_step.
        # A cheap tuple, staged unconditionally (unlike _step_note,
        # which allocates a dict and is tracer-gated).
        self._obs_note: Optional[tuple] = None
        self.offload = None
        if config.offload.enable:
            self._init_offload()
        # Disaggregated serving (docs/disaggregation.md): descriptor
        # payloads for completed prefill handoffs (drained by the
        # server via take_handoff_info) and cumulative role counters.
        self._handoff_info: Dict[str, dict] = {}
        self.disagg_prefill_requests = 0
        self.disagg_decode_requests = 0
        self.disagg_kv_bytes_shipped = 0
        # Mid-stream crash safety (docs/crash_recovery.md): latest
        # resume descriptor per live streaming sequence (drained by
        # the server via take_checkpoint and relayed to the router as
        # an SSE comment frame), plus per-seq cadence/ship bookkeeping
        # and cumulative counters.
        self._checkpoints: Dict[str, dict] = {}
        self._ckpt_last_tokens: Dict[str, int] = {}
        self._ckpt_shipped_pages: Dict[str, int] = {}
        self.checkpoint_ships = 0
        self.checkpoint_kv_bytes = 0
        self.stream_resumes = 0
        # End-to-end tracing (docs/observability.md): the server
        # installs an engine/tracing.EngineTracer here; the library
        # default is None and every emission site is behind an
        # ``is None`` check, so untraced engines allocate no span
        # objects on the hot path.
        self._tracer = None

    @property
    def tracer(self):
        return self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        # Mirrored onto the scheduler so chunk/preempt/first-token
        # events emit without a back-reference to the engine, and onto
        # the runner so it names its turn phases (build, rng, dispatch,
        # wait, parse).
        self._tracer = tracer
        self.scheduler.tracer = tracer
        self.runner.tracer = tracer

    def _init_offload(self) -> None:
        import numpy as np

        from production_stack_tpu.engine.offload import (
            HostKVPool,
            KVOffloadManager,
            RemoteKVClient,
        )
        # A per-process requester id: the managed cluster cache counts
        # DISTINCT requesters demanding a chain for admission
        # promotion (docs/kv_economy.md).
        remote = (RemoteKVClient(
                      self.config.offload.remote_url,
                      requester=f"engine-{uuid.uuid4().hex[:12]}")
                  if self.config.offload.remote_url else None)
        # Tier keys are namespaced by the actual page storage format
        # (int8 vs the model dtype) so pods with different
        # --kv-cache-dtype sharing a remote cache never alias.
        kv_dtype = ("int8" if self.runner.kv_quantized
                    else str(np.dtype(self.config.model.jax_dtype)))
        self.offload = KVOffloadManager(
            host_pool=HostKVPool(
                self.config.offload.host_pool_bytes,
                watermark_high=self.config.kvecon.watermark_high,
                watermark_low=self.config.kvecon.watermark_low),
            remote=remote,
            kv_dtype=kv_dtype,
        )
        self.cache_manager.evict_listener = self._on_page_evicted
        self.scheduler.restore_hook = self._restore_offloaded_prefix
        if self.config.qos.preempt_to_offload:
            # Preempt-to-offload (docs/qos.md): preemption victims
            # ship their committed pages over the same wire instead of
            # discarding them.
            self.scheduler.evict_hook = self._evict_sequence_kv
        logger.info("KV offload enabled (host pool %d MiB%s)",
                    self.config.offload.host_pool_bytes // 2 ** 20,
                    ", remote tier" if remote else "")

    def _on_page_evicted(self, page_id: int, page_hash) -> None:
        # 2 arrays for full-precision pages, 4 (data + scales) for
        # int8 pages; the tiers carry the tuple opaquely.
        payload = self.runner.read_page(page_id)
        self.offload.offload_page(page_hash, *payload)

    def _evict_sequence_kv(self, seq: Sequence) -> int:
        """Preempt-to-offload (docs/qos.md): ship the victim's
        committed KV pages to the offload tier before the scheduler
        frees them, returning the shipped page count.

        The restorable prefix is everything but the last token (the
        prefix-cache ``usable`` bound: the final token must reprefill
        to produce logits), and its KV is fully written — decode
        commits a token's KV one step after sampling it, so positions
        0..total_len-2 are always on device at a plan boundary. The
        generated-token pages are first committed to the hash table
        (prompt-time hashing stopped at the prompt), so the shipped
        chain and the first-touch restore chain are the same
        content-hash sequence — that identity is what makes the
        offload round trip byte-exact. The cache's lazy
        evict_listener cannot do this job: it fires on HBM slot
        reuse, long after the victim's pages were freed."""
        from production_stack_tpu.engine.kv_cache import (
            PagedCacheManager,
        )
        if self.offload is None or not seq.pages:
            return 0
        usable = seq.total_len - 1
        tokens = seq.all_token_ids[:usable]
        self.cache_manager.commit_full_pages(
            tokens, seq.pages, seq.num_hashed_pages, seq.cache_salt)
        hashes = PagedCacheManager.chain_hashes(
            tokens, self.cache_manager.page_size, seq.cache_salt)
        chain = self.offload.chain_id(hashes[0]) if hashes else None
        shipped = 0
        for page_id, page_hash in zip(seq.pages, hashes):
            payload = self.runner.read_page(page_id)
            self.offload.offload_page(page_hash, *payload, chain=chain)
            shipped += 1
        return shipped

    def _restore_offloaded_prefix(self, prompt_token_ids,
                                  matched_pages, cache_salt=0):
        """After an in-HBM prefix miss, pull further pages from the
        host/remote tiers into freshly allocated HBM pages."""
        from production_stack_tpu.engine.kv_cache import (
            OutOfPagesError,
            PagedCacheManager,
        )
        usable = len(prompt_token_ids) - 1
        hashes = PagedCacheManager.chain_hashes(
            prompt_token_ids[:usable], self.cache_manager.page_size,
            cache_salt,
        )
        remaining = hashes[len(matched_pages):]
        n = self.offload.lookup_chain(remaining)
        if n == 0:
            return []
        try:
            pages = self.cache_manager.allocate_pages(n)
        except OutOfPagesError:
            return []
        t0 = time.perf_counter()
        restored = []
        # One batched round trip for every remote miss in the chain
        # (POST /kv/batch_get) instead of N sequential GETs.
        payloads = self.offload.fetch_many(remaining[:n])
        for page_id, page_hash, payload in zip(
                pages, remaining[:n], payloads):
            expected_arity = 4 if self.runner.kv_quantized else 2
            if payload is None or len(payload) != expected_arity:
                # Tier raced an eviction, or a payload with the wrong
                # arity for this pod's page format: stop here (the
                # dtype-namespaced keys make the latter unreachable
                # short of tier corruption).
                self.cache_manager.free_sequence(
                    pages[len(restored):]
                )
                break
            self.runner.write_page(page_id, *payload)
            self.cache_manager.register_restored_page(
                page_id, page_hash
            )
            restored.append(page_id)
        self.offload.restored_pages += len(restored)
        if restored:
            self.cache_manager.prefix_hit_tokens += (
                len(restored) * self.cache_manager.page_size
            )
            # Restore latency (vllm:preempt_restore_latency_seconds):
            # the page-transfer cost that replaced a prompt recompute.
            self.metrics.on_preempt_restore(
                time.perf_counter() - t0)
        return restored

    # ---- request API ------------------------------------------------------

    def add_request(self, prompt_token_ids: List[int],
                    sampling: Optional[SamplingParams] = None,
                    seq_id: Optional[str] = None,
                    output_sink=None,
                    lora_name: Optional[str] = None,
                    handoff_prefill: bool = False,
                    request_id: Optional[str] = None,
                    priority: Optional[int] = None,
                    spec_off: bool = False) -> str:
        sampling = sampling or SamplingParams()
        stop_ids = list(sampling.stop_token_ids)
        if (not sampling.ignore_eos
                and self.tokenizer.eos_token_id is not None
                and self.tokenizer.eos_token_id not in stop_ids):
            stop_ids.append(self.tokenizer.eos_token_id)
        sampling.stop_token_ids = stop_ids
        fsm_state = None
        if sampling.guided is not None:
            if sampling.guided != "json":
                raise ValueError(
                    f"unsupported guided mode {sampling.guided!r} "
                    "(supported: 'json')")
            if self.guided_fsm is None:
                raise ValueError(
                    "guided JSON decoding requires a byte-range "
                    "tokenizer in this build (HF subword tokenizers "
                    "need an outlines-style vocabulary DFA product — "
                    "not yet supported)")
            fsm_state = 0
        self.check_sampling(sampling)
        lora_id = 0
        if lora_name is not None:
            if self.runner.lora_registry is None:
                raise ValueError("LoRA is not enabled on this engine")
            lora_id = self.runner.lora_registry.slot_for(lora_name)
        seq = Sequence(
            seq_id=seq_id or f"seq-{uuid.uuid4().hex[:16]}",
            prompt_token_ids=list(prompt_token_ids),
            sampling=sampling,
            output_sink=output_sink,
            lora_id=lora_id,
            cache_salt=(self.runner.lora_registry.cache_root(lora_id)
                        if lora_id else 0),
            fsm_state=fsm_state,
            handoff_prefill=handoff_prefill,
            request_id=request_id,
            priority=(self.default_priority if priority is None
                      else int(priority)),
            spec_off=spec_off,
        )
        with self._lock:
            if (not handoff_prefill
                    and self._cold_start_target(seq) is not None):
                # Shared cluster cache (docs/kv_economy.md): another
                # engine may already hold this prompt's prefix KV.
                # Park in AWAITING_KV so the step loop probes the
                # shared tier (one HEAD) before prefill — hit means a
                # batched restore instead of recompute, miss or tier
                # down degrades straight to compute.
                seq.transition(SequenceState.AWAITING_KV)
                seq.cold_start_probe = True
                seq.handoff_arrival_time = time.time()
            self.sequences[seq.seq_id] = seq
            try:
                self.scheduler.add_sequence(seq)
            except Exception:
                self.sequences.pop(seq.seq_id, None)
                raise
            if self._tracer is not None:
                self._tracer.start(
                    seq.seq_id, request_id=request_id,
                    prompt_tokens=seq.num_prompt_tokens)
                if seq.cold_start_probe:
                    self._tracer.event(seq.seq_id, "awaiting_kv_park")
        return seq.seq_id

    def check_sampling(self, sampling: SamplingParams) -> None:
        """Raises ValueError for what this model's generation has no
        rule for (the server answers 400). A block-diffusion family's
        request: its three fields made
        whole from the model configuration's defaults, and what a
        block's passes have no rule for refused in words
        (docs/block_diffusion.md). Any other family refuses the three
        fields."""
        from production_stack_tpu.ops.sampling import REMASKING_STRATEGIES
        model = self.config.model
        block = model.block_length
        asked = [name for name in ("denoising_steps", "remasking_strategy",
                                   "confidence_threshold")
                 if getattr(sampling, name) is not None]
        if not block:
            if asked:
                raise ValueError(
                    f"{', '.join(asked)}: {model.architecture} generates "
                    "left to right, a token a step; these are a "
                    "block-diffusion model's")
            return
        refused = [why for bad, why in (
            (sampling.guided is not None,
             "a guided grammar: its automaton walks left to right, and "
             "a block's places are committed in any order"),
            (sampling.needs_penalties,
             "presence, frequency or repetition penalties: they count "
             "the tokens before a place, and a block's places are drawn "
             "together"),
            (bool(sampling.logit_bias),
             "logit_bias: the block's sampler reads the logits as the "
             "head wrote them"),
            (sampling.min_tokens > 0,
             "min_tokens: a stop token is suppressed by how many tokens "
             "precede it, which a block's places do not know when they "
             "are drawn"),
            (sampling.seed is not None,
             "seed: a seeded draw is keyed by a token's index in the "
             "answer, and a place is drawn once a denoising pass until "
             "it is committed"),
        ) if bad]
        if refused:
            raise ValueError(
                f"{model.architecture} generates by diffusion over "
                f"blocks; refused: " + "; ".join(refused))
        steps = sampling.denoising_steps
        if steps is None:
            steps = model.diffusion_steps
        if not 1 <= steps <= block:
            raise ValueError(
                f"denoising_steps {steps}: a pass commits at least one "
                f"place of a block of {block}, so 1 to {block}")
        strategy = sampling.remasking_strategy or model.diffusion_remasking
        if strategy not in REMASKING_STRATEGIES:
            raise ValueError(
                f"remasking_strategy {strategy!r}: one of "
                f"{', '.join(REMASKING_STRATEGIES)}")
        sampling.denoising_steps = steps
        sampling.remasking_strategy = strategy
        if sampling.confidence_threshold is None:
            sampling.confidence_threshold = (
                model.diffusion_confidence_threshold)

    def _cold_start_target(self, seq: Sequence):
        """First full usable prompt page neither in HBM nor hashed
        locally — the page whose presence in the shared cluster cache
        decides whether a cold prompt restores or computes. None when
        there is no shared tier, prefix caching is off, or the local
        cache already covers the prompt (then the normal first-touch
        path handles everything). Caller holds self._lock."""
        from production_stack_tpu.engine.kv_cache import (
            PagedCacheManager,
        )
        if (self.offload is None or self.offload.remote is None
                or not self.config.cache.enable_prefix_caching):
            return None
        usable = len(seq.prompt_token_ids) - 1
        hashes = PagedCacheManager.chain_hashes(
            seq.prompt_token_ids[:usable],
            self.cache_manager.page_size, seq.cache_salt)
        for page_hash in hashes:
            if page_hash not in self.cache_manager._hash_to_page:
                return page_hash
        return None

    def add_handoff(self, prompt_token_ids: List[int],
                    first_token: int,
                    sampling: Optional[SamplingParams] = None,
                    seq_id: Optional[str] = None,
                    output_sink=None,
                    request_id: Optional[str] = None) -> str:
        """Accept a disaggregated prefill->decode handoff
        (docs/disaggregation.md): park the sequence in AWAITING_KV
        until its shipped pages are reachable in an offload tier
        (or the handoff timeout degrades it to recompute).

        The prefill engine's first sampled token is folded into the
        prompt exactly like scheduler._preempt folds generated tokens,
        with ``num_prior_output_tokens = 1`` keeping every budget
        honest; the caller (server handler) emits that first token to
        the client itself — this engine streams from token two.
        """
        sampling = sampling or SamplingParams()
        stop_ids = list(sampling.stop_token_ids)
        if (not sampling.ignore_eos
                and self.tokenizer.eos_token_id is not None
                and self.tokenizer.eos_token_id not in stop_ids):
            stop_ids.append(self.tokenizer.eos_token_id)
        sampling.stop_token_ids = stop_ids
        if sampling.guided is not None:
            raise ValueError(
                "guided decoding is not supported across a disagg "
                "handoff (automaton state does not transfer)")
        orig_max_tokens = sampling.max_tokens
        seq = Sequence(
            seq_id=seq_id or f"seq-{uuid.uuid4().hex[:16]}",
            prompt_token_ids=(list(prompt_token_ids)
                              + [int(first_token)]),
            sampling=sampling,
            output_sink=output_sink,
            state=SequenceState.AWAITING_KV,
            num_prior_output_tokens=1,
            handoff_arrival_time=time.time(),
            request_id=request_id,
        )
        with self._lock:
            self.sequences[seq.seq_id] = seq
            try:
                self.scheduler.add_sequence(seq)
            except Exception:
                self.sequences.pop(seq.seq_id, None)
                raise
            if self._tracer is not None:
                self._tracer.start(
                    seq.seq_id, request_id=request_id,
                    prompt_tokens=seq.num_prompt_tokens)
                self._tracer.event(seq.seq_id, "awaiting_kv_park")
            # Undo the admission clamp: it counts the folded first
            # token as prompt, which would end generation one token
            # earlier than the monolithic path. num_prior_output_tokens
            # plus the max_model_len finish check already bound this
            # sequence exactly as a monolithic engine would.
            sampling.max_tokens = orig_max_tokens
            self.disagg_decode_requests += 1
            if self.offload is None:
                # No tier to restore from: degrade to recompute now.
                seq.transition(SequenceState.WAITING)
                self.metrics.on_handoff_admitted(0.0)
                if self._tracer is not None:
                    self._tracer.event(
                        seq.seq_id, "awaiting_kv_restore",
                        waited_ms=0.0, outcome="no_tier")
        return seq.seq_id

    def add_resume(self, token_ids: List[int],
                   num_prior_output_tokens: int,
                   sampling: Optional[SamplingParams] = None,
                   seq_id: Optional[str] = None,
                   output_sink=None,
                   request_id: Optional[str] = None) -> str:
        """Resume a stream whose engine died mid-generation
        (docs/crash_recovery.md): ``token_ids`` is the journaled
        committed context (original prompt + every generated token up
        to the last checkpoint), folded into the prompt exactly like
        ``scheduler._preempt`` folds generated tokens, with
        ``num_prior_output_tokens`` keeping every budget honest. The
        sequence parks in ``AWAITING_KV``; the tri-state probe then
        restores the checkpointed pages from the offload tier — or
        degrades to a full recompute from the journal on a miss.
        Either way generation continues byte-identically for greedy
        sampling; nothing is replayed to the client (the server skips
        already-delivered text)."""
        sampling = sampling or SamplingParams()
        stop_ids = list(sampling.stop_token_ids)
        if (not sampling.ignore_eos
                and self.tokenizer.eos_token_id is not None
                and self.tokenizer.eos_token_id not in stop_ids):
            stop_ids.append(self.tokenizer.eos_token_id)
        sampling.stop_token_ids = stop_ids
        if sampling.guided is not None:
            raise ValueError(
                "guided decoding is not supported across a resume "
                "(automaton state does not transfer)")
        orig_max_tokens = sampling.max_tokens
        seq = Sequence(
            seq_id=seq_id or f"seq-{uuid.uuid4().hex[:16]}",
            prompt_token_ids=[int(t) for t in token_ids],
            sampling=sampling,
            output_sink=output_sink,
            state=SequenceState.AWAITING_KV,
            num_prior_output_tokens=int(num_prior_output_tokens),
            handoff_arrival_time=time.time(),
            request_id=request_id,
        )
        with self._lock:
            self.sequences[seq.seq_id] = seq
            try:
                self.scheduler.add_sequence(seq)
            except Exception:
                self.sequences.pop(seq.seq_id, None)
                raise
            if self._tracer is not None:
                self._tracer.start(
                    seq.seq_id, request_id=request_id,
                    prompt_tokens=seq.num_prompt_tokens)
                self._tracer.event(
                    seq.seq_id, "resume_restore",
                    prior_tokens=int(num_prior_output_tokens))
                self._tracer.event(seq.seq_id, "awaiting_kv_park")
            # Undo the admission clamp (see add_handoff): the folded
            # prior output would otherwise shrink the token budget.
            sampling.max_tokens = orig_max_tokens
            self.stream_resumes += 1
            if self.offload is None:
                # No tier to restore from: recompute from the journal.
                seq.transition(SequenceState.WAITING)
                self.metrics.on_handoff_admitted(0.0)
                if self._tracer is not None:
                    self._tracer.event(
                        seq.seq_id, "awaiting_kv_restore",
                        waited_ms=0.0, outcome="no_tier")
        return seq.seq_id

    def take_checkpoint(self, seq_id: str) -> Optional[dict]:
        """Drain the latest unsent resume descriptor for ``seq_id``
        (None when no new checkpoint landed since the last take)."""
        with self._lock:
            return self._checkpoints.pop(seq_id, None)

    def _checkpoint_tick(self) -> None:
        """Mid-stream crash safety (docs/crash_recovery.md): every
        ``config.checkpoint_interval_tokens`` generated tokens, ship a
        running stream's committed KV pages to the offload tier over
        the preempt-to-offload wire (incrementally — only pages not
        yet shipped) and stage a resume descriptor journaling the full
        committed token context. Skips guided and LoRA sequences
        (automaton state / adapter identity don't transfer). Without
        an offload tier the journal alone is staged, so a resume still
        recomputes rather than dying with this process."""
        from production_stack_tpu.engine.kv_cache import (
            PagedCacheManager,
        )
        interval = self.config.checkpoint_interval_tokens
        with self._lock:
            for seq in list(self.scheduler.running):
                if (seq.state != SequenceState.RUNNING
                        or seq.sampling.guided is not None
                        or seq.lora_id != 0):
                    continue
                last = self._ckpt_last_tokens.get(seq.seq_id, 0)
                if seq.num_generated - last < interval:
                    continue
                self._ckpt_last_tokens[seq.seq_id] = seq.num_generated
                # Committed restorable prefix: everything but the last
                # token (same bound as _evict_sequence_kv — the final
                # token's KV lands one step later and must reprefill).
                usable = seq.total_len - 1
                tokens = seq.all_token_ids[:usable]
                shipped = kv_bytes = 0
                if self.offload is not None and seq.pages:
                    self.cache_manager.commit_full_pages(
                        tokens, seq.pages, seq.num_hashed_pages,
                        seq.cache_salt)
                    hashes = PagedCacheManager.chain_hashes(
                        tokens, self.cache_manager.page_size,
                        seq.cache_salt)
                    done = self._ckpt_shipped_pages.get(seq.seq_id, 0)
                    pairs = list(zip(seq.pages, hashes))
                    chain = (self.offload.chain_id(hashes[0])
                             if hashes else None)
                    for page_id, page_hash in pairs[done:]:
                        payload = self.runner.read_page(page_id)
                        self.offload.offload_page(page_hash, *payload,
                                                  chain=chain)
                        kv_bytes += sum(int(a.nbytes) for a in payload)
                        shipped += 1
                    self._ckpt_shipped_pages[seq.seq_id] = len(pairs)
                self.checkpoint_ships += 1
                self.checkpoint_kv_bytes += kv_bytes
                self._checkpoints[seq.seq_id] = {
                    "tokens": [int(t) for t in seq.all_token_ids],
                    "prompt_tokens": seq.total_len - seq.num_generated,
                    "output_tokens": seq.num_generated,
                    "num_pages": self._ckpt_shipped_pages.get(
                        seq.seq_id, 0),
                    "kv_bytes": kv_bytes,
                }
                if self._tracer is not None:
                    self._tracer.event(
                        seq.seq_id, "checkpoint_ship",
                        pages=shipped, kv_bytes=kv_bytes,
                        tokens=seq.num_generated)

    def _drop_checkpoint_state(self, seq_id: str) -> None:
        """Caller holds self._lock (or the seq is already retired)."""
        self._checkpoints.pop(seq_id, None)
        self._ckpt_last_tokens.pop(seq_id, None)
        self._ckpt_shipped_pages.pop(seq_id, None)

    def take_handoff_info(self, seq_id: str) -> Optional[dict]:
        """Drain the descriptor payload recorded when ``seq_id``
        finished its prefill handoff (None if it never shipped)."""
        with self._lock:
            return self._handoff_info.pop(seq_id, None)

    def _ship_handoff(self, seq: Sequence) -> None:
        """Prefill-role completion: push the sequence's committed
        full-page KV to the offload tiers (push-on-prefill-done),
        record the descriptor payload for the server, and retire the
        sequence so its pages free for the next prefill burst. Caller
        holds self._lock."""
        from production_stack_tpu.engine.kv_cache import (
            PagedCacheManager,
        )
        info = {"num_pages": 0, "kv_bytes": 0, "page_keys": []}
        if self.offload is not None:
            hashes = PagedCacheManager.chain_hashes(
                seq.prompt_token_ids, self.cache_manager.page_size,
                seq.cache_salt)
            chain = (self.offload.chain_id(hashes[0])
                     if hashes else None)
            for page_id, page_hash in zip(seq.pages, hashes):
                payload = self.runner.read_page(page_id)
                self.offload.offload_page(page_hash, *payload,
                                          chain=chain)
                info["kv_bytes"] += sum(
                    int(a.nbytes) for a in payload)
                info["page_keys"].append(
                    self.offload.key_for(page_hash))
            info["num_pages"] = len(info["page_keys"])
        self._handoff_info[seq.seq_id] = info
        self.disagg_prefill_requests += 1
        self.disagg_kv_bytes_shipped += info["kv_bytes"]
        if self._tracer is not None:
            self._tracer.event(
                seq.seq_id, "handoff_ship",
                num_pages=info["num_pages"],
                kv_bytes=info["kv_bytes"])
        self.scheduler.finish_handoff(seq)

    def _handoff_kv_ready(self, seq: Sequence) -> Optional[bool]:
        """Availability of a parked handoff's KV. Pages ship in chain
        order, so probing the LAST shipped page (one HEAD at most)
        answers for the whole chain. True/False is definitive; None =
        tier unreachable (keep waiting until the handoff timeout).

        A cold-start probe (docs/kv_economy.md) asks a different
        question — "does the shared cache extend my local prefix?" —
        so it probes the FIRST page the local cache is missing: any
        hit there is a win (first-touch restore then pulls the longest
        available chain), and probing the last page would miss
        partially cached chains that are still worth restoring. The
        HEAD also records this engine's demand server-side, which is
        what promotes genuinely shared chains into the cache."""
        from production_stack_tpu.engine.kv_cache import (
            PagedCacheManager,
        )
        if seq.cold_start_probe:
            target = self._cold_start_target(seq)
            if target is None:
                return True  # local cache caught up meanwhile
            return self.offload.handoff_ready(target)
        usable = len(seq.prompt_token_ids) - 1
        hashes = PagedCacheManager.chain_hashes(
            seq.prompt_token_ids[:usable],
            self.cache_manager.page_size, seq.cache_salt)
        if not hashes:
            return True  # prompt shorter than a page: pure recompute
        return self.offload.handoff_ready(hashes[-1])

    def _admit_handoffs(self) -> None:
        """Flip AWAITING_KV sequences to WAITING once their pages are
        reachable (the normal first-touch restore path then pulls
        them), or degrade to recompute on definitive loss / timeout.
        Either way the request completes — never dropped."""
        now = time.time()
        with self._lock:
            for seq in list(self.scheduler.waiting):
                if seq.state != SequenceState.AWAITING_KV:
                    continue
                ready = self._handoff_kv_ready(seq)
                if ready is None and seq.cold_start_probe:
                    # Cold-start probes degrade immediately when the
                    # shared tier is down: nothing was shipped for
                    # this request, so waiting buys nothing — compute.
                    logger.debug(
                        "Cold-start probe %s: shared tier "
                        "unreachable; computing", seq.seq_id)
                elif ready is None:
                    if (now - seq.handoff_arrival_time
                            < self.config.handoff_timeout_s):
                        continue
                    logger.warning(
                        "Handoff %s timed out waiting for KV; "
                        "degrading to recompute", seq.seq_id)
                elif ready is False and not seq.cold_start_probe:
                    logger.warning(
                        "Handoff %s KV not in any offload tier; "
                        "degrading to recompute", seq.seq_id)
                seq.transition(SequenceState.WAITING)
                if not seq.cold_start_probe:
                    # Cold-start parks stay out of the disagg handoff
                    # admission histogram — they are routine admission
                    # probes, not handoff transfers.
                    self.metrics.on_handoff_admitted(
                        now - seq.handoff_arrival_time)
                if self._tracer is not None:
                    self._tracer.event(
                        seq.seq_id, "awaiting_kv_restore",
                        waited_ms=round(
                            (now - seq.handoff_arrival_time) * 1e3, 2),
                        outcome=("ready" if ready
                                 else "tier_down"
                                 if ready is None and seq.cold_start_probe
                                 else "timeout" if ready is None
                                 else "miss" if seq.cold_start_probe
                                 else "lost"))

    def register_lora(self, name_or_path: str,
                      name: Optional[str] = None) -> int:
        """Load + install a PEFT adapter; serve it under ``name``."""
        if self.runner.lora_registry is None:
            raise ValueError("LoRA is not enabled on this engine")
        from production_stack_tpu.engine.lora import load_peft_adapter
        adapter = load_peft_adapter(
            name_or_path, self.config.model,
            self.config.lora.max_lora_rank, name=name,
        )
        with self._lock:
            return self.runner.lora_registry.register(adapter)

    def lora_names(self) -> List[str]:
        if self.runner.lora_registry is None:
            return []
        return self.runner.lora_registry.names()

    def abort_request(self, seq_id: str) -> None:
        with self._lock:
            seq = self.sequences.pop(seq_id, None)
            if seq is not None:
                self.scheduler.abort_sequence(seq)
                self.metrics.on_finished(seq)
                self._drop_checkpoint_state(seq_id)
                if self._tracer is not None:
                    self._trace_finish(seq)

    def abort_after_step_failure(self) -> List[StepOutput]:
        """A step raised: abort every sequence that holds device state.

        The rows a failed step scheduled are the running sequences and
        the waiting ones that already own pages (chunked prefill keeps
        a sequence in ``waiting`` until its last chunk commits). Their
        KV cannot be trusted — the step's donated cache buffers may be
        consumed and the scheduler's bookkeeping may be ahead of what
        reached the pages — so retrying them repeats the failure
        forever (a Mosaic refusal or an HBM OOM at first dispatch used
        to show up as a request that never ends). Waiting sequences
        the device never touched stay queued."""
        # What earlier turns committed still reaches its streams, and
        # before the aborts.
        self._enqueued = None
        outputs = self.take_owed()
        aborts: List[StepOutput] = []
        with self._lock:
            self._in_flight = None
            self.metrics.set_inflight_depth(0)
            touched = list(self.scheduler.running) + [
                s for s in self.scheduler.waiting if s.pages]
            for seq in touched:
                self.scheduler.abort_sequence(seq)
                aborts.append(self._delta(seq, None))
        self._pop_finished(aborts)
        return outputs + aborts

    def _trace_finish(self, seq: Sequence) -> None:
        """Finalize ``seq``'s engine span (caller checked the tracer)."""
        self._tracer.finish(
            seq.seq_id,
            reason=(seq.finish_reason.value
                    if seq.finish_reason else None),
            arrival_ts=seq.arrival_time,
            first_scheduled_ts=seq.first_scheduled_time,
            first_token_ts=seq.first_token_time,
            finish_ts=seq.finish_time,
            prompt_tokens=seq.num_prompt_tokens,
            output_tokens=seq.num_generated)

    def has_work(self) -> bool:
        # Outputs owed to the streams are work: the loop must come
        # back to hand them over even if every row since finished.
        return bool(self._owed) or self.more_to_run()

    def more_to_run(self) -> bool:
        """Whether a device program follows: rows to plan, or a
        dispatched-but-unread decode step to reconcile."""
        return self._in_flight is not None or self.scheduler.has_work()

    # ---- engine step ------------------------------------------------------

    def step(self) -> List[StepOutput]:
        """Plan + execute one device program; returns per-seq deltas.

        The two calls of a turn back to back, and at once what the
        server loop takes behind its next dispatch: begin_step (plan,
        build, enqueue), finish_step (wait, parse, commit what the
        planner reads), take_owed (the outputs).
        """
        enqueued = self.begin_step()
        if enqueued is not None:
            self.finish_step(enqueued)
        return self.take_owed()

    def begin_step(self) -> Optional[EnqueuedStep]:
        """Plan, build and enqueue one device program from the
        committed state; returns what finish_step takes, or None where
        nothing is left enqueued: no executable work, or the
        overlapped pipeline's turn, which runs whole in here.

        ``scheduler.async_scheduling`` routes decode through the
        overlapped plan -> dispatch -> complete pipeline
        (docs/async_pipeline.md): step N+1 is planned and dispatched
        before step N's tokens are read back, hiding scheduler/commit
        host work behind the device step. Single-host only — the
        multihost step bridge broadcasts host-resident numpy payloads.
        """
        if self.scheduler.num_awaiting_kv:
            self._admit_handoffs()
        if self.config.checkpoint_interval_tokens > 0:
            self._checkpoint_tick()
        if (self.config.scheduler.async_scheduling
                and self.runner.bridge is None):
            outputs = self._step_async()
            self._owed.extend(outputs)  # a new list since _settle()
            return None
        t0 = time.perf_counter()
        plan = self._plan_locked(self._owed)
        if plan.empty:
            return None
        self._enqueued = self._enqueue(plan, t0)
        return self._enqueued

    def finish_step(self, enqueued: EnqueuedStep) -> None:
        """Wait for the program begin_step enqueued, parse its result
        and commit what the planner reads (tokens appended, finishes
        decided, pages and state slots freed, ``running`` updated);
        what only the streams read is owed (take_owed)."""
        wait_s = self._finish(enqueued)
        self._account_step(
            host_s=(time.perf_counter() - enqueued.t0) - wait_s,
            wait_s=wait_s, ahead=False)

    def take_owed(self) -> List[StepOutput]:
        """The deferred half of every commit since the last call: the
        rows' StepOutputs, their inter-token metrics, the finished
        sequences' retirement. The server loop calls it behind its
        next dispatch, or at once where no program follows."""
        if not self._owed:
            return []
        if self._tracer is not None:
            self._tracer.phase("commit")
        outputs = self._settle()
        if outputs:
            self.metrics.on_handover(behind=self._enqueued is not None)
        return outputs

    def _settle(self) -> List[StepOutput]:
        owed, self._owed = self._owed, []
        outputs: List[StepOutput] = []
        for item in owed:
            if isinstance(item, StepOutput):
                outputs.append(item)
                continue
            seq_id, lps = item.seq.seq_id, item.logprobs
            last = len(item.tokens) - 1
            for k, token in enumerate(item.tokens):
                outputs.append(StepOutput(
                    seq_id, token, item.finished and k == last,
                    item.finish_reason if k == last else None,
                    lps[k] if lps else None))
            self.metrics.on_decode_tokens(
                item.seq, len(item.tokens), item.now)
        self._pop_finished(outputs)
        return outputs

    def _plan_locked(self, outputs: list):
        if self._tracer is not None:
            self._tracer.phase("plan")
        with self._lock:
            plan = self.scheduler.plan_step()
            for seq in self.scheduler.newly_aborted:
                outputs.append(self._delta(seq, None))
            self.scheduler.newly_aborted.clear()
        return plan

    def _enqueue(self, plan: StepPlan, t0: float) -> EnqueuedStep:
        td = time.perf_counter()
        self._note_dispatch(td)
        if plan.prefill is not None and plan.decode is not None:
            # Mixed plan (scheduler._plan_mixed): one unified ragged
            # dispatch carries both sides (docs/unified_step.md).
            handle = self.runner.dispatch_unified(plan)
            commit = self._commit_unified
        elif plan.prefill is not None:
            handle = self.runner.dispatch_prefill(plan.prefill)
            commit = self._commit_prefill
        else:
            handle = self.runner.dispatch_decode_plan(plan.decode)
            commit = self._commit_decode
        return EnqueuedStep(plan, handle, commit, t0,
                            time.perf_counter() - td)

    def _finish(self, enqueued: EnqueuedStep) -> float:
        """The enqueued program's result read and committed; returns
        the seconds inside the runner (enqueue, wait, parse)."""
        tw = time.perf_counter()
        result = enqueued.handle.result()
        tr = time.perf_counter()
        self._enqueued = None
        self._idle_mark = tr
        if self._tracer is not None:
            self._tracer.phase("commit")
        enqueued.commit(enqueued.plan, result)
        return enqueued.enqueue_s + (tr - tw)

    def _execute_now(self, plan: StepPlan, outputs: list) -> float:
        """One program run to its end with nothing deferred, for the
        overlapped pipeline's synchronous turns; returns the seconds
        inside the runner."""
        wait_s = self._finish(self._enqueue(plan, time.perf_counter()))
        outputs.extend(self._settle())
        return wait_s

    def _account_step(self, host_s: float, wait_s: float, ahead: bool,
                      pipeline_break: bool = False, **extra) -> None:
        """One step's accounting fan-out: the aggregate pipeline
        metrics, plus a flight-recorder record (engine/tracing.py)
        carrying the row/spec note the execute helper staged."""
        self.metrics.on_pipeline_step(
            host_s=host_s, device_wait_s=wait_s, ahead=ahead)
        obs_note = self._obs_note
        if obs_note is not None:
            self._obs_note = None
            obs = getattr(self.runner, "observatory", None)
            if obs is not None:
                obs.on_step(obs_note[0], wait_s, obs_note[1])
        if self._tracer is not None:
            note = self._step_note or {}
            self._step_note = None
            note.update(extra)
            if self.cache_manager.num_state_slots:
                note["state_slots_used"] = (
                    self.cache_manager.num_used_state_slots)
                note["state_slots_total"] = (
                    self.cache_manager.num_state_slots)
                note["prefix_declined_tokens"] = (
                    self.cache_manager.prefix_declined_tokens)
            self._tracer.on_step(
                host_ms=round(host_s * 1e3, 3),
                device_wait_ms=round(wait_s * 1e3, 3),
                ahead=ahead, pipeline_break=pipeline_break, **note)

    def _commit_prefill_chunks(self, chunks, sampled, lp_rows) -> None:
        """Caller holds self._lock."""
        for i, (chunk, token) in enumerate(zip(chunks, sampled)):
            self.scheduler.on_prefill_executed(chunk, token)
            if chunk.is_last_chunk:
                if (chunk.seq.handoff_prefill
                        and chunk.seq.state == SequenceState.RUNNING):
                    # Disagg prefill role: ship KV + retire (unless
                    # the first token already finished the request —
                    # then there is nothing to decode and nothing
                    # worth shipping).
                    self._ship_handoff(chunk.seq)
                if token is None:
                    continue  # a prefill that yields no token
                self._owed.append(self._delta(
                    chunk.seq, token,
                    lp_rows[i] if lp_rows else None))

    def _commit_decode_rows(self, rows, token_lists, lp_lists,
                            spec_drafts, expected=None) -> tuple:
        """The planner's half of a decode program's commit, row by
        row (Scheduler.commit_decode_tokens); the rows' outputs are
        owed. Returns (drafted, accepted, tokens kept, rows walked
        token by token). Caller holds self._lock."""
        now = time.time()
        commit = self.scheduler.commit_decode_tokens
        drafted = accepted = step_tokens = slow_rows = 0
        for i, (seq, toks) in enumerate(zip(rows, token_lists)):
            if seq is None:  # plan-ahead masked slot
                continue
            if expected is not None and (
                    expected[i] is None
                    or seq.total_len != expected[i]):
                # Stale: the verify step this row was dispatched
                # behind committed more than the one token the ahead
                # plan assumed, so this sample came from incomplete
                # context. Its KV write was identical either way
                # (token_source is always the first committed token)
                # — only the sample is dropped.
                continue
            if spec_drafts is not None:
                # Device-level acceptance (each verify row emits
                # accepted + 1 tokens), counted before any host-side
                # stop truncation so the rate reflects the model, not
                # request budgets.
                drafted += len(spec_drafts[i])
                accepted += len(toks) - 1
                seq.spec_drafted_total += len(spec_drafts[i])
                seq.spec_accepted_total += max(0, len(toks) - 1)
            kept, slow = commit(seq, toks)
            slow_rows += slow
            if kept:
                step_tokens += kept
                done = seq.state != SequenceState.RUNNING
                self._owed.append(CommittedRow(
                    seq, toks if kept == len(toks) else toks[:kept],
                    lp_lists[i] if lp_lists else None, done,
                    seq.finish_reason.value if done else None, now))
            if spec_drafts is not None:
                self.scheduler.on_spec_executed(seq)
        if spec_drafts is not None:
            self.metrics.on_spec_step(drafted, accepted)
        return drafted, accepted, step_tokens, slow_rows

    def _commit_prefill(self, plan, result) -> None:
        sampled, lp_rows = result
        with self._lock:
            self._commit_prefill_chunks(plan.prefill.chunks, sampled,
                                        lp_rows)
            if self._tracer is not None:
                self._step_note = {
                    "kind": "prefill",
                    "prefill_rows": len(plan.prefill.chunks),
                    "prefill_chain": plan.prefill.chain,
                    "prefill_width": self.runner.last_prefill_width,
                    "row_bucket": self.runner.prefill_width,
                }
        self._obs_note = ("prefill",
                          sum(len(c.chunk_tokens)
                              for c in plan.prefill.chunks))

    def _commit_decode(self, plan, result) -> None:
        token_lists, lp_lists = result
        # The dispatch's result is on the host, so the expert layer's
        # counters can be read without waiting for the device.
        moe = self.runner.read_moe_stats()
        moe_note = self.metrics.on_moe_stats(moe) if moe else {}
        spec_drafts = plan.decode.drafts
        with self._lock:
            drafted, accepted, step_tokens, slow_rows = (
                self._commit_decode_rows(plan.decode.seqs, token_lists,
                                         lp_lists, spec_drafts))
            module_note = {}
            if moe and "drafts" in moe:
                # A burst whose draft module proposed inside it: the
                # program's own counts (drafts verified on live rows,
                # drafts accepted), before any host-side truncation.
                module_note = {"drafts": int(moe["drafts"]),
                               "accepted": int(moe["accepted"])}
                self.metrics.on_spec_step(module_note["drafts"],
                                          module_note["accepted"])
            if moe and "denoise_passes" in moe:
                # A block-diffusion burst: the passes that ran (they
                # replace the planned window), the blocks and what the
                # passes committed, before any host-side truncation.
                module_note = self.metrics.on_block_burst(moe)
            if self._tracer is not None:
                self._step_note = {
                    "kind": "spec" if spec_drafts is not None
                    else "decode",
                    "decode_rows": len(plan.decode.seqs),
                    "row_bucket": self.runner.decode_width,
                    "window": plan.decode.window,
                    "attn_pages": self.runner.last_attn_pages,
                    "spec_drafted": drafted,
                    "spec_accepted": accepted,
                    "commit_rows_slow": slow_rows,
                    **module_note,
                    **{k: round(v, 3) for k, v in moe_note.items()},
                }
        self._obs_note = ("spec" if spec_drafts is not None
                          else "decode", step_tokens)

    def _commit_unified(self, plan, result) -> None:
        """One unified ragged step (docs/unified_step.md): decode/
        draft rows and prefill chunk rows commit out of a single
        dispatch — decode rows through the spec-verify contract
        (1..span tokens each), prefill chunks through the ordinary
        chunked-prefill commit path, handoff shipping included."""
        token_lists, lp_lists, prefill_toks, prefill_lps = result
        seqs = plan.decode.seqs[: self.runner.decode_width]
        chunks = plan.prefill.chunks[: self.runner.prefill_width]
        pad_rows = self.runner.last_unified_rows - len(chunks) - len(seqs)
        self.metrics.on_ragged_step(
            prefill_rows=len(chunks), decode_rows=len(seqs),
            pad_rows=pad_rows)
        with self._lock:
            drafted, accepted, step_tokens, slow_rows = (
                self._commit_decode_rows(seqs, token_lists, lp_lists,
                                         plan.decode.drafts))
            self._commit_prefill_chunks(chunks, prefill_toks,
                                        prefill_lps)
            if self._tracer is not None:
                self._step_note = {
                    "kind": "unified",
                    "prefill_rows": len(chunks),
                    "decode_rows": len(seqs),
                    "pad_rows": pad_rows,
                    "row_bucket": self.runner.last_unified_rows,
                    "window": plan.decode.window,
                    "spec_drafted": drafted,
                    "spec_accepted": accepted,
                    "commit_rows_slow": slow_rows,
                }
        self._obs_note = ("unified",
                          step_tokens + sum(len(c.chunk_tokens)
                                            for c in chunks))

    # ---- overlapped async pipeline (docs/async_pipeline.md) ---------------

    def _step_async(self) -> List[StepOutput]:
        """One pipeline turn, depth 1: when a decode step is in
        flight, plan and dispatch its successor BEFORE reading its
        results. The successor consumes the in-flight step's
        sampled-token device array directly (DecodeStepHandle
        .token_source), so the device starts step N+1 while the host
        is still committing step N's tokens."""
        handle = self._in_flight
        if handle is not None:
            t0 = time.perf_counter()
            rows = None
            if self._tracer is not None:
                self._tracer.phase("plan")
            if handle.expected_lens is None:
                with self._lock:
                    rows = self.scheduler.plan_ahead(handle.rows)
            # else: this handle is the assume-1 successor of a spec
            # verify step. Complete it now with the stale-drop filter
            # (_complete) and re-plan from fresh host state — chaining
            # another step off a possibly-stale token source can never
            # recover, as every successor would sample from the same
            # incomplete context (docs/unified_step.md
            # §spec-under-async).
            if rows is not None:
                nxt = self.runner.dispatch_decode(
                    rows, token_source=handle.token_source,
                    ahead=True)
                if handle.is_spec:
                    # The successor assumed each row commits exactly
                    # one token; record the total_len that assumption
                    # predicts so _complete can drop rows where the
                    # verify committed more (its KV write is identical
                    # either way — token_source is always the first
                    # committed token).
                    nxt.expected_lens = [
                        None if seq is None else seq.total_len + 1
                        for seq in rows]
                self._in_flight = nxt
                outputs, wait_s = self._complete(handle)
                # No _idle_mark here: step N+1 was queued before step
                # N's results were read — the device never idled.
                self._account_step(
                    host_s=(time.perf_counter() - t0) - wait_s,
                    wait_s=wait_s, ahead=True)
                return outputs
            # Pipeline break (prefill waiting / ineligible row / no
            # boundary pages): drain the in-flight step, then let the
            # next step() re-plan synchronously with full knowledge.
            self._in_flight = None
            self.metrics.set_inflight_depth(0)
            outputs, wait_s = self._complete(handle)
            self._idle_mark = time.perf_counter()
            self._account_step(
                host_s=(time.perf_counter() - t0) - wait_s,
                wait_s=wait_s, ahead=False, pipeline_break=True)
            return outputs
        outputs: List[StepOutput] = []
        t0 = time.perf_counter()
        plan = self._plan_locked(outputs)
        if plan.empty:
            for out in outputs:
                self.sequences.pop(out.seq_id, None)
            return outputs
        if plan.prefill is not None:
            # Prefill (and the mixed ragged step) stays synchronous:
            # each chunk's commit feeds the next chunk's plan, so
            # these run as deliberate pipeline breaks.
            wait_s = self._execute_now(plan, outputs)
            self._account_step(
                host_s=(time.perf_counter() - t0) - wait_s,
                wait_s=wait_s, ahead=False, pipeline_break=True)
            self._pop_finished(outputs)
            return outputs
        if plan.decode.drafts is not None:
            # Speculative verify step: dispatch it in flight like a
            # decode step — its commit count is data-dependent, so
            # the NEXT turn's ahead dispatch assumes one token and
            # reconciles via the expected_lens stale-drop path
            # (docs/unified_step.md §spec-under-async).
            self._note_dispatch(time.perf_counter())
            self._in_flight = self.runner.dispatch_spec(plan.decode)
            if self._tracer is not None:
                self._tracer.phase("commit")
            self.metrics.set_inflight_depth(1)
            self._account_step(
                host_s=time.perf_counter() - t0, wait_s=0.0,
                ahead=False, kind="spec_dispatch")
            self._pop_finished(outputs)
            return outputs
        if plan.decode.window > 1:
            # Multi-step burst: the burst program already hides host
            # work for window-1 of its steps, so it runs synchronously
            # rather than through the depth-1 pipeline (stacking both
            # overlaps would speculate window tokens ahead).
            wait_s = self._execute_now(plan, outputs)
            self._account_step(
                host_s=(time.perf_counter() - t0) - wait_s,
                wait_s=wait_s, ahead=False)
            self._pop_finished(outputs)
            return outputs
        # Single-step pure-decode plan: dispatch and return without
        # waiting; the next turn plans ahead against it.
        self._note_dispatch(time.perf_counter())
        self._in_flight = self.runner.dispatch_decode(
            plan.decode.seqs[: self.runner.decode_width])
        if self._tracer is not None:
            self._tracer.phase("commit")
        self.metrics.set_inflight_depth(1)
        self._account_step(
            host_s=time.perf_counter() - t0, wait_s=0.0,
            ahead=False, kind="decode_dispatch")
        self._pop_finished(outputs)
        return outputs

    def _complete(self, handle) -> tuple:
        """Read back + reconcile one dispatched decode or verify
        step: commit tokens through the same scheduler path as the
        sync loop. Rows that finished or were aborted mid-flight
        break out exactly as there; plan-ahead boundary pages ride
        seq.pages and return through the ordinary free_sequence path,
        so a mid-flight abort leaks nothing. Handles carrying
        ``expected_lens`` (the assume-1 successor of a verify step)
        drop rows whose committed length diverged from the
        assumption — the stale-token path of
        docs/unified_step.md §spec-under-async."""
        tw = time.perf_counter()
        token_lists, lp_lists = handle.result()
        wait_s = time.perf_counter() - tw
        if self._tracer is not None:
            self._tracer.phase("commit")
        spec_drafts = handle.drafts if handle.is_spec else None
        with self._lock:
            drafted, accepted, step_tokens, slow_rows = (
                self._commit_decode_rows(
                    handle.rows, token_lists, lp_lists, spec_drafts,
                    expected=handle.expected_lens))
            if self._tracer is not None:
                self._step_note = {
                    "kind": "spec" if handle.is_spec else "decode",
                    "decode_rows": sum(
                        1 for seq in handle.rows if seq is not None),
                    "row_bucket": self.runner.decode_width,
                    "attn_pages": handle.attn_pages,
                    "spec_drafted": drafted,
                    "spec_accepted": accepted,
                    "commit_rows_slow": slow_rows,
                }
        self._obs_note = ("spec" if handle.is_spec else "decode",
                          step_tokens)
        return self._settle(), wait_s

    def _pop_finished(self, outputs: List[StepOutput]) -> None:
        for out in outputs:
            if out.finished:
                seq = self.sequences.pop(out.seq_id, None)
                if seq is not None:
                    self.metrics.on_finished(seq)
                    self._drop_checkpoint_state(out.seq_id)
                    if self._tracer is not None:
                        self._trace_finish(seq)

    def _note_dispatch(self, now: float) -> None:
        """Device-idle accounting: accumulate the gap between the
        device draining its queue and the next dispatch."""
        if self._idle_mark is not None:
            self.metrics.on_device_idle(now - self._idle_mark)
            self._idle_mark = None

    def _delta(self, seq: Sequence, token: Optional[int],
               logprobs: Optional[tuple] = None) -> StepOutput:
        finished = seq.state in (
            SequenceState.FINISHED, SequenceState.ABORTED
        )
        return StepOutput(
            seq_id=seq.seq_id,
            new_token=token,
            finished=finished,
            finish_reason=(seq.finish_reason.value
                           if seq.finish_reason else None),
            logprobs=logprobs,
        )

    def _guided_advance(self, seq, token: int) -> None:
        """Host mirror of the device automaton carry (scheduler hook);
        tokens the automaton rejects (possible only via host-enforced
        stop-set overflow) freeze the state rather than corrupt it."""
        ns = self.guided_fsm.advance(seq.fsm_state, token)
        if ns >= 0:
            seq.fsm_state = ns

    # ---- metrics ----------------------------------------------------------

    def stats(self) -> Dict[str, float]:
        out = {
            "num_requests_running": self.scheduler.num_running,
            "num_requests_waiting": self.scheduler.num_waiting,
            "gpu_cache_usage_perc": self.cache_manager.usage_perc(),
            "gpu_prefix_cache_hit_rate":
                self.cache_manager.prefix_hit_rate(),
            "num_preemptions_total": self.scheduler.num_preemptions,
            "engine_prefill_chained_steps_total":
                self.scheduler.num_chained_prefill_steps,
            "engine_prefill_narrow_steps_total":
                self.runner.num_narrow_prefill_steps,
            "spec_decode_num_draft_tokens_total":
                self.metrics.spec_draft_tokens_total,
            "spec_decode_num_accepted_tokens_total":
                self.metrics.spec_accepted_tokens_total,
            "engine_step_host_seconds_total":
                self.metrics.step_host_seconds_total,
            "engine_step_device_wait_seconds_total":
                self.metrics.step_device_wait_seconds_total,
            "engine_device_idle_seconds_total":
                self.metrics.device_idle_seconds_total,
            "engine_pipeline_steps_total":
                self.metrics.pipeline_steps_total,
            "engine_pipeline_ahead_steps_total":
                self.metrics.pipeline_ahead_steps_total,
            "engine_async_inflight_depth":
                self.metrics.async_inflight_depth,
            # Unified ragged step occupancy (docs/unified_step.md):
            # last mixed dispatch's row split plus cumulative totals
            # for pad-ratio accounting (benchmarks ragged_pad_ratio).
            "engine_step_prefill_rows":
                self.metrics.last_prefill_rows,
            "engine_step_decode_rows":
                self.metrics.last_decode_rows,
            "engine_step_pad_rows": self.metrics.last_pad_rows,
            "engine_ragged_steps_total":
                self.metrics.ragged_steps_total,
            "engine_ragged_rows_total":
                self.metrics.ragged_rows_total,
            "engine_ragged_pad_rows_total":
                self.metrics.ragged_pad_rows_total,
            # KV quantization telemetry (docs/kv_quantization.md):
            # post-expansion page budget and worst-case KV bytes a
            # full decode batch writes per step.
            "engine_kv_cache_page_capacity":
                self.config.cache.num_pages - 1,
            "engine_kv_bytes_per_decode_step":
                self.config.scheduler.max_num_seqs
                * self.config.cache.kv_bytes_per_token(
                    self.config.model),
            # Disaggregated serving (docs/disaggregation.md).
            "disagg_prefill_requests_total":
                self.disagg_prefill_requests,
            "disagg_decode_requests_total":
                self.disagg_decode_requests,
            "disagg_kv_bytes_shipped_total":
                self.disagg_kv_bytes_shipped,
            "disagg_awaiting_kv_requests":
                self.scheduler.num_awaiting_kv,
            # Mid-stream crash safety (docs/crash_recovery.md).
            "checkpoint_ships_total": self.checkpoint_ships,
            "checkpoint_kv_bytes_total": self.checkpoint_kv_bytes,
            "stream_resumes_total": self.stream_resumes,
            # Recurrent-state slots and the sparse expert layer
            # (docs/observability.md §hybrid models); zeros for a
            # model with neither.
            "engine_state_slots_used":
                self.cache_manager.num_used_state_slots,
            "engine_state_slots_total":
                self.cache_manager.num_state_slots,
            "engine_prefix_declined_tokens_total":
                self.cache_manager.prefix_declined_tokens,
            "engine_moe_tokens_per_expert_max":
                self.metrics.moe_last["moe_tokens_per_expert_max"],
            "engine_moe_tokens_per_expert_mean":
                self.metrics.moe_last["moe_tokens_per_expert_mean"],
            "engine_moe_held_choice_share":
                self.metrics.moe_last["moe_held_choice_share"],
            "engine_moe_zero_choice_share":
                self.metrics.moe_last["moe_zero_choice_share"],
        }
        if self.offload is not None:
            out.update({
                f"kv_offload_{k}": v
                for k, v in self.offload.stats().items()
            })
        return out

    # ---- convenience ------------------------------------------------------

    def generate(self, prompt_token_ids: List[int],
                 sampling: Optional[SamplingParams] = None,
                 lora_name: Optional[str] = None,
                 ) -> Sequence:
        """Blocking single-prompt generation (tests/benchmarks)."""
        seq_id = self.add_request(prompt_token_ids, sampling,
                                  lora_name=lora_name)
        seq = self.sequences[seq_id]
        while seq.state not in (SequenceState.FINISHED,
                                SequenceState.ABORTED):
            if not self.step():
                time.sleep(0)
        return seq

    def generate_batch(self, prompts: List[List[int]],
                       sampling: Optional[SamplingParams] = None,
                       ) -> List[Sequence]:
        seqs = []
        for p in prompts:
            sp = (SamplingParams(**vars(sampling))
                  if sampling else SamplingParams())
            seq_id = self.add_request(p, sp)
            seqs.append(self.sequences[seq_id])
        while any(s.state not in (SequenceState.FINISHED,
                                  SequenceState.ABORTED) for s in seqs):
            self.step()
        return seqs
