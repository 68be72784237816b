"""Continuous-batching scheduler.

The TPU twist (SURVEY.md §7 "hard parts" (a)): vLLM's scheduler emits
dynamically-shaped batches because CUDA kernels launch per step; under
XLA every shape is a compiled program, so this scheduler plans work in
*fixed* shapes — prefill chunks padded to buckets, decode as a constant-
width slot batch — and the runner caches one executable per shape.

A step is either one batch of prefill chunks (chunked prefill,
reference flag --enable-chunked-prefill,
deployment-vllm-multi.yaml:69-71) or one decode batch over all running
sequences; the two alternate when both have work so neither starves,
and a further prefill step runs before the burst only while it would
be full and rows are free (plan_step, "prefill chain").
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

from production_stack_tpu.engine.config import (
    CacheConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.kv_cache import (
    OutOfPagesError,
    PagedCacheManager,
)
from production_stack_tpu.engine.sequence import (
    STOP_SET_WIDTH,
    FinishReason,
    Sequence,
    SequenceState,
    block_start,
    decode_budget,
    draftless,
)
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)

# Sustained overload preempts on every planning pass; the per-victim
# warning is rate-limited to one line per interval (with a
# suppressed-count) so logging can't become the bottleneck.
_PREEMPT_LOG_INTERVAL_S = 5.0


def burst_blocks(window: int, block_steps: int) -> int:
    """Blocks a row works in a block-diffusion burst of ``window``
    forward passes planned at ``block_steps`` denoising passes and a
    store pass a block. Single source of truth for the scheduler's
    page reservation and the runner's compiled burst."""
    return max(1, window // (block_steps + 1))


@dataclass
class PrefillChunk:
    seq: Sequence
    chunk_start: int  # absolute position of first token in chunk
    chunk_tokens: List[int]
    is_last_chunk: bool


@dataclass
class PrefillPlan:
    """One batched prefill step: the next chunk of up to
    ``prefill_batch_size`` DISTINCT waiting sequences, padded to a
    fixed row count so the compiled program shape never varies.

    ``sp=True`` marks a context-parallel whole-prompt plan (a single
    sequence whose entire prompt prefills in one dispatch with the
    sequence sharded over the mesh's 'sp' axis —
    parallel/context_serving.py)."""

    chunks: List[PrefillChunk]
    sp: bool = False
    # Place in the chain of prefill steps since the last burst: 1 is
    # the step alternation plans, 2.. are the full steps plan_step
    # put before the burst (the turn record's ``prefill_chain``).
    chain: int = 1


@dataclass
class DecodePlan:
    seqs: List[Sequence]
    # Multi-step window for this dispatch (1 = single step). Decided
    # here so page-capacity reservation and the runner's compiled
    # program agree on the same lookahead.
    window: int = 1
    # Speculative verify step (docs/speculative.md): per-row draft
    # tokens parallel to ``seqs`` ([] = plain single-token row inside
    # the same fixed-shape program). None = normal decode.
    drafts: Optional[List[List[int]]] = None


@dataclass
class StepPlan:
    prefill: Optional[PrefillPlan] = None
    decode: Optional[DecodePlan] = None

    @property
    def empty(self) -> bool:
        return self.prefill is None and self.decode is None


class Scheduler:
    def __init__(self, config: SchedulerConfig, cache_config: CacheConfig,
                 cache_manager: PagedCacheManager,
                 sp_threshold: Optional[int] = None,
                 guided_advance=None):
        # Optional hook(seq, token) advancing a guided-decoding
        # automaton state as tokens are appended (engine/guided.py;
        # the engine binds it so host state mirrors the device carry).
        self.guided_advance = guided_advance
        self.config = config
        self.page_size = cache_config.page_size
        self.cache = cache_manager
        # Prompts >= this many tokens (first touch, no prefix hit)
        # take the context-parallel whole-prompt prefill path; None
        # disables it (engine sets this when --context-parallel-size
        # > 1).
        self.sp_threshold = sp_threshold
        self.waiting: Deque[Sequence] = deque()
        self.running: List[Sequence] = []
        self._last_was_prefill = False
        # The prefill chain (plan_step): the last prefill plan's place
        # in it, how many steps it may have (set by its first step
        # from the rows free then), and how many steps chains have put
        # before a burst so far
        # (vllm:engine_prefill_chained_steps_total).
        self._prefill_chain = 0
        self._chain_limit = 1
        self.num_chained_prefill_steps = 0
        # Optional offload-tier restore hook:
        # (prompt_token_ids, matched_pages) -> extra restored page ids.
        self.restore_hook = None
        # Optional preempt-to-offload hook (docs/qos.md): seq -> count
        # of committed KV pages shipped to the offload tier before the
        # victim's pages are freed. None / 0 = classic
        # drop-and-recompute. Installed by the engine when an offload
        # tier is configured and qos.preempt_to_offload is on.
        self.evict_hook = None
        # vllm:preempt_offload_total{outcome}: "offloaded" victims had
        # their pages shipped; "recompute" victims fell back to the
        # classic full-prompt recompute.
        self.preempt_offload_outcomes: Dict[str, int] = {
            "offloaded": 0, "recompute": 0}
        self._preempt_log_ts = float("-inf")
        self._preempt_log_suppressed = 0
        # End-to-end tracing (docs/observability.md): mirror of
        # LLMEngine.tracer, installed via its setter; None = untraced.
        self.tracer = None
        # Sequences aborted by the scheduler itself (oversized prompts,
        # permanent cache starvation); the engine drains this to emit
        # terminal outputs to their clients.
        self.newly_aborted: List[Sequence] = []
        # Cumulative count of sequences preempted for KV-cache
        # pressure (vllm:num_preemptions_total parity).
        self.num_preemptions = 0
        # Draft-free speculative decoding (docs/speculative.md): the
        # prompt-lookup proposer drafts from each sequence's own
        # history; None when the feature is off.
        self.proposer = None
        if config.speculative_k > 0:
            from production_stack_tpu.engine.spec import NgramProposer
            self.proposer = NgramProposer(
                config.speculative_k, config.speculative_min_match)
        # Self-tuning knobs (docs/autotuning.md), both host-side
        # plan-time values — no compiled shape depends on either.
        # Prefill token budget a unified (mixed) step may admit;
        # defaults to a dedicated prefill step's full bandwidth.
        self.mixed_prefill_budget = (config.prefill_chunk_size
                                     * config.prefill_batch_size)
        # QoS degrade-ladder clamp: while set, non-interactive rows
        # (priority > 0) are planned spec-off, reserving draft/verify
        # slack for interactive traffic under overload.
        self.spec_degrade_clamp = False

    # ---- queue management -------------------------------------------------

    def add_sequence(self, seq: Sequence) -> None:
        if len(self.waiting) >= self.config.max_queue_len:
            seq.transition(SequenceState.ABORTED)
            seq.finish_reason = FinishReason.ABORT
            raise RuntimeError("Scheduler queue full")
        if seq.num_prompt_tokens >= self.config.max_model_len:
            seq.transition(SequenceState.ABORTED)
            seq.finish_reason = FinishReason.ABORT
            raise ValueError(
                f"Prompt is {seq.num_prompt_tokens} tokens but "
                f"max_model_len is {self.config.max_model_len}"
            )
        max_prompt_pages = (self.config.max_pages_per_seq(self.page_size)
                            * self.page_size)
        if seq.num_prompt_tokens >= min(
                max_prompt_pages,
                (self.cache.config.num_pages - 1) * self.page_size):
            seq.transition(SequenceState.ABORTED)
            seq.finish_reason = FinishReason.ABORT
            raise ValueError(
                f"Prompt of {seq.num_prompt_tokens} tokens cannot fit "
                "in the KV cache"
            )
        if seq.num_prompt_tokens + seq.sampling.max_tokens > \
                self.config.max_model_len:
            # Clamp generation to fit the model length budget.
            seq.sampling.max_tokens = max(
                1, self.config.max_model_len - seq.num_prompt_tokens
            )
        self.waiting.append(seq)

    def abort_sequence(self, seq: Sequence) -> None:
        self._finish(seq, FinishReason.ABORT)
        if seq in self.running:
            self.running.remove(seq)
        try:
            self.waiting.remove(seq)
        except ValueError:
            pass

    @property
    def num_waiting(self) -> int:
        # Includes AWAITING_KV handoffs: they occupy a queue slot and
        # belong in num_requests_waiting (docs/disaggregation.md).
        return len(self.waiting)

    @property
    def num_awaiting_kv(self) -> int:
        return sum(1 for s in self.waiting
                   if s.state == SequenceState.AWAITING_KV)

    def _has_plannable_waiting(self) -> bool:
        """Waiting work prefill could actually plan now — AWAITING_KV
        handoffs are parked until the engine admits them, so they must
        not trigger prefill planning or break the async pipeline."""
        return any(s.state != SequenceState.AWAITING_KV
                   for s in self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # ---- planning ---------------------------------------------------------

    def plan_step(self) -> StepPlan:
        want_prefill = bool(
            self._has_plannable_waiting()
            and len(self.running) < self.config.max_num_seqs
        )
        want_decode = bool(self.running)
        if self.config.unified_step and want_prefill and want_decode:
            # Unified ragged step (docs/unified_step.md): admit
            # prefill chunks INTO the decode step under a token
            # budget instead of alternating whole steps, so long
            # prompts never stall decode ITL. Falls through to the
            # bimodal alternation when a row needs per-token host
            # state the ragged program doesn't compile.
            plan = self._plan_mixed()
            if plan is not None and not plan.empty:
                self._chain_limit = 0  # a mixed step starts no chain
                return plan
        # Alternate so neither side starves. The rows a prefill step
        # makes wait are the running ones and a step costs the same
        # whatever it carries (its rows are padded to the compiled
        # width), so a further one goes before the burst only while it
        # would be FULL, and a chain has at most one step for each
        # ``prefill_batch_size`` rows that were free at its start: a
        # batch with fewer free rows than a step alternates strictly,
        # a nearly empty one fills before it decodes.
        chained = (want_prefill and want_decode
                   and self._last_was_prefill)
        if chained:
            do_prefill = (self._prefill_chain < self._chain_limit
                          and self._full_prefill_step_waits())
        else:
            do_prefill = want_prefill
        if do_prefill:
            plan = self._plan_prefill()
            if plan is not None:
                if chained:
                    self._prefill_chain += 1
                    self.num_chained_prefill_steps += 1
                else:
                    free_rows = (self.config.max_num_seqs
                                 - len(self.running))
                    self._prefill_chain = 1
                    self._chain_limit = -(
                        -free_rows // self.config.prefill_batch_size)
                plan.chain = self._prefill_chain
                self._last_was_prefill = True
                return StepPlan(prefill=plan)
            want_decode = bool(self.running)
        if want_decode:
            self._last_was_prefill = False
            if self.proposer is not None:
                plan = self._plan_spec()
                if plan is not None:
                    return StepPlan(decode=plan)
            window = self._decode_window()
            self._ensure_decode_capacity(self._window_tokens(window))
            if self.running:
                # Re-check: preemption may have changed who can take a
                # full window.
                window = min(window, self._decode_window())
                return StepPlan(decode=DecodePlan(
                    seqs=list(self.running), window=window))
        return StepPlan()

    def _plan_spec(self) -> Optional[DecodePlan]:
        """Plan one speculative verify step, or None to fall back to
        plain decode (no row drafted anything, or a row needs per-row
        device inputs the verify program doesn't compile). Exactly two
        decode-side programs ever compile: the S-wide verify and the
        decode_steps-window decode/burst the fallback uses."""
        for seq in self.running:
            if draftless(seq):
                # Whole-step fallback: padding these rows through the
                # verify shape would need the penalty/seed/bias/
                # suppress/guided inputs compiled into it; the normal
                # decode path already serves them.
                return None
        drafts: Dict[str, List[int]] = {}
        for seq in self.running:
            if seq.spec_off or (self.spec_degrade_clamp
                                and seq.priority > 0):
                # QoS degradation (docs/qos.md): throttled-tenant rows
                # ride the verify step as plain single-token rows.
                continue
            # Cap so emitted tokens (accepted + bonus) never exceed
            # the row's budget — a draft the budget can't emit would
            # also write KV past max_model_len.
            d = self.proposer.propose(seq, self._draft_limit(seq))
            if d:
                drafts[seq.seq_id] = d
        if not drafts:
            return None
        # Hybrid profitability gate (docs/speculative.md
        # §interactions): a verify step displaces a decode_steps-deep
        # burst, and rows without drafts emit one token instead of
        # decode_steps. Take the spec step only when, at full
        # acceptance, it can emit at least as many tokens as the
        # burst it displaces (each row emits accepted+1, so the batch
        # emits <= sum(draft lens) + rows); otherwise defer — the
        # drafts regrow from the same history on a later step. With
        # decode_steps == 1 this always passes.
        window = max(1, self.config.decode_steps)
        if (sum(len(d) for d in drafts.values()) + len(self.running)
                < window * len(self.running)):
            return None
        # Reserve pages for 1 + draft_len tokens per row; preemption
        # inside the pass may shrink `running` (victims' drafts are
        # simply dropped with them).
        self._ensure_decode_capacity(per_seq={
            s.seq_id: 1 + len(drafts.get(s.seq_id, ()))
            for s in self.running})
        if not self.running:
            return None
        plan_drafts = [drafts.get(s.seq_id, [])
                       for s in self.running]
        if not any(plan_drafts):
            return None
        return DecodePlan(seqs=list(self.running), window=1,
                          drafts=plan_drafts)

    def _plan_mixed(self) -> Optional[StepPlan]:
        """Plan one unified ragged step: every running sequence as a
        decode row (with prompt-lookup drafts when the proposer has
        them — spec rows ride the same span the program already
        compiles) plus waiting prefill chunks admitted under a token
        budget matching a dedicated prefill step's full bandwidth
        (``prefill_chunk_size * prefill_batch_size``) — so admission
        under mixing proceeds exactly as fast as alternation would,
        while decode rows keep emitting instead of stalling. Returns
        None to fall back to bimodal alternation when any running
        row needs per-row device inputs the ragged program doesn't
        compile (same exclusion set as _plan_spec / plan_ahead)."""
        for seq in self.running:
            sp = seq.sampling
            if (sp.needs_penalties or sp.seed is not None
                    or sp.logit_bias
                    or sp.min_tokens > seq.num_generated
                    or seq.fsm_state is not None):
                return None
        drafts: Dict[str, List[int]] = {}
        if self.proposer is not None:
            for seq in self.running:
                if seq.spec_off or (self.spec_degrade_clamp
                                    and seq.priority > 0):
                    continue
                d = self.proposer.propose(seq,
                                          self._draft_limit(seq))
                if d:
                    drafts[seq.seq_id] = d
        # Reserve decode-side pages first (1 + draft_len per row);
        # preemption here shrinks `running` before prefill admission
        # competes for the same pages.
        self._ensure_decode_capacity(per_seq={
            s.seq_id: 1 + len(drafts.get(s.seq_id, ()))
            for s in self.running})
        if not self.running:
            return None
        prefill = self._plan_prefill(
            max_tokens=self.mixed_prefill_budget)
        if prefill is not None and prefill.sp:
            # Context-parallel whole-prompt plans run alone (their
            # dispatch shards the sequence over the mesh); the
            # decode rows keep their reserved pages for next step.
            self._last_was_prefill = True
            return StepPlan(prefill=prefill)
        plan_drafts = None
        if drafts:
            rows = [drafts.get(s.seq_id, []) for s in self.running]
            if any(rows):
                plan_drafts = rows
        if prefill is None and plan_drafts is None:
            # Nothing ragged about this step (prefill couldn't admit,
            # no drafts): let the bimodal path plan it — it knows how
            # to take a decode_steps burst.
            return None
        decode = DecodePlan(seqs=list(self.running), window=1,
                            drafts=plan_drafts)
        self._last_was_prefill = prefill is not None
        return StepPlan(prefill=prefill, decode=decode)

    def plan_ahead(self, inflight_rows) -> Optional[List[
            Optional[Sequence]]]:
        """Plan decode step N+1 while step N is still in flight
        (docs/async_pipeline.md): assume every running row commits
        exactly one token, pre-allocate the boundary pages that
        assumption needs, and return a row list ALIGNED to
        ``inflight_rows`` (None = slot masked: the row is gone or
        provably finishes when step N commits). The engine feeds step
        N's sampled-token device array straight into step N+1, so row
        slots must not shift.

        Returns None to break the pipeline (the engine then completes
        step N and re-plans synchronously with full knowledge):
        - prefill work is waiting and could admit (matches
          plan_step's want_prefill, so prefill never starves),
        - a row needs per-token host state the ahead plan would
          compute one token stale (penalties, seeded sampling,
          logit_bias, min_tokens suppression, guided decoding — the
          same exclusion set as _plan_spec),
        - boundary pages cannot be allocated (never preempt with a
          step in flight: the victim's pages are inputs of the
          running program).
        """
        if (self._has_plannable_waiting()
                and len(self.running) < self.config.max_num_seqs):
            return None
        rows: List[Optional[Sequence]] = []
        any_live = False
        for seq in inflight_rows:
            if seq is None or seq.state != SequenceState.RUNNING:
                rows.append(None)
                continue
            sp = seq.sampling
            if (sp.needs_penalties or sp.seed is not None
                    or sp.logit_bias
                    or sp.min_tokens > seq.num_generated + 1
                    or seq.fsm_state is not None):
                return None
            if self._seq_budget(seq) <= 1:
                # Step N's token exhausts the row's budget: it will
                # finish with reason=length at reconcile. Mask the
                # slot now — a live row here would write KV past the
                # row's page budget.
                rows.append(None)
                continue
            rows.append(seq)
            any_live = True
        if not any_live:
            return None
        for seq in rows:
            if seq is None:
                continue
            # Post-commit convention: before a decode step, capacity
            # covers total_len + 1 tokens; after step N commits,
            # total_len grows by one, so reserve total_len + 2 now.
            # The pages simply extend seq.pages — a finish/abort at
            # reconcile returns them through the ordinary
            # free_sequence path, no separate bookkeeping.
            needed = self._pages_needed(seq, seq.total_len + 2)
            if needed == 0:
                continue
            try:
                seq.pages.extend(self.cache.allocate_pages(needed))
            except OutOfPagesError:
                # Pages already granted to earlier rows stay with
                # them (they are those rows' legitimate next-step
                # reservation; the sync re-plan reuses them).
                return None
        self._last_was_prefill = False
        return rows

    def _decode_window(self) -> int:
        """Iterations of the next decode burst. The burst evaluates
        per-row budgets and stop sets on device
        (model_runner._decode_burst_impl), so the full window is
        always safe — rows with less than K remaining simply go
        inactive mid-burst. One burst shape compiles, ever; what an
        iteration commits is the burst's own affair: one token a row,
        or one or two where a draft module proposes inside it
        (``_window_tokens``)."""
        return max(1, self.config.decode_steps)

    def _window_tokens(self, window: int) -> int:
        """The most tokens a row commits in a burst of ``window``
        iterations, which is what its pages must hold: an iteration
        that verifies the draft module's proposal commits two; a
        block-diffusion burst's iterations are forward passes, a block
        of ``block_length`` tokens for every ``block_steps`` denoising
        passes and a store pass."""
        block = self.config.block_length
        if block:
            return block * burst_blocks(window, self.config.block_steps)
        return window * (2 if self.config.draft_module else 1)

    def _prefill_target(self, seq: Sequence) -> int:
        """The prompt tokens a prefill computes: all of them, or the
        whole blocks of a block-diffusion family's prompt (the
        remainder enters the first block as given places)."""
        block = self.config.block_length
        n = seq.num_prompt_tokens
        return n - n % block if block else n

    def _seq_budget(self, seq: Sequence) -> int:
        return decode_budget(seq, self.config.max_model_len)

    def _draft_limit(self, seq: Sequence) -> int:
        """Longest draft this row may carry: the emit budget, further
        capped per-sequence by the spec-k autotune controller
        (docs/autotuning.md). The cap only shortens the draft list —
        a non-shape input — so the compiled verify span is
        untouched."""
        limit = self._seq_budget(seq) - 1
        if seq.spec_k_cap is not None:
            limit = min(limit, seq.spec_k_cap)
        return limit

    def _admission_order(self) -> List[Sequence]:
        """QoS admission order (docs/qos.md): priority class first,
        then arrival. The sort is stable and preempted victims keep
        their original arrival_time, so a restored victim leads its
        class rather than re-queueing at the back."""
        return sorted(self.waiting,
                      key=lambda s: (s.priority, s.arrival_time))

    def _full_prefill_step_waits(self) -> bool:
        """Whether _plan_prefill would fill a whole step now: at least
        ``prefill_batch_size`` chunk rows, counted as it would take
        them (one a waiting sequence, mid-prompt ones included, parked
        hand-offs and aborted ones not, admissions only while
        ``running`` has rows for them) and as far as the free pages
        and state slots reach. Counted, not planned: planning gives a
        sequence its pages and slot at first touch and cannot be taken
        back. A prefix hit is not looked up here, so a first-touch
        prompt counts with all its pages."""
        rows = admitting = 0
        pages = self.cache.num_free_pages
        # A model that keeps no recurrent state is never short of a
        # slot for one.
        slots = (self.cache.num_free_state_slots
                 if self.cache.num_state_slots
                 else self.config.prefill_batch_size)
        for seq in self._admission_order():
            if seq.state in (SequenceState.ABORTED,
                             SequenceState.AWAITING_KV):
                continue
            if (len(self.running) + admitting
                    >= self.config.max_num_seqs):
                break
            if seq.num_computed_tokens == 0 and not seq.pages:
                if (self.sp_threshold is not None
                        and seq.num_prompt_tokens >= self.sp_threshold):
                    break  # a whole-prompt plan runs alone
                pages -= self._pages_needed(seq, seq.num_prompt_tokens)
                slots -= 1
                if pages < 0 or slots < 0:
                    break
            rows += 1
            if rows >= self.config.prefill_batch_size:
                return True
            if (seq.num_computed_tokens + self.config.prefill_chunk_size
                    >= self._prefill_target(seq)):
                admitting += 1
        return False

    def _plan_prefill(self, max_tokens: Optional[int] = None
                      ) -> Optional[PrefillPlan]:
        # ``max_tokens`` caps the total prompt tokens admitted this
        # step (unified ragged steps budget prefill work so decode
        # rows sharing the batch keep their ITL — _plan_mixed); the
        # final chunk is truncated to fit, resuming next step.
        chunks: List[PrefillChunk] = []
        tokens_planned = 0
        admitting = 0  # rows that will join `running` this step
        for seq in self._admission_order():
            if len(chunks) >= self.config.prefill_batch_size:
                break
            if seq.state == SequenceState.ABORTED:
                self.waiting.remove(seq)
                continue
            if seq.state == SequenceState.AWAITING_KV:
                # Parked handoff: its KV pages are not reachable yet
                # (engine._admit_handoffs flips it to WAITING).
                continue
            if (len(self.running) + admitting
                    >= self.config.max_num_seqs):
                break
            if (max_tokens is not None
                    and tokens_planned >= max_tokens):
                break
            if seq.num_computed_tokens == 0 and not seq.pages:
                # First touch: reuse cached prefix pages, then allocate
                # the remainder for the whole prompt up front.
                matched = self.cache.match_prefix(
                    seq.prompt_token_ids, seq.cache_salt,
                    reads_next_token=self.config.draft_module)
                if self.restore_hook is not None:
                    restored = self.restore_hook(
                        seq.prompt_token_ids, matched,
                        seq.cache_salt,
                    )
                    if restored and self.tracer is not None:
                        self.tracer.event(
                            seq.seq_id, "offload_restore",
                            pages=len(restored))
                    matched = matched + restored
                if (self.sp_threshold is not None
                        and not matched
                        and seq.num_prompt_tokens >= self.sp_threshold):
                    # Long cold prompt: context-parallel whole-prompt
                    # prefill, one sequence per dispatch. Runs alone —
                    # if chunked work was already gathered this step,
                    # emit that first and pick the long prompt up next
                    # step.
                    if chunks:
                        break
                    try:
                        seq.pages = list(self.cache.allocate_pages(
                            self._pages_needed(
                                seq, seq.num_prompt_tokens)))
                    except OutOfPagesError:
                        seq.pages = []
                        if not self.running:
                            logger.error(
                                "Request %s can never fit in the KV "
                                "cache; aborting", seq.seq_id)
                            self.waiting.remove(seq)
                            self._finish(seq, FinishReason.ABORT)
                            self.newly_aborted.append(seq)
                            continue
                        logger.warning(
                            "KV cache full: request %s waits",
                            seq.seq_id)
                        return None
                    if seq.first_scheduled_time is None:
                        seq.first_scheduled_time = time.time()
                    return PrefillPlan(chunks=[PrefillChunk(
                        seq=seq,
                        chunk_start=0,
                        chunk_tokens=list(seq.prompt_token_ids),
                        is_last_chunk=True,
                    )], sp=True)
                seq.pages = matched
                seq.num_hashed_pages = len(matched)
                seq.num_computed_tokens = len(matched) * self.page_size
                needed = self._pages_needed(seq, seq.num_prompt_tokens)
                try:
                    seq.pages.extend(self.cache.allocate_pages(needed))
                    seq.state_slot = self.cache.allocate_state_slot()
                except OutOfPagesError:
                    self.cache.free_sequence(seq.pages)
                    seq.pages = []
                    seq.num_computed_tokens = 0
                    if chunks:
                        break  # run what we already gathered
                    if not self.running:
                        # Nothing will ever free pages: permanent.
                        logger.error(
                            "Request %s can never fit in the KV cache; "
                            "aborting", seq.seq_id
                        )
                        self.waiting.remove(seq)
                        self._finish(seq, FinishReason.ABORT)
                        self.newly_aborted.append(seq)
                        continue
                    logger.warning(
                        "KV cache full: request %s waits", seq.seq_id
                    )
                    return None
            start = seq.num_computed_tokens
            target = self._prefill_target(seq)
            if start >= target:
                # A block-diffusion prompt with no whole block left to
                # compute (shorter than a block, or all of its blocks
                # found in the prefix cache): it runs from here.
                self._admit(seq)
                admitting += 1
                continue
            end = min(start + self.config.prefill_chunk_size, target)
            if max_tokens is not None:
                end = min(end, start + (max_tokens - tokens_planned))
            is_last = end == target
            if seq.first_scheduled_time is None:
                seq.first_scheduled_time = time.time()
            chunks.append(PrefillChunk(
                seq=seq,
                chunk_start=start,
                chunk_tokens=seq.prompt_token_ids[start:end],
                is_last_chunk=is_last,
            ))
            tokens_planned += end - start
            if is_last:
                admitting += 1
        if not chunks:
            return None
        return PrefillPlan(chunks=chunks)

    def _pages_needed(self, seq: Sequence, target_tokens: int) -> int:
        have = len(seq.pages) * self.page_size
        if target_tokens <= have:
            return 0
        return -(-(target_tokens - have) // self.page_size)

    def _ensure_decode_capacity(self, lookahead: int = 1,
                                per_seq: Optional[Dict[str, int]]
                                = None) -> None:
        """Every running sequence needs page slots for its next decode
        window: min(lookahead, its own remaining budget) tokens — a
        row near its budget reserves only what its burst can write.
        ``per_seq`` (speculative plans) overrides the uniform lookahead
        with a per-sequence one (1 + draft length)."""
        for seq in list(self.running):
            if seq.state != SequenceState.RUNNING:
                # Preempted earlier in this very pass (we iterate a
                # snapshot): allocating pages to a WAITING victim
                # would leak them when prefill re-allocates from
                # scratch.
                continue
            ahead = (per_seq.get(seq.seq_id, 1) if per_seq is not None
                     else lookahead)
            ahead = max(1, min(ahead, self._seq_budget(seq)))
            needed = self._pages_needed(seq, seq.total_len + ahead)
            if needed == 0:
                continue
            try:
                seq.pages.extend(self.cache.allocate_pages(needed))
            except OutOfPagesError:
                # Preempt the lowest-priority, newest running sequence
                # (docs/qos.md): max over (priority, arrival) — the
                # exact inverse of the admission sort, and never a
                # sequence more important than the one needing pages
                # (seq itself is in the candidate set).
                victim = max(self.running,
                             key=lambda s: (s.priority, s.arrival_time))
                self._preempt(victim)
                if victim is seq:
                    continue
                try:
                    seq.pages.extend(self.cache.allocate_pages(needed))
                except OutOfPagesError:
                    self._preempt(seq)

    def _preempt(self, seq: Sequence) -> None:
        self._log_preemption(seq)
        self.num_preemptions += 1
        if self.tracer is not None:
            self.tracer.event(seq.seq_id, "preempt",
                              generated=len(seq.output_token_ids))
        self.running.remove(seq)
        # Preempt-to-offload (docs/qos.md): ship the victim's committed
        # KV pages to the offload tier BEFORE freeing them — the cache
        # fires evict_listener lazily on slot reuse, far too late for a
        # deterministic restore. 0 pages / no hook / hook failure all
        # degrade to the classic drop-and-recompute.
        evicted = 0
        if self.evict_hook is not None:
            try:
                evicted = self.evict_hook(seq)
            except Exception:
                logger.exception(
                    "Preempt-to-offload failed for %s; falling back to "
                    "recompute", seq.seq_id)
                evicted = 0
        outcome = "offloaded" if evicted else "recompute"
        self.preempt_offload_outcomes[outcome] = (
            self.preempt_offload_outcomes.get(outcome, 0) + 1)
        if evicted and self.tracer is not None:
            self.tracer.event(seq.seq_id, "preempt_offload",
                              pages=evicted)
        self.cache.free_sequence(seq.pages, seq.state_slot)
        seq.pages = []
        seq.state_slot = None
        seq.num_hashed_pages = 0
        # Recompute everything including generated tokens as "prompt".
        # num_prior_output_tokens keeps every generated-so-far budget
        # (max_tokens, min_tokens, seeded emitted index) counting
        # across the fold; presence/frequency penalty counts restart
        # (the folded tokens move to the repetition-penalty prompt
        # mask instead — a documented approximation under preemption).
        seq.num_prior_output_tokens += len(seq.output_token_ids)
        seq.prompt_token_ids = seq.all_token_ids
        seq.output_token_ids = []
        seq.num_computed_tokens = 0
        if evicted:
            # Park like a disagg handoff (docs/disaggregation.md): the
            # engine re-admits via _admit_handoffs once the shipped
            # pages are reachable (immediately for the host tier), and
            # the ordinary first-touch restore path pulls them back —
            # miss/unreachable degrades to recompute via the same
            # tri-state the handoff path already handles.
            seq.transition(SequenceState.AWAITING_KV)
            seq.handoff_arrival_time = time.time()
            if self.tracer is not None:
                self.tracer.event(seq.seq_id, "awaiting_kv_park",
                                  pages=evicted)
        else:
            seq.transition(SequenceState.WAITING)
        self.waiting.appendleft(seq)

    def _log_preemption(self, seq: Sequence) -> None:
        now = time.monotonic()
        if now - self._preempt_log_ts < _PREEMPT_LOG_INTERVAL_S:
            self._preempt_log_suppressed += 1
            return
        if self._preempt_log_suppressed:
            logger.warning(
                "Preempting %s (KV cache pressure; %d preemptions "
                "suppressed in the last %.0fs)", seq.seq_id,
                self._preempt_log_suppressed, _PREEMPT_LOG_INTERVAL_S)
        else:
            logger.warning("Preempting %s (KV cache pressure)",
                           seq.seq_id)
        self._preempt_log_ts = now
        self._preempt_log_suppressed = 0

    # ---- completion callbacks (driven by the engine) ----------------------

    def on_prefill_executed(self, chunk: PrefillChunk,
                            sampled_token: Optional[int]) -> None:
        seq = chunk.seq
        if seq.state in (SequenceState.ABORTED, SequenceState.FINISHED):
            return  # aborted while the chunk was in flight on device
        seq.num_computed_tokens = (chunk.chunk_start
                                   + len(chunk.chunk_tokens))
        if self.tracer is not None:
            self.tracer.event(
                seq.seq_id, "prefill_chunk",
                start=chunk.chunk_start,
                tokens=len(chunk.chunk_tokens),
                last=chunk.is_last_chunk)
        self.cache.commit_full_pages(
            seq.prompt_token_ids[:seq.num_computed_tokens],
            seq.pages, seq.num_hashed_pages, seq.cache_salt,
        )
        seq.num_hashed_pages = min(
            len(seq.pages),
            seq.num_computed_tokens // self.page_size,
        )
        if chunk.is_last_chunk and self.config.block_length:
            # The prefill of a block-diffusion family yields no token:
            # the row's first come out of its first block.
            self._admit(seq)
        elif chunk.is_last_chunk:
            assert sampled_token is not None
            try:
                self.waiting.remove(seq)
            except ValueError:
                return  # raced with an abort that already dequeued it
            seq.transition(SequenceState.RUNNING)
            seq.first_token_time = time.time()
            if self.tracer is not None:
                self.tracer.event(seq.seq_id, "first_token",
                                  token=int(sampled_token))
            self.running.append(seq)
            self._append_token(seq, sampled_token)

    def _admit(self, seq: Sequence) -> None:
        """A waiting row whose prefill is done (or had nothing to do)
        joins ``running`` with no token of its own yet."""
        try:
            self.waiting.remove(seq)
        except ValueError:
            return  # raced with an abort that already dequeued it
        seq.transition(SequenceState.RUNNING)
        self.running.append(seq)

    def finish_handoff(self, seq: Sequence) -> None:
        """Disagg prefill handoff complete (the engine already shipped
        the committed KV to the offload tier): retire the sequence so
        its pages free immediately for the next prefill burst."""
        if seq in self.running:
            self.running.remove(seq)
        self._finish(seq, FinishReason.HANDOFF)

    def on_spec_executed(self, seq: Sequence) -> None:
        """Post-verify accounting rollback (docs/speculative.md).

        The verify pass computed KV through ``total_len_before +
        draft_len`` positions, but only the accepted prefix + bonus
        were appended; the committed-token count must reflect exactly
        the kept tokens — the rejected tail's KV is junk past
        ``total_len``, causally invisible and overwritten by the next
        step. Never counting it is the state rollback."""
        if seq.state == SequenceState.RUNNING:
            seq.num_computed_tokens = seq.total_len

    def append_decode_token(self, seq: Sequence, token: int) -> bool:
        """Append one decoded token; returns False if the sequence is
        no longer running (remaining window tokens are discarded)."""
        if seq.state != SequenceState.RUNNING:
            return False
        self._append_token(seq, token)
        return seq.state == SequenceState.RUNNING

    def commit_decode_tokens(self, seq: Sequence, tokens: List[int]
                             ) -> tuple:
        """Commit what one decode program gave a running row; returns
        (how many of ``tokens`` were kept, whether the row was walked
        token by token). The end state is the one append_decode_token
        reaches token by token: the kept tokens appended, the finish
        decided, pages and state slot freed, ``running`` updated.

        A row is appended in one go and its finish decided once,
        from the place of its first stop id and its budgets, unless
        something of it must be looked at every token, which the row
        itself says: a guided automaton to advance, a ``min_tokens``
        not yet passed (a stop id under the minimum does not end the
        row), a stop set wider than the device's (the burst ran past
        what the host ends at), logprobs (an entry a token)."""
        sp = seq.sampling
        if tokens and seq.first_token_time is None:
            # A block-diffusion row: its prefill yielded no token.
            seq.first_token_time = time.time()
            if self.tracer is not None:
                self.tracer.event(seq.seq_id, "first_token",
                                  token=int(tokens[0]))
        if (seq.fsm_state is not None or sp.logprobs
                or sp.min_tokens > seq.num_generated
                or (not sp.ignore_eos
                    and len(sp.stop_token_ids) > STOP_SET_WIDTH)):
            kept = 0
            for token in tokens:
                if seq.state != SequenceState.RUNNING:
                    break  # stop hit mid-window: drop the tail
                self.append_decode_token(seq, token)
                kept += 1
            return kept, True
        if not tokens or seq.state != SequenceState.RUNNING:
            return 0, False
        # The k-th token ends the row at the first k at which
        # _append_token would: a stop id there, else a budget met
        # (the first token is appended whatever the budget reads).
        at_length = max(1, decode_budget(seq, self.config.max_model_len))
        at_stop = len(tokens) + 1
        if not sp.ignore_eos:
            for stop in sp.stop_token_ids:
                if stop in tokens:
                    at_stop = min(at_stop, tokens.index(stop) + 1)
        kept = min(len(tokens), at_stop, at_length)
        seq.output_token_ids.extend(tokens[:kept])
        if kept == at_stop:
            self._finish(seq, FinishReason.STOP)
            self.running.remove(seq)
        elif kept == at_length:
            self._finish(seq, FinishReason.LENGTH)
            self.running.remove(seq)
        return kept, False

    def _append_token(self, seq: Sequence, token: int) -> None:
        seq.output_token_ids.append(token)
        if self.guided_advance is not None and seq.fsm_state is not None:
            self.guided_advance(seq, token)
        stop_ids = seq.sampling.stop_token_ids
        # min_tokens: the device suppresses stop ids while under the
        # minimum (model_runner._suppress_payload), but only up to
        # STOP_SET_WIDTH of them — a wider set's overflow could still
        # be sampled, and must not end the sequence early.
        past_min = seq.num_generated > seq.sampling.min_tokens
        if (not seq.sampling.ignore_eos and token in stop_ids
                and past_min):
            self._finish(seq, FinishReason.STOP)
            self.running.remove(seq)
        elif seq.num_generated >= seq.sampling.max_tokens:
            self._finish(seq, FinishReason.LENGTH)
            self.running.remove(seq)
        elif seq.total_len >= self.config.max_model_len:
            self._finish(seq, FinishReason.LENGTH)
            self.running.remove(seq)

    def _finish(self, seq: Sequence, reason: FinishReason) -> None:
        if seq.state in (SequenceState.FINISHED, SequenceState.ABORTED):
            return
        seq.transition(SequenceState.ABORTED if reason == FinishReason.ABORT
                       else SequenceState.FINISHED)
        seq.finish_reason = reason
        seq.finish_time = time.time()
        if self.proposer is not None:
            self.proposer.drop(seq.seq_id)
        if seq.pages:
            self.cache.free_sequence(seq.pages, seq.state_slot)
            seq.pages = []
            seq.state_slot = None
