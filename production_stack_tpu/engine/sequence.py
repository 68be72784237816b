"""Request/sequence state for the continuous-batching engine."""

from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional


# Fixed per-row stop-set width for the decode burst: one compiled
# shape regardless of batch composition (a data-dependent width would
# recompile the fused K-step program mid-serving). Requests with more
# stop ids than this still finish correctly — the host enforces the
# full set; the burst merely speculates a little further.
STOP_SET_WIDTH = 16


@dataclass
class SamplingParams:
    max_tokens: int = 128
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    stop_token_ids: List[int] = field(default_factory=list)
    # Text-level stop sequences (OpenAI ``stop``): enforced by the
    # server on the detokenized stream (engine/server.py
    # _StopStringScanner) — token-level state can't see them because
    # a stop string may span token boundaries.
    stop_strings: List[str] = field(default_factory=list)
    # OpenAI penalties over the tokens GENERATED so far (presence:
    # flat once seen; frequency: per occurrence) and vLLM/HF-style
    # repetition penalty over prompt+output. Applied on device inside
    # the compiled step (ops/sampling.py apply_penalties).
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    repetition_penalty: float = 1.0
    ignore_eos: bool = False
    seed: Optional[int] = None
    # OpenAI ``logprobs``/``top_logprobs``: return the sampled token's
    # logprob and up to top_logprobs alternatives per position
    # (computed on device from the unmodified distribution; capped at
    # the compiled width, engine/model_runner.py TOP_LOGPROBS_WIDTH).
    logprobs: bool = False
    top_logprobs: int = 0
    # OpenAI ``logit_bias``: {token_id: bias in [-100, 100]} added to
    # the logits before sampling (after penalties; logprobs report the
    # raw distribution per the OpenAI contract). Applied on device as
    # a dense [B, vocab] add only when some row in the batch uses it
    # (model_runner._bias_payload).
    logit_bias: Optional[Dict[int, float]] = None
    # vLLM ``min_tokens``: EOS and stop_token_ids cannot be GENERATED
    # until this many tokens have been emitted — their logits are
    # suppressed on device while under the minimum
    # (model_runner._suppress_payload), matching vLLM's semantics
    # (text-level stop strings are not gated, as in vLLM).
    min_tokens: int = 0
    # OpenAI ``response_format``: "json" = guided JSON decoding via
    # the byte-level automaton (engine/guided.py); the device masks
    # inadmissible tokens inside the sampling step. None = free text.
    guided: Optional[str] = None
    # Block-diffusion families only (docs/block_diffusion.md; the
    # names of the published generate.py): the denoising passes a
    # block (1 to the block's length), which masked places a pass
    # commits (ops/sampling.py REMASKING_STRATEGIES) and the dynamic
    # rule's threshold. None = the model configuration's.
    denoising_steps: Optional[int] = None
    remasking_strategy: Optional[str] = None
    confidence_threshold: Optional[float] = None

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0

    @property
    def needs_penalties(self) -> bool:
        return (self.presence_penalty != 0.0
                or self.frequency_penalty != 0.0
                or self.repetition_penalty != 1.0)


class SequenceState(enum.Enum):
    WAITING = "waiting"  # queued, prompt not (fully) prefilled
    # Disaggregated handoff admission (docs/disaggregation.md): the
    # sequence arrived via a prefill->decode handoff and is parked
    # until its KV pages are reachable in an offload tier (or the
    # handoff timeout elapses and it degrades to recompute). Counted
    # in num_requests_waiting; skipped by prefill planning.
    AWAITING_KV = "awaiting_kv"
    RUNNING = "running"  # decoding
    FINISHED = "finished"
    ABORTED = "aborted"


# The sequence lifecycle, as data. Single source of truth for every
# ``seq.state`` change in the stack: ``Sequence.transition`` validates
# against it at runtime, the ``state-machine`` staticcheck rule flags
# direct ``.state =`` writes and untabled transitions at lint time,
# and docs/sequence_states.md renders it (kept in sync both
# directions by the same rule). ``"new"`` is a pseudo-state meaning
# "constructed with this initial state".
SEQUENCE_TRANSITIONS = (
    ("new", "waiting",
     "ordinary admission: request queued for prefill"),
    ("new", "awaiting_kv",
     "disagg handoff / crash resume arrives parked until its shipped "
     "KV is reachable in an offload tier"),
    ("waiting", "running",
     "last prefill chunk executed and the first token sampled (a "
     "block-diffusion family: the prompt's whole blocks prefilled, or "
     "none to prefill, and no token yet)"),
    ("waiting", "awaiting_kv",
     "cold-start probe: park a fresh request to ask the shared KV "
     "tier for its prefix before computing"),
    ("waiting", "aborted",
     "admission rejected (queue full, oversized prompt) or client "
     "abort while queued"),
    ("awaiting_kv", "waiting",
     "parked KV became reachable (admit for restore) or the wait "
     "degraded to recompute (timeout / miss / no tier)"),
    ("awaiting_kv", "aborted",
     "client abort or engine shutdown while parked"),
    ("running", "waiting",
     "preempted for KV-cache pressure; generated tokens folded into "
     "the prompt for recompute"),
    ("running", "awaiting_kv",
     "preempt-to-offload: pages shipped to the offload tier, parked "
     "for re-admission"),
    ("running", "finished",
     "stop token / length budget / disagg handoff retirement"),
    ("running", "aborted",
     "client abort or crash containment mid-decode"),
)

_ALLOWED_TRANSITIONS = frozenset(
    (src, dst) for src, dst, _ in SEQUENCE_TRANSITIONS)

SEQUENCE_INITIAL_STATES = frozenset(
    dst for src, dst, _ in SEQUENCE_TRANSITIONS if src == "new")


class FinishReason(str, enum.Enum):
    STOP = "stop"
    LENGTH = "length"
    ABORT = "abort"
    # Disaggregated prefill role: the engine computed the prompt KV,
    # shipped it to the offload tier and retired the sequence after
    # the first sampled token; decoding continues on a decode-role
    # engine (docs/disaggregation.md).
    HANDOFF = "handoff"


@dataclass
class Sequence:
    seq_id: str
    prompt_token_ids: List[int]
    sampling: SamplingParams
    arrival_time: float = field(default_factory=time.time)

    state: SequenceState = SequenceState.WAITING
    output_token_ids: List[int] = field(default_factory=list)
    # How many prompt tokens have been prefilled (incl. prefix-cache hits).
    num_computed_tokens: int = 0
    pages: List[int] = field(default_factory=list)
    num_hashed_pages: int = 0
    # Slot of the recurrent-state pool (engine/kv_cache.py), held with
    # the pages; None for a model that keeps no such state.
    state_slot: Optional[int] = None
    finish_reason: Optional[FinishReason] = None
    first_token_time: Optional[float] = None
    # When the scheduler first planned this sequence's prefill: splits
    # client TTFT into queueing (arrival -> here) vs prefill compute
    # (here -> first_token_time).
    first_scheduled_time: Optional[float] = None
    # Wall time of the latest decode-step emission for this sequence:
    # inter-token latency is observed per token as steps complete
    # (engine/metrics.py on_decode_tokens), so multi-token speculative
    # steps are accounted at their true per-token cadence.
    last_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # LoRA adapter slot (0 = base model; see engine/lora.py).
    lora_id: int = 0
    # Prefix-cache namespace root (kv_cache.chain_hashes): nonzero for
    # adapter requests so adapter-specific KV never cross-hits.
    cache_salt: int = 0
    # Server-side stream hook (asyncio queue or callable), opaque here.
    output_sink: Any = None
    # Guided-decoding automaton state (engine/guided.py); None for
    # unconstrained rows. Host-side mirror of the device carry.
    fsm_state: Optional[int] = None
    # Generated tokens folded back into the prompt by preemption
    # (scheduler._preempt): every "tokens generated so far" budget
    # (max_tokens, min_tokens, seeded-sampling emitted index) must
    # count these or a preempted sequence restarts its windows.
    num_prior_output_tokens: int = 0
    # Disaggregated serving (docs/disaggregation.md): a prefill-role
    # request finishes after the first sampled token — the engine
    # ships the committed KV pages to the offload tier and returns a
    # handoff descriptor instead of decoding.
    handoff_prefill: bool = False
    # Decode-side handoff bookkeeping: when the sequence was parked in
    # AWAITING_KV (admission latency = admit time - this).
    handoff_arrival_time: Optional[float] = None
    # End-to-end trace id (docs/observability.md): the router's
    # x-request-id, carried so engine spans on every hop of a
    # disaggregated request stitch to the same router span.
    request_id: Optional[str] = None
    # QoS priority class (docs/qos.md): int value of qos.Priority —
    # lower is more important. Admission sorts waiting sequences by
    # (priority, arrival_time); preemption picks the max of the same
    # tuple (lowest-priority, newest victim). Plain int so this module
    # stays import-light.
    priority: int = 1
    # QoS degradation ladder: the router marks throttled-tenant
    # requests spec-off; the scheduler then never spends speculative
    # draft/verify slack on them (docs/qos.md).
    spec_off: bool = False
    # Self-tuning telemetry + knob (docs/autotuning.md): lifetime
    # draft/accept counters the spec-k controller windows per tick,
    # and its per-sequence draft-length cap. The cap rides the same
    # non-shape draft inputs as spec_off — the proposer just drafts
    # fewer tokens, the compiled verify shape never changes. None =
    # uncapped (--speculative-k governs).
    spec_drafted_total: int = 0
    spec_accepted_total: int = 0
    spec_k_cap: Optional[int] = None
    # Cluster KV economy (docs/kv_economy.md): parked in AWAITING_KV
    # at admission to probe the shared cache for this prompt's prefix
    # before prefill. Unlike a disagg handoff, a cold-start probe
    # degrades to compute IMMEDIATELY when the tier is unreachable —
    # nothing was shipped for it, so there is nothing to wait for.
    cold_start_probe: bool = False

    def transition(self, new_state: SequenceState) -> None:
        """The one sanctioned way to change ``state``. Validates the
        move against SEQUENCE_TRANSITIONS (same-state is a no-op, so
        idempotent callers like abort-on-already-aborted stay simple);
        an untabled pair raises instead of silently corrupting the
        lifecycle. The ``state-machine`` staticcheck rule flags any
        direct ``.state =`` write outside this method."""
        old = self.state
        if old == new_state:
            return
        if (old.value, new_state.value) not in _ALLOWED_TRANSITIONS:
            raise ValueError(
                f"untabled sequence transition {old.value} -> "
                f"{new_state.value} for {self.seq_id}; if this move is "
                "legitimate, add a row to SEQUENCE_TRANSITIONS (and "
                "docs/sequence_states.md)")
        self.state = new_state

    @property
    def num_generated(self) -> int:
        return self.num_prior_output_tokens + len(self.output_token_ids)

    @property
    def num_prompt_tokens(self) -> int:
        return len(self.prompt_token_ids)

    @property
    def total_len(self) -> int:
        return self.num_prompt_tokens + len(self.output_token_ids)

    @property
    def all_token_ids(self) -> List[int]:
        return self.prompt_token_ids + self.output_token_ids

    @property
    def prefill_done(self) -> bool:
        return self.num_computed_tokens >= self.num_prompt_tokens

    def remaining_prompt(self) -> int:
        return self.num_prompt_tokens - self.num_computed_tokens


def decode_budget(seq: "Sequence", max_model_len: int) -> int:
    """Tokens ``seq`` may still emit (max_tokens and model-length
    budgets). Single source of truth: the scheduler's page
    reservation, the host finish logic (scheduler._append_token), and
    the device decode burst (model_runner._decode_burst_impl) must all
    agree on this number or the burst could write past its pages."""
    return min(
        seq.sampling.max_tokens - seq.num_generated,
        max_model_len - seq.total_len,
    )


def block_start(seq: "Sequence", block: int) -> int:
    """Where ``seq``'s next block begins: its tokens before that are
    whole blocks, in the pages once prefilled or stored; the tokens
    from there on (the prompt's remainder, before the first block; none
    after it) enter the block as given places."""
    return seq.total_len - seq.total_len % block


def draftless(seq: "Sequence") -> bool:
    """A row that commits one token a step whatever proposes drafts:
    its logits are rewritten (penalties, a logit bias, min_tokens
    suppression, a guided grammar) or its draws follow its own seed, so
    a proposer's distribution is not the one it is sampled from. The
    exclusion set of prompt-lookup speculation (scheduler._plan_spec),
    for the draft module inside the burst (model_runner.run_decode)."""
    sp = seq.sampling
    return bool(sp.needs_penalties or sp.seed is not None
                or sp.logit_bias or sp.min_tokens > seq.num_generated
                or seq.fsm_state is not None)
