"""Model runner: owns device state and the compiled step functions.

Compilation strategy (SURVEY.md §7 hard part (a)): prefill chunks are
padded to power-of-two buckets and decode runs at a fixed slot width, so
the engine touches a small closed set of shapes; each shape jit-compiles
once and is cached by XLA thereafter. KV caches are donated through
every step so the arrays are updated in place in HBM.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.perf_observatory import (
    InstrumentedJit,
    PerfObservatory,
    add_load_splits,
    listen_for_loads,
    rounded_split,
    take_load_split,
)
from production_stack_tpu.engine.scheduler import (
    DecodePlan,
    PrefillPlan,
    burst_blocks,
)
from production_stack_tpu.engine.tracing import StartupTimeline
from production_stack_tpu.engine.sequence import (
    STOP_SET_WIDTH,
    Sequence,
    block_start,
    decode_budget,
    draftless,
)
from production_stack_tpu.models.registry import (
    deferred_kv_architectures,
    get_draft,
    get_model,
    init_hybrid_cache,
)
from production_stack_tpu.ops.attention import (
    block_pages,
    gathered_blocks,
    write_run_to_pages,
    write_to_pages,
)
from production_stack_tpu.ops.quant_kv import (
    QuantKV,
    quant_cache_struct,
    quant_cache_zeros,
)
from production_stack_tpu.ops.window_attention import write_to_ring
from production_stack_tpu.ops.sampling import (
    REMASKING_STRATEGIES,
    _needs_mask,
    apply_penalties,
    draw_proposal,
    sample_tokens,
    spec_verify,
    token_logprobs,
    unmask_block,
    verify_proposal,
)
from production_stack_tpu.parallel.mesh import (
    shard_cache,
    shard_params,
)
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)

# What attention_impl='auto' serves for the unified step on a TPU
# besides the composed prefill kernel. False: the fused ragged kernel
# is not probed under 'auto'; an explicit 'pallas' (or
# attention_impl_unified='pallas_ragged') probes and serves it. It
# rests on no number from the driver: the capture behind it was a
# builder's, ctx 2k-16k at batch 8-32, before PR 28's block gather.
# ROADMAP S2 / D2 own the re-measurement on a cell. (The decode and the
# prefill kernels are served under 'auto' wherever they compile.)
PALLAS_RAGGED_IN_AUTO = False

# Compiled top-logprobs width: OpenAI allows top_logprobs 0-20 but a
# per-request width would compile a program per value; requests are
# served min(requested, TOP_LOGPROBS_WIDTH) alternatives from one
# compiled shape. Sized to the OpenAI maximum so the server never
# silently returns fewer alternatives than requested (the server also
# rejects top_logprobs > 20 with a 400).
TOP_LOGPROBS_WIDTH = 20

# A block-diffusion burst's per-row inputs beside the sampling knobs
# (dispatch_burst): the given places of the row's first block, its
# denoising passes a block, its rule (an index into
# ops/sampling.py REMASKING_STRATEGIES) and the dynamic rule's
# threshold.
BLOCK_ROW_INPUTS = ("block_given", "block_steps", "block_strategy",
                    "block_threshold")

# Model families served by the deferred-KV-write burst: those that
# declare that their forward takes kv_tail (models/registry.py: the
# Llama shapes, every layer of which has a tail, and the hybrids, whose
# attention layers alone have one).
DEFERRED_KV_FAMILIES = deferred_kv_architectures()


def deferred_kv_eligible(architecture: str, decode_steps: int,
                         pipeline_parallel: int = 1,
                         context_parallel: int = 1,
                         speculative_k: int = 0) -> bool:
    """The ONE eligibility predicate for deferred KV writes.

    Used by the runner's capability guard (which raises on explicit
    ineligible 'on') and the server's '--deferred-kv-writes auto'
    resolution — one definition so the two cannot drift (an added
    exclusion must flow to both). Every decode attention form serves
    the burst's tail (models/llama.py ``deferred_attention``: the XLA
    form and the Pallas paged decode kernel), so the attention impl
    does not enter.
    ``architecture`` must be one whose forward takes ``kv_tail``
    (DEFERRED_KV_FAMILIES): the Llama family, every layer of which has
    a tail, and the hybrids, whose attention layers have one while
    their recurrent state rides the burst's carry.
    Speculative decoding excludes deferral: the verify step must
    write draft KV eagerly so later draft positions attend to
    earlier ones (docs/speculative.md §interactions)."""
    return (decode_steps > 1
            and architecture in DEFERRED_KV_FAMILIES
            and pipeline_parallel == 1
            and context_parallel == 1
            and speculative_k == 0)


def async_scheduling_eligible(decode_steps: int, speculative_k: int,
                              distributed: bool = False) -> bool:
    """The ONE eligibility predicate for the overlapped async
    execution pipeline (docs/async_pipeline.md).

    Used by EngineConfig's hard validation error message and the
    server's '--async-scheduling auto' resolution — one definition so
    the two cannot drift (the deferred_kv_eligible pattern). The
    pipeline's plan-ahead step assumes every running row commits
    exactly one token per dispatch, so multi-step bursts and
    speculative verify (data-dependent commit counts) are out;
    multihost serving is out because the step broadcast ships
    host-resident numpy payloads, while the ahead dispatch feeds
    device-resident arrays forward."""
    return (decode_steps == 1 and speculative_k == 0
            and not distributed)


def unified_step_eligible(distributed: bool = False,
                          engine_role: str = "both") -> bool:
    """The eligibility predicate for the unified ragged step
    (docs/unified_step.md), used by the server's '--unified-step auto'
    resolution.

    The pp and cp runners execute the ragged [R, W] block natively —
    pipeline stages thread the per-row descriptor triple through
    their microbatch handoffs, and the sp runner shards the W axis
    (docs/parallelism.md) — so neither disqualifies. Out: the
    multihost bridge broadcasts bimodal payload kinds, and a
    disaggregated role engine by construction never holds prefill and
    decode work at once, so neither can mix rows."""
    return not distributed and engine_role == "both"


def pallas_backend_error(config: EngineConfig) -> Optional[str]:
    """The ONE set of backend rules gating every Pallas attention site.

    Rules a standalone compile of the kernel cannot see, so they are
    gated explicitly — and in ONE place, used by all resolution sites
    (decode/prefill, spec verify, unified ragged), mirroring
    deferred_kv_eligible: a backend rule that drifts across sites is
    how the unified path briefly resolved independently of the
    decode/prefill gate.

    - The kernels DMA [head_dim, page_size] page slices out of HBM;
      Mosaic requires the minor dim be lane-tile (128) aligned.
    - Under plain tensor parallelism the step programs are partitioned
      by GSPMD, which cannot partition a Mosaic call ("Mosaic kernels
      cannot be automatically partitioned. Please wrap the call in a
      shard_map" — raised at the first dispatch of the sharded step,
      observed at tp=4 on a v5e host). The pp and cp runners wrap
      their forwards in shard_map bodies and are not judged here.

    Returns a reason string when Pallas cannot serve, None when the
    backend rules are satisfied."""
    if config.cache.page_size % 128:
        return ("Pallas attention needs page_size %% 128 == 0 "
                "(got %d)" % config.cache.page_size)
    par = config.parallel
    if (par.tensor_parallel_size > 1
            and par.pipeline_parallel_size == 1
            and par.context_parallel_size == 1):
        return ("Pallas attention cannot serve tensor_parallel_size=%d:"
                " GSPMD cannot partition a Mosaic call and the kernels"
                " are not wrapped in shard_map"
                % par.tensor_parallel_size)
    return None


def _as_device(x):
    """Identity for arrays already on device; transfer otherwise.

    Payload entries arrive as jax.Arrays on the local dispatch path
    (one fused device_put upstream) but as numpy on the multihost
    broadcast path. ``jnp.asarray`` is semantically a no-op for the
    former yet costs ~0.1 ms of dtype canonicalization per call —
    ~1 ms per decode step across a payload — so skip it.
    """
    return x if isinstance(x, jax.Array) else jnp.asarray(x)


def prefill_buckets(chunk_size: int) -> List[int]:
    buckets, b = [], 16
    while b < chunk_size:
        buckets.append(b)
        b *= 2
    buckets.append(chunk_size)
    return buckets


def prefill_shapes(batch_size: int, chunk_size: int
                   ) -> List[Tuple[int, int]]:
    """Every (rows, tokens) shape a prefill step is compiled at: the
    full width at each token bucket, and half the rows at the top
    bucket. One half width and no more: each shape is one more
    whole-model program in every start (the layers are unrolled), the
    padding is at the top bucket (a wide step at a low bucket is few
    token places already), and a quarter width would be a second
    program for a few per cent of the one step it serves."""
    shapes = [(batch_size, t) for t in prefill_buckets(chunk_size)]
    half = -(-batch_size // 2)
    if half < batch_size:
        shapes.append((half, chunk_size))
    return shapes


def prefill_shape(rows: int, longest: int, batch_size: int,
                  chunk_size: int) -> Tuple[int, int]:
    """The compiled shape a plan of ``rows`` chunk rows whose longest
    chunk has ``longest`` tokens runs at: of the shapes that hold it,
    the one with the fewest token places; a tie stays wide."""
    return min((s for s in prefill_shapes(batch_size, chunk_size)
                if s[0] >= rows and s[1] >= longest),
               key=lambda s: (s[0] * s[1], -s[0]))


class HostKeys:
    """The sampling keys of every step program, made on the host: each
    is the ``uint32[2]`` a threefry key is, word 0 the engine seed's
    and word 1 a counter, so one seed gives one stream of keys and no
    two of 2**32 consecutive keys are equal. A key is a numpy payload
    entry like every other input: making it runs no device program and
    waits for none."""

    def __init__(self, seed: int):
        self._seed = seed & 0xFFFFFFFF
        self._drawn = 0

    def next(self) -> np.ndarray:
        key = np.array([self._seed, self._drawn & 0xFFFFFFFF], np.uint32)
        self._drawn += 1
        return key


class DecodeStepHandle:
    """One dispatched-but-unread single-step decode program.

    Under JAX async dispatch the compiled program is already running
    (or queued) on device; ``token_source`` exposes the sampled-token
    device array so the NEXT step can consume it without a host round
    trip, and ``result()`` performs the step's ONE blocking host read
    — a single fused device_get of the sampled tokens plus, when
    requested, all three logprob arrays — parsed exactly like the
    synchronous path so sync and async consumers share one format.
    """

    is_spec = False

    def __init__(self, runner: "ModelRunner", rows, sampled,
                 want_lp: bool):
        self.runner = runner
        # List[Optional[Sequence]]: None rows are plan-ahead slots
        # whose sequence was already known to finish (dispatched as
        # masked pad rows so row alignment with token_source holds).
        self.rows = rows
        self.sampled = sampled
        self.want_lp = want_lp
        self.attn_pages = runner.last_attn_pages
        # Set by the engine when this step was dispatched ahead of an
        # unread speculative verify step: expected_lens[i] is the
        # committed length row i must reach at completion for this
        # step's assume-one-token planning to have been right; a
        # mismatch (the verify accepted >= 1 draft) drops the row's
        # token through the stale-token path (docs/unified_step.md).
        self.expected_lens = None

    @property
    def token_source(self) -> jax.Array:
        """The [B] sampled-token device array (async feed-forward)."""
        return self.sampled[0] if self.want_lp else self.sampled

    def result(self) -> Tuple[List[List[int]], Optional[list]]:
        """Block on the step's one fused device_get and parse."""
        host = self.runner.read_back(self.sampled)
        n = len(self.rows)
        if not self.want_lp:
            return [[int(host[i])] for i in range(n)], None
        toks, slp, tids, tlps = host
        token_lists = [[int(toks[i])] for i in range(n)]
        lp_lists = [
            [self.runner._lp_entry(row, slp[i], tids[i], tlps[i])
             if row is not None and row.sampling.logprobs else None]
            for i, row in enumerate(self.rows)
        ]
        return token_lists, lp_lists


class SpecStepHandle:
    """One dispatched-but-unread speculative verify step.

    The async pipeline treats a verify step as a decode step with a
    data-dependent commit count (1..S tokens per row).
    ``token_source`` exposes the [B] device array of FIRST emitted
    tokens (e_0): whatever the acceptance turns out to be, e_0 is
    committed, and the token the assume-one-token ahead dispatch
    feeds at position L writes position L's CORRECT KV in both cases
    — if the first draft was accepted the write is bit-identical to
    the verify step's own, and if it was rejected the write repairs
    the junk the rejected draft left there (docs/unified_step.md
    §spec-under-async). ``result()`` performs the step's one blocking
    device_get and parses exactly like the synchronous spec path.
    """

    is_spec = True
    # Verify steps are never themselves dispatched ahead of an unread
    # verify step (the engine breaks the pipeline instead), so the
    # stale-drop marker is always unset here.
    expected_lens = None

    def __init__(self, runner: "ModelRunner", rows, drafts, sampled,
                 want_lp: bool):
        self.runner = runner
        self.rows = rows  # List[Sequence], no None slots
        self.drafts = drafts  # per-row draft lists (parallel to rows)
        self.sampled = sampled
        self.want_lp = want_lp
        self.attn_pages = runner.last_attn_pages

    @property
    def token_source(self) -> jax.Array:
        """[B] device array of each row's first emitted token."""
        out = self.sampled[0] if self.want_lp else self.sampled
        return out[:, 0]

    def result(self) -> Tuple[List[List[int]], Optional[list]]:
        host = self.runner.read_back(self.sampled)
        n = len(self.rows)
        if not self.want_lp:
            return [[int(t) for t in host[i] if t >= 0]
                    for i in range(n)], None
        toks, slp, tids, tlps = host
        s = toks.shape[1]
        token_lists, lp_lists = [], []
        for i, seq in enumerate(self.rows):
            row_t, row_l = [], []
            for j in range(s):
                if toks[i, j] < 0:
                    break
                row_t.append(int(toks[i, j]))
                row_l.append(
                    self.runner._lp_entry(seq, slp[i, j], tids[i, j],
                                          tlps[i, j])
                    if seq.sampling.logprobs else None)
            token_lists.append(row_t)
            lp_lists.append(row_l)
        return token_lists, lp_lists


class StepHandle:
    """One dispatched-but-unread prefill step, decode burst or unified
    step. ``result()`` is its one blocking read, parsed by the function
    its dispatcher left; a step no row of which samples (mid-prompt
    chunks alone) has nothing to read and waits for nothing."""

    def __init__(self, runner: "ModelRunner", sampled, parse):
        self.runner = runner
        self.sampled = sampled
        self._parse = parse

    def result(self):
        if self.sampled is None:
            return self._parse(None)
        return self._parse(self.runner.read_back(self.sampled))


class ModelRunner:
    def __init__(self, config: EngineConfig, mesh=None,
                 params=None,
                 startup: Optional[StartupTimeline] = None):
        self.config = config
        self.mesh = mesh
        # The start's spans (engine/tracing.py STARTUP_SPANS): the
        # server's, from its main(), or this runner's own, which
        # nobody reads. The weights and the cache below say where
        # they begin, each probe is its own span (_lowering_error);
        # the rest is ``boot.engine``.
        self.startup = startup if startup is not None \
            else StartupTimeline()
        ModelRunner._probing = self.startup
        listen_for_loads()
        model_config = config.model
        # int8 paged KV (docs/kv_quantization.md): pages stored as
        # QuantKV pytrees (int8 data + per-slot f32 scales); the write
        # path quantizes in-graph and the attention impls dequantize
        # in-kernel. Resolved once here — everything downstream
        # (cache creation, lowering probes, read/write_page, offload
        # payload arity) keys off this flag.
        self.kv_quantized = config.cache.resolved_kv_dtype() == "int8"
        if config.cache.cache_layout == "auto":
            # per_layer on a builder's capture from before the driver
            # had a chip, which no cell has repeated (both run
            # per_layer; ROADMAP D4 owns the comparison). pp shards
            # the stacked L axis and the sp ring walks the stacked
            # cache, so those configs resolve to stacked.
            config.cache.cache_layout = (
                "stacked"
                if (config.parallel.pipeline_parallel_size > 1
                    or config.parallel.context_parallel_size > 1)
                else "per_layer")
        auto_impl = model_config.attention_impl == "auto"
        if auto_impl:
            model_config.attention_impl = (
                "xla" if jax.default_backend() == "cpu" else "pallas"
            )
        if (model_config.attention_impl == "pallas"
                and jax.default_backend() != "cpu"):
            # Per-kernel Mosaic lowering probe at the engine's real
            # shapes: decode and prefill degrade to XLA independently
            # (round-2 failure mode was a *global* fallback that threw
            # away the working decode kernel when prefill didn't
            # compile). Under ``auto`` each kernel is served where it
            # compiles; an explicit "pallas" that cannot be honoured
            # is a start-up error.
            self._resolve_pallas_impls(model_config, config,
                                       auto_impl=auto_impl)
        logger.info(
            "Attention impls: decode=%s prefill=%s",
            model_config.attention_impl_decode
            or model_config.attention_impl,
            model_config.attention_impl_prefill
            or model_config.attention_impl)
        self._init_fn, self._forward = get_model(model_config)

        pp = config.parallel.pipeline_parallel_size
        if pp > 1:
            # Pipeline-parallel serving: stages over the mesh's 'pp'
            # axis replace the plain layer scan
            # (parallel/pipeline_serving.py).
            if mesh is None or "pp" not in mesh.axis_names \
                    or mesh.shape["pp"] != pp:
                raise ValueError(
                    "pipeline_parallel_size needs a mesh with a 'pp' "
                    f"axis of size {pp} (parallel.mesh.build_mesh)")
            from production_stack_tpu.parallel.pipeline_serving import (
                PP_FAMILIES,
                pp_paged_forward,
            )
            if model_config.architecture not in PP_FAMILIES:
                raise NotImplementedError(
                    "pipeline parallelism serves "
                    f"{'/'.join(PP_FAMILIES)} "
                    f"(got {model_config.architecture!r})")
            if model_config.num_hidden_layers % pp:
                raise ValueError(
                    f"layers {model_config.num_hidden_layers} must "
                    f"divide by pipeline_parallel_size {pp}")
            tp = config.parallel.tensor_parallel_size
            if tp > 1 and (model_config.num_key_value_heads % tp
                           or model_config.num_attention_heads % tp):
                raise ValueError(
                    "pp x tp needs attention/kv heads divisible by "
                    f"tensor_parallel_size {tp}")
            self._forward = functools.partial(pp_paged_forward,
                                              mesh=mesh)

        cp = config.parallel.context_parallel_size
        self._sp_size = cp
        if cp > 1:
            # Context-parallel prefill: long prompts shard their
            # sequence over the 'sp' mesh axis
            # (parallel/context_serving.py).
            from production_stack_tpu.parallel.context_serving import (
                SP_FAMILIES,
            )
            if mesh is None or "sp" not in mesh.axis_names \
                    or mesh.shape["sp"] != cp:
                raise ValueError(
                    "context_parallel_size needs a mesh with an 'sp' "
                    f"axis of size {cp} (parallel.mesh.build_mesh)")
            if model_config.architecture not in SP_FAMILIES:
                raise NotImplementedError(
                    "context parallelism serves "
                    f"{'/'.join(SP_FAMILIES)} "
                    f"(got {model_config.architecture!r})")
            if config.parallel.pipeline_parallel_size > 1:
                raise NotImplementedError(
                    "context parallelism with pipeline parallelism "
                    "(sp composes with tp; pp shards the layer axis "
                    "the sp prefill walks in full)")
            sp_tp = config.parallel.tensor_parallel_size
            if sp_tp > 1 and (
                    model_config.num_attention_heads % sp_tp
                    or model_config.num_key_value_heads % sp_tp):
                raise ValueError(
                    "sp x tp needs attention/kv heads divisible by "
                    f"tensor_parallel_size {sp_tp}")
            # Ragged unified / spec-verify dispatches on the cp
            # runner shard their W (token) axis over 'sp'
            # (context_serving.shard_w_forward): multi-token rows
            # split across the ring devices instead of replicating
            # the whole [R, W] block per device. Single-token decode
            # dispatches pass through unsharded.
            from production_stack_tpu.parallel.context_serving import (
                shard_w_forward,
            )
            self._forward = shard_w_forward(self._forward, mesh)

        self._deferred = config.scheduler.deferred_kv_writes
        if self._deferred:
            # Deferred per-burst KV writes (ops/attention.write_to_tail
            # + the kv_tail path of the family's forward): motivated by
            # the round-5 ablation — the per-step scatter + same-buffer
            # gather interaction costs ~4.4 of 8.3 ms/step (XLA
            # copy-insertion). Single-runner decode of the families
            # whose forward takes kv_tail only; reject loudly
            # otherwise. The SAME predicate drives the server's and
            # bench's 'auto' resolution (deferred_kv_eligible) — keep
            # them in lockstep.
            if config.scheduler.decode_steps <= 1:
                raise ValueError(
                    "deferred_kv_writes needs decode_steps > 1 (the "
                    "tail flushes once per multi-step burst)")
            if (config.parallel.pipeline_parallel_size > 1
                    or self._sp_size > 1):
                raise NotImplementedError(
                    "deferred_kv_writes with pipeline/context "
                    "parallelism (the pp/sp runners use their own "
                    "burst bodies)")
            if model_config.architecture not in DEFERRED_KV_FAMILIES:
                raise NotImplementedError(
                    "deferred_kv_writes serves "
                    f"{', '.join(DEFERRED_KV_FAMILIES)} (got "
                    f"{model_config.architecture!r})")

        # A family's draft module proposing inside the deferred burst
        # (docs/speculative.md): the burst body is the drafting one and
        # the prefill step fills the module's cache entry.
        self._drafts = config.scheduler.draft_module
        if self._drafts:
            self._draft = get_draft(model_config)

        # A family that generates by diffusion over blocks
        # (models/registry.py Family.block): the positions a block, 0
        # for a family that generates left to right. Its prefill
        # program samples nothing and its burst is the block one.
        self._block = model_config.block_length
        self._prefill_mode = "none" if self._block else "last"
        self.burst_blocks = (burst_blocks(config.scheduler.decode_steps,
                                          model_config.diffusion_steps)
                             if self._block else 0)

        with self.startup.within("boot.weights") as span:
            self.params = self._place_params(params, model_config, mesh)
            # Until the arrays are there: the cache's planes below
            # would wait for them anyway.
            jax.block_until_ready(self.params)
            _leaves = jax.tree_util.tree_leaves(self.params)
            span["params_bytes"] = sum(int(getattr(x, "nbytes", 0))
                                       for x in _leaves)

        # Device performance observatory (engine/perf_observatory.py):
        # exact param-tree sizes (array metadata only — no host
        # reads), the real device kind for the peak-FLOPs table, and
        # the resolved attention impls so the silent XLA fallback is
        # an alarmable gauge rather than a log line. Set to None to
        # disable every hook (the parity tests pin that path).
        try:
            _device_kind = getattr(jax.devices()[0], "device_kind", "")
        except Exception:
            _device_kind = ""
        self.observatory = PerfObservatory(
            config,
            param_count=sum(int(getattr(x, "size", 0))
                            for x in _leaves),
            params_bytes=span["params_bytes"],
            device_kind=_device_kind)
        self.observatory.set_attention_impl(
            "decode", model_config.attention_impl_decode
            or model_config.attention_impl)
        self.observatory.set_attention_impl(
            "prefill", model_config.attention_impl_prefill
            or model_config.attention_impl)

        hbm = self.observatory.hbm_bytes()
        resume = self.startup.enter(
            "boot.cache", bytes=hbm["kv_pages"] + hbm["kv_scales"]
            + hbm.get("recurrent_state", 0))
        # Head-major paged cache: [L, kv_heads, pages, d, page_size].
        # The kv axis is major so TP shards a leading axis; pages are
        # token-minor so the Pallas kernels DMA (d, 128)-tile-aligned
        # page slices straight out of HBM (ops/paged_attention_pallas).
        cache_shape = (
            model_config.num_hidden_layers,
            model_config.num_key_value_heads,
            config.cache.num_pages,
            model_config.head_dim,
            config.cache.page_size,
        )
        dtype = model_config.jax_dtype

        def _fresh_cache(shape):
            if self.kv_quantized:
                return shard_cache(quant_cache_zeros(shape), mesh)
            return shard_cache(jnp.zeros(shape, dtype), mesh)

        self.cache_layout = config.cache.cache_layout
        # A model with recurrent layers: its per-layer cache tuples
        # hold state pools where a recurrent layer has no pages, as
        # its family declares them (models/registry.py: one or two a
        # layer, None where it declares none), and every
        # step hands the forward each row's state slot beside its
        # page table.
        self._hybrid = model_config.has_recurrent_state
        if self._hybrid or model_config.family.page_cache is not None:
            self.k_cache, self.v_cache = init_hybrid_cache(
                model_config, config.cache.num_pages,
                config.cache.page_size, config.cache.num_state_slots)
        elif self.cache_layout == "per_layer":
            # A tuple of L per-layer buffers instead of one stacked
            # array: scatters/kernels touch one layer's buffer and
            # donation aliases 1:1 (the round-3 decode-roofline
            # experiment — models/llama.py cached_attention).
            if (config.parallel.pipeline_parallel_size > 1
                    or self._sp_size > 1):
                raise NotImplementedError(
                    "cache_layout='per_layer' with pipeline/context "
                    "parallelism (pp shards the stacked L axis; use "
                    "the stacked layout)")
            self.k_cache = tuple(
                _fresh_cache(cache_shape[1:])
                for _ in range(model_config.num_hidden_layers))
            self.v_cache = tuple(
                _fresh_cache(cache_shape[1:])
                for _ in range(model_config.num_hidden_layers))
        elif self.cache_layout == "stacked":
            self.k_cache = _fresh_cache(cache_shape)
            self.v_cache = _fresh_cache(cache_shape)
        else:
            raise ValueError(
                "cache.cache_layout must be 'auto', 'stacked' or "
                f"'per_layer' (got {self.cache_layout!r})")
        jax.block_until_ready((self.k_cache, self.v_cache))
        self.startup.enter(resume)

        self.max_pages_per_seq = config.scheduler.max_pages_per_seq(
            config.cache.page_size
        )
        # The decode record's ``attn_pages``: how many pages of each
        # row's table the XLA attention gathers for the dispatch's
        # longest row, by the rule the program applies on the device
        # (ops/attention.py). None while a Pallas kernel serves
        # decode (it gathers none).
        self.last_attn_pages: Optional[int] = None
        self.decode_width = config.scheduler.max_num_seqs
        self.prefill_width = config.scheduler.prefill_batch_size
        # The rows the last prefill step ran at (the turn record's
        # ``prefill_width``), the steps run under the full width
        # (vllm:engine_prefill_narrow_steps_total), and whether both
        # widths of the top bucket are up (_other_width_payloads).
        self.last_prefill_width = self.prefill_width
        self.num_narrow_prefill_steps = 0
        self._top_bucket_warm = False
        self._buckets = prefill_buckets(
            config.scheduler.prefill_chunk_size
        )
        self._keys = HostKeys(config.seed + 1)
        # Reused host staging buffers for the single-step decode
        # payload (dispatch_decode): the per-step numpy allocation
        # shower is replaced by in-place fills + ONE fused
        # jax.device_put of the whole input set. DOUBLE-buffered
        # because the CPU backend may alias numpy memory into the
        # device buffer zero-copy: a buffer set is refilled only
        # after the step that consumed it has been completed
        # (pipeline depth is 1, and the engine reads step N's result
        # before dispatching N+2 — so set parity N mod 2 is free by
        # the time it is reused).
        self._decode_staging = None
        self._staging_idx = 0
        # (signature, {name: device array}) of the last dispatch's
        # static per-row inputs; reused while the row set is unchanged
        # (see dispatch_decode).
        self._decode_static_cache = None
        # Multihost step broadcast (parallel/distributed.py); host 0's
        # engine sets this so every dispatch is mirrored to workers.
        self.bridge = None
        # LLMEngine.tracer, mirrored by its setter: names this thread's
        # turn phases (engine/tracing.py TURN_PHASES). None = untraced.
        self.tracer = None
        # Embedder for /v1/embeddings|score|rerank; in multihost mode
        # every host builds one at startup so KIND_EMBED payloads can
        # be executed slice-wide (server.py main, --distributed).
        self.embedder = None

        # Multi-LoRA: device-resident adapter stacks; a per-row slot-id
        # vector selects the adapter (engine/lora.py). None when off so
        # the base model compiles with zero LoRA overhead.
        self.lora_registry = None
        if config.lora.enable:
            from production_stack_tpu.engine.lora import LoRARegistry
            self.lora_registry = LoRARegistry(
                model_config, config.lora.max_loras,
                config.lora.max_lora_rank,
            )

        self._step_jit = InstrumentedJit("step", jax.jit(
            self._step_impl,
            static_argnames=("sample_index_mode", "want_logprobs"),
            donate_argnums=(1, 2),  # k_cache, v_cache
        ), self)
        # Decode burst: K decode iterations fused into one compiled
        # program via lax.scan — sampled tokens feed back on device
        # and per-sequence budgets + stop sets are evaluated on device
        # too, so rows go inactive mid-burst without a host round-trip
        # (vLLM's --num-scheduler-steps analogue, but as a single XLA
        # program, and the window never collapses to 1 for
        # mixed-progress batches). One dispatch + one device_get per K
        # tokens.
        self._decode_burst_jit = InstrumentedJit("decode_burst", jax.jit(
            (self._decode_burst_block_impl if self._block
             else self._decode_burst_draft_impl if self._drafts
             else self._decode_burst_deferred_impl if self._deferred
             else self._decode_burst_impl),
            static_argnames=("num_steps", "want_logprobs"),
            donate_argnums=(1, 2),  # k_cache, v_cache
        ), self)
        if self._sp_size > 1:
            from production_stack_tpu.parallel.context_serving import (
                sp_prefill_forward,
            )

            def _sp_step(params, k_cache, v_cache, tokens, page_table,
                         valid, last_index, temperature, top_p, top_k,
                         rng, lora, lora_ids, penalties, seeding,
                         bias, suppress, fsm, want_logprobs=False):
                row_logits, k_cache, v_cache = sp_prefill_forward(
                    params, self.config.model, tokens, page_table,
                    valid, last_index, k_cache, v_cache,
                    lora=lora, lora_ids=lora_ids,
                    mesh=self.mesh)
                raw_logits = row_logits
                if penalties is not None:
                    row_logits = apply_penalties(row_logits, *penalties)
                if bias is not None:
                    row_logits = row_logits + bias
                if suppress is not None:
                    row_logits = ModelRunner._apply_suppression(
                        row_logits, suppress)
                if fsm is not None:
                    row_logits = self._apply_guided_mask(
                        row_logits, fsm)
                seeds, seed_on, emitted = (
                    seeding if seeding is not None
                    else (None, None, None))
                sampled = sample_tokens(row_logits, temperature,
                                        top_p, top_k, rng,
                                        seeds=seeds, emitted=emitted,
                                        seed_mask=seed_on)
                if want_logprobs:
                    lp = token_logprobs(raw_logits, sampled,
                                        TOP_LOGPROBS_WIDTH)
                    return (sampled,) + lp, k_cache, v_cache
                return sampled, k_cache, v_cache

            self._sp_prefill_jit = InstrumentedJit(
                "sp_prefill",
                jax.jit(_sp_step, donate_argnums=(1, 2),
                        static_argnames=("want_logprobs",)),
                self)

        # Speculative verify (docs/speculative.md): ONE fixed-shape
        # program scores S = speculative_k + 1 positions per decode
        # slot through the T>1 (prefill) attention path over the page
        # table; the acceptance rule runs in-graph (spec_verify).
        self.spec_width = 0
        if config.scheduler.speculative_k > 0:
            # Composes with pp/cp: the verify program routes through
            # self._forward, which the pp wiring above already swapped
            # for the staged pipeline body (same signature), and the
            # cp wrapper below shards the verify span's W axis.
            self.spec_width = config.scheduler.speculative_k + 1
            # The Pallas prefill kernel may not lower at the thin
            # (decode_width, S) verify shape (Mosaic tiling rules are
            # shape-specific), so probe exactly that shape and degrade
            # ONLY the verify program to XLA attention — real prefill
            # keeps its kernel.
            spec_model = model_config
            prefill_impl = (model_config.attention_impl_prefill
                            or model_config.attention_impl)
            if (prefill_impl.startswith("pallas")
                    and jax.default_backend() != "cpu"):
                err = (pallas_backend_error(config)
                       or self._spec_lowering_error(
                           model_config, config))
                if err is not None and not auto_impl:
                    raise RuntimeError(
                        "attention_impl='pallas': the Pallas prefill "
                        "kernel failed TPU lowering at the "
                        f"speculative-verify shape: {err}")
                if err is not None:
                    logger.error(
                        "Speculative verify serves via XLA attention "
                        "(Pallas prefill failed TPU lowering at the "
                        "verify shape): %s", err)
                    import copy
                    spec_model = copy.copy(model_config)
                    spec_model.attention_impl_prefill = "xla"
            self._spec_model = spec_model
            self.observatory.set_attention_impl(
                "spec_verify", spec_model.attention_impl_prefill
                or spec_model.attention_impl)
            self._spec_jit = InstrumentedJit("spec_verify", jax.jit(
                self._spec_verify_impl,
                static_argnames=("want_logprobs",),
                donate_argnums=(1, 2),  # k_cache, v_cache
            ), self)

        # Unified ragged step (docs/unified_step.md): ONE jitted
        # program serves genuinely mixed batches — decode/draft rows
        # and prefill chunk rows share a fixed [R, W] token block
        # (R and W each snap to closed bucket sets: W from the
        # prefill buckets, R from a doubling row lattice capped at
        # decode_width + prefill_width), sampled through the verify
        # rule so every row kind emits 1..span tokens through one
        # shape. Row bucketing keeps a lightly mixed step (the common
        # case: a few decode rows plus one chunk) from paying full-
        # width compute for pad rows. Pure-decode and pure-prefill
        # steps keep the bimodal dispatch paths, so greedy streams
        # stay byte-identical when no mixing happens.
        self.unified_span = max(self.spec_width, 1)
        self.unified_rows = self.decode_width + self.prefill_width
        buckets, b = [], 2
        while b < self.unified_rows:
            buckets.append(b)
            if b + b // 2 < self.unified_rows:
                buckets.append(b + b // 2)
            b *= 2
        buckets.append(self.unified_rows)
        self.unified_row_buckets = buckets
        # Last dispatched ragged shape, for occupancy metrics.
        self.last_unified_rows = 0
        self._unified = bool(config.scheduler.unified_step)
        if self._unified:
            # Composes with pp (the ragged [R, W] block rides the
            # staged forward — rows become microbatches, the per-row
            # descriptor triple threads through each ppermute handoff)
            # and with cp (the sp wrapper shards the W axis).
            # Resolve the unified step's own attention impl: the
            # fused ragged kernel when 'auto' admits it
            # (PALLAS_RAGGED_IN_AUTO) or 'pallas' is explicit and it
            # lowers, else the composed prefill kernel (probed at the
            # [R, W] shapes the per-bucket probe never saw), else XLA
            # — degrading ONLY the ragged program, never real prefill
            # (the _spec_model pattern).
            unified_model, resolved = self._resolve_unified_impl(
                getattr(self, "_spec_model", model_config), config,
                auto_impl)
            self._unified_model = unified_model
            logger.info("Unified step attention impl: %s", resolved)
            self.observatory.set_attention_impl("unified", resolved)
            self._unified_jit = InstrumentedJit("unified", jax.jit(
                self._unified_impl,
                static_argnames=("want_logprobs",),
                donate_argnums=(1, 2),  # k_cache, v_cache
            ), self)

    def _place_params(self, params, model_config, mesh):
        """The parameter tree on the device: random where none was
        read, quantised where asked, sharded over ``mesh``."""
        if params is None and model_config.quantization == "int8":
            # Direct int8 init: full-precision init + quantize peaks
            # at 3x the serving footprint on device and OOMs the 8B
            # config on a 16 GB chip (see init_random_quantized).
            from production_stack_tpu.engine.quantization import (
                init_random_quantized,
            )
            logger.info("Initializing random int8 weights for %s",
                        model_config.name)
            params = init_random_quantized(
                self._init_fn, model_config, self.config.seed)
        elif params is None:
            logger.info("Initializing random weights for %s",
                        model_config.name)
            params = self._init_fn(
                model_config, jax.random.PRNGKey(self.config.seed)
            )
        elif model_config.quantization == "int8":
            from production_stack_tpu.engine.quantization import (
                has_quantized_leaves,
                quantize_params,
            )
            if not has_quantized_leaves(params):
                logger.info("Quantizing projection weights to int8 "
                            "(weight-only)")
                params = quantize_params(params, model_config)
        return shard_params(params, model_config, mesh)

    def _probe_cache_struct(self, model_config, config):
        """Shared probe boilerplate: the exact serving cache struct
        (per_layer slice vs stacked + SMEM layer scalar, QuantKV when
        kv int8) and the shape scalars every lowering probe needs.
        Returns ``(nh, d, dtype, max_pages, cache, layer0)``."""
        nh, nkv, d = (model_config.num_attention_heads,
                      model_config.num_key_value_heads,
                      model_config.head_dim)
        dtype = model_config.jax_dtype
        max_pages = config.scheduler.max_pages_per_seq(
            config.cache.page_size)
        if config.cache.cache_layout == "per_layer":
            cache_shape = (nkv, config.cache.num_pages, d,
                           config.cache.page_size)
            layer0 = None
        else:
            cache_shape = (model_config.num_hidden_layers, nkv,
                           config.cache.num_pages, d,
                           config.cache.page_size)
            layer0 = jax.ShapeDtypeStruct((), np.int32)
        cache = (quant_cache_struct(cache_shape) if self.kv_quantized
                 else jax.ShapeDtypeStruct(cache_shape, dtype))
        return nh, d, dtype, max_pages, cache, layer0

    def _spec_lowering_error(self, model_config,
                             config) -> Optional[str]:
        """Probe the Pallas prefill kernel at the verify shape."""
        from production_stack_tpu.ops.prefill_attention_pallas import (
            paged_prefill_attention,
        )
        nh, d, dtype, max_pages, cache, layer0 = \
            self._probe_cache_struct(model_config, config)
        b, s = self.decode_width, self.spec_width
        return self._lowering_error(
            paged_prefill_attention,
            jax.ShapeDtypeStruct((b, s, nh, d), dtype), cache, cache,
            jax.ShapeDtypeStruct((b, max_pages), np.int32),
            jax.ShapeDtypeStruct((b, s), np.int32),
            jax.ShapeDtypeStruct((b,), np.int32), layer0)

    def _unified_widths(self) -> List[int]:
        """Every query width the mixed planner can emit."""
        return sorted({max(w, self.unified_span)
                       for w in self._buckets})

    def _unified_lowering_error(self, model_config,
                                config) -> Optional[str]:
        """Probe the Pallas prefill kernel at the ragged-step shapes
        ([unified_rows, W] for every W the mixed planner can emit;
        the smaller row buckets are strict sub-shapes and are taken
        to lower whenever the widest one does)."""
        from production_stack_tpu.ops.prefill_attention_pallas import (
            paged_prefill_attention,
        )
        nh, d, dtype, max_pages, cache, layer0 = \
            self._probe_cache_struct(model_config, config)
        r = self.unified_rows
        for w in self._unified_widths():
            err = self._lowering_error(
                paged_prefill_attention,
                jax.ShapeDtypeStruct((r, w, nh, d), dtype), cache,
                cache,
                jax.ShapeDtypeStruct((r, max_pages), np.int32),
                jax.ShapeDtypeStruct((r, w), np.int32),
                jax.ShapeDtypeStruct((r,), np.int32), layer0)
            if err is not None:
                return err
        return None

    def _ragged_lowering_error(self, model_config,
                               config) -> Optional[str]:
        """Probe the fused ragged kernel over the same [R, W] matrix
        as _unified_lowering_error, with the three-int descriptor
        operands (kv_lens, last_index, draft_lens) in place of the
        [R, W] positions the composed path takes."""
        from production_stack_tpu.ops.ragged_attention_pallas import (
            paged_ragged_attention,
        )
        nh, d, dtype, max_pages, cache, layer0 = \
            self._probe_cache_struct(model_config, config)
        r = self.unified_rows
        rows_i32 = jax.ShapeDtypeStruct((r,), np.int32)
        for w in self._unified_widths():
            err = self._lowering_error(
                paged_ragged_attention,
                jax.ShapeDtypeStruct((r, w, nh, d), dtype), cache,
                cache,
                jax.ShapeDtypeStruct((r, max_pages), np.int32),
                rows_i32, rows_i32, rows_i32, layer0)
            if err is not None:
                return err
        return None

    def _resolve_unified_impl(self, base_model, config,
                              auto_impl: bool):
        """Resolve the attention impl serving the unified [R, W] step.

        Returns ``(model, resolved)``: ``model`` is ``base_model`` or
        a shallow copy with ``attention_impl_prefill`` rewritten (the
        unified program dispatches through the T>1 path), ``resolved``
        the impl string for the observatory one-hot and bench extras.

        The ladder, top rung first:
          1. an explicit ``attention_impl_unified`` (compiled at the
             ragged shapes on a real TPU — a refusal is a start-up
             error; served verbatim in interpret/CPU testing — that
             pin is how tier-1 holds byte-parity),
          2. the fused ragged kernel (pallas_ragged) when the family
             prefill impl is Pallas on TPU, 'auto' admits it
             (PALLAS_RAGGED_IN_AUTO; an explicit family-wide 'pallas'
             skips the constant as an operator override), AND it
             compiles at every ragged shape,
          3. the composed prefill kernel when IT compiles at the
             ragged shapes (the pre-fusion path),
          4. XLA attention under 'auto'; a start-up error under an
             explicit 'pallas'.
        """
        import copy

        def with_impl(impl):
            if ((base_model.attention_impl_prefill
                 or base_model.attention_impl) == impl):
                return base_model, impl
            model = copy.copy(base_model)
            model.attention_impl_prefill = impl
            return model, impl

        explicit = base_model.attention_impl_unified
        if explicit:
            if (explicit.startswith("pallas")
                    and not explicit.endswith("-interpret")
                    and jax.default_backend() != "cpu"):
                err = pallas_backend_error(config)
                if err is None:
                    probe = (self._ragged_lowering_error
                             if explicit.startswith("pallas_ragged")
                             else self._unified_lowering_error)
                    err = probe(base_model, config)
                if err is not None:
                    raise RuntimeError(
                        f"attention_impl_unified={explicit} failed "
                        f"its lowering probe: {err}")
            return with_impl(explicit)

        prefill_impl = (base_model.attention_impl_prefill
                        or base_model.attention_impl)
        if (not prefill_impl.startswith("pallas")
                or jax.default_backend() == "cpu"):
            # XLA family (or CPU testing): compose it unchanged.
            return base_model, prefill_impl
        berr = pallas_backend_error(config)
        if berr is not None:
            # Only reachable when a caller pinned
            # attention_impl_prefill itself (_resolve_pallas_impls
            # degrades the family under 'auto' and raises for an
            # explicit 'pallas'); the unified site re-checks the ONE
            # shared predicate so the backend rule cannot drift.
            logger.error("%s; unified step serves via XLA attention",
                         berr)
            return with_impl("xla")
        # The constant is read BEFORE the probe: a kernel that will
        # not be served is not compiled at start-up (each probe is a
        # real Mosaic compile per ragged width).
        if PALLAS_RAGGED_IN_AUTO or not auto_impl:
            ragged_err = self._ragged_lowering_error(base_model,
                                                     config)
            if ragged_err is None:
                return with_impl("pallas_ragged")
            logger.error(
                "Fused ragged kernel failed TPU lowering (composing "
                "the prefill kernel): %s", ragged_err)
        err = self._unified_lowering_error(base_model, config)
        if err is not None:
            if not auto_impl:
                raise RuntimeError(
                    "attention_impl='pallas': neither the fused "
                    "ragged kernel nor the composed prefill kernel "
                    f"compiles at the unified step's shapes: {err}")
            logger.error(
                "Unified ragged step serves via XLA attention "
                "(Pallas prefill failed TPU lowering at a ragged "
                "shape): %s", err)
            return with_impl("xla")
        return base_model, prefill_impl

    # The start's timeline that the probes under way belong to (the
    # runner being built: __init__ sets it). A class attribute and not
    # an argument, so that ``_lowering_error`` is called from the very
    # frames it always was: one method's frame between the caller and
    # it made every probe's tracing and lowering half again as slow on
    # the v5e hosts (15 s a start; PERF.md section 6, PR 53).
    _probing: Optional[StartupTimeline] = None

    @staticmethod
    def _lowering_error(fn, *args, **kwargs) -> Optional[str]:
        """Compile ``fn`` for the backend in use at ``args``' shapes;
        the refusal as a string, or None. A real compile, not only the
        Python lowering rules: Mosaic's machine-code pass and the
        scoped-VMEM budget refuse kernels those rules accept, and a
        refusal has to be a start-up fact rather than the first
        request's surprise. One ``boot.probe`` span of the start: the
        kernel, the shape of its first operand, what the load was made
        of (perf_observatory.take_load_split: its executable can come
        from the compile cache like any other) and whether the kernel
        is ``served`` or the case ``degraded``."""
        timeline = ModelRunner._probing or StartupTimeline()
        with timeline.probe(kernel=getattr(fn, "func", fn).__name__,
                            shape=list(args[0].shape)) as span:
            since = time.perf_counter()
            try:
                jax.jit(fn).lower(*args, **kwargs).compile()
                err = None
            except Exception as e:  # noqa: BLE001 — any compile failure
                err = repr(e)[:400]
            span.update(rounded_split(take_load_split(since)),
                        result="served" if err is None else "degraded")
        return err

    def _resolve_pallas_impls(self, model_config, config,
                              auto_impl: bool = False) -> None:
        """Probe each Pallas kernel's TPU lowering at serving shapes.

        Under attention_impl='auto' (``auto_impl``) each kernel is
        served where it compiles and falls back to XLA, with the
        reason logged, where it does not; under an explicit 'pallas' a
        kernel that cannot be served is a start-up error. The decode
        kernel is probed in the form the burst calls: with the tails
        of a deferred-write burst where those are on.
        """
        if model_config.has_latent_cache:
            return self._resolve_latent_impls(model_config, config,
                                              auto_impl)
        # The exact serving form of the cache (_probe_cache_struct).
        nh, d, dtype, max_pages, cache, layer0 = \
            self._probe_cache_struct(model_config, config)
        nkv = model_config.num_key_value_heads

        berr = pallas_backend_error(config)
        if berr is not None:
            # Shared backend rule (pallas_backend_error), gated here
            # and at the spec/unified resolution sites.
            if not auto_impl:
                raise ValueError(
                    f"attention_impl='pallas' cannot be served: {berr}")
            logger.error("%s; serving via XLA attention", berr)
            model_config.attention_impl_decode = "xla"
            model_config.attention_impl_prefill = "xla"
            return

        from production_stack_tpu.ops.paged_attention_pallas import (
            paged_decode_attention,
        )
        from production_stack_tpu.ops.prefill_attention_pallas import (
            paged_prefill_attention,
        )
        b = config.scheduler.max_num_seqs
        pb = config.scheduler.prefill_batch_size
        rows_i32 = jax.ShapeDtypeStruct((b,), np.int32)
        tail_slots = config.scheduler.decode_steps
        block = model_config.block_length
        if block:
            # A block's queries ride the group axis of the decode
            # kernel (models/llama.py block_attention) and the tails
            # hold the burst's blocks; a chunk's sight is by block.
            nh = nh * block
            tail_slots = block * burst_blocks(
                tail_slots, model_config.diffusion_steps)
            paged_prefill_attention = functools.partial(
                paged_prefill_attention, block=block)
        tail = (jax.ShapeDtypeStruct((b, tail_slots, nkv, d), dtype)
                if config.scheduler.deferred_kv_writes else None)
        probes = {
            "decode": [(
                paged_decode_attention,
                (jax.ShapeDtypeStruct((b, nh, d), dtype), cache, cache,
                 jax.ShapeDtypeStruct((b, max_pages), np.int32),
                 rows_i32, layer0, tail, tail,
                 None if tail is None else rows_i32),
            )],
            # Serving compiles one prefill program per bucket — probe
            # them all, not just the widest (a Mosaic rule can fail at
            # one bucket shape only). The half-width shape
            # (prefill_shapes) is not probed: rows are an axis of the
            # kernel's grid and of no block, so it compiles where the
            # full width at that bucket does (tests/
            # test_pallas_lowering.py compiles it for a v5e at the
            # cells' head shapes), and a probe is seconds of every
            # start.
            "prefill": [(
                paged_prefill_attention,
                (jax.ShapeDtypeStruct(
                    (pb, t, model_config.num_attention_heads, d), dtype),
                 cache, cache,
                 jax.ShapeDtypeStruct((pb, max_pages), np.int32),
                 jax.ShapeDtypeStruct((pb, t), np.int32),
                 jax.ShapeDtypeStruct((pb,), np.int32), layer0),
            ) for t in prefill_buckets(
                config.scheduler.prefill_chunk_size)],
        }
        for name, cases in probes.items():
            err = next(
                (e for fn, shapes in cases
                 for e in [self._lowering_error(fn, *shapes)]
                 if e is not None), None)
            impl = "pallas" if err is None else "xla"
            if err and not auto_impl:
                # Explicit selection that cannot be honoured is a
                # start-up error, never a quiet XLA server.
                raise RuntimeError(
                    f"attention_impl='pallas': the Pallas {name} "
                    f"kernel failed TPU lowering at a serving shape: "
                    f"{err}")
            if err:
                logger.error(
                    "Pallas %s kernel failed TPU lowering; this shape "
                    "serves via XLA attention: %s", name.upper(), err)
            setattr(model_config, f"attention_impl_{name}", impl)

    def _resolve_latent_impls(self, model_config, config,
                              auto_impl: bool) -> None:
        """``_resolve_pallas_impls`` for a family whose pages hold one
        latent plane an entry: the decode step has its own Pallas
        kernel (ops/mla_attention_pallas.py), probed in the form the
        burst calls (one position a row, or two where the burst
        drafts); a prefill chunk is served by the XLA form, which is
        said and not probed."""
        from production_stack_tpu.ops.mla_attention_pallas import (
            latent_paged_decode_attention,
            latent_paged_verify_attention,
        )
        m, dtype = model_config, model_config.jax_dtype
        model_config.attention_impl_prefill = "xla"
        err = pallas_backend_error(config)
        if err is None:
            b = config.scheduler.max_num_seqs
            n, dn = m.num_attention_heads, m.qk_nope_head_dim
            pages = m.page_cache
            steps = config.scheduler.decode_steps
            # A burst that drafts runs two positions a row over tails
            # of twice the steps (the verify form of the same kernel).
            t = 2 if config.scheduler.draft_module else 1
            tail = (jax.ShapeDtypeStruct((b, t * steps, 1, pages.width),
                                         dtype)
                    if config.scheduler.deferred_kv_writes else None)
            shared = (
                jax.ShapeDtypeStruct(
                    (1, config.cache.num_pages, pages.width,
                     config.cache.page_size), dtype),
                jax.ShapeDtypeStruct(
                    (b, config.scheduler.max_pages_per_seq(
                        config.cache.page_size)), np.int32),
                jax.ShapeDtypeStruct((b,), np.int32),
                jax.ShapeDtypeStruct((n, dn, m.kv_lora_rank), dtype),
                jax.ShapeDtypeStruct((n, m.kv_lora_rank, m.v_head_dim),
                                     dtype))
            scale = float(m.head_dim) ** -0.5
            if t == 1:
                err = self._lowering_error(
                    functools.partial(latent_paged_decode_attention,
                                      scale=scale),
                    jax.ShapeDtypeStruct((b, n, m.head_dim), dtype),
                    *shared, tail=tail,
                    q_positions=(None if tail is None else
                                 jax.ShapeDtypeStruct((b,), np.int32)))
            else:
                err = self._lowering_error(
                    functools.partial(latent_paged_verify_attention,
                                      scale=scale),
                    jax.ShapeDtypeStruct((b, t, n, m.head_dim), dtype),
                    *shared, tail=tail,
                    q_positions=jax.ShapeDtypeStruct((b, t), np.int32))
        if err and not auto_impl:
            raise RuntimeError(
                "attention_impl='pallas': the Pallas latent decode "
                f"kernel cannot be served: {err}")
        if err:
            logger.error("Pallas latent decode kernel cannot be served; "
                         "decode serves via XLA attention: %s", err)
        model_config.attention_impl_decode = "xla" if err else "pallas"

    def set_guided_tables(self, fsm) -> None:
        """Device copies of the guided-decoding automaton tables
        (engine/guided.py). Uploaded once at engine init; the
        sampling steps gather mask[state] rows and advance
        state = transition[state, token] inside the compiled
        program (burst carry), so constrained rows run at full
        burst speed."""
        self._guided_trans = jnp.asarray(fsm.transition)
        self._guided_mask = jnp.asarray(fsm.mask)

    @property
    def _lora_stack(self):
        return (None if self.lora_registry is None
                else self.lora_registry.stack)

    # ---- compiled step ----------------------------------------------------

    def _step_impl(self, params, k_cache, v_cache, tokens, positions,
                   page_table, kv_lens, valid, last_index, temperature,
                   top_p, top_k, rng, lora, lora_ids, penalties,
                   seeding, bias, suppress, fsm,
                   sample_index_mode: str,
                   want_logprobs: bool = False, state_slots=None,
                   next_tokens=None):
        # Deliberate two-shape specialization ([B] decode feed-forward
        # vs [B, T] prefill/burst): exactly two traces, cached for the
        # process lifetime — not a per-step retrace.
        if tokens.ndim == 1:  # lint: allow-tracer-hygiene
            # Single-step decode feeds [B] tokens so the async
            # pipeline can consume the previous step's [B] sampled
            # array verbatim — zero eager ops on the feed-forward.
            # The reshape happens here, inside the traced program.
            tokens = tokens[:, None]
            positions = positions.reshape(tokens.shape)
            valid = valid.reshape(tokens.shape)
        # A prefill step of a family that drafts also fills the draft
        # module's cache entry (below, once the token is sampled).
        fills_draft = self._drafts and sample_index_mode == "last"
        if sample_index_mode == "none":
            # A block-diffusion family's prefill chunk: whole blocks
            # to their pages, no head and no token.
            _, k_cache, v_cache = self._forward(
                params, self.config.model, tokens, positions,
                page_table, kv_lens, valid, k_cache, v_cache,
                head=False)
            return (jnp.zeros((tokens.shape[0],), jnp.int32), k_cache,
                    v_cache)
        logits, *hidden, k_cache, v_cache = self._forward(
            params, self.config.model, tokens, positions, page_table,
            kv_lens, valid, k_cache, v_cache,
            lora=lora, lora_ids=lora_ids,
            **self._state_kwargs(state_slots),
            **({"return_hidden": True} if fills_draft else {}),
        )
        if sample_index_mode == "last":
            # Prefill: sample only from the final prompt position.
            row_logits = logits[jnp.arange(tokens.shape[0]), last_index]
        else:
            # Decode: T == 1.
            row_logits = logits[:, 0, :]
        raw_logits = row_logits
        if penalties is not None:
            # (counts, prompt_mask, presence, frequency, repetition);
            # None in the common no-penalty case so that path compiles
            # with zero penalty overhead.
            row_logits = apply_penalties(row_logits, *penalties)
        if bias is not None:
            # OpenAI logit_bias (dense [B, vocab], zero where unused);
            # after penalties, before sampling; logprobs stay raw.
            row_logits = row_logits + bias
        if suppress is not None:
            # min_tokens: stops cannot be generated while under the
            # row's minimum (vLLM semantics; logprobs stay raw).
            row_logits = self._apply_suppression(row_logits, suppress)
        if fsm is not None:
            # Guided decoding: the automaton masks last (the
            # grammar wins); host advances the state (one token
            # per dispatch on this path).
            row_logits = self._apply_guided_mask(row_logits, fsm)
        seeds, seed_on, emitted = (
            seeding if seeding is not None else (None, None, None))
        sampled = sample_tokens(row_logits, temperature, top_p, top_k,
                                rng, seeds=seeds, emitted=emitted,
                                seed_mask=seed_on)
        if fills_draft:
            # The module at position i reads the main model's hidden
            # state there and the token at i + 1: within the chunk the
            # next id; at its last position the next chunk's first id
            # (``next_tokens``, -1 where the prompt ends here) or the
            # token just sampled. What it would draft is not kept: a
            # burst's first iteration offers no draft.
            t = tokens.shape[1]
            after = jnp.where(next_tokens >= 0, next_tokens, sampled)
            shifted = jnp.where(
                jnp.arange(t)[None, :] == last_index[:, None],
                after[:, None], jnp.roll(tokens, -1, axis=1))
            _, k_cache = self._draft(
                params, self.config.model, hidden[0], shifted, positions,
                page_table, kv_lens, valid, k_cache)
        if want_logprobs:
            # From the raw distribution (pre-penalty/temperature), the
            # OpenAI logprobs contract. raw_logits is bound before the
            # penalty rewrite above.
            lp = token_logprobs(raw_logits, sampled,
                                TOP_LOGPROBS_WIDTH)
            return (sampled,) + lp, k_cache, v_cache
        return sampled, k_cache, v_cache

    def _state_kwargs(self, state_slots) -> dict:
        """The forward's extra argument for a model with recurrent
        layers (each row's state slot); none for any other."""
        return {"state_slots": state_slots} if self._hybrid else {}

    def _state_slot_rows(self, seqs, pad_to: int) -> np.ndarray:
        """[pad_to] int32: each row's recurrent-state slot; pad rows
        take the trash slot 0."""
        slots = np.zeros((pad_to,), np.int32)
        for i, seq in enumerate(seqs):
            slots[i] = seq.state_slot or 0
        return slots

    def read_moe_stats(self) -> Optional[dict]:
        """The expert layer's counters over the decode steps since the
        last call (the names its family declares: the entry after the
        layers in ``k_cache``), read from the device and zeroed; None
        for a model without them or with no decode step to report.
        Blocks on the last dispatched program, so call it where that
        program's result has been read already."""
        names = self.config.model.family.counters
        if not names:
            return None
        values = np.asarray(jax.device_get(self.k_cache[-1]))
        if values[0] == 0:
            return None
        self.k_cache = self.k_cache[:-1] + (
            jnp.zeros_like(self.k_cache[-1]),)
        return dict(zip(names, (float(v) for v in values)))

    def _decode_burst_impl(self, params, k_cache, v_cache, tokens,
                           positions, page_table, kv_lens, active,
                           budgets, stop_tokens, temperature, top_p,
                           top_k, rng, lora, lora_ids, penalties,
                           seeding, bias, suppress, fsm,
                           num_steps: int,
                           want_logprobs: bool = False,
                           state_slots=None):
        """K chained decode iterations in one program, with per-row
        lifecycle on device.

        Carry = (last tokens [B,1], positions [B,1], kv_lens [B],
        active [B], emitted [B], caches); each iteration writes KV for
        the active rows (``valid`` mask redirects inactive rows to the
        trash page), attends, samples, checks each row's stop set and
        token budget, and feeds the sampled token into the next — no
        host round-trip between tokens, and a row that finishes early
        simply freezes (its slots emit -1) instead of forcing the
        whole batch back to single-step.

        Args (beyond the single-step set):
          active:      [B] bool — rows that decode this burst
          budgets:     [B] int32 — max tokens this burst may emit per
                       row (min(K, max_tokens budget, model_len
                       budget) computed by the scheduler)
          stop_tokens: [B, S] int32 — per-row stop set, padded with -1

        Returns sampled tokens [K, B] (-1 for frozen slots); with
        ``want_logprobs`` a tuple ([K, B] tokens, [K, B] sampled
        logprobs, [K, B, W] top ids, [K, B, W] top logprobs).
        """
        b = active.shape[0]
        if penalties is not None:
            # (counts, prompt_mask, presence, frequency, repetition):
            # counts joins the scan carry (updated per step), the rest
            # stay loop-invariant closures.
            counts0, penalties = penalties[0], penalties[1:]
        else:
            # Zero-size placeholder keeps the carry structure uniform.
            counts0 = jnp.zeros((b, 0), jnp.int32)

        sample_step = self._burst_sample_step(
            b, penalties, seeding, bias, suppress, temperature,
            top_p, top_k, stop_tokens, budgets, want_logprobs)
        fsm0 = (jnp.zeros((0,), jnp.int32) if fsm is None else fsm)

        def body(carry, step_rng):
            tok, pos, kv, act, emitted, counts, fs, kc, vc = carry
            logits, kc, vc = self._forward(
                params, self.config.model, tok, pos, page_table,
                kv, act[:, None], kc, vc, lora=lora,
                lora_ids=lora_ids,
                **self._state_kwargs(state_slots),
            )
            out, sampled, emitted, counts, act_next, fs = \
                sample_step(logits, step_rng, act, emitted, counts,
                            fs)
            step = act_next.astype(pos.dtype)
            return ((jnp.where(act, sampled, tok[:, 0])[:, None],
                     pos + step[:, None], kv + step, act_next,
                     emitted, counts, fs, kc, vc), out)

        rngs = jax.random.split(rng, num_steps)
        emitted0 = jnp.zeros(active.shape, jnp.int32)
        carry = (tokens, positions, kv_lens, active, emitted0,
                 counts0, fsm0, k_cache, v_cache)
        (_, _, _, _, _, _, _, k_cache, v_cache), out = jax.lax.scan(
            body, carry, rngs
        )
        return out, k_cache, v_cache

    def _burst_sample_step(self, b, penalties, seeding, bias,
                           suppress, temperature, top_p, top_k,
                           stop_tokens, budgets, want_logprobs):
        # ``fsm`` rides the burst carry: a zero-size placeholder
        # means unguided (compiled without the table gathers).
        """The burst bodies' shared logits -> (out, lifecycle) step:
        penalties, (seeded) sampling, logprobs, occurrence counts,
        stop/budget freeze. One definition so the eager and deferred
        KV-write bursts cannot drift apart in sampling semantics."""

        def sample_step(logits, step_rng, act, emitted, counts,
                        fsm):
            row_logits = logits[:, 0, :]
            raw_logits = row_logits
            if penalties is not None:
                prompt_mask, presence, frequency, repetition = penalties
                row_logits = apply_penalties(
                    row_logits, counts, prompt_mask, presence,
                    frequency, repetition)
            if bias is not None:
                # OpenAI logit_bias: after penalties, before sampling;
                # logprobs stay raw.
                row_logits = row_logits + bias
            if suppress is not None:
                # min_tokens: stops masked while under the minimum
                # (emitted counts this burst's tokens on top of the
                # payload-time remainder).
                row_logits = self._apply_suppression(
                    row_logits, suppress, emitted=emitted)
            if fsm.shape[0]:
                # Guided decoding: the automaton masks last.
                row_logits = self._apply_guided_mask(row_logits,
                                                     fsm)
            if seeding is not None:
                # Seeded rows' randomness depends only on (seed,
                # absolute emitted index), so reproducibility survives
                # burst boundaries and batch composition.
                seeds, seed_on, emitted_start = seeding
                sampled = sample_tokens(
                    row_logits, temperature, top_p, top_k, step_rng,
                    seeds=seeds, emitted=emitted_start + emitted,
                    seed_mask=seed_on)
            else:
                sampled = sample_tokens(
                    row_logits, temperature, top_p, top_k, step_rng
                )
            out = jnp.where(act, sampled, -1)
            if want_logprobs:
                out = (out,) + token_logprobs(raw_logits, sampled,
                                              TOP_LOGPROBS_WIDTH)
            emitted = emitted + act
            if penalties is not None:
                # Occurrence counts track the burst on device so later
                # steps penalize tokens sampled earlier in the burst.
                counts = counts.at[jnp.arange(b), sampled].add(
                    act.astype(counts.dtype))
            hit_stop = jnp.any(
                sampled[:, None] == stop_tokens, axis=-1
            )
            act_next = act & ~hit_stop & (emitted < budgets)
            if fsm.shape[0]:
                # Constrained rows can only have sampled an in-table
                # id (the mask forbids the rest); the clip keeps the
                # gather in-bounds for unconstrained rows, whose fsm
                # stays -1 via the where.
                width = self._guided_trans.shape[1]
                nxt = self._guided_trans[
                    jnp.clip(fsm, 0), jnp.clip(sampled, 0, width - 1)]
                fsm = jnp.where(act & (fsm >= 0), nxt, fsm)
            return out, sampled, emitted, counts, act_next, fsm

        return sample_step

    def _burst_tails(self, k_cache, v_cache, page_table, kv_lens0,
                     slots: int, state_slots):
        """What a deferred-write burst carries in the place of its
        caches, for tails of ``slots`` tokens a row: ``(k_kinds,
        v_kinds, k_carry0, v_carry0, served, flush)``. ``served(cache,
        carry, kinds)`` is what the forward reads (the planes from
        outside the scan, everything else from the carry);
        ``flush(k_carry, v_carry, count)`` gives the two caches with
        each row's first ``count [B]`` tail slots written to its pages
        (slot s at position ``kv_lens0 + s``: a run, written page-wise
        and in place) and the convolution tails scattered back."""
        m = self.config.model
        b = kv_lens0.shape[0]
        pages = m.page_cache
        tail_shape = (b, slots, pages.heads, pages.width)
        # What each cache entry is to the burst: page planes where the
        # entry is not a recurrent layer's (a family that stores one
        # latent plane has None for the second, which rides as
        # nothing); of a recurrent layer the state pool
        # in k_cache (None again where its family
        # declares the tail alone) and in v_cache the convolution
        # tails, dense in the carry where the family's forward takes
        # them so; a k_cache that ends in its family's counters has
        # one entry more than there are entries.
        # A family whose state is a windowed layer's K and V rings
        # carries a tail for each as for a plane ("ring"): the pools
        # are read from outside the scan and written once, each tail
        # token to its place ``position mod window`` of the row's slot.
        ring = m.family.ring
        state_v = ("ring" if ring else
                   "conv" if m.family.conv_tail else "ride")
        second = "pages" if pages.planes == 2 else "ride"
        k_kinds = tuple(("ring" if ring else "ride") if state
                        else "pages"
                        for state in m.cache_entry_is_state) + (
            "ride",) * bool(m.family.counters)
        v_kinds = tuple(state_v if state else second
                        for state in m.cache_entry_is_state)
        per_layer = isinstance(k_cache, tuple)

        def carried(cache, kinds):
            # A K/V tail where the layer has pages, the rows' held
            # inputs where it has a convolution, else the entry itself.
            if not per_layer:  # stacked: every layer has pages
                cache = (None,) * m.num_hidden_layers

            def entry(c, kind):
                if kind in ("pages", "ring"):
                    return jnp.zeros(tail_shape, m.jax_dtype)
                if kind == "conv":
                    held = c[state_slots]
                    return tuple(held[:, j] for j in range(c.shape[1]))
                return c
            return tuple(entry(c, k) for c, k in zip(cache, kinds))

        def served(cache, carry, kinds):
            # What the forward reads: the planes from outside the
            # scan, everything else from the carry.
            if not per_layer:
                return cache
            return tuple(c if k in ("pages", "ring") else s
                         for c, s, k in zip(cache, carry, kinds))

        def flush(k_carry, v_carry, count):
            # A row's tail is a run of its own count at the row's
            # length (a row that stopped inside the burst holds the
            # tail it stopped with; a padded row has none): the planes
            # of both caches, which share the one page table, take
            # theirs page-wise and in place in one pass
            # (ops/attention.write_run_to_pages); int8 pages and the
            # stacked cache by one scatter a layer, a ring and a
            # convolution's tails by one scatter each; the other
            # entries are the carry's last.
            tail_pos = kv_lens0[:, None] + jnp.arange(slots)[None, :]
            tail_valid = jnp.arange(slots)[None, :] < count[:, None]
            sides = ((k_cache, k_carry, k_kinds),
                     (v_cache, v_carry, v_kinds))

            def scatter(cache, tail, layer=None):
                return write_to_pages(cache, tail, page_table, tail_pos,
                                      tail_valid, layer=layer)

            def stacked(cache, carry):
                for l, tail in enumerate(carry):
                    cache = scatter(cache, tail, l)
                return cache

            def entry(written, c, s, kind):
                if kind == "pages":
                    return next(written)
                if kind == "ring":
                    return write_to_ring(
                        c, s, state_slots, tail_pos, tail_valid,
                        kv_lens0 + count)
                if kind == "conv":
                    return c.at[state_slots].set(jnp.stack(s, axis=1))
                return s

            with jax.named_scope("kv_flush"):
                if not per_layer:
                    return (stacked(k_cache, k_carry),
                            stacked(v_cache, v_carry))
                paged = [(c, s) for side in sides
                         for c, s, kind in zip(*side) if kind == "pages"]
                if any(isinstance(c, QuantKV) for c, _ in paged):
                    written = iter([scatter(c, s) for c, s in paged])
                else:
                    written = iter(write_run_to_pages(
                        *map(tuple, zip(*paged)), page_table, kv_lens0,
                        count))
                return tuple(
                    tuple(entry(written, c, s, kind)
                          for c, s, kind in zip(*side)) for side in sides)

        return (k_kinds, v_kinds, carried(k_cache, k_kinds),
                carried(v_cache, v_kinds), served, flush)

    def _decode_burst_deferred_impl(self, params, k_cache, v_cache,
                                    tokens, positions, page_table,
                                    kv_lens, active, budgets,
                                    stop_tokens, temperature, top_p,
                                    top_k, rng, lora, lora_ids,
                                    penalties, seeding, bias,
                                    suppress, fsm, num_steps: int,
                                    want_logprobs: bool = False,
                                    state_slots=None):
        """_decode_burst_impl with per-burst (not per-step) KV writes.

        Same contract and carry discipline, except: each step's K/V
        goes into dense per-layer tail buffers ([B, S, kv, d] one-hot
        selects — ops/attention.write_to_tail) and attention covers
        pages + tail positionally (paged_attention k_tail/v_tail);
        the page planes stay READ-ONLY through the scan (loop
        invariants, not carry) and the tails flush to the pages once
        at burst end, every plane's page-wise and in place in one
        pass that copies no plane (ops/attention.write_run_to_pages;
        int8 pages and the stacked cache by one write_to_pages a
        layer). A decode ablation
        (builder-captured 2026-07-31, not measured by the driver) put
        the per-step scatters at ~5.1 of 11.1 ms for ~1 MB of writes;
        on the hybrid cell the eager burst copied both planes of each
        full layer every step (ledger, PR 32: 12.8% of the slice).

        The scan carries what changes and closes over what does not.
        Per cache entry: a layer that has pages
        (``not layer_is_linear``) carries its K/V tail; a recurrent
        layer's convolution tails, where the family's forward takes
        ``conv_tail``, are gathered from their pool by ``state_slots``
        before the scan, carried as the rows' K-1 held inputs
        (``[B, channels]`` each) and scattered back after it; any
        other entry of a hybrid model's caches (a recurrent layer's
        state pool, whose kernel works in place by slot; the family's
        counters at the end of ``k_cache`` where it keeps any) is read
        and written every step and rides the carry itself. A model
        whose every layer has pages carries L tails and nothing else,
        under either cache layout.

        The pages hold exactly the pre-burst tokens throughout, so
        the frozen cached-token count is positions[:, 0] (the first
        burst token's absolute position) and tail slot s sits at
        absolute position kv_lens0 + s.
        """
        b = active.shape[0]
        m = self.config.model
        if penalties is not None:
            counts0, penalties = penalties[0], penalties[1:]
        else:
            counts0 = jnp.zeros((b, 0), jnp.int32)

        kv_lens0 = positions[:, 0]  # pages hold this many tokens
        (k_kinds, v_kinds, k_carry0, v_carry0, served,
         flush) = self._burst_tails(k_cache, v_cache, page_table,
                                    kv_lens0, num_steps, state_slots)
        sample_step = self._burst_sample_step(
            b, penalties, seeding, bias, suppress, temperature,
            top_p, top_k, stop_tokens, budgets, want_logprobs)
        fsm0 = (jnp.zeros((0,), jnp.int32) if fsm is None else fsm)

        def body(carry, step_rng):
            tok, pos, act, emitted, counts, fs, kt, vt = carry
            logits, kt, vt = self._forward(
                params, m, tok, pos, page_table, kv_lens0,
                act[:, None], served(k_cache, kt, k_kinds),
                served(v_cache, vt, v_kinds),
                lora=lora, lora_ids=lora_ids, kv_tail=(kt, vt),
                **self._state_kwargs(state_slots),
                **({"conv_tail": vt} if m.family.conv_tail else {}),
            )
            out, sampled, emitted, counts, act_next, fs = \
                sample_step(logits, step_rng, act, emitted, counts,
                            fs)
            step = act_next.astype(pos.dtype)
            return ((jnp.where(act, sampled, tok[:, 0])[:, None],
                     pos + step[:, None], act_next, emitted, counts,
                     fs, kt, vt), out)

        rngs = jax.random.split(rng, num_steps)
        emitted0 = jnp.zeros(active.shape, jnp.int32)
        carry = (tokens, positions, active, emitted0, counts0, fsm0,
                 k_carry0, v_carry0)
        (_, _, _, emitted, _, _, kt, vt), out = jax.lax.scan(
            body, carry, rngs
        )

        return (out,) + flush(kt, vt, emitted)

    def _decode_burst_draft_impl(self, params, k_cache, v_cache,
                                 tokens, positions, page_table,
                                 kv_lens, active, budgets,
                                 stop_tokens, temperature, top_p,
                                 top_k, rng, lora, lora_ids,
                                 penalties, seeding, bias,
                                 suppress, fsm, num_steps: int,
                                 want_logprobs: bool = False,
                                 state_slots=None, draft_rows=None):
        """``_decode_burst_deferred_impl`` for a family whose draft
        module proposes inside the burst (docs/speculative.md, "The
        module as proposer"): an iteration commits one or two tokens a
        row.

        An iteration, for a live row with last committed token ``x`` at
        position ``P``, its tail count ``n = P - kv_lens0`` and a draft
        ``d`` drawn from the module's distribution ``q`` (under the
        row's own temperature, top-p and top-k; the argmax for a
        greedy row): the main model runs positions ``P, P + 1`` on
        ``(x, d)`` and appends both latents at tail slots ``n, n + 1``
        of each of its entries; ``verify_proposal`` accepts ``d`` with
        ``min(1, p1(d) / q(d))`` and commits ``d`` and ``y ~ p2``, or
        commits ``y' ~ norm(max(0, p1 - q))``; the module then runs on
        the committed positions (``h_P`` with the first committed
        token, ``h_{P+1}`` with ``y`` where ``d`` was accepted),
        appends its own latents at the same slots of its own entry and
        gives the next proposal and draft. A rejected draft's slot ``n +
        1`` is overwritten by the next iteration (whose ``n`` is one
        more) and is causally invisible until then: slot s is position
        ``kv_lens0 + s``, which no query before it reads. The output
        distribution is the target's.

        What the sampler touches of the vocabulary (ops/sampling.py;
        docs/speculative.md, "The module as proposer"): the two
        positions' logits come from the head POSITION-MAJOR, ``[2, B,
        vocab]`` float32, each position a dense plane (a ``[B, 2,
        vocab]`` array lies in (2, 128) tiles, a quarter of a tile's
        eight rows, and every pass over it pays for whole tiles); the proposal rides the carry between
        iterations as the module's logits ``[B, vocab]`` as its head
        wrote them, never as probabilities, and ``draw_proposal``
        draws the next draft from them by Gumbel-max in one pass. An
        iteration without top-k/top-p reads a plane some ten times, in
        six reductions, and writes none.

        A row has no draft in a burst's first iteration (the draft
        after the last burst's, or the prefill's, last token is not
        kept across programs), and none at all where ``draft_rows`` is
        False: a row whose request carries penalties, a logit bias,
        min_tokens suppression, a guided grammar or a seed, whose
        logits the module does not see. Such a row's second position
        is masked out and it commits one token an iteration by the
        rule of ``_burst_sample_step`` (a seeded row by its seed), in
        this same program.

        Budgets and stop tokens cut inside a pair: an accepted ``d``
        that ends the row drops ``y``. Tails are ``2 * num_steps``
        slots, flushed by each row's own count. Returns tokens ``[2 *
        num_steps, B]`` (-1 where nothing was committed), with
        ``want_logprobs`` the target's raw log-probabilities at each
        committed position beside them. The family's counters gain the
        drafts offered and accepted.
        """
        b = active.shape[0]
        m = self.config.model
        names = m.family.counters
        i_drafts, i_accepted = (names.index("drafts"),
                                names.index("accepted"))
        if penalties is not None:
            counts0, penalties = penalties[0], penalties[1:]
        else:
            counts0 = jnp.zeros((b, 0), jnp.int32)
        kv_lens0 = positions[:, 0]  # pages hold this many tokens
        (k_kinds, v_kinds, k_carry0, v_carry0, served,
         flush) = self._burst_tails(k_cache, v_cache, page_table,
                                    kv_lens0, 2 * num_steps, state_slots)
        guided = fsm is not None
        first_logits = self._burst_row_logits(penalties, bias, suppress,
                                              guided)
        fsm0 = fsm if guided else jnp.zeros((0,), jnp.int32)
        rows = jnp.arange(b)

        def hit_stop(tok):
            return jnp.any(tok[:, None] == stop_tokens, axis=-1)

        def body(carry, step_rng):
            (tok, pos, act, emitted, counts, fs, draft, proposal,
             has_draft, kt, vt) = carry
            key_verify, key_seeded, key_draft = jax.random.split(
                step_rng, 3)
            pos2 = jnp.concatenate([pos, pos + 1], axis=1)
            valid2 = jnp.stack([act, act & has_draft], axis=1)
            logits, hidden, kt, vt = self._forward(
                params, m, jnp.stack([tok[:, 0], draft], axis=1), pos2,
                page_table, kv_lens0, valid2,
                served(k_cache, kt, k_kinds),
                served(v_cache, vt, v_kinds), kv_tail=(kt, vt),
                return_hidden=True, position_major=True)
            with jax.named_scope("mtp_verify"):
                row_logits = logits[0]
                targets = logits
                if first_logits is not None:
                    # A plane of its own: written once, where some
                    # row's request changes its logits.
                    row_logits = first_logits(row_logits, counts,
                                              emitted, fs)
                    targets = (row_logits, logits[1])
                out2 = verify_proposal(
                    targets, draft[:, None], has_draft.astype(jnp.int32),
                    (proposal,), temperature, top_p, top_k, key_verify)
                first, second = out2[:, 0], out2[:, 1]
                if seeding is not None:
                    # A seeded row (never a drafting one) keeps its
                    # own stream: (seed, emitted index) alone.
                    seeds, seed_on, emitted_start = seeding
                    first = jnp.where(seed_on, sample_tokens(
                        row_logits, temperature, top_p, top_k,
                        key_seeded, seeds=seeds,
                        emitted=emitted_start + emitted,
                        seed_mask=seed_on), first)
                accepted = has_draft & (second >= 0)
                # Lifecycle, a token at a time: the second is committed
                # only where the first left the row alive.
                emitted1 = emitted + act
                alive = act & ~hit_stop(first) & (emitted1 < budgets)
                emit2 = alive & accepted
                emitted2 = emitted1 + emit2
                act_next = (alive & ~(emit2 & hit_stop(second))
                            & (emitted2 < budgets))
                out = jnp.stack([jnp.where(act, first, -1),
                                 jnp.where(emit2, second, -1)])
                if want_logprobs:
                    lps = [token_logprobs(logits[j], jnp.clip(t, 0),
                                          TOP_LOGPROBS_WIDTH)
                           for j, t in enumerate((first, second))]
                    out = (out,) + tuple(jnp.stack(pair)
                                         for pair in zip(*lps))
                if penalties is not None:
                    counts = counts.at[rows, first].add(
                        act.astype(counts.dtype))
                    counts = counts.at[rows, jnp.clip(second, 0)].add(
                        emit2.astype(counts.dtype))
                if guided:
                    # A guided row never drafts: one token advances it.
                    width = self._guided_trans.shape[1]
                    nxt = self._guided_trans[
                        jnp.clip(fs, 0), jnp.clip(first, 0, width - 1)]
                    fs = jnp.where(act & (fs >= 0), nxt, fs)
            # The module on what was committed: h_P with the first
            # token, h_{P+1} with the second where there is one; its
            # logits after the last of them are the next proposal, and
            # ride the carry as the head wrote them.
            proposal, kt = self._draft(
                params, m, hidden,
                jnp.stack([first, jnp.clip(second, 0)], axis=1), pos2,
                page_table, kv_lens0,
                jnp.stack([act_next, act_next & emit2], axis=1),
                served(k_cache, kt, k_kinds), kv_tail=(kt, vt),
                head_index=emit2.astype(jnp.int32))
            with jax.named_scope("mtp_draft"):
                draft = draw_proposal(proposal, temperature, top_p,
                                      top_k, key_draft)
            has_draft_next = act_next & draft_rows
            stats = kt[-1]
            stats = stats.at[i_drafts].add(
                jnp.sum(act & has_draft).astype(stats.dtype))
            stats = stats.at[i_accepted].add(
                jnp.sum(act & accepted).astype(stats.dtype))
            kt = kt[:-1] + (stats,)
            step = jnp.where(act_next, emitted2 - emitted, 0)
            tok = jnp.where(emit2, second, jnp.where(act, first,
                                                     tok[:, 0]))
            return ((tok[:, None], pos + step[:, None].astype(pos.dtype),
                     act_next, emitted2, counts, fs, draft, proposal,
                     has_draft_next, kt, vt), out)

        rngs = jax.random.split(rng, num_steps)
        zeros = jnp.zeros(active.shape, jnp.int32)
        carry = (tokens, positions, active, zeros, counts0, fsm0, zeros,
                 jnp.zeros((b, m.vocab_size), jnp.float32),
                 jnp.zeros(active.shape, bool), k_carry0, v_carry0)
        carry, out = jax.lax.scan(body, carry, rngs)
        emitted, kt, vt = carry[3], carry[-2], carry[-1]
        # [K, 2, B, ...] -> [2K, B, ...]: a row's tokens in order.
        out = jax.tree_util.tree_map(
            lambda x: x.reshape((2 * num_steps,) + x.shape[2:]), out)
        return (out,) + flush(kt, vt, emitted)

    def _decode_burst_block_impl(self, params, k_cache, v_cache,
                                 tokens, positions, page_table,
                                 kv_lens, active, budgets,
                                 stop_tokens, temperature, top_p,
                                 top_k, rng, lora, lora_ids,
                                 penalties, seeding, bias,
                                 suppress, fsm, num_steps: int,
                                 want_logprobs: bool = False,
                                 state_slots=None, block_rows=None):
        """The burst of a family that generates by diffusion over
        blocks (docs/block_diffusion.md), on ``_burst_tails``: a scan
        over ``burst_blocks(num_steps, diffusion_steps)`` blocks of
        ``B`` positions a row, the rows in lockstep by block as the
        published batched loop has them.

        A row's block starts at ``positions[:, 0] + j * B`` (the
        pages hold exactly the tokens before the burst's first block:
        ``kv_lens0``) and owns tail slots ``j * B .. j * B + B - 1``.
        It begins as ``tokens [rows, B]``' given places (the first
        block: the prompt's remainder, ``block_rows[0]`` of them) and
        masked places after them. A denoising pass runs every row's B
        places against pages, finished blocks and the block itself
        (masked places as the mask's embedding), writes the block's
        K/V to its slots PROVISIONALLY (a later pass overwrites them
        in place, as a rejected draft's slot is overwritten in the
        draft burst) and commits some masked places (``unmask_block``:
        the row's own passes a block, rule and threshold,
        ``block_rows[1:]``); passes go on while a live row has a
        masked place, at most B. Then the block's new tokens go out in
        position order, under the row's budget and stop set (a stop
        token or the budget inside a block drops the places after it
        and ends the row), and one STORE pass, without the head or the
        sampler, writes the final K/V of the rows still alive to the
        same slots: what a prefill of prompt + answer would write. A
        row that ended inside a block stores nothing of it, and the
        flush writes each row's stored blocks alone.

        ``num_steps`` (--decode-steps) is the burst's planned forward
        passes; the passes that ran are counted on the device with the
        blocks worked and the places committed, in the family's
        counters (``denoise_passes``, ``store_passes``, ``blocks``,
        ``committed``; ``sorted_passes``: the denoising passes that
        sorted the vocabulary, a row of the batch carrying a top-k or
        a top-p). Returns tokens ``[blocks * B, rows]`` (-1
        where nothing went out), with ``want_logprobs`` the raw
        log-probabilities at each token's committing pass beside
        them."""
        del kv_lens, penalties, seeding, bias, suppress, fsm  # refused
        m = self.config.model
        bl = m.block_length
        blocks = burst_blocks(num_steps, m.diffusion_steps)
        b = active.shape[0]
        given, steps, strategy, threshold = block_rows
        i_denoise = m.family.counters.index("denoise_passes")
        kv_lens0 = positions[:, 0]  # pages hold this many tokens
        (k_kinds, v_kinds, k_carry0, v_carry0, served,
         flush) = self._burst_tails(k_cache, v_cache, page_table,
                                    kv_lens0, blocks * bl, state_slots)
        place = jnp.arange(bl)
        width = TOP_LOGPROBS_WIDTH
        sorts = _needs_mask(top_p, top_k)  # unmask_block's own choice

        def counted(kt, denoise=0, store=0, worked=0, committed=0):
            add = jnp.stack([jnp.asarray(x, jnp.float32) for x in (
                denoise, store, worked, committed, sorts * denoise)])
            return kt[:-1] + (
                kt[-1].at[i_denoise:i_denoise + len(add)].add(add),)

        def run(ids, masked, pos, live, kt, vt, **how):
            return self._forward(
                params, m, ids, pos, page_table, kv_lens0,
                jnp.broadcast_to(live[:, None], ids.shape),
                served(k_cache, kt, k_kinds),
                served(v_cache, vt, v_kinds), kv_tail=(kt, vt),
                masked=masked, **how)

        def block_body(carry, xs):
            ids, new, act, emitted, stored, kt, vt = carry
            j, key = xs
            pos = kv_lens0[:, None] + j * bl + place[None, :]

            def denoise(state):
                s, ids, masked, lps, kt, vt = state
                logits, kt, vt = run(ids, masked, pos, act, kt, vt,
                                     position_major=True)
                quota = bl // steps + (s < bl % steps)
                x0, commit, _ = unmask_block(
                    logits, masked, quota, strategy, threshold,
                    temperature, top_p, top_k,
                    jax.random.fold_in(key, s))
                if want_logprobs:
                    # The raw distribution of the committing pass.
                    at = [token_logprobs(logits[i], x0[:, i], width)
                          for i in range(bl)]
                    lps = tuple(
                        jnp.where(
                            commit.reshape(commit.shape
                                           + (1,) * (old.ndim - 2)),
                            jnp.stack(new_, axis=1), old)
                        for old, new_ in zip(lps, zip(*at)))
                kt = counted(kt, denoise=1, committed=jnp.sum(commit))
                return (s + 1, jnp.where(commit, x0, ids),
                        masked & ~commit, lps, kt, vt)

            lps0 = ((jnp.zeros((b, bl), jnp.float32),
                     jnp.zeros((b, bl, width), jnp.int32),
                     jnp.zeros((b, bl, width), jnp.float32))
                    if want_logprobs else ())
            _, ids, _, lps, kt, vt = jax.lax.while_loop(
                lambda state: (state[0] < bl) & jnp.any(state[2]),
                denoise,
                (jnp.int32(0), ids, new & act[:, None], lps0, kt, vt))

            # The block's new tokens in position order: a place goes
            # out while the row lives, and a stop token or the budget
            # met ends the row there.
            alive, out = act, []
            for i in range(bl):
                emit = alive & new[:, i]
                emitted = emitted + emit
                out.append(jnp.where(emit, ids[:, i], -1))
                hit_stop = jnp.any(
                    ids[:, i, None] == stop_tokens, axis=-1)
                alive = (alive & ~(emit & hit_stop)
                         & (emitted < budgets))
            out = jnp.stack(out)
            if want_logprobs:
                out = (out,) + tuple(jnp.swapaxes(x, 0, 1) for x in lps)

            def store(kt, vt):
                _, kt, vt = run(ids, None, pos, alive, kt, vt,
                                head=False)
                return counted(kt, store=1), vt

            kt = counted(kt, worked=jnp.sum(act))
            kt, vt = jax.lax.cond(jnp.any(alive), store,
                                  lambda kt, vt: (kt, vt), kt, vt)
            stored = stored + alive.astype(stored.dtype) * bl
            return ((jnp.zeros_like(ids), jnp.ones_like(new), alive,
                     emitted, stored, kt, vt), out)

        zeros = jnp.zeros(active.shape, jnp.int32)
        carry = (tokens, place[None, :] >= given[:, None], active, zeros,
                 zeros, k_carry0, v_carry0)
        carry, out = jax.lax.scan(
            block_body, carry,
            (jnp.arange(blocks), jax.random.split(rng, blocks)))
        stored, kt, vt = carry[4], carry[5], carry[6]
        # [blocks, B, rows, ...] -> [blocks * B, rows, ...]: a row's
        # tokens in position order.
        out = jax.tree_util.tree_map(
            lambda x: x.reshape((blocks * bl,) + x.shape[2:]), out)
        return (out,) + flush(kt, vt, stored)

    def _burst_row_logits(self, penalties, bias, suppress, guided: bool):
        """``_burst_sample_step``'s way from a row's raw logits to the
        ones it is sampled from, for a burst body that samples by
        another rule than ``sample_tokens``: penalties, logit bias,
        min_tokens suppression, the guided mask last (``guided``: the
        burst carries automaton states). None where the batch has none
        of them: its rows are sampled from the logits as they are."""
        if (penalties is None and bias is None and suppress is None
                and not guided):
            return None

        def row_logits(logits, counts, emitted, fsm):
            if penalties is not None:
                prompt_mask, presence, frequency, repetition = penalties
                logits = apply_penalties(
                    logits, counts, prompt_mask, presence, frequency,
                    repetition)
            if bias is not None:
                logits = logits + bias
            if suppress is not None:
                logits = self._apply_suppression(logits, suppress,
                                                 emitted=emitted)
            if guided:
                logits = self._apply_guided_mask(logits, fsm)
            return logits

        return row_logits

    def _spec_verify_impl(self, params, k_cache, v_cache, tokens,
                          positions, page_table, kv_lens, valid,
                          drafts, draft_lens, temperature, top_p,
                          top_k, rng, lora, lora_ids,
                          want_logprobs: bool = False):
        """One fixed-shape speculative verify step.

        ``tokens[i] = [last_committed, d_1 .. d_k]`` (padded) at
        absolute positions total_len-1 .. total_len-1+k. The forward
        writes the draft tokens' KV into the sequence's pages exactly
        like a prefill chunk (invalid slots land in the trash page)
        and attends causally, so ``logits[i, j]`` is the target
        model's distribution for the token at offset j past the
        committed length — all k+1 positions scored in ONE pass.

        Rejected drafts need NO device rollback: their KV lives past
        the committed length in private pages (prefix hashing only
        ever covers prompt tokens — scheduler.on_prefill_executed),
        causally invisible to every later query until the next step
        overwrites those positions (docs/speculative.md §rollback).
        """
        logits, k_cache, v_cache = self._forward(
            params, self._spec_model, tokens, positions, page_table,
            kv_lens, valid, k_cache, v_cache,
            lora=lora, lora_ids=lora_ids,
        )
        out = spec_verify(logits, drafts, draft_lens, temperature,
                          top_p, top_k, rng)
        if want_logprobs:
            # OpenAI logprobs from the raw per-position distributions;
            # positions past a row's emitted count are discarded by
            # the host parse.
            b, s, v = logits.shape
            lp = token_logprobs(logits.reshape(b * s, v),
                                jnp.clip(out, 0).reshape(b * s),
                                TOP_LOGPROBS_WIDTH)
            lp = tuple(x.reshape((b, s) + x.shape[1:]) for x in lp)
            return (out,) + lp, k_cache, v_cache
        return out, k_cache, v_cache

    def _unified_impl(self, params, k_cache, v_cache, tokens,
                      positions, page_table, kv_lens, valid,
                      last_index, drafts, draft_lens, temperature,
                      top_p, top_k, rng, lora, lora_ids,
                      want_logprobs: bool = False):
        """One fixed-shape ragged step (docs/unified_step.md).

        ``tokens`` is the [R, W] ragged block: a decode/draft row
        occupies its leading 1 + draft_len slots exactly like a
        verify row ([last_committed, d_1..d_k] at positions
        total_len-1 ..), a prefill chunk row occupies up to W slots
        of prompt tokens, and pad slots are masked by ``valid`` (KV
        lands in the trash page). The forward is the T>1
        chunked-prefill attention path unchanged — its contract
        (per-row contiguous positions, causal mask against the
        row's cached context) already covers mixed query lengths
        against the page table.

        Sampling unifies through the verify rule: the span gather
        ``span[i, j] = logits[i, last_index_i - draft_lens_i + j]``
        collects each row's scoring span (a draft row's span starts
        at its committed token; for draft-free rows the span IS the
        last real position, draft_lens 0), and spec_verify emits
        1..span tokens per row through ONE shape — a draft-free
        greedy row degenerates to the plain argmax, bit-identical
        to sample_tokens at temperature 0.
        """
        logits, k_cache, v_cache = self._forward(
            params, self._unified_model, tokens, positions,
            page_table, kv_lens, valid, k_cache, v_cache,
            lora=lora, lora_ids=lora_ids,
        )
        s = drafts.shape[-1] + 1
        start = jnp.clip(last_index - draft_lens, 0)
        idx = jnp.clip(start[:, None] + jnp.arange(s)[None, :], 0,
                       tokens.shape[1] - 1)
        span = jnp.take_along_axis(logits, idx[:, :, None], axis=1)
        out = spec_verify(span, drafts, draft_lens, temperature,
                          top_p, top_k, rng)
        if want_logprobs:
            # Raw per-span-position distributions (the OpenAI
            # contract); positions past a row's emitted count are
            # discarded by the host parse.
            b, _, v = span.shape
            lp = token_logprobs(span.reshape(b * s, v),
                                jnp.clip(out, 0).reshape(b * s),
                                TOP_LOGPROBS_WIDTH)
            lp = tuple(x.reshape((b, s) + x.shape[1:]) for x in lp)
            return (out,) + lp, k_cache, v_cache
        return out, k_cache, v_cache

    def _next_rng(self) -> np.ndarray:
        """The next sampling key (HostKeys): the one source for every
        path, single host or many."""
        return self._keys.next()

    def read_back(self, sampled):
        """A step's one blocking device_get; to the turn's phases the
        loop thread waits, then parses."""
        tracer = self.tracer
        if tracer is not None:
            tracer.phase("wait")
        host = jax.device_get(sampled)
        if tracer is not None:
            tracer.phase("parse")
        return host

    def _bucket_for(self, n: int) -> int:
        for b in self._buckets:
            if n <= b:
                return b
        return self._buckets[-1]

    def _row_bucket_for(self, n: int) -> int:
        for b in self.unified_row_buckets:
            if n <= b:
                return b
        return self.unified_row_buckets[-1]

    # ---- payload execution (shared by host 0 and multihost workers) -------

    def execute_payload(self, kind: int, payload: dict,
                        t: int = 1) -> jax.Array:
        """Run one compiled step from a numpy payload.

        The payload is the complete device-program input (including the
        rng key), so host 0 and multihost workers — which receive it
        over the MultihostStepBridge broadcast — dispatch bit-identical
        programs (parallel/distributed.py). For decode (kind 2), ``t``
        is the multi-step window; prefill uses it as the token bucket
        (already baked into the array shapes).
        """
        from production_stack_tpu.parallel.distributed import (
            KIND_EMBED,
            KIND_SPEC,
            KIND_UNIFIED,
        )
        if kind == KIND_EMBED:
            return self.embedder.run_chunk(payload["tokens"],
                                           payload["lengths"])
        lora_ids = payload.get("lora_ids")
        lora_ids = (None if lora_ids is None
                    else _as_device(lora_ids))
        penalties, seeding, bias, suppress, fsm = \
            self._optional_device_inputs(payload)
        want_lp = bool(payload.get("want_logprobs", False))
        state = ({"state_slots": _as_device(payload["state_slots"])}
                 if "state_slots" in payload else {})
        if kind == KIND_SPEC:
            # Speculative verify: the scheduler only plans eligible
            # rows (no penalties/seeds/bias/min_tokens/guided), so
            # the program compiles without those inputs.
            sampled, self.k_cache, self.v_cache = self._spec_jit(
                self.params, self.k_cache, self.v_cache,
                _as_device(payload["tokens"]),
                _as_device(payload["positions"]),
                _as_device(payload["page_table"]),
                _as_device(payload["kv_lens"]),
                _as_device(payload["valid"]),
                _as_device(payload["drafts"]),
                _as_device(payload["draft_lens"]),
                _as_device(payload["temperature"]),
                _as_device(payload["top_p"]),
                _as_device(payload["top_k"]),
                _as_device(payload["rng"]),
                self._lora_stack, lora_ids,
                want_logprobs=want_lp,
            )
            return sampled  # [B, S] (+ logprob arrays when requested)
        if kind == KIND_UNIFIED:
            # Mixed ragged step: the scheduler only plans eligible
            # rows (no penalties/seeds/bias/min_tokens/guided — the
            # spec-row exclusion set), so the program compiles
            # without those inputs.
            sampled, self.k_cache, self.v_cache = self._unified_jit(
                self.params, self.k_cache, self.v_cache,
                _as_device(payload["tokens"]),
                _as_device(payload["positions"]),
                _as_device(payload["page_table"]),
                _as_device(payload["kv_lens"]),
                _as_device(payload["valid"]),
                _as_device(payload["last_index"]),
                _as_device(payload["drafts"]),
                _as_device(payload["draft_lens"]),
                _as_device(payload["temperature"]),
                _as_device(payload["top_p"]),
                _as_device(payload["top_k"]),
                _as_device(payload["rng"]),
                self._lora_stack, lora_ids,
                want_logprobs=want_lp,
            )
            return sampled  # [R, span] (+ logprobs when requested)
        if kind == 2 and t > 1:
            sampled, self.k_cache, self.v_cache = \
                self._decode_burst_jit(
                    self.params, self.k_cache, self.v_cache,
                    _as_device(payload["tokens"]),
                    _as_device(payload["positions"]),
                    _as_device(payload["page_table"]),
                    _as_device(payload["kv_lens"]),
                    _as_device(payload["active"]),
                    _as_device(payload["budgets"]),
                    _as_device(payload["stop_tokens"]),
                    _as_device(payload["temperature"]),
                    _as_device(payload["top_p"]),
                    _as_device(payload["top_k"]),
                    _as_device(payload["rng"]),
                    self._lora_stack, lora_ids, penalties, seeding,
                    bias, suppress, fsm,
                    num_steps=t, want_logprobs=want_lp, **state,
                    **({"draft_rows": _as_device(payload["draft_rows"])}
                       if "draft_rows" in payload else {}),
                    **({"block_rows": tuple(
                        _as_device(payload[name])
                        for name in BLOCK_ROW_INPUTS)}
                       if BLOCK_ROW_INPUTS[0] in payload else {}),
                )
            # [K, B], or [2K, B] where the burst drafts (+ logprob
            # arrays when requested)
            return sampled
        sampled, self.k_cache, self.v_cache = self._step_jit(
            self.params, self.k_cache, self.v_cache,
            _as_device(payload["tokens"]),
            _as_device(payload["positions"]),
            _as_device(payload["page_table"]),
            _as_device(payload["kv_lens"]),
            _as_device(payload["valid"]),
            _as_device(payload["last_index"]),
            _as_device(payload["temperature"]),
            _as_device(payload["top_p"]),
            _as_device(payload["top_k"]),
            _as_device(payload["rng"]),
            self._lora_stack, lora_ids, penalties, seeding, bias,
            suppress, fsm,
            sample_index_mode=(self._prefill_mode if kind == 1
                               else "first"),
            want_logprobs=want_lp, **state,
            **({"next_tokens": _as_device(payload["next_tokens"])}
               if "next_tokens" in payload else {}),
        )
        return sampled

    def _load_step_program(self, payload: dict) -> threading.Thread:
        """Bring up the plain prefill program of ``payload``'s shape
        beside whatever the caller does next: lowered here (the
        caches are read for their shapes, before the next dispatch
        donates them), compiled or read from the compile cache on a
        thread. The jitted step keeps the executable with its
        lowering, so the dispatch of that shape that follows finds it
        and is counted by ``/debug/compiles`` as any first call is:
        its record takes what this thread heard of the lowering and
        what the compiling thread heard (observatory.load_ahead), so
        the program's load is its own and not the step's that loads
        beside it."""
        since = time.perf_counter()
        args = tuple(_as_device(payload[name]) for name in (
            "tokens", "positions", "page_table", "kv_lens", "valid",
            "last_index", "temperature", "top_p", "top_k", "rng"))
        lora_ids = payload.get("lora_ids")
        state = ({"state_slots": _as_device(payload["state_slots"])}
                 if "state_slots" in payload else {})
        if "next_tokens" in payload:
            state["next_tokens"] = _as_device(payload["next_tokens"])
        lowered = self._step_jit.lower(
            self.params, self.k_cache, self.v_cache, *args,
            self._lora_stack,
            None if lora_ids is None else _as_device(lora_ids),
            None, None, None, None, None,
            sample_index_mode=self._prefill_mode, want_logprobs=False,
            **state)
        obs = self.observatory
        lowering = take_load_split(since)
        lowered_s = time.perf_counter() - since

        def compile_it():
            since = time.perf_counter()
            try:
                lowered.compile()
            except Exception:  # noqa: BLE001 — the dispatch raises it
                logger.exception("prefill program failed to compile")
            if obs is not None:
                obs.load_ahead(
                    "step", payload["tokens"].shape,
                    lowered_s + time.perf_counter() - since,
                    add_load_splits(lowering, take_load_split(since)))

        thread = threading.Thread(target=compile_it, daemon=True,
                                  name="prefill-width-compile")
        thread.start()
        return thread

    @staticmethod
    def _lp_entry(seq, slp, tids, tlps):
        """One position's logprob info, trimmed to the row's request."""
        k = min(max(seq.sampling.top_logprobs, 0), TOP_LOGPROBS_WIDTH)
        return (float(slp),
                [(int(tids[j]), float(tlps[j])) for j in range(k)])

    def _penalty_payload(self, seqs: "List[Optional[Sequence]]",
                         pad_to: int) -> dict:
        """Per-row penalty inputs, or {} when no row needs them (the
        no-penalty batch keeps its penalty-free compiled program and
        pays no [B, vocab] host->device transfer). ``None`` rows
        (e.g. mid-prompt prefill chunks that discard their sample)
        keep the no-op defaults."""
        if not any(s is not None and s.sampling.needs_penalties
                   for s in seqs):
            return {}
        v = self.config.model.vocab_size
        counts = np.zeros((pad_to, v), np.int32)
        pmask = np.zeros((pad_to, v), bool)
        presence = np.zeros((pad_to,), np.float32)
        frequency = np.zeros((pad_to,), np.float32)
        repetition = np.ones((pad_to,), np.float32)
        for i, seq in enumerate(seqs):
            if seq is None:
                continue
            sp = seq.sampling
            presence[i] = sp.presence_penalty
            frequency[i] = sp.frequency_penalty
            repetition[i] = sp.repetition_penalty
            if sp.needs_penalties:
                # Both asarray calls index host Python lists, not
                # device arrays — the host-read lint proves this
                # flow-sensitively (no waiver needed).
                if seq.output_token_ids:
                    np.add.at(
                        counts[i],
                        np.asarray(seq.output_token_ids,
                                   np.int64), 1)
                pmask[i, np.asarray(
                    seq.prompt_token_ids, np.int64)] = True
        return {"pen_counts": counts, "pen_prompt_mask": pmask,
                "pen_presence": presence, "pen_frequency": frequency,
                "pen_repetition": repetition}

    def _seed_payload(self, seqs: "List[Optional[Sequence]]",
                      pad_to: int) -> dict:
        """Per-row seed inputs, or {} when no row set a seed (the
        unseeded batch keeps its seed-free compiled program)."""
        if not any(s is not None and s.sampling.seed is not None
                   for s in seqs):
            return {}
        seeds = np.zeros((pad_to,), np.uint32)
        seed_on = np.zeros((pad_to,), bool)
        emitted = np.zeros((pad_to,), np.int32)
        for i, seq in enumerate(seqs):
            if seq is None:
                continue
            if seq.sampling.seed is not None:
                # Full 32-bit seed; seededness rides the separate
                # ``seed_on`` mask so no seed bit is sacrificed to
                # gating (a 31-bit fold would collide distinct user
                # seeds, e.g. 1 and 0x80000001).
                seeds[i] = int(seq.sampling.seed) & 0xFFFFFFFF
                seed_on[i] = True
            emitted[i] = seq.num_generated
        return {"seed_rows": seeds.view(np.int32),
                "seed_on": seed_on,
                "seed_emitted": emitted}

    def _bias_payload(self, seqs: "List[Optional[Sequence]]",
                      pad_to: int) -> dict:
        """Per-row logit-bias matrix, or {} when no row uses one (the
        bias-free batch keeps its bias-free compiled program and pays
        no [B, vocab] host->device transfer).

        The matrix is constant while the batch's row composition is —
        cached by (row seq_id, bias identity) so the single-step path
        doesn't rebuild a [B, vocab] dense matrix per token (it still
        rides each dispatch's payload: the multihost broadcast needs
        the full input set — same trade the penalty mask makes)."""
        if not any(s is not None and s.sampling.logit_bias
                   for s in seqs):
            return {}
        key = (pad_to, tuple(
            (s.seq_id, tuple(sorted(s.sampling.logit_bias.items())))
            if s is not None and s.sampling.logit_bias else None
            for s in seqs))
        cached = getattr(self, "_bias_cache", None)
        if cached is not None and cached[0] == key:
            return {"logit_bias": cached[1]}
        v = self.config.model.vocab_size
        bias = np.zeros((pad_to, v), np.float32)
        for i, seq in enumerate(seqs):
            if seq is None or not seq.sampling.logit_bias:
                continue
            for tid, b in seq.sampling.logit_bias.items():
                # Out-of-vocab ids are rejected with a 400 at request
                # time when the serving vocab is known (server.py); the
                # guard here keeps direct-engine callers safe.
                if 0 <= int(tid) < v:
                    bias[i, int(tid)] = float(b)
        self._bias_cache = (key, bias)
        return {"logit_bias": bias}

    def _suppress_payload(self, seqs: "List[Optional[Sequence]]",
                          pad_to: int) -> dict:
        """min_tokens stop-suppression inputs, or {} when no row is
        under its minimum: per-row stop-set ids (EOS included —
        padded with -1 to STOP_SET_WIDTH) and the count of tokens the
        row must still emit before a stop may be GENERATED. The
        sampling steps mask those ids to -inf while under the
        minimum; ids beyond the fixed width are protected by the host
        finish guard (scheduler._append_token) instead."""
        if not any(s is not None
                   and s.sampling.min_tokens > s.num_generated
                   for s in seqs):
            return {}
        ids = np.full((pad_to, STOP_SET_WIDTH), -1, np.int32)
        rem = np.zeros((pad_to,), np.int32)
        for i, seq in enumerate(seqs):
            if seq is None:
                continue
            r = seq.sampling.min_tokens - seq.num_generated
            if r <= 0:
                continue
            rem[i] = r
            sids = seq.sampling.stop_token_ids[:STOP_SET_WIDTH]
            ids[i, :len(sids)] = sids
        return {"sup_ids": ids, "sup_rem": rem}

    @staticmethod
    def _apply_suppression(row_logits, suppress, emitted=None):
        """Mask suppressed token ids to -inf for rows still under
        their min_tokens. ``emitted`` (burst paths) counts tokens
        emitted THIS dispatch on top of the payload-time remainder;
        None (single-step/prefill: at most one token per dispatch)
        means the payload-time remainder is current."""
        ids, rem = suppress  # [B, W] (-1 padded), [B]
        b = row_logits.shape[0]
        under = (rem > 0) if emitted is None else (emitted < rem)
        pen = jnp.where((ids >= 0) & under[:, None], -1e30, 0.0)
        return row_logits.at[
            jnp.arange(b)[:, None], jnp.clip(ids, 0)].add(pen)

    def _guided_payload(self, seqs: "List[Optional[Sequence]]",
                        pad_to: int) -> dict:
        """Per-row automaton states ([B] int32, -1 = unconstrained),
        or {} when no row is guided (unguided batches keep their
        table-free compiled program)."""
        if not any(s is not None and s.fsm_state is not None
                   for s in seqs):
            return {}
        state = np.full((pad_to,), -1, np.int32)
        for i, seq in enumerate(seqs):
            if seq is not None and seq.fsm_state is not None:
                state[i] = seq.fsm_state
        return {"fsm_state": state}

    def _apply_guided_mask(self, row_logits, fsm):
        """-inf every token the automaton disallows from each
        constrained row's state (applied LAST — the grammar wins
        over bias and penalties). The tables stop at the byte+special
        width (guided.py TABLE_WIDTH); every id beyond it is
        inadmissible for constrained rows, so the gathered rows pad
        with False up to the vocab."""
        constrained = fsm >= 0
        st = jnp.clip(fsm, 0)
        allowed = self._guided_mask[st]  # [B, table_width] bool
        pad = row_logits.shape[-1] - allowed.shape[-1]
        if pad > 0:
            allowed = jnp.pad(allowed, ((0, 0), (0, pad)),
                              constant_values=False)
        return jnp.where(constrained[:, None] & ~allowed, -1e30,
                         row_logits)

    @staticmethod
    def _optional_device_inputs(payload: dict):
        """(penalties, seeding, bias, suppress, fsm) device inputs
        from a step payload; each is None when its keys are
        absent."""
        penalties = None
        if "pen_prompt_mask" in payload:
            penalties = (
                _as_device(payload["pen_counts"]),
                _as_device(payload["pen_prompt_mask"]),
                _as_device(payload["pen_presence"]),
                _as_device(payload["pen_frequency"]),
                _as_device(payload["pen_repetition"]),
            )
        seeding = None
        if "seed_rows" in payload:
            seeding = (_as_device(payload["seed_rows"]),
                       _as_device(payload["seed_on"]),
                       _as_device(payload["seed_emitted"]))
        bias = (_as_device(payload["logit_bias"])
                if "logit_bias" in payload else None)
        suppress = ((_as_device(payload["sup_ids"]),
                     _as_device(payload["sup_rem"]))
                    if "sup_ids" in payload else None)
        fsm = (_as_device(payload["fsm_state"])
               if "fsm_state" in payload else None)
        return penalties, seeding, bias, suppress, fsm

    def _dispatch(self, kind: int, t: int, payload: dict) -> jax.Array:
        if self.tracer is not None:
            self.tracer.phase("dispatch")
        if self.bridge is not None:
            # Atomic publish+execute: see MultihostStepBridge.lock.
            with self.bridge.lock:
                self.bridge.publish(kind, t, payload)
                return self.execute_payload(kind, payload, t)
        return self.execute_payload(kind, payload, t)

    # ---- prefill ----------------------------------------------------------

    def dispatch_sp_prefill(self, plan: PrefillPlan) -> StepHandle:
        """Context-parallel whole-prompt prefill: ONE dispatch covers
        the entire prompt with the sequence sharded over 'sp'
        (parallel/context_serving.py). The handle's result is the
        sampled first token."""
        if self.bridge is not None:
            raise NotImplementedError(
                "context-parallel prefill over the multihost step "
                "bridge")
        if self.tracer is not None:
            self.tracer.phase("build")
        chunk = plan.chunks[0]
        seq = chunk.seq
        n = len(chunk.chunk_tokens)
        sp = self._sp_size
        # Pow2 T bucket, padded to an sp multiple, so the compiled
        # shape set stays small.
        t = 16
        while t < n:
            t *= 2
        t += (-t) % sp

        tokens = np.zeros((1, t), np.int32)
        valid = np.zeros((1, t), bool)
        tokens[0, :n] = chunk.chunk_tokens
        valid[0, :n] = True
        sp_params = seq.sampling
        opt = {}
        opt.update(self._penalty_payload([seq], 1))
        opt.update(self._seed_payload([seq], 1))
        opt.update(self._bias_payload([seq], 1))
        opt.update(self._suppress_payload([seq], 1))
        opt.update(self._guided_payload([seq], 1))
        penalties, seeding, bias, suppress, fsm = \
            self._optional_device_inputs(opt)
        want_lp = sp_params.logprobs
        lora_ids = (None if self.lora_registry is None
                    else jnp.asarray(
                        np.asarray([seq.lora_id], np.int32)))
        if self.tracer is not None:
            self.tracer.phase("dispatch")
        sampled, self.k_cache, self.v_cache = self._sp_prefill_jit(
            self.params, self.k_cache, self.v_cache,
            jnp.asarray(tokens),
            jnp.asarray(self._page_table_rows([seq])),
            jnp.asarray(valid),
            jnp.asarray(np.asarray([n - 1], np.int32)),
            jnp.asarray(np.asarray([sp_params.temperature],
                                   np.float32)),
            jnp.asarray(np.asarray([sp_params.top_p], np.float32)),
            jnp.asarray(np.asarray([sp_params.top_k], np.int32)),
            self._next_rng(), self._lora_stack, lora_ids,
            penalties, seeding, bias, suppress, fsm,
            want_logprobs=want_lp,
        )

        def parse(host):
            if want_lp:
                toks, slp, tids, tlps = host
                return ([int(toks[0])],
                        [self._lp_entry(seq, slp[0], tids[0], tlps[0])])
            return [int(host[0])], None

        return StepHandle(self, sampled, parse)

    def _other_width_payloads(self, b: int, t: int) -> List[dict]:
        """The top bucket has two widths and a smoke request or a
        benchmark's warm prompt reaches one: the first step there
        brings up the other in the same turn (run_prefill), so neither
        compiles under load. Its step is all pad rows: nothing valid,
        the trash page and state slot, temperature 0, and a constant
        key, so the serving key stream, the live pages and the slots
        stay as they were. It takes the plain form of the program; a
        form that an option of a request adds compiles when one asks,
        as at every shape."""
        payloads = []
        for rows, tokens in prefill_shapes(self.prefill_width, t):
            if tokens != t or rows == b:
                continue
            payload = {
                "tokens": np.zeros((rows, t), np.int32),
                "positions": np.zeros((rows, t), np.int32),
                "valid": np.zeros((rows, t), bool),
                "page_table": self._page_table_rows([], pad_to=rows),
                "kv_lens": np.zeros((rows,), np.int32),
                "last_index": np.zeros((rows,), np.int32),
                "temperature": np.zeros((rows,), np.float32),
                "top_p": np.ones((rows,), np.float32),
                "top_k": np.zeros((rows,), np.int32),
                "rng": np.zeros((2,), np.uint32),
            }
            if self._hybrid:
                payload["state_slots"] = self._state_slot_rows([], rows)
            if self.lora_registry is not None:
                payload["lora_ids"] = np.zeros((rows,), np.int32)
            if self._drafts:
                payload["next_tokens"] = np.full((rows,), -1, np.int32)
            payloads.append(payload)
        return payloads

    def run_prefill(self, plan: PrefillPlan
                    ) -> Tuple[List[Optional[int]], Optional[list]]:
        """One prefill step run to its end: dispatch + immediate
        readback."""
        return self.dispatch_prefill(plan).result()

    def dispatch_prefill(self, plan: PrefillPlan) -> StepHandle:
        """Build and dispatch one batched prefill step (the next chunk
        of up to ``prefill_batch_size`` distinct sequences, rows padded
        to the fixed width) with no blocking host read on the path.
        The handle's result is (tokens, logprobs): one sampled token
        per chunk — None for rows whose prompt is not yet fully
        prefilled — and, when any sampling row requested logprobs, a
        parallel list of per-row logprob entries (else None)."""
        if plan.sp:
            return self.dispatch_sp_prefill(plan)
        if self.tracer is not None:
            self.tracer.phase("build")
        chunks = plan.chunks
        b, t = prefill_shape(
            len(chunks), max(len(c.chunk_tokens) for c in chunks),
            self.prefill_width, self._buckets[-1])
        self.last_prefill_width = b
        if b < self.prefill_width:
            self.num_narrow_prefill_steps += 1

        tokens = np.zeros((b, t), np.int32)
        positions = np.zeros((b, t), np.int32)
        valid = np.zeros((b, t), bool)
        kv_lens = np.zeros((b,), np.int32)
        last_index = np.zeros((b,), np.int32)
        # Pad rows stay temperature 0 (see run_decode).
        temperature = np.zeros((b,), np.float32)
        top_p = np.ones((b,), np.float32)
        top_k = np.zeros((b,), np.int32)

        for i, chunk in enumerate(chunks):
            n = len(chunk.chunk_tokens)
            tokens[i, :n] = chunk.chunk_tokens
            positions[i, :n] = np.arange(
                chunk.chunk_start, chunk.chunk_start + n
            )
            valid[i, :n] = True
            kv_lens[i] = chunk.chunk_start + n
            last_index[i] = n - 1
            sp = chunk.seq.sampling
            temperature[i] = sp.temperature
            top_p[i] = sp.top_p
            top_k[i] = sp.top_k

        payload = {
            "tokens": tokens,
            "positions": positions,
            "valid": valid,
            "page_table": self._page_table_rows(
                [c.seq for c in chunks], pad_to=b),
            "kv_lens": kv_lens,
            "last_index": last_index,
            "temperature": temperature,
            "top_p": top_p,
            "top_k": top_k,
            "rng": self._next_rng(),
        }
        if self._hybrid:
            payload["state_slots"] = self._state_slot_rows(
                [c.seq for c in chunks], b)
        if self._drafts:
            # The draft module reads the token AFTER each position: a
            # mid-prompt chunk's last position takes the next chunk's
            # first id, a prompt's last the token the step samples (-1).
            after = np.full((b,), -1, np.int32)
            for i, chunk in enumerate(chunks):
                if not chunk.is_last_chunk:
                    after[i] = chunk.seq.prompt_token_ids[
                        chunk.chunk_start + len(chunk.chunk_tokens)]
            payload["next_tokens"] = after
        if self.lora_registry is not None:
            ids = np.zeros((b,), np.int32)
            for i, chunk in enumerate(chunks):
                ids[i] = chunk.seq.lora_id
            payload["lora_ids"] = ids
        # Only rows whose LAST chunk is in this dispatch keep their
        # sampled token; mid-prompt chunks skip the [B, vocab] penalty
        # transfer and the penalized program entirely.
        sampling_rows = [c.seq if c.is_last_chunk and not self._block
                         else None for c in chunks]
        payload.update(self._penalty_payload(sampling_rows, b))
        payload.update(self._seed_payload(sampling_rows, b))
        payload.update(self._bias_payload(sampling_rows, b))
        payload.update(self._suppress_payload(sampling_rows, b))
        payload.update(self._guided_payload(sampling_rows, b))
        want_lp = any(s is not None and s.sampling.logprobs
                      for s in sampling_rows)
        if want_lp:
            payload["want_logprobs"] = True

        others = []
        if t == self._buckets[-1] and not self._top_bucket_warm:
            # The other width's program loads while this step's does.
            self._top_bucket_warm = True
            others = [(p, self._load_step_program(p))
                      for p in self._other_width_payloads(b, t)]
        sampled = self._dispatch(1, t, payload)
        for other, loading in others:
            loading.join()
            # Through _dispatch, so a multihost worker follows.
            self._dispatch(1, t, other)

        def parse(host):
            out: List[Optional[int]] = []
            lps: List[Optional[tuple]] = []
            for i, chunk in enumerate(chunks):
                if not chunk.is_last_chunk:
                    out.append(None)
                    lps.append(None)
                elif want_lp:
                    out.append(int(host[0][i]))
                    lps.append(
                        self._lp_entry(chunk.seq, host[1][i],
                                       host[2][i], host[3][i])
                        if chunk.seq.sampling.logprobs else None)
                else:
                    out.append(int(host[i]))
                    lps.append(None)
            return out, (lps if want_lp else None)

        sampling = any(row is not None for row in sampling_rows)
        if not sampling:
            # Mid-prompt chunks alone, or a family whose prefill yields
            # no token: nothing to read.
            return StepHandle(
                self, None, lambda host: ([None] * len(chunks), None))
        return StepHandle(self, sampled, parse)

    # ---- decode -----------------------------------------------------------

    def _staging_set(self) -> dict:
        """Next reusable host staging buffer set (double-buffered; see
        __init__). Arrays are zero-reset here so None/pad rows are
        masked (valid False) and read the trash page (table 0)."""
        if self._decode_staging is None:
            b, p = self.decode_width, self.max_pages_per_seq

            def one():
                buf = {
                    # [B] not [B, 1]: the step program reshapes on
                    # device, so an ahead dispatch can feed the
                    # previous step's [B] sampled array directly.
                    "tokens": np.zeros((b,), np.int32),
                    "positions": np.zeros((b, 1), np.int32),
                    "valid": np.zeros((b, 1), bool),
                    "page_table": np.zeros((b, p), np.int32),
                    "kv_lens": np.zeros((b,), np.int32),
                    "last_index": np.zeros((b,), np.int32),
                    "temperature": np.zeros((b,), np.float32),
                    "top_p": np.ones((b,), np.float32),
                    "top_k": np.zeros((b,), np.int32),
                }
                if self.lora_registry is not None:
                    buf["lora_ids"] = np.zeros((b,), np.int32)
                if self._hybrid:
                    buf["state_slots"] = np.zeros((b,), np.int32)
                return buf

            self._decode_staging = (one(), one())
        st = self._decode_staging[self._staging_idx]
        self._staging_idx ^= 1
        for name, arr in st.items():
            arr.fill(1 if name == "top_p" else 0)
        return st

    def dispatch_decode(self, rows, token_source=None,
                        ahead: bool = False) -> DecodeStepHandle:
        """Build and dispatch ONE single-step decode program with no
        blocking host read anywhere on the path (the AST lint
        tests/test_dispatch_path_lint.py enforces this statically).

        The synchronous engine uses it too (run_decode's single-step
        path), so sync and async greedy decoding share one dispatch
        path and byte-exact parity is structural, not incidental.

        ``rows``: the batch's sequences; None entries (plan-ahead
        slots whose row is already known to finish) dispatch as
        masked pad rows so the batch shape — and row alignment with
        ``token_source`` — never changes. ``token_source``: the
        previous step's sampled-token device array ([B]); when given,
        this step's input tokens never touch the host. ``ahead``
        shifts positions/kv_lens by the one token the in-flight step
        will have committed by the time this program's inputs are
        consumed.
        """
        if self.bridge is not None:
            raise NotImplementedError(
                "async dispatch over the multihost step bridge (the "
                "step broadcast ships host-resident numpy payloads)")
        b = self.decode_width
        rows = list(rows)[:b]
        if self.tracer is not None:
            self.tracer.phase("build")
        st = self._staging_set()
        off = 1 if ahead else 0
        page_table = st["page_table"]
        # During a pure-decode stretch only positions/kv_lens (+1 per
        # step) and the input tokens actually change; the per-row
        # static inputs (valid mask, page table, sampling knobs, lora
        # ids) are reused as the *device arrays* of the previous
        # dispatch while this signature — row identity, liveness
        # pattern, and exact page list — is unchanged. Sampling params
        # and lora ids are immutable after admission, so they need no
        # signature term beyond the seq id.
        sig = tuple((seq.seq_id, tuple(seq.pages))
                    if seq is not None else None for seq in rows)
        cached = self._decode_static_cache
        reuse = cached is not None and cached[0] == sig
        for i, seq in enumerate(rows):
            if seq is None:
                continue
            if token_source is None:
                st["tokens"][i] = (seq.output_token_ids[-1]
                                   if seq.output_token_ids
                                   else seq.prompt_token_ids[-1])
            st["positions"][i, 0] = seq.total_len - 1 + off
            st["kv_lens"][i] = seq.total_len + off
            if reuse:
                continue
            sp = seq.sampling
            st["valid"][i, 0] = True
            st["temperature"][i] = sp.temperature
            st["top_p"][i] = sp.top_p
            st["top_k"][i] = sp.top_k
            n = min(len(seq.pages), self.max_pages_per_seq)
            page_table[i, :n] = seq.pages[:n]
            if self.lora_registry is not None:
                st["lora_ids"][i] = seq.lora_id
            if self._hybrid:
                st["state_slots"][i] = seq.state_slot or 0
        # ONE fused host->device transfer for the (changed part of
        # the) input set — replaces the per-array jnp.asarray shower.
        # An ahead dispatch additionally excludes the tokens buffer:
        # its tokens are the previous step's sampled [B] int32 device
        # array, consumed verbatim — no transfer, no eager
        # cast/reshape (the step program reshapes on device).
        dynamic = ("positions", "kv_lens") + (
            ("tokens",) if token_source is None else ())
        names = (dynamic if reuse else
                 tuple(n for n in st
                       if token_source is None or n != "tokens"))
        # Static entries are snapshotted (.copy()): on the CPU
        # backend device_put of a numpy array may be ZERO-copy, and
        # the cached device arrays must not alias a staging buffer
        # that later steps zero-reset and refill.
        # The key rides the same transfer.
        devs = jax.device_put(tuple(
            st[n] if n in dynamic else st[n].copy() for n in names)
            + (self._next_rng(),))
        payload = dict(zip(names + ("rng",), devs))
        if reuse:
            payload.update(cached[1])
        else:
            self._decode_static_cache = (sig, {
                n: payload[n] for n in payload
                if n not in ("tokens", "positions", "kv_lens", "rng")})
        if token_source is not None:
            payload["tokens"] = token_source
        if not ahead:
            # Per-row optional inputs (penalties/seed/bias/suppress/
            # guided) for the sync single-step path. Plan-ahead
            # eligibility guarantees these are all {} for ahead
            # dispatches (their host state is one token stale), so
            # those skip the five row scans outright.
            payload.update(self._penalty_payload(rows, b))
            payload.update(self._seed_payload(rows, b))
            payload.update(self._bias_payload(rows, b))
            payload.update(self._suppress_payload(rows, b))
            payload.update(self._guided_payload(rows, b))
        want_lp = any(s is not None and s.sampling.logprobs
                      for s in rows)
        if want_lp:
            payload["want_logprobs"] = True
        self._note_attn_pages(st["kv_lens"])
        sampled = self._dispatch(2, 1, payload)
        return DecodeStepHandle(self, rows, sampled, want_lp)

    def run_decode(self, plan: DecodePlan
                   ) -> Tuple[List[List[int]], Optional[list]]:
        """One decode program run to its end: dispatch + immediate
        readback."""
        return self.dispatch_decode_plan(plan).result()

    def dispatch_decode_plan(self, plan: DecodePlan):
        """Dispatch the one program ``plan`` asks for — a verify step,
        a single step or a burst — and return its handle, whose result
        is (token_lists, logprob_lists); logprob_lists is None unless a
        row requested logprobs."""
        if plan.drafts is not None:
            return self.dispatch_spec(plan)
        if self._block:
            return self.dispatch_burst(plan)
        if max(1, plan.window) == 1 and self.bridge is None:
            # Single-host single-step decode rides the async
            # pipeline's dispatch path (staged inputs, one fused
            # transfer, one fused device_get) even in sync mode, so
            # sync-vs-async parity is the same code path.
            return self.dispatch_decode(plan.seqs[: self.decode_width])
        return self.dispatch_burst(plan)

    def dispatch_burst(self, plan: DecodePlan) -> StepHandle:
        """Build and dispatch one decode burst over all running
        sequences (padded batch) with no blocking host read on the
        path. The burst program evaluates per-row budgets and stop
        sets on device, so one dispatch + one device_get covers up to
        ``window`` tokens per row even when rows finish mid-burst (a
        slot reads -1 where a row committed nothing). Over the
        multihost bridge a window of 1 comes this way too."""
        seqs = plan.seqs[: self.decode_width]
        b = self.decode_width
        window = max(1, plan.window)
        stop_w = STOP_SET_WIDTH
        if self.tracer is not None:
            self.tracer.phase("build")

        block = self._block
        tokens = np.zeros((b, block or 1), np.int32)
        positions = np.zeros((b, 1), np.int32)
        valid = np.zeros((b, 1), bool)
        kv_lens = np.zeros((b,), np.int32)
        budgets = np.zeros((b,), np.int32)
        stop_tokens = np.full((b, stop_w), -1, np.int32)
        # A block-diffusion row (BLOCK_ROW_INPUTS): a pad row takes one
        # pass a block.
        block_rows = (np.zeros((b,), np.int32), np.ones((b,), np.int32),
                      np.zeros((b,), np.int32), np.ones((b,), np.float32))
        # Pad rows stay temperature 0 so an all-greedy batch keeps the
        # sampler's sort-free fast path (ops/sampling.py).
        temperature = np.zeros((b,), np.float32)
        top_p = np.ones((b,), np.float32)
        top_k = np.zeros((b,), np.int32)

        for i, seq in enumerate(seqs):
            if block:
                # The row's next block: the tokens past its last whole
                # block are the block's given places.
                start = block_start(seq, block)
                rest = seq.all_token_ids[start:]
                tokens[i, :len(rest)] = rest
                positions[i, 0] = kv_lens[i] = start
                sp = seq.sampling
                for column, value in zip(block_rows, (
                        len(rest), sp.denoising_steps,
                        REMASKING_STRATEGIES.index(sp.remasking_strategy),
                        sp.confidence_threshold)):
                    column[i] = value
            else:
                tokens[i, 0] = (seq.output_token_ids[-1]
                                if seq.output_token_ids
                                else seq.prompt_token_ids[-1])
                positions[i, 0] = seq.total_len - 1
                kv_lens[i] = seq.total_len
            valid[i, 0] = True
            budgets[i] = decode_budget(
                seq, self.config.scheduler.max_model_len)
            if not seq.sampling.ignore_eos:
                ids = seq.sampling.stop_token_ids[:stop_w]
                stop_tokens[i, : len(ids)] = ids
            temperature[i] = seq.sampling.temperature
            top_p[i] = seq.sampling.top_p
            top_k[i] = seq.sampling.top_k

        payload = {
            "tokens": tokens,
            "positions": positions,
            "valid": valid,
            "page_table": self._page_table_rows(seqs, pad_to=b),
            "kv_lens": kv_lens,
            "last_index": np.zeros((b,), np.int32),
            "temperature": temperature,
            "top_p": top_p,
            "top_k": top_k,
            "rng": self._next_rng(),
        }
        if self._hybrid:
            payload["state_slots"] = self._state_slot_rows(seqs, b)
        if window > 1:
            payload["active"] = valid[:, 0].copy()
            payload["budgets"] = budgets
            payload["stop_tokens"] = stop_tokens
        if block:
            payload.update(zip(BLOCK_ROW_INPUTS, block_rows))
        if self._drafts:
            # Rows the draft module proposes for: those whose logits
            # are sampled as the model gives them (the exclusion set
            # of scheduler._plan_spec); the others commit one token
            # an iteration in the same program.
            drafting = np.zeros((b,), bool)
            for i, seq in enumerate(seqs):
                drafting[i] = not draftless(seq)
            payload["draft_rows"] = drafting
        if self.lora_registry is not None:
            ids = np.zeros((b,), np.int32)
            for i, seq in enumerate(seqs):
                ids[i] = seq.lora_id
            payload["lora_ids"] = ids
        payload.update(self._penalty_payload(seqs, b))
        payload.update(self._seed_payload(seqs, b))
        payload.update(self._bias_payload(seqs, b))
        payload.update(self._suppress_payload(seqs, b))
        payload.update(self._guided_payload(seqs, b))
        want_lp = any(s.sampling.logprobs for s in seqs)
        if want_lp:
            payload["want_logprobs"] = True

        # A deferred burst's pages hold the tokens before its first
        # (positions), frozen through the burst; an eager one starts
        # at kv_lens and may take more blocks as its rows grow.
        self._note_attn_pages(positions if self._deferred and window > 1
                              else kv_lens)
        sampled = self._dispatch(2, window, payload)

        def parse(host):
            if not want_lp:
                if window == 1:
                    return [[int(host[i])]
                            for i in range(len(seqs))], None
                # A burst's slots: ``window``, or twice that where it
                # drafts; -1 where a row committed nothing.
                return [[int(tok) for tok in host[:, i] if tok >= 0]
                        for i in range(len(seqs))], None
            toks, slp, tids, tlps = host
            if window == 1:
                return ([[int(toks[i])] for i in range(len(seqs))],
                        [[self._lp_entry(seqs[i], slp[i], tids[i],
                                         tlps[i])
                          if seqs[i].sampling.logprobs else None]
                         for i in range(len(seqs))])
            token_lists, lp_lists = [], []
            for i, seq in enumerate(seqs):
                row_t, row_l = [], []
                for k in range(toks.shape[0]):
                    if toks[k, i] < 0:
                        continue
                    row_t.append(int(toks[k, i]))
                    row_l.append(
                        self._lp_entry(seq, slp[k, i], tids[k, i],
                                       tlps[k, i])
                        if seq.sampling.logprobs else None)
                token_lists.append(row_t)
                lp_lists.append(row_l)
            return token_lists, lp_lists

        return StepHandle(self, sampled, parse)

    def dispatch_spec(self, plan: DecodePlan) -> SpecStepHandle:
        """Build and dispatch ONE speculative verify step with no
        blocking host read on the path (docs/speculative.md).

        Every running row rides the same fixed [B, S] program: rows
        with a draft verify it, rows without (draft_len 0) decode
        exactly one token through the identical shape — occupancy and
        acceptance counts never change the compiled program. The
        handle's ``result()`` parses each row's accepted prefix plus
        the bonus/resample token (1..S tokens, order-correct); its
        ``token_source`` lets the async pipeline chain an
        assume-one-token successor before the readback. The scheduler
        guarantees row eligibility and that pages cover
        total_len + draft_len.
        """
        from production_stack_tpu.parallel.distributed import KIND_SPEC
        if self.tracer is not None:
            self.tracer.phase("build")
        seqs = plan.seqs[: self.decode_width]
        b = self.decode_width
        s = self.spec_width

        tokens = np.zeros((b, s), np.int32)
        positions = np.zeros((b, s), np.int32)
        valid = np.zeros((b, s), bool)
        kv_lens = np.zeros((b,), np.int32)
        drafts = np.full((b, s - 1), -1, np.int32)
        draft_lens = np.zeros((b,), np.int32)
        # Pad rows stay temperature 0 so an all-greedy batch keeps the
        # verify rule's argmax-only fast path (ops/sampling.py).
        temperature = np.zeros((b,), np.float32)
        top_p = np.ones((b,), np.float32)
        top_k = np.zeros((b,), np.int32)

        for i, seq in enumerate(seqs):
            d = plan.drafts[i]
            n = 1 + len(d)
            tokens[i, 0] = (seq.output_token_ids[-1]
                           if seq.output_token_ids
                           else seq.prompt_token_ids[-1])
            tokens[i, 1:n] = d
            positions[i, :n] = np.arange(seq.total_len - 1,
                                         seq.total_len - 1 + n)
            valid[i, :n] = True
            kv_lens[i] = seq.total_len + len(d)
            drafts[i, :len(d)] = d
            draft_lens[i] = len(d)
            temperature[i] = seq.sampling.temperature
            top_p[i] = seq.sampling.top_p
            top_k[i] = seq.sampling.top_k

        payload = {
            "tokens": tokens,
            "positions": positions,
            "valid": valid,
            "page_table": self._page_table_rows(seqs, pad_to=b),
            "kv_lens": kv_lens,
            "last_index": np.zeros((b,), np.int32),
            "temperature": temperature,
            "top_p": top_p,
            "top_k": top_k,
            "rng": self._next_rng(),
            "drafts": drafts,
            "draft_lens": draft_lens,
        }
        if self.lora_registry is not None:
            ids = np.zeros((b,), np.int32)
            for i, seq in enumerate(seqs):
                ids[i] = seq.lora_id
            payload["lora_ids"] = ids
        want_lp = any(q.sampling.logprobs for q in seqs)
        if want_lp:
            payload["want_logprobs"] = True

        self._note_attn_pages(kv_lens, "prefill")
        sampled = self._dispatch(KIND_SPEC, s, payload)
        return SpecStepHandle(
            self, list(seqs),
            [list(plan.drafts[i]) for i in range(len(seqs))],
            sampled, want_lp)

    # ---- unified ragged step (docs/unified_step.md) -----------------------

    def run_unified(self, plan):
        """One unified step run to its end: dispatch + immediate
        readback."""
        return self.dispatch_unified(plan).result()

    def dispatch_unified(self, plan) -> StepHandle:
        """Build and dispatch one genuinely mixed step: decode/draft
        rows and prefill chunk rows in ONE fixed-shape [R, W] ragged
        program, with no blocking host read on the path.

        Row layout (the per-row descriptor is the
        kv_lens/last_index/draft_lens triple — docs/unified_step.md):
        compact — decode rows at 0..len(seqs)-1 (aligned with
        plan.decode.seqs), prefill chunk rows immediately after
        (aligned with plan.prefill.chunks), pads only at the tail.
        R snaps to the closed ``unified_row_buckets`` lattice so the
        compiled shape depends on occupancy only through the (row
        bucket, W bucket) pair, never on batch composition. The
        handle's result is
        (decode_token_lists, decode_lp_lists, prefill_tokens,
        prefill_lp_rows): decode rows commit 1..span tokens (the
        verify contract), prefill rows one sampled token for last
        chunks (None mid-prompt).
        """
        from production_stack_tpu.parallel.distributed import (
            KIND_UNIFIED,
        )
        if self.tracer is not None:
            self.tracer.phase("build")
        seqs = plan.decode.seqs[: self.decode_width]
        chunks = plan.prefill.chunks[: self.prefill_width]
        spec_drafts = plan.decode.drafts
        off = len(seqs)
        r = self._row_bucket_for(off + len(chunks))
        self.last_unified_rows = r
        s = self.unified_span
        w = max(self._bucket_for(
            max(len(c.chunk_tokens) for c in chunks)), s)

        tokens = np.zeros((r, w), np.int32)
        positions = np.zeros((r, w), np.int32)
        valid = np.zeros((r, w), bool)
        kv_lens = np.zeros((r,), np.int32)
        last_index = np.zeros((r,), np.int32)
        drafts = np.full((r, s - 1), -1, np.int32)
        draft_lens = np.zeros((r,), np.int32)
        # Pad rows stay temperature 0 so an all-greedy batch keeps
        # the verify rule's argmax-only fast path (ops/sampling.py).
        temperature = np.zeros((r,), np.float32)
        top_p = np.ones((r,), np.float32)
        top_k = np.zeros((r,), np.int32)
        page_table = np.zeros((r, self.max_pages_per_seq), np.int32)
        lora_ids = (np.zeros((r,), np.int32)
                    if self.lora_registry is not None else None)

        def _row_static(i, seq):
            temperature[i] = seq.sampling.temperature
            top_p[i] = seq.sampling.top_p
            top_k[i] = seq.sampling.top_k
            n = min(len(seq.pages), self.max_pages_per_seq)
            page_table[i, :n] = seq.pages[:n]
            if lora_ids is not None:
                lora_ids[i] = seq.lora_id

        for i, seq in enumerate(seqs):
            d = (spec_drafts[i] if spec_drafts is not None else ())
            n = 1 + len(d)
            tokens[i, 0] = (seq.output_token_ids[-1]
                            if seq.output_token_ids
                            else seq.prompt_token_ids[-1])
            tokens[i, 1:n] = d
            positions[i, :n] = np.arange(seq.total_len - 1,
                                         seq.total_len - 1 + n)
            valid[i, :n] = True
            kv_lens[i] = seq.total_len + len(d)
            last_index[i] = n - 1
            drafts[i, :len(d)] = d
            draft_lens[i] = len(d)
            _row_static(i, seq)

        for j, chunk in enumerate(chunks):
            i = off + j
            n = len(chunk.chunk_tokens)
            tokens[i, :n] = chunk.chunk_tokens
            positions[i, :n] = np.arange(chunk.chunk_start,
                                         chunk.chunk_start + n)
            valid[i, :n] = True
            kv_lens[i] = chunk.chunk_start + n
            last_index[i] = n - 1
            _row_static(i, chunk.seq)

        payload = {
            "tokens": tokens,
            "positions": positions,
            "valid": valid,
            "page_table": page_table,
            "kv_lens": kv_lens,
            "last_index": last_index,
            "drafts": drafts,
            "draft_lens": draft_lens,
            "temperature": temperature,
            "top_p": top_p,
            "top_k": top_k,
            "rng": self._next_rng(),
        }
        if lora_ids is not None:
            payload["lora_ids"] = lora_ids
        sampling_rows = (list(seqs)
                         + [c.seq for c in chunks if c.is_last_chunk])
        want_lp = any(q.sampling.logprobs for q in sampling_rows)
        if want_lp:
            payload["want_logprobs"] = True

        sampled = self._dispatch(KIND_UNIFIED, w, payload)

        def parse(host):
            if want_lp:
                toks, slp, tids, tlps = host
            else:
                toks = host
            token_lists, lp_lists = [], []
            for i, seq in enumerate(seqs):
                row_t, row_l = [], []
                for j in range(s):
                    if toks[i, j] < 0:
                        break
                    row_t.append(int(toks[i, j]))
                    if want_lp:
                        row_l.append(
                            self._lp_entry(seq, slp[i, j], tids[i, j],
                                           tlps[i, j])
                            if seq.sampling.logprobs else None)
                token_lists.append(row_t)
                lp_lists.append(row_l)
            prefill_out, prefill_lps = [], []
            for j, chunk in enumerate(chunks):
                i = off + j
                if not chunk.is_last_chunk:
                    prefill_out.append(None)
                    prefill_lps.append(None)
                    continue
                prefill_out.append(int(toks[i, 0]))
                prefill_lps.append(
                    self._lp_entry(chunk.seq, slp[i, 0], tids[i, 0],
                                   tlps[i, 0])
                    if want_lp and chunk.seq.sampling.logprobs
                    else None)
            return (token_lists, lp_lists if want_lp else None,
                    prefill_out, prefill_lps if want_lp else None)

        return StepHandle(self, sampled, parse)

    # ---- page-granular IO (offload tiers) ---------------------------------

    def read_page(self, page_id: int) -> Tuple[np.ndarray, ...]:
        """Copy one page's KV out of HBM: [L, kv, d, page_size] each.

        The offload serde page format is layer-stacked regardless of
        the HBM layout, so tiers and the remote cache server stay
        layout-agnostic.  Quantized caches return a 4-tuple
        (k, v, k_scale, v_scale) with [L, kv, page_size] scales.
        """
        if self.kv_quantized:
            if self.cache_layout == "per_layer":
                k = np.stack(jax.device_get(
                    [kc.data[:, page_id] for kc in self.k_cache]))
                v = np.stack(jax.device_get(
                    [vc.data[:, page_id] for vc in self.v_cache]))
                ks = np.stack(jax.device_get(
                    [kc.scale[:, page_id] for kc in self.k_cache]))
                vs = np.stack(jax.device_get(
                    [vc.scale[:, page_id] for vc in self.v_cache]))
                return k, v, ks, vs
            k = jax.device_get(self.k_cache.data[:, :, page_id])
            v = jax.device_get(self.v_cache.data[:, :, page_id])
            ks = jax.device_get(self.k_cache.scale[:, :, page_id])
            vs = jax.device_get(self.v_cache.scale[:, :, page_id])
            return k, v, ks, vs
        if self.cache_layout == "per_layer":
            k = np.stack(jax.device_get(
                [kc[:, page_id] for kc in self.k_cache]))
            v = np.stack(jax.device_get(
                [vc[:, page_id] for vc in self.v_cache]))
            return k, v
        k = jax.device_get(self.k_cache[:, :, page_id])
        v = jax.device_get(self.v_cache[:, :, page_id])
        return k, v

    def write_page(self, page_id: int, k_page: np.ndarray,
                   v_page: np.ndarray,
                   k_scale: Optional[np.ndarray] = None,
                   v_scale: Optional[np.ndarray] = None) -> None:
        """Restore one page's KV into HBM (donated in-place update)."""
        if self.kv_quantized:
            self._write_page_quantized(page_id, k_page, v_page,
                                       k_scale, v_scale)
            return
        if not hasattr(self, "_write_page_jit"):
            self._write_page_jit = jax.jit(
                lambda cache, page, pid:
                    cache.at[:, :, pid].set(page.astype(cache.dtype)),
                donate_argnums=(0,),
            )
            self._write_layer_page_jit = jax.jit(
                lambda cache, page, pid:
                    cache.at[:, pid].set(page.astype(cache.dtype)),
                donate_argnums=(0,),
            )
        if self.cache_layout == "per_layer":
            self.k_cache = tuple(
                self._write_layer_page_jit(
                    kc, jnp.asarray(k_page[layer]), page_id)
                for layer, kc in enumerate(self.k_cache))
            self.v_cache = tuple(
                self._write_layer_page_jit(
                    vc, jnp.asarray(v_page[layer]), page_id)
                for layer, vc in enumerate(self.v_cache))
            return
        self.k_cache = self._write_page_jit(
            self.k_cache, jnp.asarray(k_page), page_id
        )
        self.v_cache = self._write_page_jit(
            self.v_cache, jnp.asarray(v_page), page_id
        )

    def _write_page_quantized(self, page_id: int, k_page: np.ndarray,
                              v_page: np.ndarray, k_scale: np.ndarray,
                              v_scale: np.ndarray) -> None:
        if k_scale is None or v_scale is None:
            raise ValueError(
                "quantized cache restore requires k_scale/v_scale")
        if not hasattr(self, "_write_page_q_jit"):
            self._write_page_q_jit = jax.jit(
                lambda cache, page, scale, pid: QuantKV(
                    cache.data.at[:, :, pid].set(
                        page.astype(jnp.int8)),
                    cache.scale.at[:, :, pid].set(
                        scale.astype(jnp.float32))),
                donate_argnums=(0,),
            )
            self._write_layer_page_q_jit = jax.jit(
                lambda cache, page, scale, pid: QuantKV(
                    cache.data.at[:, pid].set(
                        page.astype(jnp.int8)),
                    cache.scale.at[:, pid].set(
                        scale.astype(jnp.float32))),
                donate_argnums=(0,),
            )
        if self.cache_layout == "per_layer":
            self.k_cache = tuple(
                self._write_layer_page_q_jit(
                    kc, jnp.asarray(k_page[layer]),
                    jnp.asarray(k_scale[layer]), page_id)
                for layer, kc in enumerate(self.k_cache))
            self.v_cache = tuple(
                self._write_layer_page_q_jit(
                    vc, jnp.asarray(v_page[layer]),
                    jnp.asarray(v_scale[layer]), page_id)
                for layer, vc in enumerate(self.v_cache))
            return
        self.k_cache = self._write_page_q_jit(
            self.k_cache, jnp.asarray(k_page), jnp.asarray(k_scale),
            page_id)
        self.v_cache = self._write_page_q_jit(
            self.v_cache, jnp.asarray(v_page), jnp.asarray(v_scale),
            page_id)

    def _note_attn_pages(self, kv_lens: np.ndarray,
                         phase: str = "decode") -> None:
        """``last_attn_pages`` for a decode dispatch whose pages hold
        ``kv_lens`` tokens a row; ``phase`` names the attention that
        serves its shape (a verify step's T > 1 is prefill's)."""
        m = self.config.model
        if (getattr(m, f"attention_impl_{phase}")
                or m.attention_impl) != "xla":
            self.last_attn_pages = None
            return
        page, most = self.config.cache.page_size, self.max_pages_per_seq
        self.last_attn_pages = min(most, block_pages(most, page)
                                   * gathered_blocks(int(kv_lens.max()),
                                                     most, page))

    def _page_table_rows(self, seqs: List[Sequence],
                         pad_to: Optional[int] = None) -> np.ndarray:
        rows = pad_to or len(seqs)
        table = np.zeros((rows, self.max_pages_per_seq), np.int32)
        for i, seq in enumerate(seqs):
            n = min(len(seq.pages), self.max_pages_per_seq)
            table[i, :n] = seq.pages[:n]
        return table
