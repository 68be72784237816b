"""Engine /metrics scraper.

Capability parity with reference src/vllm_router/stats/engine_stats.py:
a daemon thread polls every serving engine's Prometheus ``/metrics``
endpoint and keeps the latest physical-load numbers per engine URL.

Metric names are the vLLM exposition names, which our TPU engine also
emits (engine/metrics.py), so the router works against either backend.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

import requests
from prometheus_client.parser import text_string_to_metric_families

from production_stack_tpu.utils import SingletonMeta
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)

_SCRAPE_TIMEOUT_S = 5.0

# Exposition name -> EngineStats attribute. Counter samples keep
# their rendered ``_total`` names through the parser, so the map keys
# them as exposed.
_METRIC_MAP = {
    "vllm:num_requests_running": "num_running_requests",
    "vllm:num_requests_waiting": "num_queuing_requests",
    "vllm:gpu_prefix_cache_hit_rate": "kv_cache_hit_rate",
    "vllm:gpu_cache_usage_perc": "kv_usage_perc",
    "vllm:spec_decode_num_draft_tokens_total":
        "spec_decode_num_draft_tokens",
    "vllm:spec_decode_num_accepted_tokens_total":
        "spec_decode_num_accepted_tokens",
    "vllm:engine_step_host_seconds_total":
        "engine_step_host_seconds",
    "vllm:engine_step_device_wait_seconds_total":
        "engine_step_device_wait_seconds",
    "vllm:engine_device_idle_seconds_total":
        "engine_device_idle_seconds",
    "vllm:engine_pipeline_steps_total": "engine_pipeline_steps",
    "vllm:engine_pipeline_ahead_steps_total":
        "engine_pipeline_ahead_steps",
    "vllm:engine_async_inflight_depth": "engine_async_inflight_depth",
    # Unified ragged step occupancy (engine docs/unified_step.md):
    # per-step row split gauges plus cumulative row totals; pad ratio
    # = pad_rows / rows when rows > 0.
    "vllm:engine_step_prefill_rows": "engine_step_prefill_rows",
    "vllm:engine_step_decode_rows": "engine_step_decode_rows",
    "vllm:engine_step_pad_rows": "engine_step_pad_rows",
    "vllm:engine_ragged_steps_total": "engine_ragged_steps",
    "vllm:engine_ragged_rows_total": "engine_ragged_rows",
    "vllm:engine_ragged_pad_rows_total": "engine_ragged_pad_rows",
    # KV quantization telemetry (engine docs/kv_quantization.md):
    # post-expansion page budget and worst-case bytes written per
    # decode step. The storage dtype itself travels as a label on
    # vllm:engine_kv_cache_dtype (handled in from_prometheus_text).
    "vllm:engine_kv_cache_page_capacity":
        "engine_kv_cache_page_capacity",
    "vllm:engine_kv_bytes_per_decode_step":
        "engine_kv_bytes_per_decode_step",
    # Disaggregated serving (docs/disaggregation.md): per-role request
    # counters, KV bytes shipped over the handoff wire, and the
    # AWAITING_KV queue depth on decode-role engines.
    "vllm:disagg_prefill_requests_total": "disagg_prefill_requests",
    "vllm:disagg_decode_requests_total": "disagg_decode_requests",
    "vllm:disagg_kv_bytes_shipped_total": "disagg_kv_bytes_shipped",
    "vllm:disagg_awaiting_kv_requests": "disagg_awaiting_kv_requests",
    # Zero-loss drain (docs/fleet.md): 1 while the engine rejects new
    # admissions and finishes its in-flight sequences.
    "vllm:engine_draining": "engine_draining",
    # Topology observability (docs/parallelism.md): which slice this
    # engine process's devices belong to; the labeled mesh-shape and
    # per-slice-liveness families are handled in from_prometheus_text.
    "vllm:engine_slice_id": "engine_slice_id",
    # Device performance observatory (docs/observability.md): the
    # unlabeled MFU gauge; the labeled compile/HBM/step-time families
    # are handled in from_prometheus_text.
    "vllm:engine_mfu": "engine_mfu",
    # KV economy (docs/kv_economy.md): summary gauges mirrored off
    # GET /kv/summary (the scraper also fetches the summary body for
    # the hot-chain hashes themselves) plus the engine-side cluster
    # cache counters.
    "vllm:kv_summary_hot_chains": "kv_summary_hot_chains",
    "vllm:kv_free_page_headroom": "kv_free_page_headroom",
    "vllm:kv_total_pages": "kv_total_pages",
    "vllm:kv_cluster_hits_total": "kv_cluster_hits",
    "vllm:kv_cluster_misses_total": "kv_cluster_misses",
    "vllm:kv_cluster_admissions_total": "kv_cluster_admissions",
    "vllm:kv_cluster_rejections_total": "kv_cluster_rejections",
    # Self-tuning (docs/autotuning.md): controllers currently allowed
    # to act on this engine; the labeled frozen/knob families are
    # handled in from_prometheus_text.
    "vllm:autotune_active_controllers": "autotune_active_controllers",
}

# Engine latency histograms the scraper summarizes: it keeps each
# one's running sum/count (exposition name -> EngineStats field
# prefix, fields ``<prefix>_sum``/``<prefix>_count``) so the router
# can re-export a mean; buckets stay with cluster Prometheus. Covers
# the handoff-admission latency and the per-phase request histograms
# (queue / prefill-compute / awaiting-KV / decode,
# docs/observability.md).
_SUMMARY_HISTS = {
    "vllm:disagg_handoff_latency_seconds": "disagg_handoff_latency",
    "vllm:request_queue_time_seconds": "request_queue_time",
    "vllm:request_prefill_time_seconds": "request_prefill_time",
    "vllm:request_awaiting_kv_time_seconds": "request_awaiting_kv_time",
    "vllm:request_decode_time_seconds": "request_decode_time",
    # Preempt-to-offload restore latency (docs/qos.md): allocate +
    # fetch_many + write_page time when a preempted victim's KV comes
    # back from the offload tier instead of being recomputed.
    "vllm:preempt_restore_latency_seconds": "preempt_restore_latency",
}

# Engine metrics the router deliberately does NOT scrape: request
# latency histograms and lifecycle counters are read by cluster
# Prometheus straight off each engine's /metrics (the router's
# per-request stats monitor computes its own latency view from live
# traffic). Listed here so the staticcheck metrics-contract analyzer
# can tell a decided drop from silent drift — a NEW engine metric
# must be added to _METRIC_MAP, _SUMMARY_HISTS, or this set.
_ROUTER_UNSCRAPED = frozenset({
    "vllm:time_to_first_token_seconds",
    "vllm:time_per_output_token_seconds",
    "vllm:e2e_request_latency_seconds",
    "vllm:prompt_tokens_total",
    "vllm:generation_tokens_total",
    "vllm:request_success_total",
    "vllm:request_failure_total",
    "vllm:num_preemptions_total",
    # Prefill steps chained before a burst (engine scheduler): says
    # a replica is admission-bound; an operator's rate.
    "vllm:engine_prefill_chained_steps_total",
    # Prefill steps run at the half width (engine model runner): how
    # often a step is at most half full; an operator's rate.
    "vllm:engine_prefill_narrow_steps_total",
    # Why a start was slow (docs/observability.md): the start by
    # span, the loads by part, the compile cache's answers. An
    # operator's question of one pod, not a routing signal.
    "vllm:engine_startup_seconds",
    "vllm:engine_compile_part_seconds_total",
    "vllm:engine_compile_cache_total",
    # Autotune decision counts are an operator/dashboard rate, not a
    # routing signal — cluster Prometheus reads them directly.
    "vllm:autotune_decisions_total",
    # Hybrid-model state pool and expert load: capacity planning and
    # the benchmark's readers, not routing signals.
    "vllm:engine_state_slots_used",
    "vllm:engine_state_slots_total",
    "vllm:engine_prefix_declined_tokens_total",
    "vllm:engine_moe_tokens_per_expert_max",
    "vllm:engine_moe_tokens_per_expert_mean",
    "vllm:engine_moe_held_choice_share",
    "vllm:engine_moe_zero_choice_share",
    "vllm:moe_room_overflow_steps_total",
    # A block-diffusion engine's forward passes by kind, blocks and
    # committed tokens (docs/block_diffusion.md): an operator's rates
    # (tokens a pass, the store passes' share, the passes that sorted
    # the vocabulary); nothing routes on them.
    "vllm:diffusion_denoise_passes_total",
    "vllm:diffusion_store_passes_total",
    "vllm:diffusion_blocks_total",
    "vllm:diffusion_committed_tokens_total",
    "vllm:diffusion_sorted_passes_total",
    # The interpreter's two threads (docs/observability.md, "Is the
    # front the wall?"): an operator's rate, not a routing signal.
    "vllm:engine_front_cpu_seconds_total",
    "vllm:engine_loop_offcpu_seconds_total",
    # The served loop's hand-overs, behind a dispatch or at once
    # (docs/async_pipeline.md, "The served loop"): likewise.
    "vllm:engine_handovers_total",
    "vllm:engine_handover_behind_share",
})


@dataclass
class EngineStats:
    num_running_requests: int = 0
    num_queuing_requests: int = 0
    kv_cache_hit_rate: float = 0.0
    kv_usage_perc: float = 0.0
    # Speculative decoding counters (engine docs/speculative.md);
    # acceptance rate = accepted / drafted when drafted > 0.
    spec_decode_num_draft_tokens: float = 0.0
    spec_decode_num_accepted_tokens: float = 0.0
    # Async execution pipeline counters (engine
    # docs/async_pipeline.md): host vs device-wait step time, device
    # idle gap, and ahead-dispatched step counts. Overlap fraction =
    # 1 - idle / host when host > 0.
    engine_step_host_seconds: float = 0.0
    engine_step_device_wait_seconds: float = 0.0
    engine_device_idle_seconds: float = 0.0
    engine_pipeline_steps: float = 0.0
    engine_pipeline_ahead_steps: float = 0.0
    engine_async_inflight_depth: float = 0.0
    # Unified ragged step occupancy (engine docs/unified_step.md):
    # last mixed dispatch's prefill/decode/pad row split and the
    # cumulative row totals behind the pad ratio.
    engine_step_prefill_rows: float = 0.0
    engine_step_decode_rows: float = 0.0
    engine_step_pad_rows: float = 0.0
    engine_ragged_steps: float = 0.0
    engine_ragged_rows: float = 0.0
    engine_ragged_pad_rows: float = 0.0
    # KV page storage (engine docs/kv_quantization.md): page budget
    # after any int8 expansion, worst-case KV write bytes per decode
    # step, and the storage dtype ("bf16"/"int8"; "" until scraped).
    engine_kv_cache_page_capacity: float = 0.0
    engine_kv_bytes_per_decode_step: float = 0.0
    engine_kv_cache_dtype: str = ""
    # Disaggregated serving (docs/disaggregation.md): role counters,
    # shipped KV volume, AWAITING_KV depth, and the handoff-latency
    # histogram's running sum/count (mean = sum / count when > 0).
    disagg_prefill_requests: float = 0.0
    disagg_decode_requests: float = 0.0
    disagg_kv_bytes_shipped: float = 0.0
    disagg_awaiting_kv_requests: float = 0.0
    disagg_handoff_latency_sum: float = 0.0
    disagg_handoff_latency_count: float = 0.0
    # Per-phase request latency histograms (docs/observability.md):
    # running sum/count per phase; mean = sum / count when count > 0.
    request_queue_time_sum: float = 0.0
    request_queue_time_count: float = 0.0
    request_prefill_time_sum: float = 0.0
    request_prefill_time_count: float = 0.0
    request_awaiting_kv_time_sum: float = 0.0
    request_awaiting_kv_time_count: float = 0.0
    request_decode_time_sum: float = 0.0
    request_decode_time_count: float = 0.0
    # Zero-loss drain (docs/fleet.md): 1 while the engine is draining.
    engine_draining: float = 0.0
    # QoS under overload (docs/qos.md): labeled counters — requests
    # shed at the engine's 429 gate per priority class
    # (vllm:qos_shed_total{class=...}), preemptions per outcome
    # (vllm:preempt_offload_total{outcome="offloaded"|"recompute"}) —
    # and the preempt-restore latency histogram's running sum/count.
    qos_shed_by_class: Dict[str, float] = field(default_factory=dict)
    preempt_offload_by_outcome: Dict[str, float] = field(
        default_factory=dict)
    preempt_restore_latency_sum: float = 0.0
    preempt_restore_latency_count: float = 0.0
    # Device performance observatory (docs/observability.md): per-kind
    # compile events/seconds (vllm:engine_compile_events_total{kind},
    # vllm:engine_compile_seconds_total{kind}), live executable-cache
    # sizes (vllm:engine_executable_cache_size{kind}), the analytic
    # HBM breakdown (vllm:engine_hbm_bytes{category}), per-kind device
    # step time (vllm:engine_step_device_seconds_total{kind}), the
    # scalar MFU gauge, and the resolved attention impl per phase
    # (vllm:engine_attention_impl{phase,impl} one-hot).
    compile_events_by_kind: Dict[str, float] = field(
        default_factory=dict)
    compile_seconds_by_kind: Dict[str, float] = field(
        default_factory=dict)
    executable_cache_size_by_kind: Dict[str, float] = field(
        default_factory=dict)
    hbm_bytes_by_category: Dict[str, float] = field(
        default_factory=dict)
    step_device_seconds_by_kind: Dict[str, float] = field(
        default_factory=dict)
    # Median recent step duration per kind
    # (vllm:engine_step_time_median_seconds{kind}) — the drift
    # sentinel's input (obs/drift.py, docs/observability.md).
    step_time_median_by_kind: Dict[str, float] = field(
        default_factory=dict)
    engine_mfu: float = 0.0
    attention_impl_by_phase: Dict[str, str] = field(
        default_factory=dict)
    # Topology observability (docs/parallelism.md): the engine's mesh
    # axis sizes (vllm:engine_mesh_shape{axis="dp|pp|sp|tp"}), the
    # slice its devices sit on (vllm:engine_slice_id), and per-slice
    # liveness from the multihost bridge
    # (vllm:engine_slice_live{slice}) — a dead host shows up here as
    # ONE slice going 0.0 while the rest of the mesh stays 1.0.
    mesh_shape_by_axis: Dict[str, float] = field(default_factory=dict)
    engine_slice_id: float = 0.0
    slice_live_by_id: Dict[str, float] = field(default_factory=dict)
    # KV economy (docs/kv_economy.md): the engine's rolling KV-state
    # summary. Gauges mirror GET /kv/summary; kv_hot_chains carries
    # the advertised chain hashes themselves (hash -> decayed hits),
    # fetched alongside /metrics by the scraper, and kv_summary_time
    # stamps when that fetch happened so KVStateAwarePolicy can bound
    # staleness. The kv_cluster_* counters are the engine's view of
    # the shared cache tier (hits/misses on fetch, admission verdicts
    # on write-through).
    kv_summary_hot_chains: float = 0.0
    kv_free_page_headroom: float = 0.0
    kv_total_pages: float = 0.0
    kv_cluster_hits: float = 0.0
    kv_cluster_misses: float = 0.0
    kv_cluster_admissions: float = 0.0
    kv_cluster_rejections: float = 0.0
    kv_hot_chains: Dict[int, float] = field(default_factory=dict)
    kv_summary_time: float = 0.0
    # Self-tuning (docs/autotuning.md): count of controllers allowed
    # to act (0 in off/shadow), latched guardrail freezes per
    # controller (vllm:autotune_frozen{controller}), and live knob
    # values (vllm:autotune_knob_value{controller}) — stacktop's
    # AUTOTUNE column and the fleet dashboard read these.
    autotune_active_controllers: float = 0.0
    autotune_frozen_by_controller: Dict[str, float] = field(
        default_factory=dict)
    autotune_knob_by_controller: Dict[str, float] = field(
        default_factory=dict)

    @classmethod
    def from_prometheus_text(cls, text: str) -> "EngineStats":
        stats = cls()
        for family in text_string_to_metric_families(text):
            for sample in family.samples:
                base, _, suffix = sample.name.rpartition("_")
                if (suffix in ("sum", "count")
                        and base in _SUMMARY_HISTS):
                    setattr(stats,
                            f"{_SUMMARY_HISTS[base]}_{suffix}",
                            sample.value)
                    continue
                if sample.name == "vllm:qos_shed_total":
                    stats.qos_shed_by_class[
                        sample.labels.get("class", "")] = sample.value
                    continue
                if sample.name == "vllm:preempt_offload_total":
                    stats.preempt_offload_by_outcome[
                        sample.labels.get("outcome", "")] = sample.value
                    continue
                if sample.name == "vllm:engine_compile_events_total":
                    stats.compile_events_by_kind[
                        sample.labels.get("kind", "")] = sample.value
                    continue
                if sample.name == "vllm:engine_compile_seconds_total":
                    stats.compile_seconds_by_kind[
                        sample.labels.get("kind", "")] = sample.value
                    continue
                if sample.name == "vllm:engine_executable_cache_size":
                    stats.executable_cache_size_by_kind[
                        sample.labels.get("kind", "")] = sample.value
                    continue
                if sample.name == "vllm:engine_hbm_bytes":
                    stats.hbm_bytes_by_category[
                        sample.labels.get("category", "")
                    ] = sample.value
                    continue
                if (sample.name
                        == "vllm:engine_step_device_seconds_total"):
                    stats.step_device_seconds_by_kind[
                        sample.labels.get("kind", "")] = sample.value
                    continue
                if (sample.name
                        == "vllm:engine_step_time_median_seconds"):
                    stats.step_time_median_by_kind[
                        sample.labels.get("kind", "")] = sample.value
                    continue
                if sample.name == "vllm:engine_mesh_shape":
                    stats.mesh_shape_by_axis[
                        sample.labels.get("axis", "")] = sample.value
                    continue
                if sample.name == "vllm:engine_slice_live":
                    stats.slice_live_by_id[
                        sample.labels.get("slice", "")] = sample.value
                    continue
                if sample.name == "vllm:autotune_frozen":
                    stats.autotune_frozen_by_controller[
                        sample.labels.get("controller", "")
                    ] = sample.value
                    continue
                if sample.name == "vllm:autotune_knob_value":
                    stats.autotune_knob_by_controller[
                        sample.labels.get("controller", "")
                    ] = sample.value
                    continue
                if (sample.name == "vllm:engine_attention_impl"
                        and sample.value == 1.0):
                    # One-hot labeled info gauge: phase -> impl.
                    stats.attention_impl_by_phase[
                        sample.labels.get("phase", "")
                    ] = sample.labels.get("impl", "")
                    continue
                if (sample.name == "vllm:engine_kv_cache_dtype"
                        and sample.value == 1.0):
                    # One-hot labeled gauge: the label carries the
                    # dtype string.
                    stats.engine_kv_cache_dtype = sample.labels.get(
                        "kv_dtype", "")
                    continue
                attr = _METRIC_MAP.get(sample.name)
                if attr is not None:
                    current = getattr(stats, attr)
                    setattr(stats, attr, type(current)(sample.value))
        return stats


class EngineStatsScraper(metaclass=SingletonMeta):
    """Daemon thread scraping every discovered engine at a fixed interval."""

    def __init__(self, scrape_interval: Optional[float] = None):
        if getattr(self, "_initialized", False):
            return
        if scrape_interval is None:
            raise ValueError("EngineStatsScraper needs scrape_interval")
        self.scrape_interval = float(scrape_interval)
        self._stats: Dict[str, EngineStats] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="engine-stats-scraper"
        )
        self._thread.start()
        self._initialized = True

    def _engine_urls(self):
        # Imported lazily to avoid a circular import at module load.
        from production_stack_tpu.router.service_discovery import (
            get_service_discovery,
        )
        try:
            discovery = get_service_discovery()
        except ValueError:
            return []
        return [ep.url for ep in discovery.get_endpoint_info()]

    def _scrape_one(self, url: str) -> Optional[EngineStats]:
        try:
            resp = requests.get(f"{url}/metrics", timeout=_SCRAPE_TIMEOUT_S)
            resp.raise_for_status()
            stats = EngineStats.from_prometheus_text(resp.text)
        except Exception as e:
            logger.warning("Failed to scrape %s/metrics: %s", url, e)
            return None
        self._scrape_kv_summary(url, stats)
        return stats

    def _scrape_kv_summary(self, url: str, stats: EngineStats) -> None:
        """Fetch GET /kv/summary for the hot-chain hashes themselves.

        Best-effort: engines that predate the KV economy 404 here and
        ``kv_summary_time`` stays 0, which KVStateAwarePolicy reads as
        "no summary" and degrades to prefix-affinity."""
        try:
            resp = requests.get(f"{url}/kv/summary",
                                timeout=_SCRAPE_TIMEOUT_S)
            if resp.status_code != 200:
                return
            body = resp.json()
        except Exception as e:
            logger.debug("No /kv/summary from %s: %s", url, e)
            return
        try:
            stats.kv_hot_chains = {
                int(h): float(v) for h, v in body.get("hot_chains", [])
            }
            stats.kv_summary_hot_chains = float(len(stats.kv_hot_chains))
            stats.kv_free_page_headroom = float(
                body.get("free_pages", stats.kv_free_page_headroom))
            stats.kv_total_pages = float(
                body.get("total_pages", stats.kv_total_pages))
            if body.get("kv_dtype"):
                stats.engine_kv_cache_dtype = str(body["kv_dtype"])
            stats.kv_summary_time = time.time()
        except (TypeError, ValueError) as e:
            logger.warning("Malformed /kv/summary from %s: %s", url, e)

    def scrape_once(self) -> None:
        """One synchronous scrape pass over the discovered engines.

        The daemon thread calls this on its interval; tests and the
        fleet bench rig call it directly for a deterministic refresh.
        """
        urls = self._engine_urls()
        fresh: Dict[str, EngineStats] = {}
        for url in urls:
            stats = self._scrape_one(url)
            if stats is not None:
                fresh[url] = stats
        with self._lock:
            # Drop engines that disappeared from discovery.
            self._stats = {
                u: fresh.get(u, self._stats.get(u, EngineStats()))
                for u in urls
            }

    def _run(self) -> None:
        while not self._stop.wait(self.scrape_interval):
            self.scrape_once()

    def get_engine_stats(self) -> Dict[str, EngineStats]:
        with self._lock:
            return dict(self._stats)

    def get_health(self) -> bool:
        return self._thread.is_alive()

    def close(self) -> None:
        self._stop.set()


def initialize_engine_stats_scraper(scrape_interval: float) -> EngineStatsScraper:
    return EngineStatsScraper(scrape_interval)


def get_engine_stats_scraper() -> EngineStatsScraper:
    return EngineStatsScraper()
