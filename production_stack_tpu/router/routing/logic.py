"""Pluggable routing policies.

Capability parity with reference src/vllm_router/routers/routing_logic.py:
roundrobin (L50), session consistent-hash + QPS fallback (L88), llq
least-loaded (L186), hra head-room admission with SJF queue (L272), and the
work-estimate custom policy (L408). Fresh implementation: policies receive a
plain headers mapping (not a framework request object) and the HRA policy
returns an ``asyncio.Future`` the proxy awaits until admission.
"""

from __future__ import annotations

import abc
import asyncio
import enum
import heapq
import itertools
import random
import time
from dataclasses import dataclass, field as dataclass_field
from math import ceil
from typing import Dict, List, Mapping, Optional, Tuple, Union

from production_stack_tpu.kvecon.summary import (
    TOKENS_PER_BLOCK,
    chain_text,
    expected_hit_blocks,
)
from production_stack_tpu.router.routing.hashring import ConsistentHashRing
from production_stack_tpu.router.service_discovery import EndpointInfo
from production_stack_tpu.router.stats.engine_stats import EngineStats
from production_stack_tpu.router.stats.request_stats import (
    BLOCK_SIZE,
    DECODE_TO_PREFILL_RATIO,
    SAFETY_FRACTION,
    TOTAL_NUMBER_OF_BLOCKS,
    RequestStats,
    get_request_stats_monitor,
)
from production_stack_tpu.utils import SingletonABCMeta
from production_stack_tpu.utils.log import init_logger

logger = init_logger(__name__)

RouteResult = Union[str, "asyncio.Future[str]"]

# -- canary traffic weighting (fleet rollouts, docs/fleet.md) ---------------
# url -> dispatch traffic share for baking canaries, and the set of
# backends in a migrate-mode drain. Both are pushed by the dynamic
# config (apply_dynamic_config) whenever the fleet rewrites its file.
_canary_weights: Dict[str, float] = {}
_migrating_urls: frozenset = frozenset()
_canary_rng = random.Random()


def set_canary_weights(weights: Optional[Dict[str, float]]) -> None:
    global _canary_weights
    _canary_weights = dict(weights or {})


def set_migrating_urls(urls) -> None:
    global _migrating_urls
    _migrating_urls = frozenset(urls or ())


def get_migrating_urls() -> frozenset:
    """Backends whose mid-stream deaths are planned migrations: the
    failover path resumes their streams elsewhere under the
    ``migrated`` outcome instead of charging a crash."""
    return _migrating_urls


def canary_split(candidates: List[EndpointInfo]) -> List[EndpointInfo]:
    """Steer one dispatch between baking canaries and the stable set.

    With probability equal to its weight a canary takes the request
    (the candidate list collapses to canaries only); otherwise canaries
    drop out so the stable set keeps serving the remainder. Only the
    initial dispatch is weighted — retry/failover/resume paths pass
    their candidates straight to the policy so a struggling stable set
    can still fail over onto a healthy canary."""
    if not _canary_weights or not candidates:
        return candidates
    canaries = [ep for ep in candidates if ep.url in _canary_weights]
    if not canaries or len(canaries) == len(candidates):
        return candidates
    weight = max(_canary_weights[ep.url] for ep in canaries)
    if _canary_rng.random() < weight:
        return canaries
    return [ep for ep in candidates if ep.url not in _canary_weights]


def usable_endpoints(endpoints: List[EndpointInfo],
                     exclude=()) -> List[EndpointInfo]:
    """The endpoints a new attempt may target: not in *exclude* (URLs
    already tried by this request), not marked unhealthy by the active
    health checker, and not behind a tripped circuit breaker. With the
    resilience layer uninitialized this is just the exclude filter."""
    from production_stack_tpu.router.resilience import get_resilience
    pool = [ep for ep in endpoints if ep.url not in exclude]
    mgr = get_resilience()
    if mgr is None:
        return pool
    return [ep for ep in pool if mgr.endpoint_available(ep.url)]


def filter_by_role(endpoints: List[EndpointInfo],
                   role: str) -> List[EndpointInfo]:
    """Endpoints deployed as exactly *role*. Disagg dispatch
    (request_service._route_disagg) engages only when both a strict
    'prefill' and a strict 'decode' pool are non-empty; 'both'
    (monolithic) endpoints never join either hop — they serve the
    fallback path instead."""
    return [ep for ep in endpoints
            if getattr(ep, "role", "both") == role]


class RoutingLogic(str, enum.Enum):
    ROUND_ROBIN = "roundrobin"
    SESSION_BASED = "session"
    LEAST_LOADED = "llq"
    HRA = "hra"
    PREFIX_AWARE = "prefixaware"
    KV_STATE_AWARE = "kvstateaware"
    CUSTOM_LOGIC = "custom"


class RoutingPolicy(metaclass=SingletonABCMeta):
    """A routing decision: pick an engine URL for one request.

    ``route_request`` may return the URL directly, or (for admission-control
    policies) an asyncio Future resolving to the URL once admitted.
    """

    @abc.abstractmethod
    def route_request(
        self,
        endpoints: List[EndpointInfo],
        engine_stats: Dict[str, EngineStats],
        request_stats: Dict[str, RequestStats],
        headers: Mapping[str, str],
        request_id: str,
        num_prefill_tokens: int = 0,
        prompt_text: Optional[str] = None,
    ) -> RouteResult:
        raise NotImplementedError

    # Policies that score the request's prompt text set this; the
    # proxy only pays the text extraction when someone will read it.
    uses_prompt_text = False

    def on_request_complete(self, engine_url: str) -> None:
        """Hook fired when any request finishes; admission policies use it."""


def _mark_routed(url: str, request_id: str, num_prefill_tokens: int) -> str:
    get_request_stats_monitor().on_request_routed(
        url, request_id, num_prefill_tokens
    )
    return url


class RoundRobinPolicy(RoutingPolicy):
    def __init__(self):
        if getattr(self, "_initialized", False):
            return
        self._counter = itertools.count()
        self._initialized = True

    def route_request(self, endpoints, engine_stats, request_stats, headers,
                      request_id, num_prefill_tokens=0,
                      prompt_text=None) -> str:
        ordered = sorted(endpoints, key=lambda e: e.url)
        url = ordered[next(self._counter) % len(ordered)].url
        return _mark_routed(url, request_id, num_prefill_tokens)


class SessionPolicy(RoutingPolicy):
    """Sticky sessions via consistent hashing on a header key.

    Requests without the session header fall back to lowest-QPS placement.
    """

    def __init__(self, session_key: Optional[str] = None):
        if getattr(self, "_initialized", False):
            return
        if not session_key:
            raise ValueError("SessionPolicy requires a session_key")
        self.session_key = session_key
        self._ring = ConsistentHashRing()
        self._initialized = True

    @staticmethod
    def _lowest_qps(endpoints, request_stats) -> str:
        best_url, best_qps = None, float("inf")
        for ep in endpoints:
            stat = request_stats.get(ep.url)
            if stat is None:
                return ep.url  # never seen traffic: coldest
            if stat.qps < best_qps:
                best_qps, best_url = stat.qps, ep.url
        return best_url

    def route_request(self, endpoints, engine_stats, request_stats, headers,
                      request_id, num_prefill_tokens=0,
                      prompt_text=None) -> str:
        self._ring.sync([ep.url for ep in endpoints])
        session_id = headers.get(self.session_key)
        if session_id is None:
            url = self._lowest_qps(endpoints, request_stats)
        else:
            url = self._ring.get_node(session_id)
        return _mark_routed(url, request_id, num_prefill_tokens)


class LeastLoadedPolicy(RoutingPolicy):
    """LLQ: route to the engine with the fewest in-flight requests.

    Ties break RANDOMLY among the least-loaded engines. A stable
    ``min()`` tie-break routed every equal-load arrival to the
    lowest-index engine, so consecutive arrivals burst onto one
    backend between count updates — measured 10-15% lower throughput
    and ~2x p99 TTFT vs roundrobin at 16 QPS on the fake-engine rig
    (a CPU behaviour check, not a chip number). Randomizing the tie spreads
    those bursts without weakening the load signal.
    """

    def __init__(self):
        if getattr(self, "_initialized", False):
            return
        # Seeded so tests are reproducible; the tie population itself
        # is load-driven, the seed only orders equal choices.
        self._rng = random.Random(0x11A)
        self._initialized = True

    def route_request(self, endpoints, engine_stats, request_stats, headers,
                      request_id, num_prefill_tokens=0,
                      prompt_text=None) -> str:
        def load(url: str) -> int:
            stat = request_stats.get(url)
            if stat is None:
                return 0
            return stat.in_prefill_requests + stat.in_decoding_requests

        loads = [(load(ep.url), ep.url) for ep in endpoints]
        best = min(l for l, _ in loads)
        candidates = [u for l, u in loads if l == best]
        url = (candidates[0] if len(candidates) == 1
               else self._rng.choice(candidates))
        return _mark_routed(url, request_id, num_prefill_tokens)


@dataclass(order=True)
class _PendingAdmission:
    """Heap entry: ordering fields first so heapq compares SJF-then-FIFO
    ((prefill_tokens, seqno)) without ever comparing futures."""

    prefill_tokens: int
    seqno: int  # arrival order; also the FIFO tiebreak among equals
    arrived_at: float = dataclass_field(compare=False, default=0.0)
    endpoints: List[EndpointInfo] = dataclass_field(
        compare=False, default_factory=list)
    future: "asyncio.Future[str]" = dataclass_field(
        compare=False, default=None)
    request_id: str = dataclass_field(compare=False, default="")


class AdmissionError(Exception):
    """Raised (via the admission future) when a request can never fit."""


class HeadRoomAdmissionPolicy(RoutingPolicy):
    """HRA: block-budget admission control with an SJF queue.

    A request is only admitted to a replica whose projected KV-block usage
    (allocated + pending-reserved + this request's pessimistic demand)
    leaves at least ``SAFETY_FRACTION`` of the budget free. Inadmissible
    requests wait on a future; completions re-trigger scheduling. Shortest
    job first, FIFO among equals; head-of-line blocking is intentional
    (a short unschedulable request gates longer ones). Requests whose
    demand exceeds the budget of an *empty* engine are rejected outright
    rather than wedging the queue forever.

    The queue is a binary heap keyed (prefill_tokens, seqno): O(log n)
    per arrival/admission instead of the round-1 re-sort per arrival +
    list.pop(0) per admission — under burst churn (hundreds queued,
    tests/test_routing_logic.py churn test) drains stay cheap.
    """

    def __init__(self):
        if getattr(self, "_initialized", False):
            return
        self._queue: List[_PendingAdmission] = []  # heapq
        self._seq = itertools.count()
        self._initialized = True

    def route_request(self, endpoints, engine_stats, request_stats, headers,
                      request_id, num_prefill_tokens=0,
                      prompt_text=None):
        # get_running_loop, not get_event_loop: the policy only ever
        # runs inside the router's serving loop, and under Python 3.12
        # semantics get_event_loop() from a coroutine without a set
        # loop deprecation-warns (and will raise) instead of returning
        # the running one.
        future: "asyncio.Future[str]" = (
            asyncio.get_running_loop().create_future()
        )
        max_admissible = int(
            TOTAL_NUMBER_OF_BLOCKS * (1 - SAFETY_FRACTION)
        )
        if self.block_demand(num_prefill_tokens) > max_admissible:
            future.set_exception(AdmissionError(
                f"Request needs {self.block_demand(num_prefill_tokens)} KV "
                f"blocks but at most {max_admissible} can ever be admitted"
            ))
            return future
        heapq.heappush(self._queue, _PendingAdmission(
            prefill_tokens=num_prefill_tokens,
            seqno=next(self._seq),
            arrived_at=time.time(),
            endpoints=list(endpoints),
            future=future,
            request_id=request_id,
        ))
        self._drain_queue()
        return future

    def on_request_complete(self, engine_url: str) -> None:
        self._drain_queue()

    @staticmethod
    def block_demand(prefill_tokens: int) -> int:
        return ceil(
            prefill_tokens * (1 + DECODE_TO_PREFILL_RATIO) / BLOCK_SIZE
        )

    def _drain_queue(self) -> None:
        if not self._queue:
            return
        monitor = get_request_stats_monitor()
        snapshot = monitor.get_request_stats(time.time())

        urls = {ep.url for p in self._queue for ep in p.endpoints}
        allocated = {u: monitor.estimate_allocated_blocks(u) for u in urls}
        reserved = {
            u: monitor.estimate_pending_reserved_blocks(u) for u in urls
        }
        qlen = {
            u: (snapshot[u].in_prefill_requests
                + snapshot[u].in_decoding_requests) if u in snapshot else 0
            for u in urls
        }
        headroom = int(TOTAL_NUMBER_OF_BLOCKS * SAFETY_FRACTION)

        while self._queue:
            pending = self._queue[0]
            if pending.future.done():
                # Client gave up (disconnect cancels the future): drop the
                # entry without registering a phantom reservation.
                heapq.heappop(self._queue)
                continue
            demand = self.block_demand(pending.prefill_tokens)
            fits = [
                ep.url for ep in pending.endpoints
                if (TOTAL_NUMBER_OF_BLOCKS
                    - (allocated[ep.url] + reserved[ep.url] + demand))
                >= headroom
            ]
            if not fits:
                break  # SJF head-of-line block
            heapq.heappop(self._queue)
            target = min(fits, key=lambda u: (qlen[u],
                                              allocated[u] + reserved[u]))
            monitor.on_request_routed(
                target, pending.request_id, pending.prefill_tokens
            )
            pending.future.set_result(target)
            reserved[target] += demand
            qlen[target] += 1


class PrefixAwarePolicy(RoutingPolicy):
    """KV-aware placement: route to the engine most likely to hold the
    request's prompt prefix in its paged KV cache.

    The engines' prefix caches are content-chained on token pages
    (engine/kv_cache.py); the router cannot tokenize, so it
    approximates the same structure on TEXT: the prompt is split into
    fixed-size character blocks and chain-hashed, and each engine
    carries a bounded LRU of the chains it has recently served. A new
    request scores every candidate by longest matching chain prefix
    and routes to the best (ties broken by fewest in-flight). Requests
    with no text or no match fall back to least-loaded.

    Affinity is LOAD-BOUNDED: the prefix match only wins while the
    preferred engine's in-flight count stays within
    ``SPILL_FACTOR x min + SPILL_SLACK`` of the least-loaded
    candidate; beyond that the request spills to the least-loaded
    engine and its chain is remembered THERE too (the spill target
    will hold the prefix after serving it), so a hot shared prefix
    replicates across engines instead of pinning the fleet's traffic
    to one replica forever.

    This is the BASELINE.md north-star "KV-aware routing" (the
    reference's roadmap item via LMCache-aware routing) built on this
    stack's own chain-hash prefix model — multi-round chats and
    shared-system-prompt fleets keep hitting a replica whose HBM
    already holds their context, without session headers.
    """

    BLOCK_CHARS = 256  # ~64 tokens per block at 4 chars/token
    MAX_CHAINS_PER_ENGINE = 4096
    SPILL_FACTOR = 2
    SPILL_SLACK = 4
    uses_prompt_text = True

    def __init__(self):
        if getattr(self, "_initialized", False):
            return
        # url -> ordered {chain_hash: None} acting as an LRU set.
        self._index: Dict[str, "OrderedDict[int, None]"] = {}
        self._initialized = True

    def _chain(self, text: str) -> List[int]:
        # Canonical implementation lives in kvecon.summary so the
        # router's text chains stay byte-identical to the hot chains
        # the engines advertise at GET /kv/summary (blake2b, not
        # builtin hash(), because str hashing is salted per process).
        return chain_text(text, self.BLOCK_CHARS)

    def _remember(self, url: str, chain: List[int]) -> None:
        from collections import OrderedDict
        lru = self._index.setdefault(url, OrderedDict())
        for h in chain:
            lru.pop(h, None)
            lru[h] = None
        while len(lru) > self.MAX_CHAINS_PER_ENGINE:
            lru.popitem(last=False)

    def _score(self, url: str, chain: List[int]) -> int:
        lru = self._index.get(url)
        if not lru:
            return 0
        n = 0
        for h in chain:
            if h not in lru:
                break
            n += 1
        return n

    def route_request(self, endpoints, engine_stats, request_stats, headers,
                      request_id, num_prefill_tokens=0,
                      prompt_text=None) -> str:
        def load(url: str) -> int:
            stat = request_stats.get(url)
            if stat is None:
                return 0
            return stat.in_prefill_requests + stat.in_decoding_requests

        # Engines that left the pool must not pin stale chains.
        live = {ep.url for ep in endpoints}
        for url in list(self._index):
            if url not in live:
                del self._index[url]

        chain = self._chain(prompt_text) if prompt_text else []
        loads = {ep.url: load(ep.url) for ep in endpoints}
        min_load = min(loads.values())
        if chain:
            scores = {ep.url: self._score(ep.url, chain)
                      for ep in endpoints}
            best = max(endpoints,
                       key=lambda ep: (scores[ep.url],
                                       -loads[ep.url])).url
            within_bound = loads[best] <= (
                self.SPILL_FACTOR * min_load + self.SPILL_SLACK)
            if scores[best] > 0 and within_bound:
                self._remember(best, chain)
                return _mark_routed(best, request_id,
                                    num_prefill_tokens)
        # Cold prefix, no text, or the preferred engine is overloaded:
        # least-loaded placement — and remember the chain there, so a
        # hot prefix replicates instead of pinning one engine.
        url = min(endpoints, key=lambda ep: loads[ep.url]).url
        if chain:
            self._remember(url, chain)
        return _mark_routed(url, request_id, num_prefill_tokens)


class KVStateAwarePolicy(RoutingPolicy):
    """Route on the KV state engines actually HOLD, not on chains the
    router remembers serving (docs/kv_economy.md).

    Each engine exports a rolling summary of its KV economy at
    ``GET /kv/summary`` — top-k hot chain hashes (hit-count-decayed),
    free-page headroom, kv_dtype — which rides the engine-stats scrape
    loop into ``EngineStats.kv_hot_chains`` / ``kv_free_page_headroom``.
    A request's prompt is chain-hashed with the same blake2b scheme
    and every candidate is scored:

        score = W_HIT * expected_hit_frac          # prefix reuse
              + W_HEADROOM * free_page_frac        # room to serve it
              - W_LOAD * load_frac                 # queue depth

    ``expected_hit_frac`` is the deepest chain hash of the prompt found
    in the engine's advertised hot set, over the prompt's block count.
    Unlike PrefixAwarePolicy's remembered-chain guess, this sees
    chains the engine computed for OTHER routers' traffic, chains it
    has evicted, and how much headroom is left — headroom varies
    1.9-3.55x with ``--kv-cache-dtype``, which remembered chains can't
    know.

    Summaries are trusted only within ``SUMMARY_STALENESS_S`` of their
    scrape; when NO candidate has a fresh summary (engines predate
    /kv/summary, scraper down) the policy degrades to a private
    PrefixAwarePolicy instance, which it keeps warm by recording every
    routed chain — the fallback starts with full affinity state, not
    cold.
    """

    SUMMARY_STALENESS_S = 30.0
    W_HIT = 2.0
    W_HEADROOM = 1.0
    W_LOAD = 0.25
    uses_prompt_text = True

    def __init__(self):
        if getattr(self, "_initialized", False):
            return
        # Private (non-singleton) fallback so configuring this policy
        # never registers a PrefixAwarePolicy in SingletonMeta.
        self._fallback = PrefixAwarePolicy.__new__(PrefixAwarePolicy)
        self._fallback._index = {}
        self._fallback._initialized = True
        # url -> expected prefix-hit tokens of the last request routed
        # there; exported as router gauge kv_route_expected_hit_tokens.
        self.expected_hit_tokens_by_url: Dict[str, float] = {}
        self._initialized = True

    def _summary_fresh(self, stats: Optional[EngineStats],
                       now: float) -> bool:
        return (stats is not None
                and stats.kv_summary_time > 0
                and now - stats.kv_summary_time
                <= self.SUMMARY_STALENESS_S)

    def route_request(self, endpoints, engine_stats, request_stats, headers,
                      request_id, num_prefill_tokens=0,
                      prompt_text=None) -> str:
        now = time.time()
        fresh = {ep.url for ep in endpoints
                 if self._summary_fresh(engine_stats.get(ep.url), now)}
        chain = chain_text(prompt_text) if prompt_text else []
        if not fresh:
            return self._fallback.route_request(
                endpoints, engine_stats, request_stats, headers,
                request_id, num_prefill_tokens, prompt_text)

        def load(url: str) -> int:
            stat = request_stats.get(url)
            if stat is None:
                return 0
            return stat.in_prefill_requests + stat.in_decoding_requests

        loads = {ep.url: load(ep.url) for ep in endpoints}
        max_load = max(loads.values()) or 1

        def score(url: str) -> Tuple[float, float]:
            es = engine_stats.get(url)
            hit_frac = 0.0
            headroom_frac = 0.5  # neutral when the engine is opaque
            if url in fresh:
                if chain:
                    hit_frac = expected_hit_blocks(
                        chain, es.kv_hot_chains) / len(chain)
                total = es.kv_total_pages
                if total > 0:
                    headroom_frac = min(
                        1.0, es.kv_free_page_headroom / total)
            s = (self.W_HIT * hit_frac
                 + self.W_HEADROOM * headroom_frac
                 - self.W_LOAD * loads[url] / max_load)
            return s, hit_frac

        scored = {ep.url: score(ep.url) for ep in endpoints}
        best = max(endpoints,
                   key=lambda ep: (scored[ep.url][0],
                                   -loads[ep.url], ep.url)).url
        self.expected_hit_tokens_by_url[best] = (
            scored[best][1] * len(chain) * TOKENS_PER_BLOCK)
        for url in list(self.expected_hit_tokens_by_url):
            if url not in loads:
                del self.expected_hit_tokens_by_url[url]
        if chain:
            # Keep the fallback's affinity index warm for degradation.
            self._fallback._remember(best, chain)
        return _mark_routed(best, request_id, num_prefill_tokens)


class WorkEstimatePolicy(RoutingPolicy):
    """'custom' policy: routes by estimated outstanding work per engine.

    Work = (queued prefills x avg decode length) + sum over decoding
    requests of max(age, avg decode length). Falls back to QPS while no
    decode-length estimate exists yet.
    """

    def __init__(self):
        if getattr(self, "_initialized", False):
            return
        self._initialized = True

    def route_request(self, endpoints, engine_stats, request_stats, headers,
                      request_id, num_prefill_tokens=0,
                      prompt_text=None) -> str:
        def work(url: str) -> float:
            stat = request_stats.get(url)
            if stat is None:
                return 0.0
            avg_dec = stat.avg_decoding_length
            if avg_dec < 0:
                return stat.qps
            queued = len(stat.ts_prefill_enqueue) * avg_dec
            decoding = sum(
                max(age, avg_dec) for age in stat.ts_decoding_enqueue
            )
            return queued + decoding

        url = min(endpoints, key=lambda ep: work(ep.url)).url
        return _mark_routed(url, request_id, num_prefill_tokens)


_POLICY_CLASSES = (
    RoundRobinPolicy, SessionPolicy, LeastLoadedPolicy,
    HeadRoomAdmissionPolicy, PrefixAwarePolicy, KVStateAwarePolicy,
    WorkEstimatePolicy,
)


def initialize_routing_logic(routing_logic: Union[str, RoutingLogic],
                             **kwargs) -> RoutingPolicy:
    logic = RoutingLogic(routing_logic)
    logger.info("Initializing routing logic: %s", logic.value)
    if logic == RoutingLogic.ROUND_ROBIN:
        return RoundRobinPolicy()
    if logic == RoutingLogic.SESSION_BASED:
        return SessionPolicy(kwargs.get("session_key"))
    if logic == RoutingLogic.LEAST_LOADED:
        return LeastLoadedPolicy()
    if logic == RoutingLogic.HRA:
        return HeadRoomAdmissionPolicy()
    if logic == RoutingLogic.PREFIX_AWARE:
        return PrefixAwarePolicy()
    if logic == RoutingLogic.KV_STATE_AWARE:
        return KVStateAwarePolicy()
    if logic == RoutingLogic.CUSTOM_LOGIC:
        return WorkEstimatePolicy()
    raise ValueError(f"Unknown routing logic: {routing_logic}")


def reconfigure_routing_logic(routing_logic: Union[str, RoutingLogic],
                              **kwargs) -> RoutingPolicy:
    from production_stack_tpu.utils import SingletonMeta
    for cls in _POLICY_CLASSES:
        SingletonMeta._instances.pop(cls, None)
    return initialize_routing_logic(routing_logic, **kwargs)


def get_routing_logic() -> RoutingPolicy:
    from production_stack_tpu.utils import SingletonMeta
    for cls in _POLICY_CLASSES:
        if cls in SingletonMeta._instances:
            return SingletonMeta._instances[cls]
    raise ValueError("Routing logic has not been initialized")
