"""Cluster-wide KV economy (docs/kv_economy.md).

Covers the three layers as one system: the text-domain prefix
summaries engines export at GET /kv/summary (and the router policy
that routes on them, with staleness fallback), the managed shared
cache's admission/eviction state machines (driven by a fake clock),
and the engine-side cold-start probe — a cold prompt whose prefix KV
another engine already shipped restores it from the shared tier
byte-identically (bf16 AND int8) instead of recomputing, and degrades
to compute on miss or tier-down without ever dropping the request.
"""

import asyncio
import socket
import threading
import time

import numpy as np
import pytest
from aiohttp import web

from production_stack_tpu.engine.cache_server import build_cache_server
from production_stack_tpu.kvecon.cluster_cache import ManagedKVStore
from production_stack_tpu.kvecon.summary import (
    PrefixSummaryTracker,
    TOKENS_PER_BLOCK,
    chain_text,
    expected_hit_blocks,
    routable_text,
)
from production_stack_tpu.router.routing.logic import (
    KVStateAwarePolicy,
    PrefixAwarePolicy,
    initialize_routing_logic,
)
from production_stack_tpu.router.service_discovery import EndpointInfo
from production_stack_tpu.router.stats.engine_stats import EngineStats
from production_stack_tpu.router.stats.request_stats import (
    initialize_request_stats_monitor,
)

EPS = [EndpointInfo(url=f"http://e{i}:8000") for i in range(3)]


@pytest.fixture(autouse=True)
def stats_monitor():
    return initialize_request_stats_monitor(60.0)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---- text-domain chains ---------------------------------------------------

def test_chain_text_is_the_policy_chain():
    """Router policy and engine tracker must hash the same domain:
    PrefixAwarePolicy._chain delegates to kvecon.chain_text."""
    text = "x" * 900
    p = PrefixAwarePolicy.__new__(PrefixAwarePolicy)
    assert p._chain(text) == chain_text(text)
    assert len(chain_text(text)) == 4  # ceil(900 / 256) blocks


def test_routable_text_shapes():
    msgs = {"messages": [{"role": "system", "content": "a"},
                         {"role": "user", "content": "b"}]}
    assert routable_text(msgs) == "system\x1fa\x1euser\x1fb"
    assert routable_text({"prompt": "hello"}) == "hello"
    assert routable_text({"prompt": ["a", "b"]}) == "a\x1eb"
    assert routable_text({"prompt": [1, 2, 3]}) is None  # token ids
    assert routable_text({}) is None


def test_expected_hit_blocks_deepest_advertised_hash_wins():
    """Chain hash i commits to the whole prefix through block i, so a
    decayed-out intermediate hash must not truncate the estimate."""
    chains = chain_text("y" * 1024)  # 4 blocks
    assert expected_hit_blocks(chains, set(chains)) == 4
    # Only the deepest hash survives in the hot set: still 4 blocks.
    assert expected_hit_blocks(chains, {chains[-1]}) == 4
    assert expected_hit_blocks(chains, {chains[0]}) == 1
    assert expected_hit_blocks(chains, set()) == 0
    assert expected_hit_blocks([], {1, 2}) == 0


# ---- engine summary tracker ----------------------------------------------

def test_summary_tracker_admit_floor_and_decay():
    clock = FakeClock()
    tr = PrefixSummaryTracker(top_k=8, admit_hits=2, ttl_s=0.0,
                              clock=clock)
    text = "z" * 300  # 2 blocks
    tr.observe_text(text)
    # One sighting is below the admit floor: nothing advertised.
    assert tr.snapshot() == []
    tr.observe_text(text)
    snap = dict(tr.snapshot())
    assert set(snap) == set(chain_text(text))
    assert all(v >= 2 for v in snap.values())
    # One half-life later the decayed count falls below the floor.
    clock.t += PrefixSummaryTracker.HALF_LIFE_S
    assert tr.snapshot() == []
    # ...but the chain is still tracked, so one more hit re-admits.
    tr.observe_text(text)
    assert len(tr.snapshot()) == 2


def test_summary_tracker_ttl_and_capacity():
    clock = FakeClock()
    tr = PrefixSummaryTracker(top_k=2, admit_hits=1, ttl_s=60.0,
                              clock=clock)
    tr.observe_text("a" * 300)
    clock.t = 61.0
    tr.observe_text("b" * 300)  # observe prunes the idle chain
    assert set(dict(tr.snapshot())) == set(chain_text("b" * 300))
    # Bounded memory: tracked chains capped at top_k * CAPACITY_FACTOR.
    for i in range(200):
        tr.observe_text(f"prompt-{i:04d}" + "p" * 260)
    assert len(tr) <= 2 * PrefixSummaryTracker.CAPACITY_FACTOR
    assert len(tr.snapshot()) <= 2


# ---- managed shared cache: admission/eviction -----------------------------

def test_managed_store_admission_by_distinct_requesters():
    clock = FakeClock()
    store = ManagedKVStore(10 ** 6, admit_hits=2, ttl_s=0.0,
                           watermark_high=1.0, watermark_low=1.0,
                           clock=clock)
    # Same requester asking twice is not demand promotion.
    assert store.put("k0", b"x" * 8, chain_id="c", requester="A") is False
    assert store.put("k0", b"x" * 8, chain_id="c", requester="A") is False
    assert store.get("k0", requester="A") is None
    assert store.stats()["rejected_puts"] == 2
    # A second distinct requester promotes the chain; the whole chain
    # is admitted, later pages ride in without re-courting.
    assert store.put("k0", b"x" * 8, chain_id="c", requester="B") is True
    assert store.put("k1", b"y" * 8, chain_id="c", requester="A") is True
    assert store.get("k0", requester="C") == b"x" * 8
    s = store.stats()
    assert s["admissions"] == 1 and s["chains"] == 1 and s["entries"] == 2


def test_managed_store_probe_miss_records_demand():
    """A HEAD miss is a statement of demand: two engines probing for
    the same (bare-key) chain promote it before any PUT lands."""
    clock = FakeClock()
    store = ManagedKVStore(10 ** 6, admit_hits=2, ttl_s=0.0,
                           watermark_high=1.0, watermark_low=1.0,
                           clock=clock)
    assert store.contains("root", requester="engine-a") is False
    assert store.contains("root", requester="engine-b") is False
    assert store.put("root", b"kv", requester="engine-a") is True


def test_managed_store_associate_merges_bare_key_demand():
    """Probe misses only know the page key; the PUT knows the chain.
    associate() folds the courted bare-key demand into the chain so
    the promotion threshold counts both."""
    clock = FakeClock()
    store = ManagedKVStore(10 ** 6, admit_hits=2, ttl_s=0.0,
                           watermark_high=1.0, watermark_low=1.0,
                           clock=clock)
    assert store.contains("page7", requester="engine-b") is False
    store.associate("page7", "chain-root")
    assert store.put("page7", b"kv", chain_id="chain-root",
                     requester="engine-a") is True


def test_managed_store_watermark_evicts_coldest_chain_whole():
    clock = FakeClock()
    store = ManagedKVStore(1000, admit_hits=1, ttl_s=0.0,
                           watermark_high=0.9, watermark_low=0.5,
                           clock=clock)
    store.put("a0", b"x" * 300, chain_id="cold", requester="A")
    clock.t = 1.0
    store.put("a1", b"x" * 300, chain_id="cold", requester="A")
    clock.t = 5.0
    store.put("b0", b"y" * 400, chain_id="hot", requester="A")
    # 1000 stored > 900 high: the cold chain dies WHOLE (both pages),
    # landing at 400 <= 500 low.
    assert store.get("a0") is None and store.get("a1") is None
    assert store.get("b0") is not None
    s = store.stats()
    assert s["evictions"] == 1 and s["bytes"] == 400 and s["chains"] == 1


def test_managed_store_ttl_sweeps_idle_chains():
    clock = FakeClock()
    store = ManagedKVStore(10 ** 6, admit_hits=1, ttl_s=100.0,
                           watermark_high=1.0, watermark_low=1.0,
                           clock=clock)
    store.put("k", b"kv", chain_id="c", requester="A")
    clock.t = 99.0
    assert store.get("k") is not None  # access refreshes last_access
    clock.t = 99.0 + 101.0
    assert store.get("k") is None
    assert store.stats()["evictions"] == 1


def test_managed_store_watermark_validation():
    with pytest.raises(ValueError, match="watermark"):
        ManagedKVStore(100, watermark_high=0.5, watermark_low=0.8)
    with pytest.raises(ValueError, match="watermark"):
        ManagedKVStore(100, watermark_high=1.2, watermark_low=0.8)


# ---- cache server: verdicts over HTTP -------------------------------------

def _wire_body(arr: np.ndarray) -> bytes:
    import msgpack
    return msgpack.packb({"arrays": [
        {"data": arr.tobytes(), "shape": list(arr.shape),
         "dtype": str(arr.dtype)}]})


def test_cache_server_admission_verdicts_and_chain_header():
    """PUT answers 200 + {"admitted": bool}; distinct X-KV-Requester
    identities promote a chain tagged via X-KV-Chain."""
    from aiohttp.test_utils import TestClient, TestServer

    async def run():
        client = TestClient(TestServer(
            build_cache_server(1024 ** 2, admit_hits=2)))
        await client.start_server()
        try:
            body = _wire_body(np.zeros((2, 2), np.float32))
            hdr_a = {"X-KV-Requester": "engine-a", "X-KV-Chain": "root"}
            hdr_b = {"X-KV-Requester": "engine-b", "X-KV-Chain": "root"}
            first = await client.put("/kv/p0", data=body, headers=hdr_a)
            assert first.status == 200
            assert (await first.json()) == {"admitted": False}
            assert (await client.head("/kv/p0",
                                      headers=hdr_a)).status == 404
            second = await client.put("/kv/p0", data=body,
                                      headers=hdr_b)
            assert (await second.json()) == {"admitted": True}
            assert (await client.get("/kv/p0")).status == 200
            stats = await (await client.get("/stats")).json()
            assert stats["rejected_puts"] == 1
            assert stats["admissions"] == 1
            metrics = await (await client.get("/metrics")).text()
            assert "kvcache:rejected_puts_total 1" in metrics
            assert "kvcache:chains 1" in metrics
        finally:
            await client.close()
    asyncio.run(run())


def test_remote_client_treats_rejected_put_as_success():
    """Satellite: {"admitted": false} is a verdict, not an error — the
    client reports success (no retry storm) and counts the rejection."""
    from production_stack_tpu.engine.offload import RemoteKVClient

    url, stop = _serve_app_in_thread(
        build_cache_server(64 * 1024 ** 2, admit_hits=2))
    try:
        client = RemoteKVClient(url, requester="engine-solo")
        payload = (np.ones((2, 2), np.float32),)
        assert client.put("page", payload, chain="root") is True
        assert client.rejections == 1 and client.admissions == 0
        # The same engine retrying stays rejected (demand needs a
        # SECOND identity) and stays a success.
        assert client.put("page", payload, chain="root") is True
        assert client.rejections == 2
        other = RemoteKVClient(url, requester="engine-other")
        assert other.put("page", payload, chain="root") is True
        assert other.admissions == 1 and other.rejections == 0
        got = client.get("page")
        assert got is not None and client.hits == 1
    finally:
        stop()


# ---- KV-state-aware routing -----------------------------------------------

def _fresh_stats(hot_chains=None, free=100, total=128):
    return EngineStats(
        kv_hot_chains=dict.fromkeys(hot_chains or [], 4.0),
        kv_free_page_headroom=float(free),
        kv_total_pages=float(total),
        kv_summary_time=time.time(),
    )


def test_kvstateaware_routes_to_engine_holding_the_prefix():
    policy = initialize_routing_logic("kvstateaware")
    assert isinstance(policy, KVStateAwarePolicy)
    text = "conversation history " * 40  # > 3 blocks
    chain = chain_text(text)
    stats = {
        "http://e0:8000": _fresh_stats(),
        "http://e1:8000": _fresh_stats(hot_chains=chain),
        "http://e2:8000": _fresh_stats(),
    }
    got = policy.route_request(EPS, stats, {}, {}, "r1", 64,
                               prompt_text=text)
    assert got == "http://e1:8000"
    expected = policy.expected_hit_tokens_by_url["http://e1:8000"]
    assert expected == len(chain) * TOKENS_PER_BLOCK


def test_kvstateaware_prefers_headroom_for_cold_prompts():
    """No engine holds the prefix: free-page headroom (which varies
    ~2x with --kv-cache-dtype) breaks the tie."""
    policy = initialize_routing_logic("kvstateaware")
    stats = {
        "http://e0:8000": _fresh_stats(free=4, total=128),
        "http://e1:8000": _fresh_stats(free=120, total=128),
        "http://e2:8000": _fresh_stats(free=30, total=128),
    }
    got = policy.route_request(EPS, stats, {}, {}, "r1", 64,
                               prompt_text="brand new prompt " * 40)
    assert got == "http://e1:8000"


def test_kvstateaware_stale_summaries_fall_back_to_affinity():
    """Engines that predate /kv/summary (kv_summary_time == 0) or a
    scraper outage must not break routing: the policy degrades to
    prefix-affinity and stays sticky per chain."""
    policy = initialize_routing_logic("kvstateaware")
    stale = {url: EngineStats() for url in (ep.url for ep in EPS)}
    text = "stale summary conversation " * 40
    first = policy.route_request(EPS, stale, {}, {}, "r1", 64,
                                 prompt_text=text)
    for i in range(4):
        assert policy.route_request(
            EPS, stale, {}, {}, f"r{i+2}", 64,
            prompt_text=text) == first


def test_kvstateaware_fallback_is_warm_after_fresh_routing():
    """Chains routed while summaries were fresh seed the fallback's
    affinity index — a scraper outage degrades to the SAME placement,
    not a cold shuffle."""
    policy = initialize_routing_logic("kvstateaware")
    text = "keep me warm " * 60
    chain = chain_text(text)
    stats = {
        "http://e0:8000": _fresh_stats(),
        "http://e1:8000": _fresh_stats(),
        "http://e2:8000": _fresh_stats(hot_chains=chain),
    }
    assert policy.route_request(EPS, stats, {}, {}, "r1", 64,
                                prompt_text=text) == "http://e2:8000"
    stale = {url: EngineStats() for url in (ep.url for ep in EPS)}
    assert policy.route_request(EPS, stale, {}, {}, "r2", 64,
                                prompt_text=text) == "http://e2:8000"


def test_kvstateaware_does_not_pollute_policy_singleton():
    """The private PrefixAwarePolicy fallback must not register in
    SingletonMeta: get_routing_logic() must still resolve to the
    configured policy."""
    from production_stack_tpu.router.routing.logic import (
        get_routing_logic,
    )
    policy = initialize_routing_logic("kvstateaware")
    stale = {ep.url: EngineStats() for ep in EPS}
    policy.route_request(EPS, stale, {}, {}, "r1", 64,
                         prompt_text="p" * 600)
    assert get_routing_logic() is policy


# ---- scrape loop + fake engine -------------------------------------------

def _serve_app_in_thread(app: web.Application):
    loop = asyncio.new_event_loop()
    started = threading.Event()
    port_box = {}

    def serve():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        port_box["port"] = site._server.sockets[0].getsockname()[1]
        started.set()
        loop.run_forever()
        loop.run_until_complete(runner.cleanup())

    thread = threading.Thread(target=serve, daemon=True)
    thread.start()
    assert started.wait(10)

    def stop():
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=10)

    return f"http://127.0.0.1:{port_box['port']}", stop


def test_fake_engine_kv_summary_and_scrape_loop():
    """The fake serves GET /kv/summary (with a POST override for
    tests) and the engine-stats scraper folds it into EngineStats on
    the same pass as /metrics."""
    from production_stack_tpu.router.service_discovery import (
        initialize_service_discovery,
    )
    from production_stack_tpu.router.stats.engine_stats import (
        initialize_engine_stats_scraper,
    )
    from production_stack_tpu.testing.fake_engine import (
        build_fake_engine,
    )

    url, stop = _serve_app_in_thread(build_fake_engine())
    try:
        import requests
        pinned = {"hot_chains": [[123, 5.0], [456, 2.0]],
                  "free_pages": 7, "total_pages": 64,
                  "kv_dtype": "int8"}
        requests.post(f"{url}/kv/summary", json=pinned, timeout=5)
        assert requests.get(f"{url}/kv/summary",
                            timeout=5).json() == pinned
        metrics = requests.get(f"{url}/metrics", timeout=5).text
        assert "vllm:kv_summary_hot_chains 2.0" in metrics
        assert "vllm:kv_free_page_headroom 7.0" in metrics

        initialize_service_discovery(
            "static", urls=[url], models=["fake/model"])
        scraper = initialize_engine_stats_scraper(3600.0)
        try:
            scraper.scrape_once()
            es = scraper.get_engine_stats()[url]
            assert es.kv_hot_chains == {123: 5.0, 456: 2.0}
            assert es.kv_free_page_headroom == 7.0
            assert es.kv_total_pages == 64.0
            assert es.engine_kv_cache_dtype == "int8"
            assert es.kv_summary_time > 0
        finally:
            scraper.close()
    finally:
        stop()


def test_fake_engine_prefix_hot_set_thrashes_at_capacity():
    """The fake's hot set is a CAPPED LRU: pinning more distinct
    prefixes than the capacity on one fake evicts, so a routing
    policy that over-concentrates load measurably loses hit rate."""
    from production_stack_tpu.testing.fake_engine import (
        FakeEngineState,
    )
    s = FakeEngineState("m", 100.0, 0.02, kv_hot_capacity=2)
    bodies = [{"prompt": f"tenant-{i} " * 60} for i in range(3)]
    for b in bodies:
        assert s.observe_prefix(b) == 0.0  # all cold
    # Three distinct chains through capacity 2: the first is gone.
    assert s.observe_prefix(bodies[0]) == 0.0
    s2 = FakeEngineState("m", 100.0, 0.02, kv_hot_capacity=64)
    for b in bodies:
        s2.observe_prefix(b)
    assert all(s2.observe_prefix(b) == 1.0 for b in bodies)
    assert 0.0 < s2.prefix_hit_rate() < 1.0


# ---- engine cold-start probe (slow lane: builds engines) ------------------

def _free_port_url() -> str:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return f"http://127.0.0.1:{port}"


def _make_engine(remote_url, role="both", kv_dtype="auto",
                 offload=True):
    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        OffloadConfig,
        SchedulerConfig,
        tiny_model_config,
    )
    from production_stack_tpu.engine.engine import LLMEngine
    return LLMEngine(EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64,
                          kv_cache_dtype=kv_dtype),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=256,
                                  prefill_chunk_size=64),
        offload=OffloadConfig(enable=offload, remote_url=remote_url,
                              host_pool_bytes=0),
        engine_role=role,
    ))


def _sampling():
    from production_stack_tpu.engine.sequence import SamplingParams
    return SamplingParams(max_tokens=12, temperature=0.0,
                          ignore_eos=True)


def _run_to_finish(engine, sid):
    from production_stack_tpu.engine.sequence import SequenceState
    seq = engine.sequences[sid]
    while seq.state not in (SequenceState.FINISHED,
                            SequenceState.ABORTED):
        engine.step()
    return seq


@pytest.mark.parametrize("kv_dtype", ["auto", "int8"])
def test_cold_start_restores_another_engines_kv(kv_dtype):
    """The tentpole acceptance: engine A computes a prompt's KV and
    ships it to the shared cache; a COLD engine B receiving the same
    prompt parks, probes, restores A's pages through the wire, and
    produces byte-identical greedy output — for bf16 and int8."""
    from production_stack_tpu.engine.sequence import SequenceState

    url, stop = _serve_app_in_thread(
        build_cache_server(256 * 1024 ** 2))
    try:
        prompt = list(range(1, 50))  # 3 full pages + a tail
        ref = _make_engine(None, offload=False,
                           kv_dtype=kv_dtype).generate(
            list(prompt), _sampling())

        pre = _make_engine(url, role="prefill", kv_dtype=kv_dtype)
        sid = pre.add_request(list(prompt), _sampling(),
                              handoff_prefill=True)
        outs = []
        while not outs or not outs[-1].finished:
            outs.extend(pre.step())
        assert outs[-1].finish_reason == "handoff"

        dec = _make_engine(url, kv_dtype=kv_dtype)
        did = dec.add_request(list(prompt), _sampling())
        seq = dec.sequences[did]
        # Parked for the shared-cache probe, with the tri-state flag
        # telling the admission loop this is a cold start.
        assert seq.state == SequenceState.AWAITING_KV
        assert seq.cold_start_probe
        assert dec.stats()["num_requests_waiting"] == 1
        _run_to_finish(dec, did)
        assert seq.output_token_ids == ref.output_token_ids
        # The win was a restore, not a recompute.
        assert dec.offload.restored_pages > 0
        assert dec.offload.remote.hits > 0
        assert dec.offload.stats()["cluster_hits"] > 0
    finally:
        stop()


def test_cold_start_miss_computes_without_waiting():
    """Shared tier up but empty: the probe answers a definitive miss
    and the sequence computes on the next admission pass — and the
    recorded demand is what later promotes the chain."""
    url, stop = _serve_app_in_thread(
        build_cache_server(64 * 1024 ** 2))
    try:
        prompt = list(range(201, 250))
        ref = _make_engine(None, offload=False).generate(
            list(prompt), _sampling())
        dec = _make_engine(url)
        did = dec.add_request(list(prompt), _sampling())
        seq = _run_to_finish(dec, did)
        assert seq.output_token_ids == ref.output_token_ids
        assert dec.offload.restored_pages == 0
        assert dec.offload.remote.misses == 0  # probe is HEAD-only
    finally:
        stop()


def test_cold_start_tier_down_degrades_immediately():
    """Remote tier unreachable: unlike a disagg handoff (which waits
    out handoff_timeout_s for pages that WERE shipped), a cold-start
    probe has nothing in flight — it must compute on the very first
    admission pass, not park for the timeout."""
    prompt = list(range(61, 110))
    ref = _make_engine(None, offload=False).generate(
        list(prompt), _sampling())
    dec = _make_engine(_free_port_url())
    t0 = time.monotonic()
    did = dec.add_request(list(prompt), _sampling())
    seq = _run_to_finish(dec, did)
    assert time.monotonic() - t0 < dec.config.handoff_timeout_s
    assert seq.output_token_ids == ref.output_token_ids
    assert dec.offload.restored_pages == 0


def test_abort_during_cold_start_probe_leaks_no_pages():
    """Regression guard: aborting a request while it is parked for the
    cold-start probe (and aborting one that restored and started
    decoding) must leave zero pages referenced."""
    from production_stack_tpu.engine.sequence import SequenceState

    url, stop = _serve_app_in_thread(
        build_cache_server(256 * 1024 ** 2))
    try:
        prompt = list(range(1, 50))
        pre = _make_engine(url, role="prefill")
        sid = pre.add_request(list(prompt), _sampling(),
                              handoff_prefill=True)
        outs = []
        while not outs or not outs[-1].finished:
            outs.extend(pre.step())

        dec = _make_engine(url)
        # Abort while still parked in AWAITING_KV.
        a = dec.add_request(list(prompt), _sampling())
        assert dec.sequences[a].state == SequenceState.AWAITING_KV
        dec.abort_request(a)
        assert dec.cache_manager.num_used_pages == 0
        assert not dec.scheduler.has_work()
        # Abort mid-flight: probe admitted, restore + prefill ran.
        b = dec.add_request(list(prompt), _sampling())
        for _ in range(3):
            dec.step()
        dec.abort_request(b)
        while dec.has_work():
            dec.step()
        assert dec.cache_manager.num_used_pages == 0
    finally:
        stop()
