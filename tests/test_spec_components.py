"""Unit tests for the speculative-decoding building blocks: the
prompt-lookup proposer, the vectorized acceptance rule, config/feature
gating, and the metrics surfaces. Fast lane — no engine end-to-end
runs here (those live in test_spec_decode.py, slow lane)."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.spec import NgramProposer


def _seq(tokens, seq_id="s0"):
    return SimpleNamespace(seq_id=seq_id, all_token_ids=list(tokens))


# ---- NgramProposer ---------------------------------------------------------


def test_proposer_basic_lookup():
    # ... 7 8 9 10 ... 7 8 -> continuation 9 10
    p = NgramProposer(k=4, min_match=2)
    drafts = p.propose(_seq([1, 7, 8, 9, 10, 2, 3, 7, 8]), 4)
    assert drafts[:2] == [9, 10]


def test_proposer_no_match_returns_empty():
    p = NgramProposer(k=4, min_match=2)
    assert p.propose(_seq([1, 2, 3, 4, 5, 6]), 4) == []


def test_proposer_short_history_returns_empty():
    p = NgramProposer(k=4, min_match=2)
    assert p.propose(_seq([1, 2]), 4) == []
    assert p.propose(_seq([1, 2, 3]), 0) == []


def test_proposer_clamps_to_k_and_budget():
    p = NgramProposer(k=3, min_match=2)
    hist = [5, 6, 7, 8, 9, 5, 6]
    assert len(p.propose(_seq(hist, "a"), 10)) <= 3
    assert len(p.propose(_seq(hist, "b"), 1)) == 1


def test_proposer_periodic_self_continuation():
    """A looping tail must draft FULL-length, wrapping around the
    period — not stop at the end of recorded history. This is the
    case speculation pays most for, and where a naive slice yields
    one token per step."""
    p = NgramProposer(k=8, min_match=2)
    loop = [11, 12, 13]
    drafts = p.propose(_seq(loop * 6), 8)
    assert len(drafts) == 8
    # History ends ...11 12 13; the continuation keeps looping.
    expect = [loop[i % 3] for i in range(8)]
    assert drafts == expect


def test_proposer_period_one_loop():
    p = NgramProposer(k=6, min_match=2)
    drafts = p.propose(_seq([3, 9, 9, 9, 9, 9]), 6)
    assert drafts == [9] * 6


def test_proposer_prefers_longer_backward_match():
    """Two occurrences of the tail bigram with different
    continuations: the one whose preceding context also matches
    (max-match) wins even though the other is more recent."""
    p = NgramProposer(k=2, min_match=2)
    #       [ctx-match]            [recent, no ctx]
    hist = [40, 41, 1, 2, 77, 77, 50, 1, 2, 88, 88, 40, 41, 1, 2]
    assert p.propose(_seq(hist), 2) == [77, 77]


def test_proposer_candidate_scan_is_capped():
    """A constant-token history indexes O(n) occurrences of the same
    gram; proposal must stay cheap (MAX_CANDIDATES scored, and the
    capped backward scan short-circuits on the first max hit)."""
    p = NgramProposer(k=4, min_match=2)
    drafts = p.propose(_seq([7] * 5000), 4)
    assert drafts == [7, 7, 7, 7]


def test_proposer_drop_releases_index():
    p = NgramProposer(k=4, min_match=2)
    p.propose(_seq([1, 2, 3, 1, 2], "gone"), 4)
    assert "gone" in p._index
    p.drop("gone")
    assert "gone" not in p._index
    p.drop("never-indexed")  # idempotent


def test_proposer_validates_args():
    with pytest.raises(ValueError):
        NgramProposer(k=0)
    with pytest.raises(ValueError):
        NgramProposer(k=2, min_match=0)


# ---- spec_verify acceptance rule ------------------------------------------


def _point_logits(targets, vocab=16, scale=50.0):
    """[1, S, V] logits whose argmax (and ~all mass) at offset j is
    targets[j]."""
    s = len(targets)
    out = np.zeros((1, s, vocab), np.float32)
    for j, t in enumerate(targets):
        out[0, j, t] = scale
    return jnp.asarray(out)


def _verify(logits, drafts, lens, temps):
    from production_stack_tpu.ops.sampling import spec_verify

    b = logits.shape[0]
    return np.asarray(spec_verify(
        logits, jnp.asarray(drafts, jnp.int32),
        jnp.asarray(lens, jnp.int32),
        jnp.asarray(temps, jnp.float32),
        jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.int32),
        jax.random.PRNGKey(0)))


def test_verify_greedy_partial_accept():
    logits = _point_logits([3, 5, 7, 9])
    out = _verify(logits, [[3, 5, 2]], [3], [0.0])
    # Drafts 3,5 match the argmax chain; 2 != 7 rejects, the
    # correction is the target argmax at the rejection offset.
    assert out.tolist() == [[3, 5, 7, -1]]


def test_verify_greedy_full_accept_emits_bonus():
    logits = _point_logits([3, 5, 7, 9])
    out = _verify(logits, [[3, 5, 7]], [3], [0.0])
    assert out.tolist() == [[3, 5, 7, 9]]


def test_verify_greedy_zero_drafts_is_plain_decode():
    logits = _point_logits([3, 5, 7, 9])
    out = _verify(logits, [[-1, -1, -1]], [0], [0.0])
    assert out.tolist() == [[3, -1, -1, -1]]


def test_verify_greedy_first_reject_stops_acceptance():
    # A later "match" after a rejection must not count.
    logits = _point_logits([3, 5, 7, 9])
    out = _verify(logits, [[4, 5, 7]], [3], [0.0])
    assert out.tolist() == [[3, -1, -1, -1]]


def test_verify_stochastic_point_mass_accepts():
    """With near-point-mass target distributions, rejection sampling
    accepts drafts equal to the mass point w.p. ~1 and the bonus
    sample is the mass point."""
    logits = _point_logits([3, 5, 7, 9])
    out = _verify(logits, [[3, 5, 7]], [3], [1.0])
    assert out.tolist() == [[3, 5, 7, 9]]


def test_verify_stochastic_rejects_off_mass_draft():
    logits = _point_logits([3, 5, 7, 9])
    out = _verify(logits, [[4, 5, 7]], [3], [1.0])
    row = out[0].tolist()
    # Rejected at offset 0; exactly one emitted token drawn from the
    # residual (draft token 4 removed) — the mass point 3.
    assert row == [3, -1, -1, -1]


def test_verify_mixed_batch_keeps_greedy_rows_exact():
    """A stochastic row in the batch must not perturb a greedy row's
    byte-exact acceptance (the whole-batch stochastic branch still
    applies the greedy rule per-row)."""
    targets = [3, 5, 7, 9]
    logits = jnp.concatenate(
        [_point_logits(targets), _point_logits(targets)])
    out = _verify(logits, [[3, 5, 2], [3, 5, 7]], [3, 3], [0.0, 1.0])
    assert out[0].tolist() == [3, 5, 7, -1]
    assert out[1].tolist()[:3] == [3, 5, 7]


# ---- spec_verify with a proposal that is a distribution -------------------


def _verify_q(logits, drafts, lens, temps, q, seed=0, top_k=None):
    from production_stack_tpu.ops.sampling import spec_verify

    b = logits.shape[0]
    return np.asarray(spec_verify(
        logits, jnp.asarray(drafts, jnp.int32),
        jnp.asarray(lens, jnp.int32),
        jnp.asarray(temps, jnp.float32),
        jnp.ones((b,), jnp.float32),
        jnp.zeros((b,), jnp.int32) if top_k is None
        else jnp.asarray(top_k, jnp.int32),
        jax.random.PRNGKey(seed), draft_probs=jnp.asarray(q)))


@pytest.mark.parametrize("case,logits,drafts,lens,temps,want", [
    ("greedy partial accept", [3, 5, 7, 9], [[3, 5, 2]], [3], [0.0],
     [3, 5, 7, -1]),
    ("greedy full accept emits the bonus", [3, 5, 7, 9], [[3, 5, 7]], [3],
     [0.0], [3, 5, 7, 9]),
    ("zero drafts is plain decode", [3, 5, 7, 9], [[-1, -1, -1]], [0],
     [0.0], [3, -1, -1, -1]),
    ("the first reject stops acceptance", [3, 5, 7, 9], [[4, 5, 7]], [3],
     [0.0], [3, -1, -1, -1]),
    ("a stochastic point mass accepts", [3, 5, 7, 9], [[3, 5, 7]], [3],
     [1.0], [3, 5, 7, 9]),
    ("a stochastic off-mass draft is rejected", [3, 5, 7, 9], [[4, 5, 7]],
     [3], [1.0], [3, -1, -1, -1]),
])
def test_the_one_hot_proposal_reads_as_the_point_mass_rule(
        case, logits, drafts, lens, temps, want):
    """``draft_probs`` = one-hot at each draft is the prompt-lookup
    form: the same rows come out as from the rule without the
    argument, case for case of the tests above."""
    logits = _point_logits(logits)
    one_hot = np.zeros((1, 3, 16), np.float32)
    for j, d in enumerate(drafts[0]):
        one_hot[0, j, max(d, 0)] = 1.0
    assert _verify_q(logits, drafts, lens, temps,
                     one_hot)[0].tolist() == want, case
    assert _verify(logits, drafts, lens, temps)[0].tolist() == want


def _proposal_case(n, temperature=0.8):
    from production_stack_tpu.ops.sampling import sampling_probs
    lp = jnp.asarray([0.5, 1.0, -1.0, 0.2, 2.0, 0.0])
    lq = jnp.asarray([1.5, -1.0, 0.3, 0.2, 0.0, 1.0])
    temps = jnp.full((n,), temperature)
    logits = jnp.tile(jnp.stack([lp, lp * 0.5])[None], (n, 1, 1))
    q = sampling_probs(jnp.tile(lq[None], (n, 1)), temps,
                       jnp.ones((n,)), jnp.zeros((n,), jnp.int32))
    drafts = jax.random.categorical(jax.random.PRNGKey(7),
                                    jnp.log(q)).astype(jnp.int32)
    p = np.asarray(jax.nn.softmax(lp / temperature))
    p2 = np.asarray(jax.nn.softmax(lp * 0.5 / temperature))
    return logits, drafts, temps, q, p, p2


def test_a_sampled_proposal_leaves_the_targets_distribution():
    """Drafts drawn from ``q``, verified against ``p``: the first
    emitted token is distributed as ``p`` (accepted draft or residual
    draw), the share accepted is ``1 - TV(p, q)``, and the token after
    an accepted draft as the second position's ``p``. 40000 rows, six
    tokens: sampling noise under 0.01."""
    n = 40000
    logits, drafts, temps, q, p, p2 = _proposal_case(n)
    out = _verify_q(logits, np.asarray(drafts)[:, None], np.ones(n),
                    np.asarray(temps), q[:, None], seed=1)
    first = np.bincount(out[:, 0], minlength=6) / n
    assert np.abs(first - p).max() < 0.01
    accepted = out[:, 1] >= 0
    overlap = 1 - 0.5 * np.abs(p - np.asarray(q[0])).sum()
    assert abs(accepted.mean() - overlap) < 0.01
    assert (out[accepted, 0] == np.asarray(drafts)[accepted]).all()
    second = np.bincount(out[accepted, 1], minlength=6) / accepted.sum()
    assert np.abs(second - p2).max() < 0.015


def test_always_accepting_would_fail_that_bound():
    """The control: the first token taken from ``q`` outright is 0.3
    from ``p`` in its worst cell, thirty times the bound."""
    _, _, _, q, p, _ = _proposal_case(4)
    assert np.abs(np.asarray(q[0]) - p).max() > 0.3


def test_a_row_without_a_draft_and_a_greedy_row_beside_sampled_ones():
    """``draft_lens`` 0 draws one token from ``p`` whatever ``q`` holds;
    a greedy row accepts its draft iff it is the argmax."""
    n = 20000
    logits, drafts, temps, q, p, _ = _proposal_case(n)
    lens = np.ones(n, np.int32)
    lens[::2] = 0
    temps = np.asarray(temps).copy()
    temps[1] = temps[3] = 0.0
    drafts = np.asarray(drafts).copy()
    drafts[1], drafts[3] = 4, 2               # the argmax, and not
    out = _verify_q(logits, drafts[:, None], lens, temps, q[:, None],
                    seed=2)
    assert (out[::2, 1] == -1).all()
    plain = np.bincount(out[::2, 0], minlength=6) / (n // 2)
    assert np.abs(plain - p).max() < 0.015
    assert out[1].tolist()[0] == 4 and out[1].tolist()[1] >= 0
    assert out[3].tolist() == [4, -1]


def test_top_k_masks_target_and_proposal_alike():
    """Under top-k 2 both distributions live on their own two largest
    tokens: nothing outside the target's two is ever emitted."""
    from production_stack_tpu.ops.sampling import sampling_probs
    n = 4000
    logits, _, temps, _, _, _ = _proposal_case(n)
    top_k = np.full(n, 2, np.int32)
    lq = jnp.asarray([1.5, -1.0, 0.3, 0.2, 0.0, 1.0])
    q = sampling_probs(jnp.tile(lq[None], (n, 1)), temps, jnp.ones((n,)),
                       jnp.asarray(top_k))
    assert set(np.flatnonzero(np.asarray(q[0]))) == {0, 5}
    drafts = jax.random.categorical(jax.random.PRNGKey(3),
                                    jnp.log(q)).astype(jnp.int32)
    out = _verify_q(logits, np.asarray(drafts)[:, None], np.ones(n),
                    np.asarray(temps), q[:, None], seed=4, top_k=top_k)
    assert set(out[:, 0].tolist()) <= {1, 4}      # the target's two


# ---- config + feature gating ----------------------------------------------


def _sched(**kw):
    from production_stack_tpu.engine.config import (
        CacheConfig,
        EngineConfig,
        SchedulerConfig,
        tiny_model_config,
    )

    return EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=64),
        scheduler=SchedulerConfig(max_num_seqs=2, max_model_len=128,
                                  prefill_chunk_size=32, **kw),
    )


def test_config_spec_composes_with_decode_steps():
    cfg = _sched(speculative_k=4, decode_steps=4)
    assert cfg.scheduler.speculative_k == 4


def test_config_spec_rejects_deferred_kv():
    with pytest.raises(ValueError, match="deferred_kv"):
        _sched(speculative_k=4, decode_steps=4, deferred_kv_writes=True)


def test_config_spec_rejects_bad_min_match():
    with pytest.raises(ValueError, match="min_match"):
        _sched(speculative_k=4, speculative_min_match=0)


def test_deferred_kv_eligibility_excludes_spec():
    from production_stack_tpu.engine.model_runner import (
        deferred_kv_eligible,
    )

    base = dict(architecture="llama", decode_steps=4)
    assert deferred_kv_eligible(**base)
    assert not deferred_kv_eligible(**base, speculative_k=4)


# ---- metrics surfaces ------------------------------------------------------


def test_metrics_render_spec_counters():
    from production_stack_tpu.engine.metrics import EngineMetrics

    m = EngineMetrics()
    m.on_spec_step(drafted=8, accepted=5)
    m.on_spec_step(drafted=4, accepted=4)
    text = "\n".join(m.render())
    assert "vllm:spec_decode_num_draft_tokens_total 12" in text
    assert "vllm:spec_decode_num_accepted_tokens_total 9" in text


def test_router_scrapes_spec_counters():
    from production_stack_tpu.router.stats.engine_stats import (
        EngineStats,
    )

    text = "\n".join([
        "# TYPE vllm:num_requests_running gauge",
        "vllm:num_requests_running 2.0",
        "# TYPE vllm:gpu_prefix_cache_hit_rate gauge",
        "vllm:gpu_prefix_cache_hit_rate 0.25",
        "# TYPE vllm:spec_decode_num_draft_tokens_total counter",
        "vllm:spec_decode_num_draft_tokens_total 120.0",
        "# TYPE vllm:spec_decode_num_accepted_tokens_total counter",
        "vllm:spec_decode_num_accepted_tokens_total 90.0",
        "",
    ])
    stats = EngineStats.from_prometheus_text(text)
    assert stats.spec_decode_num_draft_tokens == 120.0
    assert stats.spec_decode_num_accepted_tokens == 90.0


def test_router_reexports_scraped_spec_gauges():
    """refresh_gauges surfaces the scraped engine counters on the
    router's own /metrics exposition, labeled per server."""
    from production_stack_tpu.router.services import metrics_service
    from production_stack_tpu.router.stats.engine_stats import (
        EngineStats,
        initialize_engine_stats_scraper,
    )
    from production_stack_tpu.router.stats.request_stats import (
        initialize_request_stats_monitor,
    )

    initialize_request_stats_monitor(60.0)
    scraper = initialize_engine_stats_scraper(scrape_interval=3600.0)
    try:
        with scraper._lock:
            scraper._stats = {"http://e1:8000": EngineStats(
                kv_cache_hit_rate=0.5,
                spec_decode_num_draft_tokens=40.0,
                spec_decode_num_accepted_tokens=30.0)}
        metrics_service.refresh_gauges()
        g = metrics_service.spec_decode_num_draft_tokens
        assert g.labels(server="http://e1:8000")._value.get() == 40.0
        g = metrics_service.spec_decode_num_accepted_tokens
        assert g.labels(server="http://e1:8000")._value.get() == 30.0
        g = metrics_service.engine_prefix_cache_hit_rate
        assert g.labels(server="http://e1:8000")._value.get() == 0.5
    finally:
        scraper.close()
