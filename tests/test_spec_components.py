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


# ---- verify_proposal: a proposal that is a distribution -------------------


def _planes(x):
    """``[B, S, V]`` -> the S dense ``[B, V]`` planes the rule takes."""
    x = jnp.asarray(x)
    return tuple(x[:, j] for j in range(x.shape[1]))


def _verify_q(logits, drafts, lens, temps, proposal, seed=0, top_k=None,
              top_p=None):
    """``proposal [B, S-1, V]``: the proposer's raw logits, which the
    rule reads under the row's own temperature and mask."""
    from production_stack_tpu.ops.sampling import verify_proposal

    b = logits.shape[0]
    return np.asarray(verify_proposal(
        _planes(logits), jnp.asarray(drafts, jnp.int32),
        jnp.asarray(lens, jnp.int32), _planes(proposal),
        jnp.asarray(temps, jnp.float32),
        jnp.ones((b,), jnp.float32) if top_p is None
        else jnp.asarray(top_p, jnp.float32),
        jnp.zeros((b,), jnp.int32) if top_k is None
        else jnp.asarray(top_k, jnp.int32),
        jax.random.PRNGKey(seed)))


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
    """A proposal that is a point mass at each draft (logits 0 there
    and -1e30 elsewhere) is the prompt-lookup form: the same rows come
    out as from ``spec_verify``, case for case of the tests above."""
    logits = _point_logits(logits)
    point = np.full((1, 3, 16), -1e30, np.float32)
    for j, d in enumerate(drafts[0]):
        point[0, j, max(d, 0)] = 0.0
    assert _verify_q(logits, drafts, lens, temps,
                     point)[0].tolist() == want, case
    assert _verify(logits, drafts, lens, temps)[0].tolist() == want


LP = [0.5, 1.0, -1.0, 0.2, 2.0, 0.0]
LQ = [1.5, -1.0, 0.3, 0.2, 0.0, 1.0]


def _proposal_case(n, temperature=0.8, top_k=0):
    """n rows of one target (``LP``, then ``LP / 2`` after an accepted
    draft) and one proposer (``LQ``), the drafts drawn as the burst
    draws them: ``(logits [n, 2, 6], drafts, temps, proposal [n, 6],
    q, p, p2)``, the last three as probabilities."""
    from production_stack_tpu.ops.sampling import (
        _mask_top_k_top_p,
        draw_proposal,
    )
    lp, lq = jnp.asarray(LP), jnp.asarray(LQ)
    temps = jnp.full((n,), temperature)
    ones, ks = jnp.ones((n,)), jnp.full((n,), top_k, jnp.int32)
    logits = jnp.tile(jnp.stack([lp, lp * 0.5])[None], (n, 1, 1))
    proposal = jnp.tile(lq[None], (n, 1))
    drafts = draw_proposal(proposal, temps, ones, ks,
                           jax.random.PRNGKey(7))
    q = jax.nn.softmax(_mask_top_k_top_p(
        proposal[:1] / temperature, ones[:1], ks[:1]))[0]
    p = np.asarray(jax.nn.softmax(lp / temperature))
    p2 = np.asarray(jax.nn.softmax(lp * 0.5 / temperature))
    return logits, drafts, temps, proposal, np.asarray(q), p, p2


def test_a_sampled_proposal_leaves_the_targets_distribution():
    """Drafts drawn from ``q``, verified against ``p``: the first
    emitted token is distributed as ``p`` (accepted draft or residual
    draw), the share accepted is ``1 - TV(p, q)``, and the token after
    an accepted draft as the second position's ``p``. 40000 rows, six
    tokens: sampling noise under 0.01."""
    n = 40000
    logits, drafts, temps, proposal, q, p, p2 = _proposal_case(n)
    drawn = np.bincount(np.asarray(drafts), minlength=6) / n
    assert np.abs(drawn - q).max() < 0.01         # the drafts are q's
    out = _verify_q(logits, np.asarray(drafts)[:, None], np.ones(n),
                    np.asarray(temps), proposal[:, None], seed=1)
    first = np.bincount(out[:, 0], minlength=6) / n
    assert np.abs(first - p).max() < 0.01
    accepted = out[:, 1] >= 0
    overlap = 1 - 0.5 * np.abs(p - q).sum()
    assert abs(accepted.mean() - overlap) < 0.01
    assert (out[accepted, 0] == np.asarray(drafts)[accepted]).all()
    second = np.bincount(out[accepted, 1], minlength=6) / accepted.sum()
    assert np.abs(second - p2).max() < 0.015


def test_always_accepting_would_fail_that_bound():
    """The control: the first token taken from ``q`` outright is 0.3
    from ``p`` in its worst cell, thirty times the bound."""
    _, _, _, _, q, p, _ = _proposal_case(4)
    assert np.abs(q - p).max() > 0.3


def test_a_row_without_a_draft_and_a_greedy_row_beside_sampled_ones():
    """``draft_lens`` 0 draws one token from ``p`` whatever the
    proposal holds; a greedy row accepts its draft iff it is the
    argmax."""
    n = 20000
    logits, drafts, temps, proposal, _, p, _ = _proposal_case(n)
    lens = np.ones(n, np.int32)
    lens[::2] = 0
    temps = np.asarray(temps).copy()
    temps[1] = temps[3] = 0.0
    drafts = np.asarray(drafts).copy()
    drafts[1], drafts[3] = 4, 2               # the argmax, and not
    out = _verify_q(logits, drafts[:, None], lens, temps,
                    proposal[:, None], seed=2)
    assert (out[::2, 1] == -1).all()
    plain = np.bincount(out[::2, 0], minlength=6) / (n // 2)
    assert np.abs(plain - p).max() < 0.015
    assert out[1].tolist()[0] == 4 and out[1].tolist()[1] >= 0
    assert out[3].tolist() == [4, -1]


def test_top_k_masks_target_and_proposal_alike():
    """Under top-k 2 both distributions live on their own two largest
    tokens: nothing outside the target's two is ever emitted."""
    n = 4000
    logits, drafts, temps, proposal, q, _, _ = _proposal_case(n, top_k=2)
    assert set(np.flatnonzero(q)) == {0, 5}
    assert set(np.asarray(drafts).tolist()) == {0, 5}
    out = _verify_q(logits, np.asarray(drafts)[:, None], np.ones(n),
                    np.asarray(temps), proposal[:, None], seed=4,
                    top_k=np.full(n, 2, np.int32))
    assert set(out[:, 0].tolist()) <= {1, 4}      # the target's two


def test_a_greedy_batch_drafts_and_verifies_by_the_argmax_alone():
    """No stochastic row: the draft is the proposer's argmax and the
    rule its greedy branch, whatever the key."""
    from production_stack_tpu.ops.sampling import draw_proposal
    n = 8
    logits, _, _, proposal, _, _, _ = _proposal_case(n)
    zeros = jnp.zeros((n,))
    drafts = draw_proposal(proposal, zeros, jnp.ones((n,)),
                           jnp.zeros((n,), jnp.int32),
                           jax.random.PRNGKey(5))
    assert np.asarray(drafts).tolist() == [0] * n  # LQ's argmax
    out = _verify_q(logits, np.full((n, 1), 4), np.ones(n),
                    np.zeros(n), proposal[:, None])
    assert out.tolist() == [[4, 4]] * n            # LP's argmax, twice


def _reference_rule(lp, lq, draft, has_draft, temperature, top_k, top_p):
    """One row of the rule in float64 NumPy: ``(P(accept), the
    replacement's weights after a rejection, the weights at the bonus
    offset)`` for target logits ``lp [2, V]`` and proposal ``lq [V]``.
    A greedy row (temperature 0): acceptance 0 or 1 and one-hot
    weights at the raw argmax (what it commits)."""
    def dist(x):
        x = np.asarray(x, np.float64)
        if temperature == 0:
            return np.eye(len(x))[int(np.argmax(x))]
        x = x / temperature
        order = np.argsort(-x, kind="stable")
        e = np.exp(x[order] - x[order][0])
        sp = e / e.sum()
        keep = np.arange(len(x)) < (top_k if top_k > 0 else len(x))
        keep &= (np.cumsum(sp) - sp) < top_p
        out = np.zeros(len(x))
        out[order[keep]] = e[keep] / e[keep].sum()
        return out

    p0, p1 = dist(lp[0]), dist(lp[1])
    if not has_draft:
        return 0.0, p0, p1
    if temperature == 0:
        return float(draft == np.argmax(lp[0])), p0, p1
    q = dist(lq)
    residual = np.maximum(p0 - q, 0.0)
    return (min(1.0, p0[draft] / q[draft]), residual / residual.sum(), p1)


RULE_ROWS = [
    # name, temperature, top_k, top_p, has a draft
    ("stochastic", 0.7, 0, 1.0, True),
    ("stochastic, hot", 1.3, 0, 1.0, True),
    ("greedy", 0.0, 0, 1.0, True),
    ("greedy, its draft off the argmax", 0.0, 0, 1.0, True),
    ("draftless", 0.7, 0, 1.0, False),
    ("top-k", 0.7, 20, 1.0, True),
    ("top-p", 0.7, 0, 0.8, True),
    ("top-k and top-p", 0.9, 50, 0.9, True),
]


@pytest.mark.parametrize("masked", [False, True],
                         ids=["plain branch", "masked branch"])
def test_the_rules_probabilities_against_a_float64_reference(masked):
    """No draw in the comparison: ``_proposal_rule`` is the part of
    ``verify_proposal`` that draws nothing, and hands what the draws
    are made from to a function of the caller's. The rule accepts
    where ``u q(d) < p(d)``, so ``p(d)`` and ``q(d)`` are its
    acceptance probability ``min(1, p/q)``; the replacement is drawn
    from the log-weights by Gumbel-max. Both against float64 NumPy at
    a vocabulary of 1000, over the rows of ``RULE_ROWS``; the
    top-k/top-p rows are left out of the plain batch (one such row
    takes the whole batch through the sort). A greedy row's commits
    are no draw: they are read off the whole rule."""
    from production_stack_tpu.ops import sampling

    rows = [r for r in RULE_ROWS if masked or (r[2] == 0 and r[3] == 1.0)]
    rng = np.random.RandomState(11)
    vocab, n = 1000, len(rows)
    lp = (rng.randn(n, 2, vocab) * 2.0).astype(np.float32)
    lq = (0.6 * lp[:, 0] + 1.6 * rng.randn(n, vocab)).astype(np.float32)
    temps = np.asarray([r[1] for r in rows], np.float32)
    top_k = np.asarray([r[2] for r in rows], np.int32)
    top_p = np.asarray([r[3] for r in rows], np.float32)
    lens = np.asarray([int(r[4]) for r in rows], np.int32)
    # Each row's draft: a token its proposal gives real mass (the
    # proposer's third largest), the target's argmax for the greedy row.
    drafts = np.argsort(-lq, axis=-1)[:, 2].astype(np.int32)
    for i, r in enumerate(rows):
        if r[0] == "greedy":
            drafts[i] = int(np.argmax(lp[i, 0]))
    planes = _planes(lp)

    def finish(p_draft, q_draft, log_weights):
        offsets = jnp.arange(2)[:, None] * jnp.ones((n,), jnp.int32)
        return (jnp.minimum(1.0, p_draft / q_draft),
                jnp.stack([log_weights(a) for a in offsets]))

    accept_p, log_w = (np.asarray(x, np.float64) for x in
                       sampling._proposal_rule(
        planes, (jnp.asarray(lq),), jnp.asarray(drafts)[:, None],
        jnp.asarray(lens), jnp.asarray(temps), jnp.asarray(top_p),
        jnp.asarray(top_k), [jnp.argmax(x, axis=-1) for x in planes],
        finish))
    out = _verify_q(jnp.asarray(lp), drafts[:, None], lens, temps,
                    lq[:, None], seed=3, top_k=top_k, top_p=top_p)
    for i, r in enumerate(rows):
        want_accept, want_reject, want_bonus = _reference_rule(
            lp[i], lq[i], int(drafts[i]), r[4], r[1], r[2], r[3])
        if r[1] == 0:
            first = int(np.argmax(want_reject))
            assert out[i].tolist() == (
                [first, int(np.argmax(want_bonus))] if want_accept
                else [first, -1]), r[0]
            continue
        if r[4]:
            assert accept_p[i, 0] == pytest.approx(want_accept,
                                                   rel=2e-4), r[0]
        else:
            assert out[i, 1] == -1      # nothing to accept
        # Offset 0 after a rejection (or with no draft), offset 1 after
        # the acceptance: the weights, normalised here.
        for offset, want in ((0, want_reject), (1, want_bonus)):
            if offset == 1 and not r[4]:
                continue        # a draftless row never reaches offset 1
            w = np.exp(log_w[offset, i])
            w = w / w.sum()
            assert np.abs(w - want).max() < 2e-6, (r[0], offset)
            assert (w[want == 0] < 1e-9).all(), (r[0], offset)


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
