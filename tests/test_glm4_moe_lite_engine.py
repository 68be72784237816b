"""GLM-4 MoE lite through the engine with its prediction module
drafting inside the deferred burst: the scheduler's reservation of two
tokens an iteration, prefill steps that fill the module's cache entry,
bursts that verify a draft a row and commit one or two tokens, tails
flushed by each row's own count, budgets and stop tokens that cut
inside a pair, a draftless row beside rows that draft, preemption and
resume, and what start-up says and refuses (the model and its ops:
tests/test_glm4_moe_lite.py).

Tiny widths, float32, seeded, on the CPU, with a vocabulary of 16: a
greedy draft is accepted at chance about once in 16, and at temperature
1 the flat distributions of tiny random weights overlap so far that
most drafts are. The oracle is the plain reference of the family
(chipbench/reference/glm4_moe_lite_family.py). ``FLOAT32`` 2e-5 on
log-probabilities (the readings are under 2e-6).
"""

import dataclasses
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import glm4_moe_lite_family as reference
from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_glm4_moe_lite_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models import registry

FLOAT32 = 2e-5
VOCAB = 16


def model_config(vocab_size=VOCAB, **over):
    config = tiny_glm4_moe_lite_config(vocab_size=vocab_size)
    config.attention_impl = "xla"
    return dataclasses.replace(config, **over)


def engine_config(model=None, draft=True, num_pages=64, **scheduler):
    sched = dict(max_num_seqs=4, max_model_len=256, prefill_chunk_size=32,
                 prefill_batch_size=2, decode_steps=4,
                 deferred_kv_writes=True, draft_module=draft)
    sched.update(scheduler)
    return EngineConfig(
        model=model or model_config(),
        cache=CacheConfig(page_size=16, num_pages=num_pages),
        scheduler=SchedulerConfig(**sched))


def prompt_of(n, seed=0):
    return [int(t) for t in
            np.random.RandomState(seed).randint(0, VOCAB, size=n)]


def finish(engine, seqs):
    while any(s.state.name not in ("FINISHED", "ABORTED") for s in seqs):
        engine.step()


def generate(engine, prompts, **sampling):
    sampling = {"temperature": 0.0, "max_tokens": 40, "ignore_eos": True,
                **sampling}
    ids = [engine.add_request(p, SamplingParams(**sampling))
           for p in prompts]
    seqs = [engine.sequences[i] for i in ids]
    finish(engine, seqs)
    return seqs


PROMPTS = [prompt_of(n, seed=n) for n in (70, 20, 45, 33, 64, 12)]


# An engine of one configuration is built once a module
# (docs/source/dev_guide/testing.md): the cases that take
# ``engine_config()`` or ``engine_config(draft=False)`` as they stand
# share these two. A shared engine carries its prefix cache, its
# counters and its sampler's key from case to case, so those cases
# assert a counter's difference and never an unseeded stream; a case
# that needs a cold prefix cache, preempts, leaves a request in flight
# or swaps the tracer builds its own.


@pytest.fixture(scope="module")
def drafting():
    return LLMEngine(engine_config())


@pytest.fixture(scope="module")
def draftless():
    engine = LLMEngine(engine_config(draft=False))
    assert engine.config.model.num_nextn_predict_layers == 0
    assert len(engine.runner.k_cache) == 3 + 1       # no entry for it
    assert "mtp_enorm" not in engine.runner.params
    return engine


def drafted_and_accepted(engine):
    return (engine.metrics.spec_draft_tokens_total,
            engine.metrics.spec_accepted_tokens_total)


@pytest.fixture(scope="module")
def without_drafts(draftless):
    """Greedy answers of 40 tokens with the module switched off."""
    return [s.output_token_ids for s in generate(draftless, PROMPTS)]


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_greedy_output_with_drafts_equals_the_output_without(
        impl, without_drafts, drafting):
    """Six prompts over four rows, 40 tokens each, some 200 verify
    iterations: token for token what the same model gives with the
    module off, whatever was drafted; about one greedy draft in 16 is
    accepted, so both commits of a pair are among them."""
    engine = drafting if impl == "xla" else LLMEngine(
        engine_config(model_config(attention_impl=impl)))
    before = drafted_and_accepted(engine)
    seqs = generate(engine, PROMPTS)
    assert [s.output_token_ids for s in seqs] == without_drafts
    drafted, accepted = np.subtract(drafted_and_accepted(engine), before)
    assert drafted > 100
    assert 0 < accepted < drafted / 4
    # Four latent planes (three layers and the module's) and the
    # counters; no second plane anywhere.
    assert [e.shape for e in engine.runner.k_cache] == [
        (1, 64, 32, 16)] * 4 + [(8,)]
    assert engine.runner.v_cache == (None,) * 4


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
def test_sampled_bursts_agree_with_the_reference_at_every_commit(impl):
    """At temperature 1 most drafts are accepted, so most iterations
    commit a pair and the rows run apart (tails flushed by each row's
    own count, burst after burst): the target's raw log-probabilities
    served at EVERY committed position, an accepted draft's and the
    token's after it among them, agree with the reference's full
    forward pass on the sequence that came out. (A vocabulary of 32:
    the served top log-probabilities are 20 wide.)"""
    engine = LLMEngine(engine_config(model_config(vocab_size=32,
                                                  attention_impl=impl)))
    ids = [engine.add_request(p, SamplingParams(
        temperature=1.0, max_tokens=30, ignore_eos=True, logprobs=True,
        top_logprobs=5)) for p in PROMPTS]
    seqs = [engine.sequences[i] for i in ids]
    served = {i: [] for i in ids}
    apart = False
    while any(s.state.name not in ("FINISHED", "ABORTED") for s in seqs):
        for out in engine.step():
            if out.new_token is not None:
                served[out.seq_id].append(out.logprobs)
        lengths = {len(s.output_token_ids) for s in seqs
                   if s.state.name == "RUNNING"}
        apart = apart or len(lengths) > 1
    assert apart
    drafted = engine.metrics.spec_draft_tokens_total
    accepted = engine.metrics.spec_accepted_tokens_total
    assert accepted > drafted / 2 > 20
    ref = reference.model_of(engine.config.model, engine.runner.params)
    worst = 0.0
    for prompt, seq in zip(PROMPTS, seqs):
        assert len(seq.output_token_ids) == 30 == len(served[seq.seq_id])
        tokens = prompt + seq.output_token_ids
        want = np.asarray(reference.log_probs(
            ref, tokens, list(range(len(prompt) - 1, len(tokens) - 1))))
        for j, entry in enumerate(served[seq.seq_id]):
            assert len(entry[1]) == 5
            worst = max(worst, abs(entry[0]
                                   - want[j, seq.output_token_ids[j]]))
            for tid, lp in entry[1]:
                worst = max(worst, abs(lp - want[j, tid]))
    assert worst < (2e-4 if "pallas" in impl else FLOAT32)


def test_the_sampled_distribution_is_the_targets():
    """One prompt, 3000 requests at temperature 0.1 (which sharpens the
    tiny model's flat logits until target and module disagree: between
    a tenth and nine tenths of the drafts are accepted), bursts of two
    iterations: the third output token is the first that a verify
    iteration commits (the first iteration of a burst offers no
    draft), as an accepted draft or a residual draw, and the fourth
    follows an accepted draft in the same iteration or opens the next
    burst. Their empirical marginals are within 0.06 in total variation
    of the target's own, computed exactly by the model's forward over
    all 16^3 continuations (the sampling noise of 3000 draws over 16
    cells is 0.03)."""
    engine = LLMEngine(engine_config(max_num_seqs=16, prefill_batch_size=8,
                                     decode_steps=2, num_pages=128))
    prompt = prompt_of(20, seed=5)
    temperature, draws = 0.1, 3000
    seqs = []
    for _ in range(draws // 100):
        seqs += generate(engine, [prompt] * 100, temperature=temperature,
                         max_tokens=4)
    drafted = engine.metrics.spec_draft_tokens_total
    accepted = engine.metrics.spec_accepted_tokens_total
    assert 0.1 < accepted / drafted < 0.9
    # The exact joint of the four tokens, from one batched forward.
    config, params = engine.config.model, engine.runner.params
    _, forward = registry.get_model(config)
    grid = np.stack(np.meshgrid(*[np.arange(VOCAB)] * 3, indexing="ij"),
                    -1).reshape(-1, 3)
    tokens = np.concatenate(
        [np.tile(prompt, (len(grid), 1)), grid], axis=1).astype(np.int32)
    b, t = tokens.shape
    k_cache, v_cache = registry.init_hybrid_cache(config, 1 + 2 * b, 16, 0)
    table = 1 + np.arange(2 * b, dtype=np.int32).reshape(b, 2)
    logits, _, _ = forward(
        params, config, jnp.asarray(tokens),
        jnp.tile(jnp.arange(t), (b, 1)), jnp.asarray(table),
        jnp.full((b,), t), jnp.ones((b, t), bool), k_cache, v_cache)
    p = np.asarray(jax.nn.softmax(logits[:, -4:] / temperature, -1),
                   np.float64)          # after the prompt, t0, t1, t2
    g0, g1, g2 = grid.T
    rows = np.arange(len(grid))
    prefix = p[rows, 0, g0] * p[rows, 1, g1]       # P(t0, t1)
    third = np.zeros(VOCAB)
    np.add.at(third, g2, prefix * p[rows, 2, g2])
    fourth = (prefix * p[rows, 2, g2])[:, None] * p[:, 3]
    fourth = fourth.sum(0)
    assert abs(third.sum() - 1) < 1e-6 and abs(fourth.sum() - 1) < 1e-6
    for index, exact in ((2, third), (3, fourth)):
        seen = Counter(s.output_token_ids[index] for s in seqs)
        empirical = np.asarray([seen[v] for v in range(VOCAB)]) / len(seqs)
        assert 0.5 * np.abs(empirical - exact).sum() < 0.06, index


def test_max_tokens_and_a_stop_token_cut_inside_an_accepted_pair(drafting):
    """At temperature 1 nine drafts in ten are accepted. A budget that
    ends on a pair's first token drops the second; a stop token that is
    an accepted draft ends the row and the token after it is dropped:
    no output is longer than asked and none goes on past its stop."""
    engine = drafting
    assert engine.config.scheduler.decode_steps == 4
    _, accepted_before = drafted_and_accepted(engine)
    prompts = [prompt_of(18 + i % 5, seed=i) for i in range(48)]
    for budget in (3, 4, 5):
        seqs = generate(engine, prompts[:16], temperature=1.0,
                        max_tokens=budget)
        assert all(len(s.output_token_ids) == budget for s in seqs)
        assert all(s.finish_reason.name == "LENGTH" for s in seqs)
    seqs = generate(engine, prompts, temperature=1.0, max_tokens=40,
                    ignore_eos=False, stop_token_ids=[3, 7])
    stopped = [s for s in seqs if s.finish_reason.name == "STOP"]
    assert len(stopped) > 40
    for s in seqs:
        hits = [i for i, t in enumerate(s.output_token_ids) if t in (3, 7)]
        if s.finish_reason.name == "STOP":
            assert hits == [len(s.output_token_ids) - 1]
        else:
            assert not hits and len(s.output_token_ids) == 40
    # Stops fell on both commits of a pair: at even and at odd places.
    assert len({len(s.output_token_ids) % 2 for s in stopped}) == 2
    assert (engine.metrics.spec_accepted_tokens_total
            - accepted_before) > 100


def test_a_row_with_a_penalty_runs_draftless_beside_rows_that_draft(
        without_drafts, drafting, draftless):
    """One burst program: the penalised row commits one token an
    iteration by its own rule, the others draft. All four rows give
    what they give with the module off."""
    def run(draft):
        engine = drafting if draft else draftless
        ids = [engine.add_request(p, SamplingParams(
            temperature=0.0, max_tokens=40, ignore_eos=True,
            repetition_penalty=1.0 if i else 1.3, presence_penalty=0.0
            if i else 0.5)) for i, p in enumerate(PROMPTS[:4])]
        seqs = [engine.sequences[i] for i in ids]
        finish(engine, seqs)
        return engine, [s.output_token_ids for s in seqs]

    offered_before, _ = drafted_and_accepted(drafting)
    engine, drafted = run(True)
    _, plain = run(False)
    assert drafted == plain
    assert drafted[1:] == without_drafts[1:4]
    assert drafted[0] != without_drafts[0]           # the penalty bites
    # Three of four rows offered drafts: under the 39 iterations x 4.
    offered = engine.metrics.spec_draft_tokens_total - offered_before
    assert 60 < offered <= 3 * 39


def test_a_logit_bias_and_a_min_tokens_row_run_draftless_too(
        drafting, draftless):
    """The other rewrites of a row's logits: a bias, and stop tokens
    suppressed under ``min_tokens`` (the row drafts once it is past its
    minimum: a later burst's payload says so)."""
    def run(draft):
        engine = drafting if draft else draftless
        params = [dict(logit_bias={5: 4.0}),
                  dict(min_tokens=6, ignore_eos=False,
                       stop_token_ids=list(range(8))), {}]
        ids = [engine.add_request(p, SamplingParams(**{
            "temperature": 0.0, "max_tokens": 24, "ignore_eos": True, **sp}))
            for p, sp in zip(PROMPTS[:3], params)]
        seqs = [engine.sequences[i] for i in ids]
        finish(engine, seqs)
        return [s.output_token_ids for s in seqs]

    drafted, plain = run(True), run(False)
    assert drafted == plain
    assert 6 <= len(drafted[1]) < 24 and drafted[1][-1] < 8
    assert not set(drafted[1][:5]) & set(range(8))


def test_a_seeded_row_keeps_its_stream_beside_rows_that_draft(
        drafting, draftless):
    def run(draft):
        engine = drafting if draft else draftless
        ids = [engine.add_request(p, SamplingParams(
            temperature=1.0, max_tokens=20, ignore_eos=True,
            seed=None if i else 1234)) for i, p in enumerate(PROMPTS[:3])]
        seqs = [engine.sequences[i] for i in ids]
        finish(engine, seqs)
        return seqs[0].output_token_ids

    assert run(True) == run(False)


def test_a_preempted_request_is_recomputed_with_the_modules_cache(
        without_drafts):
    """Preempted mid-generation: the pages go, the prompt and what was
    generated are prefilled again, the module's entry with them (the
    next ids are the generated ones), and the answer is the one an
    undisturbed run gives."""
    engine = LLMEngine(engine_config())
    ids = [engine.add_request(p, SamplingParams(
        temperature=0.0, max_tokens=40, ignore_eos=True))
        for p in PROMPTS[:3]]
    seqs = [engine.sequences[i] for i in ids]
    while len(seqs[0].output_token_ids) < 7:
        engine.step()
    free = engine.cache_manager.num_free_pages
    held = len(seqs[0].pages)
    engine.scheduler._preempt(seqs[0])
    assert not seqs[0].pages
    assert engine.cache_manager.num_free_pages == free + held
    finish(engine, seqs)
    assert seqs[0].all_token_ids[len(PROMPTS[0]):] == without_drafts[0]
    assert [s.output_token_ids for s in seqs[1:]] == without_drafts[1:3]


def test_a_burst_reserves_pages_for_two_tokens_an_iteration():
    """A row one token short of a page's end, a burst of 4 iterations:
    with drafts it may commit 8 tokens and holds the next page before
    the burst; without, 4 tokens fit where it is."""
    def pages_held(draft):
        engine = LLMEngine(engine_config(draft=draft))
        prompt = prompt_of(16 * 2 - 6, seed=3)
        seq = engine.sequences[engine.add_request(prompt, SamplingParams(
            temperature=0.0, max_tokens=40, ignore_eos=True))]
        engine.step()                           # prefill: one token out
        assert len(seq.output_token_ids) == 1 and len(seq.pages) == 2
        engine.scheduler.plan_step()
        return len(seq.pages)

    assert (pages_held(True), pages_held(False)) == (3, 2)


def test_a_prefix_hit_leaves_out_the_page_whose_last_latent_read_ahead():
    """The module's latent at a page's last position read the token
    after it, which the page's hash does not cover: with drafts a
    second request takes three of the four matching pages, without all
    four, and answers the same."""
    prompt = prompt_of(70, seed=11)
    for draft, hit in ((True, 48), (False, 64)):
        engine = LLMEngine(engine_config(draft=draft))
        first = generate(engine, [prompt], max_tokens=9)[0]
        second = generate(engine, [prompt], max_tokens=9)[0]
        assert engine.cache_manager.prefix_hit_tokens == hit
        assert second.output_token_ids == first.output_token_ids


def test_the_step_record_the_counters_and_the_version_say_what_drafts():
    from production_stack_tpu.engine.tracing import EngineTracer
    engine = LLMEngine(engine_config())
    engine.tracer = EngineTracer(ring_size=256)
    generate(engine, PROMPTS[:4], temperature=1.0, max_tokens=12)
    bursts = [s for s in engine.tracer.recent_steps(limit=256)
              if s.get("kind") == "decode"]
    assert bursts and all("drafts" in s and "accepted" in s
                          for s in bursts)
    assert sum(s["drafts"] for s in bursts) == \
        engine.metrics.spec_draft_tokens_total > 0
    assert sum(s["accepted"] for s in bursts) == \
        engine.metrics.spec_accepted_tokens_total > 0
    # 6 + 1 expert-layer steps... three layers here: two and the module.
    assert all(s["moe_experts_hit"] > 0 for s in bursts)
    fam = registry.family("glm4_moe_lite")
    assert fam.draft_module and fam.deferred_kv
    assert fam.counters[-2:] == ("drafts", "accepted")
    assert registry.page_cache(model_config()) == registry.PageCache(
        entries=4, heads=1, width=32, planes=1)
    assert not any(f.draft_module for name, f in registry.FAMILIES.items()
                   if name != "glm4_moe_lite")


def test_start_up_says_in_words_what_the_switch_needs_and_refuses():
    with pytest.raises(ValueError, match="draft_module needs a family"):
        from production_stack_tpu.engine.config import (
            tiny_longcat_flash_config,
        )
        EngineConfig(model=tiny_longcat_flash_config(),
                     scheduler=SchedulerConfig(
                         decode_steps=4, deferred_kv_writes=True,
                         draft_module=True))
    with pytest.raises(ValueError,
                       match="draft_module needs deferred_kv_writes"):
        EngineConfig(model=model_config(),
                     scheduler=SchedulerConfig(decode_steps=4,
                                               draft_module=True))
    # Prompt lookup stays refused over a latent, in the words that are
    # true now; a family that drafts is pointed to its own switch.
    with pytest.raises(ValueError,
                       match="speculative decoding by prompt lookup") as no:
        EngineConfig(model=model_config(),
                     scheduler=SchedulerConfig(speculative_k=2))
    assert "--draft-module" in str(no.value)
    # Off: the configuration keeps no module, so nothing makes one.
    config = engine_config(draft=False)
    assert config.model.num_nextn_predict_layers == 0
    assert not config.model.has_draft_module
    assert registry.page_cache(config.model).entries == 3


def test_the_server_resolves_the_switch_from_the_checkpoints_own_key():
    import argparse

    from production_stack_tpu.engine.server import _resolve_draft_module
    from production_stack_tpu.engine.config import tiny_longcat_flash_config

    def resolve(choice, model, deferred=True, k=0):
        return _resolve_draft_module(argparse.Namespace(
            draft_module=choice, speculative_k=k), model, deferred)

    glm, longcat = model_config(), tiny_longcat_flash_config()
    assert resolve("auto", glm) is True
    assert resolve("auto", glm, deferred=False) is False
    assert resolve("auto", model_config(num_nextn_predict_layers=0)) is False
    assert resolve("auto", longcat) is False
    assert resolve("off", glm) is False and resolve("on", longcat) is True


def test_a_glm_checkpoint_is_refused(tmp_path):
    from production_stack_tpu.engine.weights import load_weights
    with pytest.raises(NotImplementedError, match="--random-weights"):
        load_weights(str(tmp_path), model_config())


def test_the_runner_names_no_model():
    import inspect

    from production_stack_tpu.engine import model_runner
    source = inspect.getsource(model_runner)
    assert "glm" not in source.lower()
    assert "glm4_moe_lite" in registry.deferred_kv_architectures()
