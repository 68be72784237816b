"""The served loop's turn dispatches first (docs/async_pipeline.md,
"The served loop"): between two programs the host does only what the
next program reads.

- the order: ``enqueue(N+1)``, then ``hand_over(outputs of N)``, then
  ``wait(N+1)``, and nothing owed to an engine that parks;
- equivalence: the same requests through ``step()`` and through the
  loop's two calls end in the same tokens, finish reasons, computed
  counts, free pages and state slots, whatever ends a row;
- the planner's half of the commit: appended row by row, or token by
  token where the row says so, to the same end state;
- the sampling key: made on the host, one source, one stream a seed.

Tiny widths, seeded, on the CPU; the engines are built once a module.
"""

import asyncio
import random

import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    SchedulerConfig,
    tiny_glm4_moe_lite_config,
    tiny_jamba_config,
    tiny_model_config,
)
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.kv_cache import PagedCacheManager
from production_stack_tpu.engine.model_runner import HostKeys
from production_stack_tpu.engine.scheduler import Scheduler
from production_stack_tpu.engine.sequence import (
    STOP_SET_WIDTH,
    SamplingParams,
    Sequence,
    SequenceState,
)

PROMPT = [5 + i % 7 for i in range(39)]
OTHER = [11 + i % 5 for i in range(23)]


def _config(family="llama", seed=0, **scheduler):
    model = {"llama": tiny_model_config,
             "jamba": tiny_jamba_config,
             "glm": lambda: tiny_glm4_moe_lite_config(vocab_size=16),
             }[family]()
    model.attention_impl = "xla"
    sched = dict(max_num_seqs=4, max_model_len=128, prefill_chunk_size=32,
                 prefill_batch_size=2, decode_steps=4)
    if family == "glm":
        sched.update(deferred_kv_writes=True, draft_module=True)
    sched.update(scheduler)
    # No prefix cache: the engines are reused from case to case, and a
    # hit would make the second user of a prompt compute less.
    return EngineConfig(
        model=model, seed=seed,
        cache=CacheConfig(page_size=16, num_pages=64,
                          enable_prefix_caching=False),
        scheduler=SchedulerConfig(**sched))


def stepped(engine, between=None):
    """Through ``step()``; ``between`` runs between two steps."""
    outputs = []
    while engine.has_work():
        outputs += engine.step()
        if between is not None:
            between(engine)
    return outputs


def served(engine, between=None, before_owed=False):
    """The server loop's order (AsyncEngine._run), on one thread:
    ``between`` runs between the loop's two calls, before or after the
    turn before's outputs are taken."""
    outputs = []
    while engine.has_work():
        enqueued = engine.begin_step()
        if between is not None and before_owed:
            between(engine)
        outputs += engine.take_owed()
        if between is not None and not before_owed:
            between(engine)
        if enqueued is not None:
            engine.finish_step(enqueued)
        if not engine.more_to_run():
            outputs += engine.take_owed()
    return outputs


# ---- the order, under the real loop ----------------------------------------


class _Loop:
    """Stands in for the event loop: keeps what the loop thread asks
    it to call and passes it on to the running loop."""

    def __init__(self):
        self.real = asyncio.get_running_loop()

    def call_soon_threadsafe(self, fn, *args):
        self.real.call_soon_threadsafe(fn, *args)


def _record(engine, served_engine):
    """Wraps the runner's enqueue and read-back and the loop's
    hand-over; returns the list the events go to."""
    events = []
    runner = engine.runner
    execute, read_back = runner.execute_payload, runner.read_back
    hand_over, begin = served_engine._hand_over, engine.begin_step
    turn = [0]

    def begin_step():
        turn[0] += 1
        return begin()

    runner.execute_payload = lambda kind, payload, t=1: (
        events.append(("enqueue", turn[0], type(payload["rng"]))),
        execute(kind, payload, t))[1]
    runner.read_back = lambda sampled: (
        events.append(("wait", turn[0])), read_back(sampled))[1]
    served_engine._hand_over = lambda outputs, stamp=None: (
        events.append(("hand_over", turn[0], len(outputs))),
        hand_over(outputs, stamp))[1]
    engine.begin_step = begin_step
    return events


@pytest.mark.parametrize("tracer", [False, True], ids=["plain", "traced"])
async def test_a_turn_enqueues_then_hands_over_then_waits(tracer):
    from production_stack_tpu.engine.server import AsyncEngine
    from production_stack_tpu.engine.tracing import EngineTracer

    engine = LLMEngine(_config())
    # The first step at the top bucket would bring up the other width
    # of it in the same turn (run once a process: _other_width_payloads).
    engine.runner._top_bucket_warm = True
    if tracer:
        engine.tracer = EngineTracer()
    loop = AsyncEngine(engine)
    events = _record(engine, loop)
    loop.start(_Loop())
    streams = [await loop.submit(
        prompt, SamplingParams(temperature=0.0, max_tokens=11,
                               ignore_eos=True))
        for prompt in (PROMPT, OTHER)]
    tokens = 0
    for _, stream in streams:
        while True:
            out = await asyncio.wait_for(stream.get(), 120)
            tokens += out.new_token is not None
            if out.finished:
                break
    assert tokens == 22
    # Every program is one enqueue and one wait of its own turn, in
    # that order, and a turn enqueues exactly one program.
    enqueues = [e[1] for e in events if e[0] == "enqueue"]
    assert enqueues == sorted(set(enqueues)) and len(enqueues) >= 4
    # (A prefill step of mid-prompt chunks alone samples nothing and
    # waits for nothing.)
    waits = [e[1] for e in events if e[0] == "wait"]
    assert waits == [t for t in enqueues if t in waits]
    assert len(waits) >= len(enqueues) - 1
    # The outputs of turn N go between turn N+1's enqueue and its wait.
    # The clients are parked on their streams, so the last hand-over
    # has happened: it is the last turn's own, at once, behind its wait.
    hands = [i for i, e in enumerate(events) if e[0] == "hand_over"]
    assert len(hands) >= 3
    for i in hands[:-1]:
        assert events[i - 1][:2] == ("enqueue", events[i][1])
        assert events[i + 1] == ("wait", events[i][1])
    assert events[-2][0] == "wait" and events[-1][0] == "hand_over"
    assert sum(e[2] for e in events if e[0] == "hand_over") == 22
    assert not engine.has_work()
    metrics = engine.metrics
    assert metrics.handovers_behind_total == len(hands) - 1
    assert metrics.handovers_flushed_total == 1
    assert "vllm:engine_handover_behind_share" in "\n".join(
        metrics.render())
    if tracer:
        # The last turn hands its own outputs over before its record
        # closes: the client can be here first.
        for _ in range(500):
            turns = [s for s in engine.tracer.recent_steps(limit=0)
                     if "phases" in s]
            if len(turns) == len(enqueues):
                break
            await asyncio.sleep(0.01)
        assert [t["handover"] for t in turns if "handover" in t] == [
            "behind"] * (len(hands) - 1)
        assert sum(t["emitted"] for t in turns) == 22
        assert all(t["commit_rows_slow"] == 0 for t in turns
                   if t["kind"] == "decode")
        # Behind the dispatch: the owed outputs are made (commit) and
        # handed over (emit) inside the next turn's record.
        assert all({"commit", "emit"} <= set(t["phases"])
                   for t in turns if t.get("handover") == "behind")


async def test_a_step_that_fails_hands_over_what_was_owed_first():
    from production_stack_tpu.engine.server import AsyncEngine

    engine = LLMEngine(_config())
    loop = AsyncEngine(engine)
    loop.start(_Loop())
    finish, failed = engine.finish_step, []

    def finish_step(enqueued):
        if (enqueued.plan.decode is not None
                and len(seq.output_token_ids) >= 5 and not failed):
            failed.append(len(seq.output_token_ids))
            raise RuntimeError("the device program failed")
        finish(enqueued)

    seq_id, stream = await loop.submit(PROMPT, SamplingParams(
        temperature=0.0, max_tokens=30, ignore_eos=True))
    while seq_id not in engine.sequences:
        await asyncio.sleep(0.01)
    seq = engine.sequences[seq_id]
    engine.finish_step = finish_step
    got = []
    while not got or not got[-1].finished:
        got.append(await asyncio.wait_for(stream.get(), 120))
    # Every token the engine committed before the failure reached the
    # stream, and the abort came last.
    assert failed and [o.new_token for o in got[:-1]] == (
        seq.output_token_ids)
    assert len(got) - 1 == failed[0]
    assert (got[-1].new_token, got[-1].finish_reason) == (None, "abort")
    assert not engine.has_work()


# ---- equivalence: step() against the loop's two calls ----------------------


@pytest.fixture(scope="module")
def pairs():
    """Two engines a family, built when a case first asks: one driven
    through step(), one through the loop's two calls."""
    made = {}

    def get(family, **scheduler):
        key = (family, tuple(sorted(scheduler.items())))
        if key not in made:
            first = LLMEngine(_config(family, **scheduler))
            made[key] = (first, LLMEngine(_config(family, **scheduler),
                                          params=first.runner.params))
        return made[key]

    return get


@pytest.fixture(scope="module")
def greedy(pairs):
    """PROMPT's greedy continuation on the tiny llama."""
    # An engine of its own: the pairs' key streams stay in step.
    engine = LLMEngine(_config(), params=pairs("llama")[0].runner.params)
    seq = engine.generate(PROMPT, SamplingParams(
        temperature=0.0, max_tokens=24, ignore_eos=True))
    return list(seq.output_token_ids)


def _mid_burst(tokens, lo=5):
    """An index from ``lo`` on, inside a burst of 4 behind the prefill
    step's token and not its last, whose token occurs nowhere before."""
    return next(i for i in range(lo, len(tokens))
                if i % 4 and tokens[i] not in tokens[:i])


def _abort(index):
    """Aborts request ``s<index>`` once it has eight tokens."""
    def between(engine):
        seq = engine.sequences.get(f"s{index}")
        if seq is not None and len(seq.output_token_ids) >= 8:
            engine.abort_request(seq.seq_id)
    return between


# name -> (family, scheduler options, [(prompt, sampling)...] given the
# greedy tokens, between).  Bursts of 4 behind a prefill token: output
# index 6 is the second token of the second burst.
CASES = {
    "stop-id-mid-burst": ("llama", {}, lambda g: [
        (PROMPT, dict(stop_token_ids=[g[_mid_burst(g)]],
                      max_tokens=24)),
        (OTHER, dict(max_tokens=9, ignore_eos=True))]),
    "max-tokens-mid-burst": ("llama", {}, lambda g: [
        (PROMPT, dict(max_tokens=7, ignore_eos=True)),
        (OTHER, dict(max_tokens=14, ignore_eos=True))]),
    "max-model-len": ("llama", {"max_model_len": 50}, lambda g: [
        (PROMPT, dict(max_tokens=100, ignore_eos=True)),
        (OTHER, dict(max_tokens=100, ignore_eos=True))]),
    "wide-stop-set": ("llama", {}, lambda g: [
        (PROMPT, dict(stop_token_ids=list(range(400, 400 + STOP_SET_WIDTH
                                                + 2))
                      + [g[_mid_burst(g)]], max_tokens=24)),
        (OTHER, dict(max_tokens=9, ignore_eos=True))]),
    "min-tokens": ("llama", {}, lambda g: [
        (PROMPT, dict(stop_token_ids=[g[_mid_burst(g, lo=1)]],
                      min_tokens=7, max_tokens=24)),
        (OTHER, dict(max_tokens=9, ignore_eos=True))]),
    "guided": ("llama", {}, lambda g: [
        (PROMPT[:20], dict(guided="json", temperature=0.8, seed=7,
                           max_tokens=40)),
        (OTHER, dict(max_tokens=9, ignore_eos=True))]),
    "logprobs": ("llama", {}, lambda g: [
        (PROMPT, dict(logprobs=True, top_logprobs=2, max_tokens=10,
                      ignore_eos=True)),
        (OTHER, dict(max_tokens=9, ignore_eos=True))]),
    "sampled": ("llama", {}, lambda g: [
        (PROMPT, dict(temperature=1.0, top_p=0.9, max_tokens=18,
                      ignore_eos=True)),
        (OTHER, dict(temperature=0.7, max_tokens=11, ignore_eos=True))]),
    "single-step": ("llama", {"decode_steps": 1}, lambda g: [
        (PROMPT, dict(max_tokens=6, ignore_eos=True)),
        (OTHER, dict(stop_token_ids=[g[2]], max_tokens=9))]),
    "prompt-lookup-drafts": (
        "llama", {"decode_steps": 1, "speculative_k": 3}, lambda g: [
            (PROMPT, dict(max_tokens=17, ignore_eos=True)),
            (OTHER, dict(max_tokens=12, ignore_eos=True))]),
    "unified": (
        "llama", {"decode_steps": 1, "unified_step": True}, lambda g: [
            (PROMPT, dict(max_tokens=9, ignore_eos=True)),
            (OTHER * 3, dict(max_tokens=7, ignore_eos=True)),
            (PROMPT[:17], dict(max_tokens=12, ignore_eos=True))]),
    "drafting-burst": ("glm", {}, lambda g: [
        ([3 + i % 11 for i in range(45)],
         dict(temperature=1.0, max_tokens=21, ignore_eos=True)),
        ([1 + i % 13 for i in range(20)],
         dict(temperature=1.0, max_tokens=13, ignore_eos=True))]),
    "hybrid-state-slots": ("jamba", {}, lambda g: [
        (PROMPT, dict(max_tokens=7, ignore_eos=True)),
        (OTHER, dict(max_tokens=14, ignore_eos=True))]),
    "prefill-role-handoff": ("llama", {}, lambda g: [
        (PROMPT, dict(max_tokens=12, ignore_eos=True, handoff=True)),
        (OTHER, dict(max_tokens=9, ignore_eos=True))]),
}


def _submit(engine, requests):
    seqs = []
    for i, (prompt, options) in enumerate(requests):
        options = dict({"temperature": 0.0}, **options)
        handoff = options.pop("handoff", False)
        engine.add_request(list(prompt), SamplingParams(**options),
                           seq_id=f"s{i}", handoff_prefill=handoff)
        seqs.append(engine.sequences[f"s{i}"])
    return seqs


def _outcome(engine, seqs, outputs):
    cache = engine.cache_manager
    return {
        "seqs": [(s.state, s.output_token_ids,
                  s.finish_reason and s.finish_reason.value,
                  s.num_computed_tokens, s.pages, s.state_slot)
                 for s in seqs],
        "free": (cache.num_free_pages, cache.num_free_state_slots),
        "running": list(engine.scheduler.running),
        "left": dict(engine.sequences),
        "keys": engine.runner._keys._drawn,
        # What each stream got, in its order.
        "streams": [[(o.new_token, o.finished, o.finish_reason,
                      o.logprobs) for o in outputs
                     if o.seq_id == s.seq_id] for s in seqs],
    }


@pytest.mark.parametrize("case", list(CASES))
def test_the_two_calls_end_where_step_ends(pairs, greedy, case):
    family, scheduler, requests = CASES[case]
    by_step, by_calls = pairs(family, **scheduler)
    results = []
    for engine, drive in ((by_step, stepped), (by_calls, served)):
        assert not engine.has_work()
        seqs = _submit(engine, requests(greedy))
        try:
            outputs = drive(engine)
        finally:
            for seq in seqs:
                engine.abort_request(seq.seq_id)
        results.append(_outcome(engine, seqs, outputs))
    want, got = results
    assert got == want
    assert want["free"][0] == 63 and not want["running"]
    assert not want["left"]
    for (state, tokens, reason, *_), stream in zip(want["seqs"],
                                                   want["streams"]):
        assert state == SequenceState.FINISHED
        assert [t for t, *_ in stream] == tokens
        assert [f for _, f, *_ in stream] == (
            [False] * (len(tokens) - 1) + [True])
        assert stream[-1][2] == reason
    first = want["seqs"][0]
    if case in ("stop-id-mid-burst", "wide-stop-set"):
        assert (first[2], len(first[1])) == ("stop",
                                             _mid_burst(greedy) + 1)
    elif case == "min-tokens":
        # The stop id under the minimum was suppressed, not kept.
        assert len(first[1]) >= 7
    elif case == "max-tokens-mid-burst":
        assert (first[2], len(first[1])) == ("length", 7)
    elif case == "max-model-len":
        assert (first[2], len(first[1])) == ("length", 50 - len(PROMPT))
    elif case == "logprobs":
        assert all(lp is not None and len(lp[1]) == 2
                   for *_, lp in want["streams"][0])
    elif case == "prefill-role-handoff":
        assert (first[2], len(first[1])) == ("handoff", 1)
    elif case == "drafting-burst":
        # Two slots an iteration: some iterations committed both.
        assert by_calls.metrics.spec_accepted_tokens_total > 0


@pytest.mark.parametrize("before_owed", [True, False],
                         ids=["before-the-owed-half", "behind-it"])
def test_an_abort_between_the_two_calls_ends_a_row_once(
        pairs, greedy, before_owed):
    """The abort lands after the planner's half of a burst's commit
    and before its deferred half (or right behind it), while the next
    program, which holds the row, is enqueued: the row ends once, its
    pages come back, and the other rows never notice."""
    by_step, by_calls = pairs("llama")
    requests = [(PROMPT, dict(max_tokens=24, ignore_eos=True)),
                (OTHER, dict(max_tokens=19, ignore_eos=True)),
                (PROMPT[:9], dict(max_tokens=6, ignore_eos=True))]
    seqs = _submit(by_step, requests)
    stepped(by_step, between=_abort(0))
    want = [list(s.output_token_ids) for s in seqs]
    finished = dict(by_calls.metrics.requests_total)
    seqs = _submit(by_calls, requests)
    outputs = served(by_calls, between=_abort(0),
                     before_owed=before_owed)
    assert [list(s.output_token_ids) for s in seqs[1:]] == want[1:]
    gone = seqs[0]
    assert gone.state == SequenceState.ABORTED and gone.pages == []
    assert len(gone.output_token_ids) in (8, 9)
    # The stream got what was committed before the abort and no finish
    # (the server drops an aborted stream; the engine never ends it).
    mine = [o for o in outputs if o.seq_id == "s0"]
    assert [o.new_token for o in mine] == gone.output_token_ids[:len(mine)]
    assert len(mine) >= 8 and not any(o.finished for o in mine)
    after = by_calls.metrics.requests_total
    assert after.get("abort", 0) - finished.get("abort", 0) == 1
    assert after["length"] - finished.get("length", 0) == 2
    assert by_calls.cache_manager.num_free_pages == 63
    assert not by_calls.sequences and not by_calls.has_work()


def test_an_abort_of_a_row_that_finished_in_the_planners_half_counts_once(
        pairs):
    """The burst's commit has finished the row, its outputs are still
    owed, and the client goes: one terminal output, one count."""
    _, engine = pairs("llama")
    finished = dict(engine.metrics.requests_total)
    engine.add_request(list(PROMPT), SamplingParams(
        temperature=0.0, max_tokens=5, ignore_eos=True), seq_id="once")
    seq = engine.sequences["once"]
    outputs = []
    while seq.state != SequenceState.FINISHED:
        enqueued = engine.begin_step()
        outputs += engine.take_owed()
        engine.finish_step(enqueued)
    assert engine.has_work() and not engine.more_to_run()  # owed alone
    engine.abort_request("once")
    outputs += engine.take_owed()
    assert [o.finished for o in outputs] == [False] * 4 + [True]
    assert outputs[-1].finish_reason == "length"
    after = engine.metrics.requests_total
    assert after["length"] - finished.get("length", 0) == 1
    assert after.get("abort", 0) == finished.get("abort", 0)
    assert not engine.has_work() and not engine.sequences


# ---- the planner's half: row by row against token by token -----------------


def _scheduler(max_model_len):
    config = _config(max_model_len=max_model_len)
    cache = PagedCacheManager(config.cache)
    return Scheduler(config.scheduler, config.cache, cache), cache


def _running(scheduler, cache, row):
    seq = Sequence(
        seq_id="row", prompt_token_ids=list(row["prompt"]),
        sampling=SamplingParams(
            max_tokens=row["max_tokens"], ignore_eos=row["ignore_eos"],
            stop_token_ids=list(row["stop_ids"])),
        num_prior_output_tokens=row["prior"])
    seq.transition(SequenceState.RUNNING)
    seq.output_token_ids = list(row["output"])
    seq.pages = cache.allocate_pages(2)
    scheduler.running.append(seq)
    return seq


@pytest.mark.parametrize("seed", range(8))
def test_row_by_row_and_token_by_token_commit_alike(seed):
    """125 random rows a seed: tokens, budgets and stop sets drawn
    with no regard for what a device would have cut, so a stop id or a
    budget falls anywhere in a row, before it, or nowhere."""
    rng = random.Random(seed)

    def ids(n):
        return [rng.randrange(24) for _ in range(n)]

    by_row, cache_r = _scheduler(max_model_len=40)
    by_token, cache_t = _scheduler(max_model_len=40)
    ended = {"stop": 0, "length": 0, None: 0}
    for _ in range(125):
        row = {"prompt": ids(rng.randrange(1, 20)),
               "output": ids(rng.randrange(0, 6)),
               "prior": rng.choice((0, 0, 3)),
               "max_tokens": rng.randrange(1, 14),
               "stop_ids": ids(rng.choice((0, 1, 1, 2, 5))),
               "ignore_eos": rng.random() < 0.2}
        tokens = ids(rng.randrange(0, 9))
        fast = _running(by_row, cache_r, row)
        ref = _running(by_token, cache_t, row)
        kept, slow = by_row.commit_decode_tokens(fast, list(tokens))
        assert not slow
        walked = 0
        for token in tokens:
            if ref.state != SequenceState.RUNNING:
                break
            by_token.append_decode_token(ref, token)
            walked += 1
        assert kept == walked, (row, tokens)
        assert fast.output_token_ids == ref.output_token_ids
        assert (fast.state, fast.finish_reason) == (
            ref.state, ref.finish_reason), (row, tokens)
        assert (fast.pages, fast.state_slot) == (ref.pages, ref.state_slot)
        assert cache_r.num_free_pages == cache_t.num_free_pages
        assert (fast in by_row.running) == (ref in by_token.running) == (
            fast.state == SequenceState.RUNNING)
        ended[fast.finish_reason and fast.finish_reason.value] += 1
        by_row.abort_sequence(fast)  # make room, where it goes on
        by_token.abort_sequence(ref)
    assert min(ended.values()) >= 10, ended


@pytest.mark.parametrize("row,slow", [
    (dict(), False),
    (dict(fsm_state=0), True),
    (dict(sampling=dict(logprobs=True)), True),
    (dict(sampling=dict(min_tokens=9)), True),
    (dict(sampling=dict(min_tokens=2)), False),  # passed already
    (dict(sampling=dict(stop_token_ids=list(range(STOP_SET_WIDTH + 1)))),
     True),
    (dict(sampling=dict(stop_token_ids=list(range(STOP_SET_WIDTH + 1)),
                        ignore_eos=True)), False),
    (dict(sampling=dict(stop_token_ids=list(range(STOP_SET_WIDTH)))),
     False),
], ids=["plain", "guided", "logprobs", "under-min-tokens",
        "past-min-tokens", "wide-stop-set", "wide-set-ignored",
        "stop-set-at-the-width"])
def test_a_row_says_itself_which_walk_it_takes(row, slow):
    scheduler, cache = _scheduler(max_model_len=128)
    scheduler.guided_advance = lambda seq, token: None
    seq = Sequence(
        seq_id="row", prompt_token_ids=[1, 2, 3],
        sampling=SamplingParams(max_tokens=64,
                                **row.get("sampling", {})),
        fsm_state=row.get("fsm_state"))
    seq.transition(SequenceState.RUNNING)
    seq.output_token_ids = [100, 101, 102]
    scheduler.running.append(seq)
    assert scheduler.commit_decode_tokens(seq, [200, 201]) == (2, slow)
    assert seq.output_token_ids[-2:] == [200, 201]


# ---- the sampling key -------------------------------------------------------


def test_a_hundred_thousand_consecutive_keys_are_distinct():
    keys = HostKeys(1)
    drawn = {keys.next().tobytes() for _ in range(100_000)}
    assert len(drawn) == 100_000
    key = keys.next()
    assert (key.dtype, key.shape) == (np.uint32, (2,))
    # One seed, one stream; another seed, another.
    again, other = HostKeys(1), HostKeys(2)
    first = [again.next().tolist() for _ in range(3)]
    assert first == [[1, 0], [1, 1], [1, 2]]
    assert other.next().tolist() != first[0]


@pytest.mark.parametrize("path", ["burst", "single-step"])
def test_the_engine_seed_fixes_the_keys_and_the_sampled_tokens(path):
    steps = {"burst": 4, "single-step": 1}[path]

    def run(seed):
        engine = LLMEngine(_config(seed=seed, decode_steps=steps))
        seqs = _submit(engine, [
            (PROMPT, dict(temperature=1.0, max_tokens=14,
                          ignore_eos=True)),
            (OTHER, dict(temperature=1.0, top_k=8, max_tokens=9,
                         ignore_eos=True))])
        keys = []
        execute = engine.runner.execute_payload
        engine.runner.execute_payload = lambda kind, payload, t=1: (
            keys.append(np.asarray(payload["rng"]).tolist()),
            execute(kind, payload, t))[1]
        served(engine)
        return keys, [s.output_token_ids for s in seqs]

    keys, tokens = run(seed=3)
    assert run(seed=3) == (keys, tokens)
    other_keys, other_tokens = run(seed=4)
    assert keys[0] == [3 + 1, 0] and other_keys[0] == [4 + 1, 0]
    assert len({tuple(k) for k in keys}) == len(keys) >= 4
    assert other_tokens != tokens


@pytest.mark.parametrize("case", ["burst", "single-step",
                                  "prompt-lookup-drafts", "unified"])
def test_a_turn_is_one_device_program_and_no_key_program(
        pairs, greedy, monkeypatch, case):
    """The recording runner counts the programs a turn dispatches, of
    whatever kind, and after the shapes are warm nothing on the host's
    side may split a key on the device (``_threefry_split``, the
    largest idle gap of every cell before PR 48)."""
    import jax

    family, scheduler, requests = CASES[
        {"burst": "sampled", "single-step": "single-step"}.get(case, case)]
    _, engine = pairs(family, **scheduler)
    _submit(engine, requests(greedy))
    served(engine)  # every shape of the case is compiled now

    def no_key_program(*args, **kwargs):
        raise AssertionError("a key was split on the device")

    monkeypatch.setattr(jax.random, "split", no_key_program)
    monkeypatch.setattr(jax.random, "PRNGKey", no_key_program)
    runner = engine.runner
    programs, keys = [], []
    jitted = ["_step_jit", "_decode_burst_jit", "_spec_jit",
              "_unified_jit"]

    def counting(name):
        call = getattr(runner, name)

        def run(*args, **kwargs):
            programs.append(name)
            return call(*args, **kwargs)
        return run

    for name in jitted:
        if hasattr(runner, name):
            monkeypatch.setattr(runner, name, counting(name))
    execute = runner.execute_payload
    monkeypatch.setattr(
        runner, "execute_payload", lambda kind, payload, t=1: (
            keys.append(((kind, t) == (2, 1), payload["rng"])),
            execute(kind, payload, t))[1])
    seqs = _submit(engine, requests(greedy))
    turns = 0
    while engine.has_work():
        before = len(programs)
        enqueued = engine.begin_step()
        engine.take_owed()
        if enqueued is not None:
            assert len(programs) == before + 1
            engine.finish_step(enqueued)
            assert len(programs) == before + 1
            turns += 1
        if not engine.more_to_run():
            engine.take_owed()
    assert turns == len(programs) == len(keys) >= 3
    assert all(s.state == SequenceState.FINISHED for s in seqs)
    # A numpy entry like every other input, which is what the
    # multihost bridge broadcasts (a single step's key rides
    # dispatch_decode's one fused transfer, single host only).
    assert all(isinstance(k, np.ndarray) and k.dtype == np.uint32
               and k.shape == (2,) for single, k in keys if not single)
    assert not all(single for single, _ in keys)


def test_the_bridge_publishes_the_key_as_numpy(pairs, greedy):
    """The multihost worker's path: what host 0 publishes is what the
    workers execute, so the key in it is host data, at a window of 1
    too (over the bridge a single step goes the burst's way)."""
    import contextlib

    class Bridge:
        lock = contextlib.nullcontext()

        def __init__(self):
            self.published = []

        def publish(self, kind, t, payload):
            self.published.append((kind, t, payload))

    engine = LLMEngine(_config(decode_steps=1),
                       params=pairs("llama")[0].runner.params)
    engine.runner.bridge = bridge = Bridge()
    engine.runner._top_bucket_warm = True  # no other width's program
    seq = engine.generate(PROMPT, SamplingParams(
        temperature=0.0, max_tokens=5, ignore_eos=True))
    assert seq.output_token_ids == greedy[:5]
    assert [kind for kind, _, _ in bridge.published] == [1, 1] + [2] * 4
    for _, _, payload in bridge.published:
        assert all(isinstance(v, (np.ndarray, bool))
                   for v in payload.values()), {
            k: type(v) for k, v in payload.items()}
        assert payload["rng"].dtype == np.uint32
