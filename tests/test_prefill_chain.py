"""The prefill chain (scheduler.plan_step): with both sides wanting
work a further prefill step goes before the burst only while it would
be full, and a chain has at most one step for each
``prefill_batch_size`` rows that were free at its start. On the
scheduler alone: a fake executor commits what a plan says a device
would have computed, and no test waits on a clock."""

import random

import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.kv_cache import PagedCacheManager
from production_stack_tpu.engine.scheduler import Scheduler
from production_stack_tpu.engine.sequence import (
    SamplingParams,
    Sequence,
    SequenceState,
)

CHUNK = 16
WIDTH = 8


class Alternating(Scheduler):
    """The scheduler as it was: no step is ever full enough."""

    def _full_prefill_step_waits(self) -> bool:
        return False


class FakeEngine:
    """Plans with a real Scheduler over a real page manager and
    commits each plan as the engine would after the device ran it:
    a prefill chunk's tokens computed (the last chunk's row starts to
    run), ``window`` tokens a decode row, one a mixed or verify
    row."""

    def __init__(self, cls=Scheduler, max_num_seqs=32, num_pages=4096,
                 page_size=16, chunk=CHUNK, width=WIDTH, window=4,
                 state_slots=0, max_model_len=2048, **sched):
        cache = CacheConfig(page_size=page_size, num_pages=num_pages,
                            enable_prefix_caching=False,
                            num_state_slots=state_slots)
        self.cache = PagedCacheManager(cache)
        self.sched = cls(
            SchedulerConfig(max_num_seqs=max_num_seqs,
                            max_model_len=max_model_len,
                            prefill_chunk_size=chunk,
                            prefill_batch_size=width,
                            decode_steps=window, **sched),
            cache, self.cache)
        self.count = 0
        self.finished = []
        self.base = 0

    def add(self, prompt_len, max_tokens=10 ** 6, state=None,
            prompt=None):
        self.count += 1
        seq = Sequence(
            seq_id=f"s{self.count}",
            prompt_token_ids=prompt or [1 + self.count % 7] * prompt_len,
            sampling=SamplingParams(max_tokens=max_tokens,
                                    temperature=0.0, ignore_eos=True),
            arrival_time=float(self.count))
        self.sched.add_sequence(seq)
        if state is not None:
            seq.state = state
        return seq

    def fill_running(self, n, prompt_len=CHUNK):
        """``n`` rows decoding, admitted the ordinary way."""
        for _ in range(n):
            self.add(prompt_len)
        while self.sched.waiting:
            self.turn()
        assert len(self.sched.running) == n
        if self.sched._last_was_prefill:
            self.turn()  # leave it as a burst leaves it
        self.base = self.sched.num_chained_prefill_steps

    @property
    def chained(self):
        """Steps that chains have added since fill_running."""
        return self.sched.num_chained_prefill_steps - self.base

    def turn(self):
        """Plan and commit one step; 'P<rows>' or 'D<rows>', a mixed
        step both, '-' for an empty plan."""
        plan = self.sched.plan_step()
        name = ""
        if plan.prefill is not None:
            name += f"P{len(plan.prefill.chunks)}"
        if plan.decode is not None:
            name += f"D{len(plan.decode.seqs)}"
            tokens = 1 if (plan.prefill is not None
                           or plan.decode.drafts is not None
                           ) else plan.decode.window
            for seq in plan.decode.seqs:
                for _ in range(tokens):
                    if not self.sched.append_decode_token(seq, 3):
                        break
                if seq.state == SequenceState.FINISHED:
                    self.finished.append(seq)
        if plan.prefill is not None:
            for chunk in plan.prefill.chunks:
                self.sched.on_prefill_executed(
                    chunk, 3 if chunk.is_last_chunk else None)
        self.last = plan
        return name or "-"

    def turns(self, n):
        return [self.turn() for _ in range(n)]


def kinds(names):
    return "".join(n[0] for n in names)


# ---- (i), (ii): full or not ------------------------------------------------

@pytest.mark.parametrize("waiting, expected", [
    # 8 rows go with the first step; what is left decides.
    (16, ["P8", "P8", "D26"]),   # 8 left: a full second step
    (17, ["P8", "P8", "D26"]),   # 9 left, one chain of 2 at 22 free
    (8 + 1, ["P8", "D18", "P1"]),
    (8 + 4, ["P8", "D18", "P4"]),
    (8 + 7, ["P8", "D18", "P7"]),
    (3, ["P3", "D13", "D13"]),
])
def test_second_step_only_when_full(waiting, expected):
    eng = FakeEngine()
    eng.fill_running(10)
    for _ in range(waiting):
        eng.add(CHUNK - 1)
    assert eng.turns(3) == expected


@pytest.mark.parametrize("waiting", range(1, 8))
def test_under_a_step_waiting_is_alternation_turn_for_turn(waiting):
    """1..7 chunk rows waiting at every plan: the parent's plans."""
    new, old = FakeEngine(), FakeEngine(cls=Alternating)
    for eng in (new, old):
        eng.fill_running(10)
        for _ in range(waiting):
            eng.add(5 * CHUNK)  # five chunks each: they keep waiting
    assert new.turns(14) == old.turns(14)
    assert new.chained == 0


def test_mid_prompt_rows_count_toward_full():
    eng = FakeEngine()
    eng.fill_running(10)
    for _ in range(8):
        eng.add(2 * CHUNK)  # two chunks: all eight wait again
    assert eng.turns(3) == ["P8", "P8", "D18"]
    assert [eng.chained,
            eng.last.decode is not None] == [1, True]


def test_chain_place_is_on_the_plan():
    eng = FakeEngine()
    eng.fill_running(8)
    for _ in range(24):
        eng.add(CHUNK - 1)
    places = []
    for _ in range(4):
        eng.turn()
        places.append(eng.last.prefill.chain if eng.last.prefill
                      else 0)
    assert places == [1, 2, 3, 0]
    assert eng.chained == 2


# ---- (iii): the bound ------------------------------------------------------

@pytest.mark.parametrize("free, longest", [
    (3, 1), (7, 1), (8, 1), (9, 2), (16, 2), (28, 4), (32, 4)])
def test_chain_never_longer_than_free_rows_allow(free, longest):
    """Eight 20-chunk prompts beside a batch with ``free`` free rows:
    every step would be full, so only the bound ends a chain, and a
    burst follows every chain."""
    eng = FakeEngine(max_num_seqs=40)
    eng.fill_running(40 - free)
    for _ in range(8):
        eng.add(20 * CHUNK)
    seen = kinds(eng.turns(60))
    # Until the eight run out of chunks every chain is as long as the
    # bound lets it be, and a burst follows each.
    assert seen.startswith(("P" * longest + "D") * (19 // longest))
    assert max(len(r) for r in seen.split("D")) == longest


def test_empty_batch_fills_before_it_decodes():
    eng = FakeEngine(max_num_seqs=32)
    for _ in range(40):
        eng.add(CHUNK - 1)
    assert eng.turns(6) == ["P8", "P8", "P8", "P8", "D32", "D32"]


# ---- (iv), (v): what does not count ----------------------------------------

@pytest.mark.parametrize("spoil", ["awaiting_kv", "aborted"])
def test_parked_and_aborted_do_not_count(spoil):
    eng = FakeEngine()
    eng.fill_running(10)
    for _ in range(8 + 5):
        eng.add(CHUNK - 1)
    state = (SequenceState.AWAITING_KV if spoil == "awaiting_kv"
             else SequenceState.ABORTED)
    for _ in range(6):
        eng.add(CHUNK - 1, state=state)
    # 8 go, 5 plannable rows and 6 others are left: not full.
    assert eng.turns(2) == ["P8", "D18"]
    assert eng.chained == 0


def test_admissions_beyond_the_free_rows_do_not_count():
    eng = FakeEngine(max_num_seqs=32)
    eng.fill_running(19)  # 13 free: a chain may have two steps
    for _ in range(20):
        eng.add(CHUNK - 1)  # one chunk each: every row an admission
    # After the first step 5 rows are free and 12 wait: a second step
    # would carry 5 rows of 8.
    assert eng.turns(3) == ["P8", "D27", "P5"]
    assert eng.chained == 0


def test_mid_prompt_rows_count_where_admissions_do_not():
    eng = FakeEngine(max_num_seqs=32)
    eng.fill_running(19)
    for _ in range(8):
        eng.add(3 * CHUNK)
    assert eng.turns(4) == ["P8", "P8", "D19", "P8"]


# ---- (vi): pages and slots run out -----------------------------------------

@pytest.mark.parametrize("short_of", ["pages", "slots"])
def test_running_out_ends_the_chain_without_a_leak(short_of):
    """Room for the row that runs, the first step's eight and three
    more: the count sees that a second step would not be full, the
    burst follows, and what the planner took from the pools goes back
    when the rows end."""
    pages_a_seq = 2  # 20 prompt tokens and 6 to 11 more at 16 a page
    eng = FakeEngine(
        max_num_seqs=32, chunk=32,
        num_pages=(1 + 12 * pages_a_seq + 1 if short_of == "pages"
                   else 4096),
        state_slots=12 if short_of == "slots" else 40)
    eng.add(20, max_tokens=11)
    assert eng.turns(2) == ["P1", "D1"]
    for _ in range(16):
        eng.add(20, max_tokens=6)
    assert eng.turns(3) == ["P8", "D9", "P3"]
    assert eng.chained == 0
    while eng.sched.has_work():
        eng.turn()
    assert len(eng.finished) == 17
    assert eng.cache.num_used_pages == 0
    assert eng.cache.num_used_state_slots == 0


def test_chain_that_meets_a_full_cache_falls_to_the_burst():
    """The count reads the free pages; a plan that still finds none
    (here: the pool emptied behind its back) gives the turn to the
    burst through the path a full cache always took, and keeps no
    page or slot."""
    eng = FakeEngine(max_num_seqs=32, state_slots=40)
    eng.fill_running(4, prompt_len=5)
    for _ in range(16):
        eng.add(5)
    assert eng.turn() == "P8"
    assert eng.sched._full_prefill_step_waits()
    used_slots = eng.cache.num_used_state_slots
    hoard = eng.cache.allocate_pages(eng.cache.num_free_pages)
    assert not eng.sched._full_prefill_step_waits()
    eng.sched._full_prefill_step_waits = lambda: True
    assert eng.turn() == "D12"
    assert eng.chained == 0
    assert eng.cache.num_used_state_slots == used_slots
    assert all(not s.pages and s.state_slot is None
               for s in eng.sched.waiting)
    eng.cache.free_sequence(hoard)


# ---- (vii): the other planners ---------------------------------------------

def _arrivals(eng, turn):
    """A recorded sequence of arrivals: bursts of prompts of one to
    three chunks, repetitive enough for the n-gram proposer."""
    rs = random.Random(turn)
    if turn % 3 == 0:
        for _ in range(rs.randint(0, 12)):
            n = rs.randint(4, 3 * CHUNK)
            eng.add(n, max_tokens=rs.randint(4, 40),
                    prompt=[5, 6, 7, 8] * (n // 4 + 1))


@pytest.mark.parametrize("config", [
    dict(unified_step=True),
    dict(unified_step=True, speculative_k=3),
    dict(unified_step=True, speculative_k=3, window=1),
])
def test_unified_and_spec_plans_are_the_parents(config):
    """Under --unified-step the mixed planner takes every turn on
    which both sides want work, with or without a proposer: the
    chain never engages and the plans are strict alternation's."""
    new = FakeEngine(**config)
    old = FakeEngine(cls=Alternating, **config)
    for turn in range(120):
        for eng in (new, old):
            _arrivals(eng, turn)
        assert new.turn() == old.turn(), turn
    assert new.chained == 0
    assert len(new.finished) == len(old.finished) > 20


# The parent's own plans for the same arrivals with unified_step and
# speculative_k=3 (recorded on commit 547c3f7 with this file's
# FakeEngine; 'P' a prefill step, 'D' a burst or verify step, 'M' a
# mixed step).
PARENT_UNIFIED_SPEC = (
    "PPMMMMMMMMMMMMMMDMMMDMMMMMMMMMDMMMMMMMMMMMMMMDMMMMMMDMMMDMMM")


def test_unified_spec_plans_match_the_recording():
    eng = FakeEngine(unified_step=True, speculative_k=3)
    got = []
    for turn in range(60):
        _arrivals(eng, turn)
        name = eng.turn()
        got.append("M" if "P" in name and "D" in name else name[0])
    assert "".join(got) == PARENT_UNIFIED_SPEC


def test_a_mixed_step_is_no_part_of_a_chain():
    """A row that needs per-token host state sends the mixed planner
    back to the bimodal path; the mixed step before it carried
    prefill rows and is no link of a chain: the burst comes next, as
    it always did, and the chain after it starts at 1."""
    eng = FakeEngine(unified_step=True)
    eng.fill_running(10)
    seeded = eng.add(CHUNK - 1)
    seeded.sampling.seed = 1
    for _ in range(20):
        eng.add(CHUNK - 1)
    assert eng.turns(2) == ["P8D10", "D18"]
    assert eng.turn() == "P8" and eng.last.prefill.chain == 1


def test_spec_without_unified_keeps_its_verify_steps():
    """A proposer alone (bimodal steps): the decode side's plans are
    _plan_spec's as before; only the prefill side chains."""
    new = FakeEngine(speculative_k=3, window=1)
    old = FakeEngine(cls=Alternating, speculative_k=3, window=1)
    for eng in (new, old):
        eng.fill_running(10)
        for _ in range(5):
            eng.add(4 * CHUNK, prompt=[5, 6, 7, 8] * CHUNK)
    assert new.turns(12) == old.turns(12)


# ---- (viii): the admission-bound closed loop -------------------------------

def _closed_loop(cls, cycles=260, settle=140):
    """The hybrid cell's traffic on its flags: 128 clients, prompts
    uniform 64-512 at chunk 256 (1.57 chunk rows a request), outputs
    uniform 256-1024 in bursts of 32 (20 a request), width 8, 128
    rows. A client sends its next request when it has the last
    token of the one before: it reaches the scheduler during the turn
    after the one that finished it. Mean decode rows a burst once
    settled, and the prefill rows of every chained step."""
    rs = random.Random(40)
    eng = FakeEngine(cls=cls, max_num_seqs=128, num_pages=2048,
                     page_size=128, chunk=256, width=8, window=32,
                     state_slots=136, max_model_len=8192)

    def send(n):
        for _ in range(n):
            eng.add(rs.randint(64, 512),
                    max_tokens=rs.randint(256, 1024))

    send(128)
    rows, chained, in_flight = [], [], 0
    while len(rows) < cycles:
        done = len(eng.finished)
        eng.turn()
        send(in_flight)  # arrived while that turn ran
        in_flight = len(eng.finished) - done
        if eng.last.decode is not None:
            rows.append(len(eng.last.decode.seqs))
        elif eng.last.prefill.chain > 1:
            chained.append(len(eng.last.prefill.chunks))
    return sum(rows[settle:]) / (cycles - settle), chained


def test_closed_loop_fills_the_rows():
    old, none = _closed_loop(Alternating)
    new, chained = _closed_loop(Scheduler)
    assert none == []
    assert 99 <= old <= 105      # what the chip's windows show
    assert new > 112
    assert chained and set(chained) == {8}


@pytest.mark.parametrize("cls", [Scheduler, Alternating])
def test_a_chained_step_is_never_narrow(cls):
    """Whatever the closed loop plans, a step of place 2 and up holds
    ``prefill_batch_size`` rows, so the runner's rule (the fewest
    token places among its shapes) keeps it at the full width; steps
    of place 1 do go narrow there."""
    from production_stack_tpu.engine.model_runner import prefill_shape

    rs = random.Random(3)
    eng = FakeEngine(cls=cls, max_num_seqs=128, chunk=256, page_size=128,
                     num_pages=2048, max_model_len=8192)
    for _ in range(160):
        eng.add(rs.randint(64, 512), max_tokens=rs.randint(64, 256))
    widths = {1: set(), 2: set()}
    for _ in range(400):
        done = len(eng.finished)
        eng.turn()
        for _ in range(len(eng.finished) - done):
            eng.add(rs.randint(64, 512), max_tokens=rs.randint(64, 256))
        plan = eng.last.prefill
        if plan is not None:
            rows, _ = prefill_shape(
                len(plan.chunks),
                max(len(c.chunk_tokens) for c in plan.chunks), WIDTH, 256)
            widths[min(plan.chain, 2)].add(rows)
    assert widths[1] == {WIDTH // 2, WIDTH}
    assert widths[2] == ({WIDTH} if cls is Scheduler else set())


# ---- the real engine: same tokens, the record and the counter --------------

def _tiny_engine(prefill_batch_size, max_num_seqs=16):
    from production_stack_tpu.engine.config import (
        EngineConfig, tiny_model_config)
    from production_stack_tpu.engine.engine import LLMEngine

    return LLMEngine(EngineConfig(
        model=tiny_model_config("llama"),
        cache=CacheConfig(page_size=16, num_pages=256,
                          enable_prefix_caching=False),
        scheduler=SchedulerConfig(max_num_seqs=max_num_seqs,
                                  max_model_len=256,
                                  prefill_chunk_size=32,
                                  prefill_batch_size=prefill_batch_size,
                                  decode_steps=4)))


def test_chained_steps_generate_what_serial_admission_does():
    """Only WHEN a full prefill step runs changes: every request gets
    the tokens it gets alone, the turn records say each prefill step's
    place in its chain, and stats() counts the chained ones."""
    from production_stack_tpu.engine.tracing import EngineTracer

    rs = random.Random(7)
    prompts = [[rs.randint(1, 500) for _ in range(rs.randint(5, 60))]
               for _ in range(13)]
    sampling = dict(max_tokens=9, temperature=0.0, ignore_eos=True)
    serial = _tiny_engine(prefill_batch_size=1, max_num_seqs=1)
    expected = [serial.generate(p, SamplingParams(**sampling))
                .output_token_ids for p in prompts]

    engine = _tiny_engine(prefill_batch_size=4)
    engine.tracer = EngineTracer()
    first = engine.add_request(prompts[0], SamplingParams(**sampling))
    engine.step()  # one row runs; the others arrive beside it
    ids = [first] + [engine.add_request(p, SamplingParams(**sampling))
                     for p in prompts[1:]]
    seqs = [engine.sequences[i] for i in ids]
    while engine.has_work():
        engine.step()
    assert [s.output_token_ids for s in seqs] == expected
    steps = engine.tracer.recent_steps(limit=0)
    chain = [s.get("prefill_chain", 0) for s in steps]
    rows = [s.get("prefill_rows") for s in steps]
    # Twelve one- and two-chunk prompts beside one running row of 16:
    # the row's own step, three full ones behind it (16 free rows at
    # its start allow four in all), then the burst.
    assert chain[:5] == [1, 2, 3, 4, 0]
    assert all(r == 4 for c, r in zip(chain, rows) if c > 1)
    chained = sum(c > 1 for c in chain)
    assert engine.stats()["engine_prefill_chained_steps_total"] \
        == chained >= 3
    # A chained step is full, so it never runs the half-width program
    # (model_runner.prefill_shape); the steps of place 1 may.
    widths = [s.get("prefill_width") for s in steps]
    assert all(w == 4 for c, w in zip(chain, widths) if c > 1)
    assert 2 in widths


def test_metrics_exposes_the_chained_steps_counter():
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server import EngineServer

    async def run():
        server = EngineServer(_tiny_engine(prefill_batch_size=4),
                              "tiny-llama")
        client = TestClient(TestServer(server.build_app()))
        await client.start_server()
        try:
            text = await (await client.get("/metrics")).text()
        finally:
            await client.close()
        assert ("# TYPE vllm:engine_prefill_chained_steps_total counter"
                "\nvllm:engine_prefill_chained_steps_total 0.0") in text
    asyncio.run(run())
