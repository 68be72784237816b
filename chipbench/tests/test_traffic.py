"""Every seed offers the same sizes and arrivals, in another order."""

import asyncio

import pytest

from chipbench.traffic import closed_clients, lengths, open_poisson

PARAMS = {"rate_per_s": 5.0, "ramp_s": 2.0, "drain_limit_s": 5.0,
          "prompt_tokens": {"dist": "lognormal", "median": 512,
                            "sigma": 0.9, "min": 64, "max": 4096},
          "output_tokens": {"dist": "lognormal", "median": 128,
                            "sigma": 0.7, "min": 16, "max": 512}}


def sizes(requests):
    return [(len(r["prompt_ids"]), r["max_tokens"]) for r in requests]


def test_closed_loop_every_block_holds_the_same_sizes_in_the_seeds_order():
    params = dict(PARAMS, clients=4, pool=198)
    a = closed_clients.plan(params, 1.0, 1, 1000)
    b = closed_clients.plan(params, 1.0, 2**31 + 7, 1000)
    assert len(a) == len(b) == 200 and len({r["id"] for r in a}) == 200
    assert sizes(a) != sizes(b)
    prompts = sorted(len(r["prompt_ids"]) for r in a[:4])
    outputs = sorted(r["max_tokens"] for r in a[:4])
    for plan in (a, b):
        for k in range(0, 200, 4):
            block = plan[k:k + 4]
            assert sorted(len(r["prompt_ids"]) for r in block) == prompts
            assert sorted(r["max_tokens"] for r in block) == outputs
    assert all(x["prompt_ids"] != y["prompt_ids"] for x, y in zip(a, b))
    assert a == closed_clients.plan(params, 1.0, 1, 1000)


def test_open_loop_every_seed_offers_the_same_sizes_and_gaps_in_its_order():
    a = open_poisson.plan(PARAMS, 20.0, 1, 1000)
    b = open_poisson.plan(PARAMS, 20.0, 2**31 + 7, 1000)
    for phase in ("ramp", "window"):
        one = [r for r in a if r["phase"] == phase]
        two = [r for r in b if r["phase"] == phase]
        assert sizes(one) != sizes(two)
        assert sorted(len(r["prompt_ids"]) for r in one) == sorted(
            len(r["prompt_ids"]) for r in two)
        assert sorted(r["max_tokens"] for r in one) == sorted(
            r["max_tokens"] for r in two)

        def gaps(rs):
            dues = [r["due"] for r in rs]
            return sorted(round(y - x, 9) for x, y in zip(dues, dues[1:]))
        # All gaps but the first (from the phase's start) compared.
        assert len(set(gaps(one)) ^ set(gaps(two))) <= 4
        assert one[-1]["due"] == pytest.approx(two[-1]["due"])
    window = [r for r in a if r["phase"] == "window"]
    assert len(window) == 100
    # The mid-quantile gaps of an exponential sum to n x mean, within
    # the tail's mass: the last arrival lands at the window's end.
    assert window[-1]["due"] == pytest.approx(20.0, rel=0.02)
    assert all(0 <= r["due"] for r in window)
    assert all(r["due"] < 0 for r in a if r["phase"] == "ramp")
    assert all(64 <= len(r["prompt_ids"]) <= 4096 for r in a)
    assert all(16 <= r["max_tokens"] <= 512 for r in a)
    assert a == open_poisson.plan(PARAMS, 20.0, 1, 1000)


def test_quantile_draws_hit_the_median_and_the_clips():
    import random
    draws = sorted(lengths.quantile_draws(
        PARAMS["prompt_tokens"], 101, random.Random(0)))
    assert draws[50] == pytest.approx(512.0)
    assert draws[0] == 64 and draws[-1] == 4096


class FakeLoad:
    """A server that takes 5 ms a request, on the real clock."""

    def __init__(self, params, seconds):
        import time
        self.params, self.seconds = params, seconds
        self.clock = time.perf_counter
        self.t0 = self.clock() + params["ramp_s"]
        self.sent = []

    def now(self):
        return self.clock() - self.t0

    async def sleep_until(self, t):
        await asyncio.sleep(max(0.0, t - self.now()))

    def cancel_in_flight(self):
        pass

    async def send(self, request):
        self.sent.append((request["phase"], request["id"]))
        await asyncio.sleep(0.005)


def test_closed_loop_runs_until_the_windows_requests_have_ended():
    params = dict(PARAMS, clients=4, pool=5000, ramp_s=0.1)
    requests = closed_clients.plan(params, 0.3, 3, 1000)
    load = FakeLoad(params, 0.3)
    asyncio.run(closed_clients.drive(requests, load))
    phases = [p for p, _ in load.sent]
    assert phases.count("window") > 10 and "ramp" in phases
    assert len({i for _, i in load.sent}) == len(load.sent)


def test_closed_loop_says_so_when_the_pool_runs_out():
    params = dict(PARAMS, clients=2, pool=3, ramp_s=0.1)
    load = FakeLoad(params, 0.3)
    with pytest.raises(RuntimeError, match="pool"):
        asyncio.run(closed_clients.drive(
            closed_clients.plan(params, 0.3, 3, 1000), load))
