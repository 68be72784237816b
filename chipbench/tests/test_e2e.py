"""The percentile and TPOT arithmetic on hand-made timelines."""

import math

import pytest

from chipbench import e2e


def record(due, sent, first, last, tokens, phase="window", **over):
    r = {"id": "r", "phase": phase, "due": due, "sent": sent,
         "first": first, "last": last, "tokens": tokens,
         "max_tokens": tokens, "usage_tokens": tokens, "done": True,
         "error": None, "prompt_tokens": 10}
    r.update(over)
    return r


def test_percentile_interpolates_between_closest_ranks():
    values = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert e2e.percentile(values, 50) == 30.0
    assert e2e.percentile(values, 90) == pytest.approx(46.0)
    assert e2e.percentile([7.0], 90) == 7.0
    assert e2e.percentile(list(range(1, 12)), 90) == 10.0


def test_percentile_with_a_failure_in_the_tail_is_infinite():
    assert e2e.percentile([1.0] * 8 + [math.inf], 90) == math.inf
    # 1 failure in 21 sits beyond the 90th percentile's two ranks.
    assert e2e.percentile([1.0] * 20 + [math.inf], 90) == 1.0


def test_ttft_counts_from_when_the_request_was_due():
    r = record(due=1.0, sent=1.2, first=1.5, last=2.5, tokens=11)
    assert e2e.ttft_ms(r) == pytest.approx(500.0)
    assert e2e.ttft_ms(r, "sent") == pytest.approx(300.0)


def test_tpot_is_last_minus_first_over_tokens_less_one():
    # 11 tokens, first at 1.5 s, last at 2.5 s: 10 gaps in 1000 ms.
    r = record(due=1.0, sent=1.0, first=1.5, last=2.5, tokens=11)
    assert e2e.tpot_ms(r) == pytest.approx(100.0)
    # A burst of 32 tokens delivered together still has a TPOT: the
    # burst's cost spread over its tokens.
    burst = record(due=0, sent=0, first=0.64, last=1.28, tokens=33)
    assert e2e.tpot_ms(burst) == pytest.approx(20.0)


@pytest.mark.parametrize("over", [
    {"error": "http 500"}, {"done": False}, {"usage_tokens": 5},
    {"tokens": 5}])
def test_a_failed_or_short_request_misses_every_percentile(over):
    r = record(due=0, sent=0, first=0.1, last=0.2, tokens=8)
    r.update(over)
    assert not e2e.request_ok(r)
    assert e2e.ttft_ms(r) == math.inf and e2e.tpot_ms(r) == math.inf


def test_summarize_counts_only_the_window_and_all_of_it():
    records = [record(-1.0, -1.0, -0.9, -0.5, 5, phase="ramp")]
    records += [record(i, i, i + 0.1 * (i + 1), i + 1.0, 10)
                for i in range(10)]
    records.append(record(10, 10, None, None, 0, error="http 503"))
    out = e2e.summarize(records, window_tokens=240, seconds=12.0)
    assert (out["attempted"], out["failed"]) == (11, 1)
    assert out["output_tok_s"] == 20.0
    # ttft of the ten good ones: 100..1000 ms, then +inf; the 90th
    # percentile of 11 values is the 10th.
    assert out["ttft_p90_ms"] == pytest.approx(1000.0)
    assert out["ttft_p50_ms"] == pytest.approx(600.0)


def test_a_request_cut_at_the_drain_limit_misses_every_statistic():
    done = [record(i, i, i + 0.1, i + 1.0, 10) for i in range(9)]
    cut = record(9, 9, 9.5, 12.0, 4, error="unfinished", done=False,
                 usage_tokens=None)
    never_started = record(9.5, 9.5, None, None, 0, error="unfinished",
                           done=False, usage_tokens=None)
    out = e2e.summarize(done + [cut, never_started], 0, 10.0)
    assert (out["attempted"], out["failed"], out["unfinished"]) == (11, 0, 2)
    # Two of eleven lie beyond the 90th percentile's ranks: a stall
    # cannot take its own slowest requests out of a tail.
    for name in ("ttft_p90_ms", "tpot_p90_ms", "ttft_mean_ms"):
        assert out[name] == math.inf
    assert out["ttft_p50_ms"] == pytest.approx(100.0)
    # One cut request in 37 lies beyond the tail's ranks, and is still
    # counted: run.py reports such a run as not correct.
    out = e2e.summarize(done * 4 + [cut], 0, 10.0)
    assert out["unfinished"] == 1
    assert out["tpot_p90_ms"] == pytest.approx(100.0)


def test_the_mean_is_over_all_the_windows_requests():
    records = [record(i, i, i + 0.1 * (i + 1), i + 1.0, 10)
               for i in range(10)]
    out = e2e.summarize(records, 0, 10.0)
    assert out["ttft_mean_ms"] == pytest.approx(550.0)
    records.append(record(10, 10, None, None, 0, error="http 503"))
    assert e2e.summarize(records, 0, 10.0)["ttft_mean_ms"] == math.inf


def test_in_flight_counts_what_was_sent_and_has_not_ended():
    records = [dict(record(0, 0.0, 0.5, 2.0, 5), ended=2.1),
               dict(record(1, 1.0, 1.5, 4.0, 5), ended=4.1),
               dict(record(3, 3.0, None, None, 0), ended=None),
               dict(record(9, None, None, None, 0), ended=None)]
    assert [e2e.in_flight(records, t) for t in (0.5, 1.5, 3.5, 5.0)] == [
        1, 2, 2, 1]
