"""The percentile, TPOT and tokens-per-second arithmetic on hand-made
timelines and arrivals."""

import math

import pytest

from chipbench import e2e, phase


def record(due, sent, first, last, tokens, phase="window", **over):
    r = {"id": "r", "phase": phase, "due": due, "sent": sent,
         "first": first, "last": last, "tokens": tokens,
         "max_tokens": tokens, "usage_tokens": tokens, "done": True,
         "error": None, "prompt_tokens": 10}
    r.update(over)
    return r


def even_stream(per_s, start, end):
    """One token every 1/per_s seconds from ``start`` to ``end``, as
    ``client.Load.arrivals`` would hold them."""
    return [[start + k / per_s, 1]
            for k in range(round((end - start) * per_s))]


def deliveries(offset, cycle=2.6, spread=0.135, end=60.0):
    """The decode-closed cell's shape: every ``cycle`` seconds 60 rows'
    32 tokens reach the clients over ``spread`` seconds, the first
    delivery ``offset`` after -12 s."""
    arrivals, at = [], -12.0 + offset
    while at < end:
        arrivals += [[at + spread * k / 60, 32] for k in range(60)]
        at += cycle
    return arrivals


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
    # Tokens are counted from the arrivals, whatever request brought
    # them: 20 a second all through, the ramp's and the drain's too.
    out = e2e.summarize(records, even_stream(20, -2.0, 16.0), seconds=12.0)
    assert (out["attempted"], out["failed"]) == (11, 1)
    assert out["output_tok_s"] == pytest.approx(20.0)
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
    out = e2e.summarize(done + [cut, never_started], [], 10.0)
    assert (out["attempted"], out["failed"], out["unfinished"]) == (11, 0, 2)
    # Two of eleven lie beyond the 90th percentile's ranks: a stall
    # cannot take its own slowest requests out of a tail.
    for name in ("ttft_p90_ms", "tpot_p90_ms", "ttft_mean_ms"):
        assert out[name] == math.inf
    assert out["ttft_p50_ms"] == pytest.approx(100.0)
    # One cut request in 37 lies beyond the tail's ranks, and is still
    # counted: run.py reports such a run as not correct.
    out = e2e.summarize(done * 4 + [cut], [], 10.0)
    assert out["unfinished"] == 1
    assert out["tpot_p90_ms"] == pytest.approx(100.0)


def test_the_mean_is_over_all_the_windows_requests():
    records = [record(i, i, i + 0.1 * (i + 1), i + 1.0, 10)
               for i in range(10)]
    out = e2e.summarize(records, [], 10.0)
    assert out["ttft_mean_ms"] == pytest.approx(550.0)
    records.append(record(10, 10, None, None, 0, error="http 503"))
    assert e2e.summarize(records, [], 10.0)["ttft_mean_ms"] == math.inf


def test_in_flight_counts_what_was_sent_and_has_not_ended():
    records = [dict(record(0, 0.0, 0.5, 2.0, 5), ended=2.1),
               dict(record(1, 1.0, 1.5, 4.0, 5), ended=4.1),
               dict(record(3, 3.0, None, None, 0), ended=None),
               dict(record(9, None, None, None, 0), ended=None)]
    assert [e2e.in_flight(records, t) for t in (0.5, 1.5, 3.5, 5.0)] == [
        1, 2, 2, 1]


@pytest.mark.parametrize("per_s,seconds", [(100, 45.0), (675, 45.0),
                                           (40, 6.0), (8, 30.0)])
def test_an_even_stream_reads_its_rate(per_s, seconds):
    arrivals = even_stream(per_s, -10.0, seconds + 20.0)
    assert e2e.output_tok_s(arrivals, seconds) == pytest.approx(
        per_s, rel=2e-3)
    assert phase.plain_tok_s(arrivals, seconds) == pytest.approx(
        per_s, rel=2e-3)


def slid_over_a_cycle(cycle, spread):
    runs = [deliveries(cycle * k / 52, cycle=cycle, spread=spread)
            for k in range(52)]
    return ([e2e.output_tok_s(a, 45.0) for a in runs],
            [phase.plain_tok_s(a, 45.0) for a in runs])


@pytest.mark.parametrize("spread", [0.135, 0.0, 0.4])
def test_deliveries_slid_over_a_cycle_hold_still(spread):
    """The decode-closed cell's cycle of 2.6 s: where the plain count
    moves by a delivery with the phase, the weighted one stays within
    half a percent, peak to peak."""
    weighted, plain = slid_over_a_cycle(2.6, spread)
    assert phase.peak_to_peak(weighted) < 0.005
    assert phase.peak_to_peak(plain) > 0.04
    assert sum(weighted) / len(weighted) == pytest.approx(1920 / 2.6,
                                                          rel=1e-3)


@pytest.mark.parametrize("cycle", [0.5, 1.0, 1.3, 2.0, 2.45, 2.7, 3.0, 5.2])
def test_whatever_the_cycle_the_phase_moves_the_rate_by_a_bounded_share(
        cycle):
    """The trapezoid is a 36 s window averaged over 9 s of origins, so
    deliveries every P seconds move it by at most 4 P^2 / (pi^2 x 9 x
    36) peak to peak: 0.85% at 2.6 s, less as the burst shortens.  The
    plain count moves by up to P / 45."""
    weighted, _ = slid_over_a_cycle(cycle, 0.0)
    limit = 4 * cycle ** 2 / (math.pi ** 2 * 9 * 36)
    assert phase.peak_to_peak(weighted) <= 1.05 * limit < cycle / 45 / 2


def test_sliding_one_runs_window_is_sliding_its_phase():
    arrivals = deliveries(0.0, end=70.0)
    weighted = phase.slide(arrivals, 45.0, e2e.output_tok_s)
    plain = phase.slide(arrivals, 45.0, phase.plain_tok_s)
    assert len(weighted) == len(plain) == 105
    assert weighted[0] == e2e.output_tok_s(arrivals, 45.0)
    assert phase.peak_to_peak(weighted) < 0.005 < 0.04 < phase.peak_to_peak(
        plain)
    # Two whole cycles on, the window sees what it saw at the start.
    assert weighted[104] == pytest.approx(weighted[0], rel=1e-9)
    later = phase.slide(arrivals, 45.0, e2e.output_tok_s, start=2.6)
    assert later[:53] == pytest.approx(weighted[52:], rel=1e-9)


@pytest.mark.parametrize("arrivals", [
    [], [[-0.001, 500]], [[45.0, 500]], [[-3.0, 9], [47.5, 9], [300.0, 1]]])
def test_tokens_outside_the_window_weigh_nothing(arrivals):
    assert e2e.output_tok_s(arrivals, 45.0) == 0.0
    inside = even_stream(100, 0.0, 45.0)
    assert e2e.output_tok_s(arrivals + inside, 45.0) == pytest.approx(
        e2e.output_tok_s(inside, 45.0), rel=1e-12)


@pytest.mark.parametrize("at,weight", [
    (0.0, 0.0), (4.5, 0.5), (9.0, 1.0), (22.5, 1.0), (36.0, 1.0),
    (40.5, 0.5), (44.999, 0.001 / 9)])
def test_a_tokens_weight_rises_over_a_fifth_of_the_window(at, weight):
    assert e2e.output_tok_s([[at, 360]], 45.0) == pytest.approx(
        360 * weight / 36.0)


@pytest.mark.parametrize("hole_from", [9.0, 15.0, 24.0])
def test_a_stall_in_the_flat_part_lowers_the_rate_by_its_share(hole_from):
    """12 s without a token between 9 s and 36 s of a 45 s window take
    12/36 of an even stream's reading."""
    stream = [a for a in even_stream(200, -5.0, 50.0)
              if not hole_from <= a[0] < hole_from + 12.0]
    assert e2e.output_tok_s(stream, 45.0) == pytest.approx(
        200 * (1 - 12 / 36), rel=2e-3)
