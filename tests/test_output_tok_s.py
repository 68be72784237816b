"""Tier 1 guards the definition of the benchmark's ``output_tok_s``
(chipbench/e2e.py; PERF.md section 2): the even-stream and the
slid-delivery cases of chipbench/tests/test_e2e.py, which tier 1 does
not collect."""

import pytest

from chipbench import e2e, phase


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


@pytest.mark.parametrize("per_s,seconds", [(100, 45.0), (675, 45.0),
                                           (40, 6.0), (8, 30.0)])
def test_an_even_stream_reads_its_rate(per_s, seconds):
    arrivals = even_stream(per_s, -10.0, seconds + 20.0)
    assert e2e.output_tok_s(arrivals, seconds) == pytest.approx(
        per_s, rel=2e-3)


@pytest.mark.parametrize("spread", [0.135, 0.0, 0.4])
def test_deliveries_slid_over_a_cycle_hold_still(spread):
    """Bursts of 1920 tokens every 2.6 s, the first slid over one
    cycle in 52 steps: the weighted reading stays within half a
    percent peak to peak where the plain count it replaced moves by a
    delivery, over 4%."""
    runs = [deliveries(2.6 * k / 52, spread=spread) for k in range(52)]
    weighted = [e2e.output_tok_s(a, 45.0) for a in runs]
    plain = [phase.plain_tok_s(a, 45.0) for a in runs]
    assert phase.peak_to_peak(weighted) < 0.005
    assert phase.peak_to_peak(plain) > 0.04
    assert sum(weighted) / len(weighted) == pytest.approx(1920 / 2.6,
                                                          rel=1e-3)
