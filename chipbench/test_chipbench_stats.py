"""Latency arithmetic on hand-made samples."""

import math

import pytest

from chipbench import stats
from chipbench.client import Record


def rec(due, first, last, text, status=200, done=True, finish="stop"):
    r = Record(0, "short", len(text.split()), "p", due)
    r.t_first, r.t_last, r.deltas = first, last, [text]
    r.status, r.done, r.finish = status, done, finish
    return r


def test_percentile_interpolates_like_numpy():
    xs = [4.0, 1.0, 3.0, 2.0, 5.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 90) == pytest.approx(4.6)
    assert stats.percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_failed_requests_count_as_missing():
    xs = [1.0, 2.0, 3.0, 4.0, math.inf]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 75) == 4.0
    assert math.isinf(stats.percentile(xs, 90))


def test_ttft_runs_from_the_due_time():
    r = rec(10.0, 10.25, 11.0, "t1 t2 t3")
    assert stats.ttft_s(r) == pytest.approx(0.25)
    assert math.isinf(stats.ttft_s(rec(10.0, 10.25, 11.0, "t1",
                                       status=503)))
    assert math.isinf(stats.ttft_s(rec(10.0, 10.25, 11.0, "t1",
                                       done=False)))


def test_tpot_over_tokens_after_the_first():
    r = rec(0.0, 1.0, 1.5, "t1 t2 t3 t4 t5 t6")
    assert stats.tpot_ms(r) == pytest.approx(100.0)
    assert stats.tpot_ms(rec(0.0, 1.0, 1.0, "t1")) is None
    assert stats.tpot_ms(rec(0.0, 1.0, 2.0, "t1 t2",
                             finish="timeout")) is None
