"""Percentile and time-per-output-token arithmetic on a hand-made
timeline."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import stats  # noqa: E402


@pytest.mark.parametrize("q,want", [(50, 5), (90, 9), (100, 10), (1, 1)])
def test_percentile_is_nearest_rank(q, want):
    assert stats.percentile(range(1, 11), q) == want


def test_percentile_of_nothing():
    assert stats.percentile([], 90) is None


def test_tpot():
    assert stats.tpot_s(10.0, 10.7, 8) == pytest.approx(0.1)
    assert stats.tpot_s(10.0, 10.0, 1) is None


def _msg(due, first=None, last=None, n=0, reply=None, done=None):
    return {"due": due, "first_t": first, "last_t": last, "n_tokens": n,
            "reply_t": reply, "done_t": done}


def test_end_to_end_counts_from_due_and_only_the_window():
    t0 = 100.0
    msgs = [
        _msg(99.0, 99.1, 99.2, 5, 99.3, 99.25),          # warm: not counted
        _msg(100.0, 100.2, 100.9, 8, 101.0, 100.95),
        _msg(101.0, 101.5, 102.5, 11, 102.6, 102.55),
        _msg(109.0, 109.3, 110.3, 21, 110.4, 110.35),    # done after window
        _msg(109.5),                                     # never answered
        _msg(110.0, 110.1, 110.2, 3, 110.3, 110.25),     # cool: not counted
    ]
    out = stats.end_to_end(msgs, t0, 10.0)
    assert out["ttft_p90_ms"] == pytest.approx(500.0)
    assert out["reply_p90_ms"] == pytest.approx(1600.0)
    assert out["tpot_p90_ms"] == pytest.approx(100.0)
    # 8 + 11 tokens completed inside [100, 110)
    assert out["out_tokens_per_s"] == pytest.approx(1.9)


def test_histogram_quantile_interpolates_in_the_bucket():
    bounds = [0.001, 0.002, 0.004]
    assert stats.histogram_quantile(bounds, [0, 10, 0, 0], 50) == (
        pytest.approx(0.0015))
    assert stats.histogram_quantile(bounds, [4, 4, 0, 0], 75) == (
        pytest.approx(0.0015))
    assert stats.histogram_quantile(bounds, [0, 0, 0, 0], 50) is None
