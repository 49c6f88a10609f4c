"""Metric arithmetic against hand-computed cases."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.harness import serving, stats  # noqa: E402


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([10, 20, 30, 40, 50], 95, 48.0),          # rank 3.8: 40 + 0.8 * 10
    ([5], 95, 5.0),
    ([3, 1, 2], 0, 1.0),
    ([3, 1, 2], 100, 3.0),
])
def test_percentile(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("first,last,n,want", [
    (1.0, 2.0, 11, 0.1),      # ten gaps in one second
    (1.0, 1.0, 1, None),      # one token: no gap
    (0.0, 0.7, 8, 0.1),       # a chunk of 8 arriving over 0.7 s
])
def test_per_request_gap(first, last, n, want):
    got = stats.per_request_gap_s(first, last, n)
    assert got is None if want is None else got == pytest.approx(want)


def test_tokens_per_second_counts_answers_inside_the_window_only():
    records = [
        {"ok": True, "done": 9.9, "n_tokens": 100},    # answered before the window
        {"ok": True, "done": 10.0, "n_tokens": 30},    # sent in the ramp, answered inside: counts
        {"ok": True, "done": 19.99, "n_tokens": 50},
        {"ok": True, "done": 20.0, "n_tokens": 70},    # the window is half-open
        {"ok": False, "done": 15.0, "n_tokens": 999},  # failed: its tokens do not count
    ]
    assert stats.tokens_per_second(records, 10.0, 20.0) == pytest.approx(8.0)


def test_histogram_ms():
    h = stats.histogram_ms([0.0001, 0.0009, 0.004, 0.7])
    assert h["<=0.5ms"] == 1 and h["<=1ms"] == 1 and h["<=5ms"] == 1 and h[">500ms"] == 1


PROM_BEFORE = """# TYPE app_tpu_ttft_seconds histogram
app_tpu_ttft_seconds_bucket{le="0.1"} 2
app_tpu_ttft_seconds_sum 1.0
app_tpu_ttft_seconds_count 4
app_tpu_batch_occupancy_sum{kind="decode"} 5.0
app_tpu_batch_occupancy_count{kind="decode"} 10
app_tpu_batch_occupancy_sum{kind="prefill"} 1.0
app_tpu_batch_occupancy_count{kind="prefill"} 4
"""
PROM_AFTER = """app_tpu_ttft_seconds_sum 4.0
app_tpu_ttft_seconds_count 10
app_tpu_batch_occupancy_sum{kind="decode"} 14.0
app_tpu_batch_occupancy_count{kind="decode"} 20
app_tpu_batch_occupancy_sum{kind="prefill"} 2.0
app_tpu_batch_occupancy_count{kind="prefill"} 8
app_tpu_engine_restarts 0
"""


def test_histogram_mean_delta_is_delta_sum_over_delta_count():
    assert serving.histogram_mean_delta(PROM_BEFORE, PROM_AFTER, "app_tpu_ttft_seconds") == pytest.approx(0.5)
    assert serving.histogram_mean_delta(
        PROM_BEFORE, PROM_AFTER, "app_tpu_batch_occupancy", kind="decode") == pytest.approx(0.9)
    assert serving.histogram_mean_delta(PROM_AFTER, PROM_AFTER, "app_tpu_ttft_seconds") is None
    assert serving.metric(PROM_AFTER, "app_tpu_batch_occupancy_count") == 28  # labels summed


def test_first_token_check_counts_exact_near_and_miss():
    import numpy as np

    logits = np.asarray([[0.0, 5.0, 4.99], [1.0, 0.0, 0.0], [0.0, 0.0, 9.0]])
    got = serving.check_first_tokens([1, 0, 0], logits, tol=0.05)
    assert (got["exact"], got["near_tie"], got["miss"]) == (2, 0, 1)
    got = serving.check_first_tokens([2, 0, 2], logits, tol=0.05)
    assert (got["exact"], got["near_tie"], got["miss"]) == (2, 1, 0)
    assert serving.near_tie_tol(2 ** -8, logits, 4.0) == pytest.approx(4 * 9 / 256)
