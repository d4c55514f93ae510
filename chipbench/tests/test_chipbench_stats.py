"""Metric arithmetic on synthetic host timestamps."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from harness import stats  # noqa: E402
from harness.served import FREE, OBSERVE, START, WINDOW, Timeline  # noqa: E402


def window(tl, t0, t1, slots, nd, n_act=1):
    labels = np.zeros((nd, n_act, len(slots)), np.int64)
    finals = np.zeros((nd, len(slots)), np.int64)
    exits = np.full((nd, len(slots)), -1, np.int64)
    exits[0, 0] = 0
    tl.add(WINDOW, t0, t1, nd, nd, (tuple(slots), tuple(range(n_act)), labels, finals, exits))


def two_requests():
    """Request 0 (5 tokens) and request 1 (9 tokens) on slots 0 and 1."""
    tl = Timeline(64)
    tl.add(START, 0.0, 1.0, 0, 0)
    tl.add(START, 1.0, 2.0, 1, 1)
    window(tl, 2.0, 3.0, [0, 1], 4)  # req 0 done (1 + 4 = 5 tokens)
    tl.add(OBSERVE, 3.0, 3.5)
    tl.add(FREE, 3.5, 3.6, 0)
    window(tl, 4.0, 5.0, [1], 4)  # req 1 at 9 tokens: done
    return tl, np.array([5, 9])


def test_tokens_inside_the_window_only():
    tl, n = two_requests()
    assert stats.tokens_between(tl, 0.0, 10.0) == 2 + 8 + 4
    assert stats.tokens_between(tl, 1.5, 3.0) == 1 + 8  # request 1's first, then a 2-row window
    assert stats.tokens_between(tl, 3.1, 4.9) == 0


def test_per_request_tpot():
    tl, n = two_requests()
    req = stats.requests(tl, n)
    np.testing.assert_array_equal(req["count"], [5, 9])
    done, tpot = stats.tpot_ms(req, n, 0.0, 10.0)
    assert list(done) == [0, 1]
    # (last - first) / (tokens - 1): request 0 from 1.0 to 3.0 over 4 gaps
    np.testing.assert_allclose(tpot, [1e3 * 2.0 / 4, 1e3 * 3.0 / 8])
    done, _ = stats.tpot_ms(req, n, 3.5, 10.0)  # request 0 ended before
    assert list(done) == [1]


def test_p90_has_ten_beyond_with_a_hundred_and_ten():
    x = np.arange(1.0, 111.0)
    v, beyond = stats.percentile(x, 90)
    assert v == pytest.approx(np.percentile(x, 90))
    assert beyond >= 10
    v, beyond = stats.percentile(np.arange(1.0, 21.0), 90)
    assert beyond == 2  # too few requests: the tail is a maximum


def test_host_record_sums():
    tl, n = two_requests()
    req = stats.requests(tl, n)
    h = stats.host_record(tl, req, np.array([100, 200]), 0.0, 10.0, slots=2, gather_slots=4)
    assert h["windows"] == 2 and h["steps"] == 8 and h["row_steps"] == 12
    # contexts: window 1 rows at (100 + 1 + t) and (200 + 1 + t), t < 4;
    # window 2 row 1 at 200 + 5 + t
    want = sum(101 + t + 201 + t for t in range(4)) + sum(205 + t for t in range(4))
    assert h["ctx_row_steps"] == want
    assert h["prefill_s"] == pytest.approx(2.0) and h["window_s"] == pytest.approx(2.0)
    assert h["controller_s"] == pytest.approx(0.5)
    assert h["exits"] == 2 and h["ramp_calls"] == 8 * 4
    assert h["span_s"] == pytest.approx(5.0)


def test_host_gaps_name_the_longest_stall():
    tl, _ = two_requests()
    g = stats.host_gaps(tl, 0.0, 10.0, gc_spans=[(3.6, 3.9, 2), (4.2, 4.25, 0), (11.0, 12.0, 2)])
    assert g["step_multi"] == (pytest.approx(2.0), pytest.approx(1.0), 2)
    assert g["start"][2] == 2 and g["observe"][2] == 1 and g["free"][2] == 1
    # between calls: 0 + 0 + 0 + 0 + 0.4 (free at 3.6, the next window at 4.0)
    total, longest, where = g["between"]
    assert total == pytest.approx(0.4) and longest == pytest.approx(0.4)
    assert where == "free>step_multi"
    # the collection outside the window is not counted
    assert g["gc"] == (pytest.approx(0.35), pytest.approx(0.3), 2, 1)
