"""Window arithmetic: rate with in-flight tokens, raw gaps over all
requests, censored TTFT, and the counter-based readers."""
import types

import pytest

from benchmarks.chip import harness, readings
from benchmarks.chip.metrics import (itl_p95_ms, output_tok_s,
                                     prefix_hit_share, queue_wait_p95_ms,
                                     ttft_p95_ms)


def sent(due, times, n_prompt=10, admit=None, refused=None):
    s = harness.Sent(due=due, sent=due, n_prompt=n_prompt, max_tokens=99,
                     prompt=None, times=list(times), refused=refused)
    s.request = types.SimpleNamespace(t_admit=admit, error=None,
                                      truncated=False)
    return s


def rec(reqs, lo=0.0, hi=10.0, **kw):
    return dict({"window": (lo, hi), "seconds": hi - lo, "sent": reqs}, **kw)


def test_rate_counts_tokens_of_requests_in_flight():
    reqs = [sent(0.0, [1.0, 2.0, 3.0]),          # finished
            sent(5.0, [6.0, 9.0, 11.0, 12.0]),   # in flight at the close
            sent(-1.0, [-0.5, 0.5])]             # began before the open
    r = rec(reqs)
    assert readings.tokens_in_window(r) == 3 + 2 + 1
    assert output_tok_s.value(r) == pytest.approx(0.6)


def test_gaps_are_raw_over_all_requests():
    # one request sees a 4 s stall; a per-request mean would hide it
    reqs = [sent(0.0, [1.0, 1.1, 1.2, 5.2, 5.3]),
            sent(0.0, [2.0, 2.1])]
    gaps = sorted(readings.gaps_in_window(rec(reqs)))
    assert gaps == pytest.approx([0.1, 0.1, 0.1, 0.1, 4.0])
    assert itl_p95_ms.value(rec(reqs)) == pytest.approx(
        readings.percentile(gaps, 95) * 1e3)


def test_ttft_is_censored_at_the_close():
    reqs = [sent(1.0, [1.5]),           # 0.5 s
            sent(8.0, []),              # no token by the close: 2 s
            sent(9.0, [10.5]),          # first token after the close: 1 s
            sent(4.0, [], refused="queue_full"),   # refused: 6 s
            sent(12.0, [12.1])]         # due after the close: not counted
    assert sorted(readings.ttft_censored(rec(reqs))) == pytest.approx(
        [0.5, 1.0, 2.0, 6.0])
    assert ttft_p95_ms.value(rec(reqs)) == pytest.approx(
        readings.percentile([0.5, 1.0, 2.0, 6.0], 95) * 1e3)


def test_percentile_is_numpys_linear_interpolation():
    assert readings.percentile([1, 2, 3, 4], 50) == 2.5
    assert readings.percentile([], 95) is None


def test_queue_wait_and_prefix_share():
    reqs = [sent(1.0, [1.4], n_prompt=100, admit=1.3),
            sent(2.0, [], n_prompt=300, admit=None),
            sent(3.0, [3.9], n_prompt=100, admit=3.8)]
    r = rec(reqs, stats_open={"prefix_tokens_saved": 10},
            stats_close={"prefix_tokens_saved": 60})
    waits = [0.3, 8.0, 0.8]
    assert queue_wait_p95_ms.value(r) == pytest.approx(
        readings.percentile(waits, 95) * 1e3)
    # 50 tokens saved of 200 admitted in the window
    assert prefix_hit_share.value(r) == pytest.approx(25.0)
