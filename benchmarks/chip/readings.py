"""Window arithmetic shared by the metric readers.

A run's record ``rec`` holds, in host ``perf_counter`` seconds, the
window ``(open, close)`` and every request sent (``harness.Sent``: due
and send times, the time each token reached its client). Percentiles
are numpy's linear interpolation (the arithmetic of
``repro.serving.metrics.percentile``, copied so that the yardstick does
not move with the program).
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float):
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def tokens_in_window(rec) -> int:
    """Output tokens that reached a client inside the window, requests
    still in flight included."""
    lo, hi = rec["window"]
    return sum(1 for r in rec["sent"] for t in r.times if lo <= t <= hi)


def gaps_in_window(rec) -> list:
    """Every gap between two consecutive tokens of one request that a
    client saw, both inside the window: raw gaps, not per-request means,
    so a tick that held a prefill shows."""
    lo, hi = rec["window"]
    out = []
    for r in rec["sent"]:
        ts = [t for t in r.times if lo <= t <= hi]
        out += list(np.diff(ts))
    return out


def ttft_censored(rec) -> list:
    """Seconds from due time to first token for every request due in the
    window; a request with no first token by the close (refused ones
    included) counts at its censored value, close - due."""
    lo, hi = rec["window"]
    out = []
    for r in rec["sent"]:
        if not lo <= r.due < hi:
            continue
        first = r.times[0] if r.times else None
        out.append((first if first is not None and first <= hi else hi)
                   - r.due)
    return out


def calls(tr, span: str, op: str) -> list:
    """For each ``bench.<span>`` call in the traced window: (its keyword
    stats, the summed device time of ops named ``op`` that started
    inside it). The step spans wait for their program, so its ops fall
    inside them."""
    import bisect

    from benchmarks.chip.trace import op_base

    starts = [o[1] for o in tr.ops]
    out = []
    for _, s0, dur, stats in tr.spans_named("bench." + span):
        i, j = bisect.bisect_left(starts, s0), bisect.bisect_left(
            starts, s0 + dur)
        t = sum(o[2] for o in tr.ops[i:j] if op_base(o[0]) == op)
        out.append((stats, t * 1e-9))
    return out


def host_self_ms(tr, span: str) -> float | None:
    """Mean milliseconds per ``bench.<span>`` in which no device op ran."""
    import bisect

    busy = tr.busy_intervals(0)
    ends = [e for _, e in busy]
    vals = []
    for _, s0, dur, _ in tr.spans_named("bench." + span):
        s1 = s0 + dur
        covered = 0
        for bs, be in busy[bisect.bisect_right(ends, s0):]:
            if bs >= s1:
                break
            covered += min(be, s1) - max(bs, s0)
        vals.append((dur - covered) * 1e-6)
    return float(np.mean(vals)) if vals else None


def module_ms(tr, name: str) -> float | None:
    """Mean device milliseconds per run of program ``name``."""
    d = [m[2] for m in tr.modules if m[0] == name]
    return float(np.mean(d)) * 1e-6 if d else None
