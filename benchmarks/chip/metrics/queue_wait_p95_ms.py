"""95th percentile over requests due in the window of due time to the
engine's admission stamp (``Request.t_admit``, set once the request's
prefill has run); not admitted by the close counts at close - due."""
from benchmarks.chip import readings


def value(rec):
    lo, hi = rec["window"]
    waits = []
    for r in rec["sent"]:
        if lo <= r.due < hi:
            t = getattr(r.request, "t_admit", None)
            waits.append((t if t is not None and t <= hi else hi) - r.due)
    v = readings.percentile(waits, 95)
    return None if v is None else v * 1e3
