"""95th percentile over requests due in the window of due time to the
launch of the first prefill program that held the request
(``Request.t_prefill``, stamped by the engine just before the launch);
not launched by the close counts at close - due. Read in traced runs;
nothing to read where the program stamps no ``t_prefill``."""
from benchmarks.chip import readings


def value(rec):
    if rec["trace"] is None:
        return None
    lo, hi = rec["window"]
    due = [r for r in rec["sent"] if lo <= r.due < hi]
    if not any(hasattr(r.request, "t_prefill") for r in due):
        return None
    waits = []
    for r in due:
        t = getattr(r.request, "t_prefill", None)
        waits.append((t if t is not None and t <= hi else hi) - r.due)
    v = readings.percentile(waits, 95)
    return None if v is None else v * 1e3
