"""Mean host milliseconds per engine tick: the benchmark's span around
``engine.step`` less the device-busy time inside it."""
from benchmarks.chip import readings


def value(rec):
    tr = rec["trace"]
    return None if tr is None else readings.host_self_ms(tr, "tick")
