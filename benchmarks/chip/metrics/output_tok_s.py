"""Output tokens that reached a client inside the window, over the
window's length (tokens of requests still in flight count)."""
from benchmarks.chip import readings


def value(rec):
    return readings.tokens_in_window(rec) / rec["seconds"]
