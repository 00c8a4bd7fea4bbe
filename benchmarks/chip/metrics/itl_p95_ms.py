"""95th percentile of every inter-token gap clients saw in the window."""
from benchmarks.chip import readings


def value(rec):
    v = readings.percentile(readings.gaps_in_window(rec), 95)
    return None if v is None else v * 1e3
