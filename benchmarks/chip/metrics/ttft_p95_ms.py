"""95th percentile over requests due in the window of due time to first
token; no first token by the close counts at its censored value."""
from benchmarks.chip import readings


def value(rec):
    v = readings.percentile(readings.ttft_censored(rec), 95)
    return None if v is None else v * 1e3
