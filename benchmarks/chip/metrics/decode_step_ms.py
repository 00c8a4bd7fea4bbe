"""Mean device milliseconds per run of the paged decode program."""
from benchmarks.chip import readings


def value(rec):
    tr = rec["trace"]
    return None if tr is None else readings.module_ms(
        tr, "jit_paged_ragged_serve_step")
