"""Mean device milliseconds per run of the paged prefill program."""
from benchmarks.chip import readings


def value(rec):
    tr = rec["trace"]
    return None if tr is None else readings.module_ms(
        tr, "jit_paged_prefill_step")
