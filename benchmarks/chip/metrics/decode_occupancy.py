"""Share of the decode program's rows that served a request, over the
window: ``engine.stats`` ``decode_rows`` (active rows summed over decode
steps) over ``decode_slots`` (``max_batch`` a step). Read in traced
runs; nothing to read where the program keeps no such counters."""


def value(rec):
    if rec["trace"] is None:
        return None
    opn, cls = rec["stats_open"], rec["stats_close"]
    if "decode_slots" not in cls:
        return None
    slots = cls["decode_slots"] - opn["decode_slots"]
    rows = cls["decode_rows"] - opn["decode_rows"]
    return 100.0 * rows / slots if slots else None
