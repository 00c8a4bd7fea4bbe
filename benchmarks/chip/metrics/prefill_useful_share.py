"""Share of the positions the prefill programs computed in the window
that held a prompt token they wrote: ``engine.stats`` ``prefill_tokens``
over ``prefill_positions`` (``max_batch`` x bucket a batched call). Read
in traced runs; nothing to read where the program keeps no such
counters."""


def value(rec):
    if rec["trace"] is None:
        return None
    opn, cls = rec["stats_open"], rec["stats_close"]
    if "prefill_positions" not in cls:
        return None
    positions = cls["prefill_positions"] - opn["prefill_positions"]
    tokens = cls["prefill_tokens"] - opn["prefill_tokens"]
    return 100.0 * tokens / positions if positions else None
