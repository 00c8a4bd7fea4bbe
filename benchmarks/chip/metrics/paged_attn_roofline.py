"""Roofline share of the decode attention kernel: the least time the
chip needs to read the keys and values the active slots hold (at the
configuration's KV width, scales included) and do their QK and PV
products, over the kernel's device time."""
from benchmarks.chip import costs, readings


def value(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    kv_bits = (rec["quant"] or {}).get("kv_bits")
    need = spent = 0.0
    for stats, t in readings.calls(tr, "decode_call",
                                   "paged_decode_attention"):
        f, b = costs.paged_attention(rec["model"], stats["rows"],
                                     stats["keys"], kv_bits)
        need += costs.roofline_seconds(f, b, rec["peaks"])
        spent += t
    return 100.0 * need / spent if spent else None
