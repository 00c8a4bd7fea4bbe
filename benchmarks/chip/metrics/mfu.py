"""Model FLOPs of the useful work done in the traced window, over the
window times the chip's bfloat16 peak: prompt tokens prefilled (not
padding, not prefix-cache hits) and decode tokens through every layer,
attention at their context, and logits where they are used (each
decode token, the last prompt token of each admitted request)."""
from benchmarks.chip import costs


def value(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    m, flops = rec["model"], 0.0
    for _, _, _, st in tr.spans_named("bench.decode_call"):
        flops += costs.model_flops(m, st["rows"], st["keys"], st["rows"])
    for _, _, _, st in tr.spans_named("bench.prefill_call"):
        flops += costs.model_flops(m, st["tokens"], st["keys"], st["rows"])
    if not flops:
        return None
    return 100.0 * flops / (tr.window_s * rec["peaks"]["bf16_flops"])
