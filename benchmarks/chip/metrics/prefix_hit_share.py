"""Share of the prompt tokens admitted in the window whose prefill the
prefix cache skipped (``engine.stats["prefix_tokens_saved"]``)."""


def value(rec):
    lo, hi = rec["window"]
    admitted = sum(r.n_prompt for r in rec["sent"]
                   if r.request is not None
                   and r.request.t_admit is not None
                   and lo <= r.request.t_admit <= hi)
    saved = (rec["stats_close"]["prefix_tokens_saved"]
             - rec["stats_open"]["prefix_tokens_saved"])
    return 100.0 * saved / admitted if admitted else None
