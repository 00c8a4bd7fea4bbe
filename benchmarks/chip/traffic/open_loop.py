"""Open loop: requests sent at their due times, whatever the server does.

Mix keys: ``rate_per_s`` (mean arrival rate); arrivals are Poisson (a
fixed schedule of exponential gaps, see ``sampling.poisson_dues``) from
the start of traffic, ``preroll_s`` before the window opens, to its
close. Each request is one of the mix's fixed sizes of ``prompt``
(shared prefix, if any, plus a unique part) and ``output``; the seed
draws the token ids.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip import sampling


async def drive(win) -> None:
    mix, rng = win.mix, np.random.default_rng(win.seed)
    dues = sampling.poisson_dues(mix["rate_per_s"],
                                 win.preroll + win.seconds)
    prefixes = win.space.prefixes(rng)
    for due, triple in zip(dues, win.space.sizes(len(dues))):
        prompt = win.space.prompt(rng, prefixes, triple)
        await win.sleep_until(win.t_start + float(due))
        win.send(prompt, triple[2], win.t_start + float(due))
