"""Find a chat cell's knee once, on the chip, in one process.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 0.25,0.5,1

Serves one window per offered rate on one warmed engine (the mix's
``rate_per_s`` replaced) and prints one JSON line per rate: the cell's
end-to-end metrics, how many requests were due, and the backlog — due
requests with no first token at the close, and the median TTFT of the
first and the second half of the window (a growing backlog shows as a
second half far slower than the first). The knee is the highest rate
whose ``ttft_p95_ms`` and ``itl_p95_ms`` meet the limits in PERF.md with
no growing backlog; the mix's rate is then set to 0.8 x the knee by
hand. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--root", default=str(ROOT),
                    help="checkout holding BENCHMARK.json")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness, readings

    import jax
    import numpy as np
    from repro.launch import compile_cache

    compile_cache.enable()
    harness.configure_cache(jax)
    t0 = time.perf_counter()
    bench = harness.Bench(args.root)
    cell = harness.Cell(bench, args.workload, args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.eng.reset()
        mix = dict(cell.mix, rate_per_s=rate)
        win = cell.serve(args.seconds, mix=mix)
        rec = {"seconds": args.seconds, "window": (win.t_open, win.t_close),
               "sent": win.sent, "setup_s": 0.0}
        row = {"rate_per_s": rate}
        for entry, mod in bench.metrics(args.workload, traced=False):
            row[entry["name"]] = mod.value(rec)
        lo, hi = rec["window"]
        due = [r for r in win.sent if lo <= r.due < hi]
        ttft = readings.ttft_censored(rec)
        half = lo + args.seconds / 2
        first = [t for r, t in zip(due, ttft) if r.due < half]
        second = [t for r, t in zip(due, ttft) if r.due >= half]
        row.update({
            "due": len(due),
            "no_first_token_at_close": sum(
                1 for r in due if not r.times or r.times[0] > hi),
            "ttft_p50_first_half_ms": float(np.median(first)) * 1e3
            if first else None,
            "ttft_p50_second_half_ms": float(np.median(second)) * 1e3
            if second else None,
        })
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
