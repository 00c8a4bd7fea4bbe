"""Readings that set the limit of ``correct``, on the chip, in one process.

    python3 benchmarks/chip/limits.py --workload <cell> --seconds <s> \
        --seeds <n,n,...>

For each seed it puts new weights into the cell's compiled engine,
serves one window at the cell's own load, samples requests as a run
does, and reads two widest gaps over the same prompts and served tokens
(``reference.served_gaps``): the program's served tokens under the
reference at the stated precision (the lower reading: a sound run), and
the first choice of the reference computed one precision step below the
stated one (the control, ``precision.control`` in the configuration:
the upper reading), each as the widest and the mean gap, and whether
each passes the cell's limits (``harness.passes``). One JSON line per
seed on standard output, then a summary line with the largest program
reading and the smallest control reading of each, and how many seeds of
each passed.
The benchmark's own runs never run this.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--root", default=str(ROOT),
                    help="checkout holding BENCHMARK.json")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    from repro.launch import compile_cache

    from benchmarks.chip import harness

    compile_cache.enable()
    harness.configure_cache(jax)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.Cell(harness.Bench(args.root), args.workload, seeds[0])
    conf, ref = cell.conf, cell.ref
    prec = conf["precision"]
    rows = []
    for seed in seeds:
        if seed != cell.seed:
            cell.reseed(seed)
        t0 = time.perf_counter()
        win = cell.serve(args.seconds)
        picked = harness.sample(win.sent, int(cell.mix["check_requests"]),
                                seed)
        w = ref.init_weights(conf, seed)
        served, ctl = [], []
        for s in picked:
            g, c = ref.served_gaps(conf, w, s.prompt, s.tokens,
                                   prec["stated"], prec["control"])
            served.append(g)
            ctl.append(c)
        program = harness.numbers(cell.check, served)
        control = harness.numbers(cell.check, ctl)
        rec = {"seconds": args.seconds, "window": (win.t_open, win.t_close),
               "sent": win.sent, "setup_s": 0.0}
        e2e = {e["name"]: mod.value(rec) for e, mod in
               cell.bench.metrics(args.workload, traced=False)
               if e["name"] != "setup_s"}
        row = {"seed": seed, **e2e,
               "served_gap_mean": program["served_gap_mean"]["value"],
               "control_gap_mean": control["served_gap_mean"]["value"],
               "served_gap_max": max((float(g.max()) for g in served),
                                     default=None),
               "control_gap_max": max((float(c.max()) for c in ctl),
                                      default=None),
               "tokens_compared": program["tokens_compared"]["value"],
               "program_correct": harness.passes(program),
               "control_correct": harness.passes(control),
               "requests": len(picked),
               "seconds": time.perf_counter() - t0}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del w
    good = [r for r in rows if r["served_gap_mean"] is not None]
    summary = {"workload": args.workload, "seeds": len(good),
               "program_correct": sum(r["program_correct"] for r in rows),
               "control_correct": sum(r["control_correct"] for r in rows)}
    for stat in ("max", "mean"):
        summary[f"gap_{stat}_lower"] = max(
            r[f"served_gap_{stat}"] for r in good)
        summary[f"gap_{stat}_upper"] = min(
            r[f"control_gap_{stat}"] for r in good)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
