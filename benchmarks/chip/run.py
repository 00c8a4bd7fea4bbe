"""Run one cell of the chip benchmark once, on the machine it starts on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

(``python -m benchmarks.chip.run`` works too), from the checkout root.
The cell, its configuration, traffic mix, metrics and limits are read
from ``BENCHMARK.json`` and the files it names (see ``harness.py``).

It refuses to run, exits non-zero and prints no result unless JAX's
devices are TPUs and there are as many as the cell asks for. Lines on
standard error say what ran; the last ones are each number compared for
``correct`` beside its limit. The last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``check``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchmarks.chip import harness

    try:
        bench = harness.Bench(ROOT)
        chips = bench.workload(args.workload)["chips"]
    except (OSError, KeyError, ValueError) as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 2

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"FAIL: the cell needs {chips} TPU chip(s); JAX has "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind}). There is no fallback.",
              file=sys.stderr)
        return 1
    from repro.launch import compile_cache

    compile_cache.enable()
    harness.configure_cache(jax)
    result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace), T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
