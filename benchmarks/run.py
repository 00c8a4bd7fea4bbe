"""Benchmark entry point — one table per paper figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * vggb/<layer>/<variant>      — paper Figs. 15/16 analogue (this host's
                                  CPU): measured us, derived = speedup vs
                                  native int8.
  * a57-model/<variant>         — paper Figs. 17/18 analogue: modeled
                                  ops/value, derived = modeled speedup
                                  ('packed' variant reproduces the paper's
                                  6x/10x claims; 'extract' is our general
                                  TPU-port implementation).
  * samd-matmul/<bits>          — packed-weight GEMM (the TPU serving
                                  kernel's XLA path, CPU-measured): us,
                                  derived = speedup vs bf16 matmul of the
                                  same logical shape.
  * serving/<variant>           — continuous-batching decode throughput at
                                  mixed arrival times: value = tokens/s,
                                  derived = speedup vs the per-row
                                  fallback baseline (bench_serving.py).

``--json`` additionally writes machine-readable BENCH_<table>.json files
(per-table rows + host info; see jsonio.py) so the perf trajectory is
tracked across commits.

Full sweep: python -m benchmarks.run --full (slower; all 10 VGG layers,
bit widths 8..2).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def bench_samd_matmul(bits_list=(2, 4, 8)):
    from repro.quant import QuantConfig, pack_weights
    from repro.quant.packing import qmatmul

    rows = []
    m, k, n = 32, 2048, 2048
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32).astype(jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32).astype(jnp.bfloat16)

    f_ref = jax.jit(lambda x, w: x @ w)
    jax.block_until_ready(f_ref(x, w))
    ts = []
    for _ in range(10):
        t0 = time.perf_counter()
        jax.block_until_ready(f_ref(x, w))
        ts.append(time.perf_counter() - t0)
    t_ref = float(np.median(ts)) * 1e6
    rows.append(("samd-matmul/bf16", t_ref, 1.0))

    for bits in bits_list:
        cfg = QuantConfig(bits=bits)
        packed, scale = pack_weights(w.astype(jnp.float32), cfg)
        f = jax.jit(lambda x, p, s: qmatmul(x, p, s, k, cfg))
        jax.block_until_ready(f(x, packed, scale))
        ts = []
        for _ in range(10):
            t0 = time.perf_counter()
            jax.block_until_ready(f(x, packed, scale))
            ts.append(time.perf_counter() - t0)
        t = float(np.median(ts)) * 1e6
        rows.append((f"samd-matmul/b{bits}", t, t_ref / t))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_<table>.json artifacts")
    ap.add_argument("--out-dir", default=".")
    ap.add_argument("--no-serving", action="store_true",
                    help="skip the serving throughput table")
    args = ap.parse_args()

    from benchmarks import bench_serving, bench_vggb

    all_rows: list[tuple[str, float, float]] = []

    def emit(name: str, value: float, derived: float,
             fmt: str = "{:.1f},{:.3f}"):
        print(("{}," + fmt).format(name, value, derived))
        all_rows.append((name, float(value), float(derived)))

    print("name,us_per_call,derived")

    from repro.configs.vggb import VGGB_LAYERS

    if args.full:
        layers, bits = None, (8, 6, 4, 3, 2)
    else:
        layers = [VGGB_LAYERS[0], VGGB_LAYERS[4], VGGB_LAYERS[8]]
        bits = (8, 4, 2)

    vggb_json_rows = bench_vggb.run(layers=layers, bit_list=bits,
                                    quick=not args.full)
    vggb_json_rows += bench_vggb.tpu_decode_model(
        layers or VGGB_LAYERS, tuple(b for b in bits if b in (2, 4, 8)))
    for row in vggb_json_rows:
        emit(row["name"], row["us"],
             row.get("speedup_vs_native_int8_full")
             or row.get("speedup_vs_native_int8")
             or row.get("speedup_vs_native") or 0.0)

    for name, per_val, speedup in bench_vggb.op_count_model(bits):
        emit(name, per_val, speedup, fmt="{:.2f},{:.2f}")

    for name, us, derived in bench_samd_matmul():
        emit(name, us, derived)

    serving_json_rows = None
    if not args.no_serving:
        csv_rows, serving_json_rows = bench_serving.run(quick=not args.full)
        for name, tps, speedup in csv_rows:
            emit(name, tps, speedup, fmt="{:.2f},{:.2f}")

    if args.json:
        from benchmarks.jsonio import write_bench_json

        by_table: dict[str, list[dict]] = {}
        for name, value, derived in all_rows:
            table = name.split("/", 1)[0]
            by_table.setdefault(table, []).append(
                {"name": name, "value": value, "derived": derived}
            )
        # the vggb + tpu-model rows share one artifact (richer dict rows)
        by_table.pop("vggb", None)
        by_table.pop("tpu-model", None)
        path = write_bench_json("vggb", vggb_json_rows,
                                out_dir=args.out_dir)
        print(f"# wrote {path}")
        for table, trows in by_table.items():
            if table == "serving" and serving_json_rows is not None:
                trows = serving_json_rows  # richer rows for serving
            path = write_bench_json(table, trows, out_dir=args.out_dir)
            print(f"# wrote {path}")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
