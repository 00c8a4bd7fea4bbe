"""Block-size hillclimb for the blocked SAMD kernels.

Runs the hypothesis->change->re-measure ladder over the tunable block
shapes of ``samd_matmul`` (reduction block ``block_kw``) and
``samd_conv2d`` (channel block ``block_cw``) on the VGG-B layer shapes at
bits in {2, 4, 8} — the sweep that selected the kernels' defaults. Conv
cells time the full layer; matmul cells time the layer's im2col GEMM
(M = H*W, K = 9*C_in, N = C_out) plus a decode-shaped GEMM (M = 8, the
serving draft's regime).

On CPU hosts the ladder times the unrolled-jnp lowerings (what CPU CI and
the serving draft actually run); on a TPU it times the Mosaic kernels,
where ``block_n`` joins the sweep (multi-MXU-tile N-blocks). Re-run on
real TPU hardware to retune the Pallas defaults.

Every variant is appended to ``artifacts/hillclimb.jsonl``; the winner
per cell is printed at the end.

Run:  PYTHONPATH=src python -m benchmarks.hillclimb [--repeats 3]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

# (name, c_in, c_out, h, w) — the two acceptance layers plus the ladder's
# smoke layer; pass --full for the whole table
LAYER_PICKS = ("conv1_1", "conv3_1", "conv5_1")
BITS = (2, 4, 8)
KW_LADDER = (32, 64, 128, 256)
CW_LADDER = (16, 32, 64, 128)
BN_LADDER = (128, 256, 512)   # TPU-only (the jnp lowerings have no N block)


def _static_reject(check, vmem=None):
    """Lane-safety gate run before a ladder cell is ever timed: returns
    a rejection reason, or None when the cell is statically safe. The
    autotuner can therefore never recommend a configuration the checker
    (repro.analysis) would refuse at trace time."""
    from repro.analysis import contracts

    verdict = check()
    if not verdict.ok:
        return f"{verdict.status}: {verdict.detail}"
    if vmem is not None:
        est, limit = vmem(), contracts.vmem_limit("tpu")
        if est > limit:
            return f"vmem-budget: {est} bytes > {limit} (tpu)"
    return None


def _time(fn, *args, repeats=3):
    jax.block_until_ready(fn(*args))
    runs = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        runs.append(time.perf_counter() - t0)
    return float(min(runs)) * 1e6, [r * 1e6 for r in runs]


def matmul_variants(m, k, n, bits, repeats, on_tpu):
    from repro.kernels import samd_matmul as mm
    from repro.quant.config import QuantConfig
    from repro.quant.packing import pack_weights

    cfg = QuantConfig(bits=bits)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    packed, scale = pack_weights(w, cfg)
    from repro.analysis import contracts

    for bkw in KW_LADDER:
        bns = BN_LADDER if on_tpu else (None,)
        for bn in bns:
            if on_tpu:
                def f(x, p, s, bkw=bkw, bn=bn):
                    return mm.samd_matmul(x, p, s, k, cfg, block_kw=bkw,
                                          block_n=bn)
                params = {"block_kw": bkw, "block_n": bn}
                vmem = lambda bkw=bkw, bn=bn: contracts.matmul_vmem_bytes(
                    cfg, block_m=min(128, m), block_n=bn, block_kw=bkw
                )
            else:
                def f(x, p, s, bkw=bkw):
                    return mm.samd_matmul_xla(x, p, s, k, cfg,
                                              block_kw=bkw)
                params = {"block_kw": bkw}
                vmem = None
            reason = _static_reject(
                lambda: contracts.check_matmul_config(cfg, k), vmem
            )
            if reason is not None:
                yield params, None, reason
                continue
            us, runs = _time(f, x, packed, scale, repeats=repeats)
            yield params, us, runs


def conv_variants(c_in, c_out, h, w, bits, repeats, on_tpu):
    from repro.kernels import samd_conv as cv
    from repro.quant.config import QuantConfig
    from repro.quant.packing import pack_conv_weights

    cfg = QuantConfig(bits=bits)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(c_in, h, w)), jnp.float32)
    wt = jnp.asarray(rng.normal(size=(3, 3, c_in, c_out)), jnp.float32)
    packed, scale = pack_conv_weights(wt, cfg)
    from repro.analysis import contracts

    for bcw in CW_LADDER:
        bns = BN_LADDER if on_tpu else (None,)
        for bn in bns:
            if on_tpu:
                def f(x, p, s, bcw=bcw, bn=bn):
                    return cv.samd_conv2d(x, p, s, cfg, block_cw=bcw,
                                          block_n=bn)
                params = {"block_cw": bcw, "block_n": bn}
                vmem = lambda bcw=bcw, bn=bn: contracts.conv2d_vmem_bytes(
                    cfg, w_img=w, block_cw=bcw, block_n=bn
                )
            else:
                def f(x, p, s, bcw=bcw):
                    return cv.samd_conv2d_xla(x, p, s, cfg, block_cw=bcw)
                params = {"block_cw": bcw}
                vmem = None
            reason = _static_reject(
                lambda: contracts.check_conv2d_config(cfg, 3, 3, c_in),
                vmem,
            )
            if reason is not None:
                yield params, None, reason
                continue
            us, runs = _time(f, x, packed, scale, repeats=repeats)
            yield params, us, runs


def main(out="artifacts/hillclimb.jsonl"):
    from repro.configs.vggb import VGGB_LAYERS

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all 10 VGG-B layers (default: "
                         + ",".join(LAYER_PICKS) + ")")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args()

    layers = VGGB_LAYERS if args.full else [
        l for l in VGGB_LAYERS if l[0] in LAYER_PICKS
    ]
    on_tpu = jax.default_backend() == "tpu"
    lowering = "pallas-mosaic" if on_tpu else "jnp-unrolled"
    os.makedirs(os.path.dirname(out), exist_ok=True)
    winners = []
    with open(out, "a") as fh:
        for (name, c_in, c_out, h, w) in layers:
            for bits in BITS:
                cells = [
                    (f"conv/{name}/b{bits}",
                     conv_variants(c_in, c_out, h, w, bits, args.repeats,
                                   on_tpu)),
                    (f"matmul/{name}-im2col/b{bits}",
                     matmul_variants(h * w, 9 * c_in, c_out, bits,
                                     args.repeats, on_tpu)),
                    (f"matmul/{name}-decode/b{bits}",
                     matmul_variants(8, 9 * c_in, c_out, bits,
                                     args.repeats, on_tpu)),
                ]
                for cell, variants in cells:
                    best = None
                    for params, us, runs in variants:
                        if us is None:  # statically rejected, never timed
                            rec = {"cell": cell, "lowering": lowering,
                                   "params": params, "rejected": runs}
                            fh.write(json.dumps(rec) + "\n")
                            print(f"{cell} {params}: REJECTED ({runs})")
                            continue
                        rec = {"cell": cell, "lowering": lowering,
                               "params": params, "us": us, "runs_us": runs}
                        fh.write(json.dumps(rec) + "\n")
                        print(f"{cell} {params}: {us:.0f}us")
                        if best is None or us < best[1]:
                            best = (params, us)
                    if best is None:
                        print(f"{cell}: every variant statically rejected")
                        continue
                    winners.append((cell, *best))
                    jax.clear_caches()
    print("\n# winners")
    for cell, params, us in winners:
        print(f"{cell}: {params} ({us:.0f}us)")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
