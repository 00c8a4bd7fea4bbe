"""Paper figures 15-18: quantized VGG-B convolution, SAMD vs native 8-bit.

Reproduces the paper's evaluation protocol on this host's CPU (the Intel
figures' analogue; the Cortex-A57 figures are reproduced as an op-count
model, since no ARM silicon is attached):

  * workload: each VGG-B conv layer = 3x3 kernels over C_in channels
    (Simonyan & Zisserman table 1B).
  * native baseline: signed 8-bit direct convolution (Fig. 14 loop) via
    XLA's conv on int8 with int32 accumulation.
  * SAMD scalar kernels (historical rows): the synthesized bit-precise
    conv-as-multiplication / vector-scale ops, one output CHANNEL per
    layer (time is linear in output channels).
  * blocked kernels (this PR's rows): the production ``samd_conv2d``
    path — packed-weight storage, fused-im2col block loop, integer-code
    contraction on the matmul unit — measured over the FULL layer
    (all output channels), against full-layer native int8 AND f32
    references.

Row naming (the perf-gate rename rule: a row name pins a MEANING):

  * vggb/<layer>/native-int8       — 1-output-channel int8 lax.conv,
                                     VALID padding (the original rows;
                                     unchanged meaning since the seed)
  * vggb/<layer>/samd<b>-temp      — 1-output-channel scalar SAMD kernel
                                     (conv-as-multiplication for b<=4,
                                     vector-scale above)
  * vggb/<layer>/native-int8-full  — full-layer int8 lax.conv, padding 1
  * vggb/<layer>/native-f32-full   — full-layer f32 lax.conv, padding 1
                                     (XLA's fast conv path — the honest
                                     "what you'd actually run" reference)
  * vggb/<layer>/blocked<b>        — full-layer blocked SAMD conv2d at
                                     b bits (the new kernel; CPU hosts
                                     run the unrolled-jnp lowering,
                                     TPU the Mosaic kernel). Extras:
                                     speedup vs both full references,
                                     us_per_out_channel, and
                                     speedup_vs_scalar_kernel (the
                                     per-channel ratio against the
                                     samd<b>-temp row — the ">= 4x over
                                     the pre-PR kernel" acceptance).
  * tpu-model/<layer>/decode-b<b>  — analytic TPU roofline for the
                                     serving decode regime (excluded
                                     from the perf gate: deterministic
                                     model, not a measurement).

All measured rows are best-of-``--repeats`` LATENCIES (us; the runs are
recorded per row) after one untimed compile+warmup call, and the gate
diffs them with ``--metric us --lower-is-better``.

Run:  PYTHONPATH=src python -m benchmarks.bench_vggb \
          [--full] [--layers conv3_1,conv5_1] [--bits 2,4,8]
          [--repeats 5] [--out-dir .]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.vggb import VGGB_LAYERS
from repro.core import conv as cconv, overflow
from repro.core.samd import scale_format

REPEATS = 5


def time_fn(fn, *args, repeats: int = REPEATS):
    """Best-of-N seconds after one untimed compile+warmup call.

    Returns (best, runs): min is the scheduler-noise floor — the value
    the perf gate diffs — and the full run list lands in the json row so
    spread stays diagnosable from the artifact alone."""
    jax.block_until_ready(fn(*args))  # compile + first-touch, untimed
    runs = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        runs.append(time.perf_counter() - t0)
    return float(min(runs)), runs


def native_int8_conv(x, k, padding="VALID"):
    """Direct 2D conv, int8 data, int32 accumulation (the Fig. 14 loop as
    XLA expresses it)."""
    return jax.lax.conv_general_dilated(
        x.astype(jnp.int8), k.astype(jnp.int8),
        window_strides=(1, 1), padding=padding,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=jnp.int32,
    )


def bench_layer_native(c_in, h, w, rng, repeats=REPEATS):
    """One output channel, VALID padding — the original seed row."""
    x = jnp.asarray(rng.integers(-128, 128, size=(1, c_in, h, w)), jnp.int8)
    k = jnp.asarray(rng.integers(-128, 128, size=(1, c_in, 3, 3)), jnp.int8)
    f = jax.jit(native_int8_conv)
    return time_fn(f, x, k, repeats=repeats)


def bench_layer_native_full(c_in, c_out, h, w, rng, dtype,
                            repeats=REPEATS):
    """Full layer (all output channels), padding 1 — the reference the
    blocked rows compete with. ``dtype`` int8 (paper's native baseline)
    or float32 (XLA's fast conv path)."""
    if dtype == jnp.int8:
        x = jnp.asarray(rng.integers(-128, 128, size=(1, c_in, h, w)),
                        jnp.int8)
        k = jnp.asarray(rng.integers(-128, 128, size=(c_out, c_in, 3, 3)),
                        jnp.int8)
        f = jax.jit(lambda x, k: native_int8_conv(x, k, padding=[(1, 1),
                                                                 (1, 1)]))
    else:
        x = jnp.asarray(rng.normal(size=(1, c_in, h, w)), dtype)
        k = jnp.asarray(rng.normal(size=(c_out, c_in, 3, 3)), dtype)
        f = jax.jit(lambda x, k: jax.lax.conv_general_dilated(
            x, k, window_strides=(1, 1), padding=[(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        ))
    return time_fn(f, x, k, repeats=repeats)


def bench_layer_blocked(c_in, c_out, h, w, bits, rng, repeats=REPEATS,
                        interpret=None):
    """Full layer through the blocked SAMD conv2d (ops.py dispatch:
    unrolled-jnp lowering on CPU, Mosaic kernel on TPU)."""
    from repro.kernels import ops as kops
    from repro.quant.config import QuantConfig
    from repro.quant.packing import pack_conv_weights

    cfg = QuantConfig(bits=bits)
    x = jnp.asarray(rng.normal(size=(c_in, h, w)), jnp.float32)
    wt = jnp.asarray(rng.normal(size=(3, 3, c_in, c_out)), jnp.float32)
    packed, scale = pack_conv_weights(wt, cfg)

    def f(x, p, s):
        return kops.samd_conv2d(x, p, s, cfg, padding=1,
                                interpret=interpret)

    return time_fn(jax.jit(f), x, packed, scale, repeats=repeats)


def bench_layer_samd(c_in, h, w, bits, regime, rng, repeats=REPEATS):
    """One output channel: 3 rows of multichannel conv-as-multiplication
    (b<=4) or vector-scale convolution (b>4), vmapped over output rows.
    The pre-PR scalar kernel — kept as the speedup baseline."""
    lo, hi = overflow.input_range(bits, True)
    kern = rng.integers(lo, hi + 1, size=(c_in * 3, 3))

    x = jnp.asarray(
        rng.integers(lo, hi + 1, size=(h - 2, c_in * 3, w)), jnp.int32
    )  # per output row: 3 input rows x c_in channels as "channels"
    kj = jnp.asarray(kern, jnp.int32)

    if bits <= 4:  # conv-as-multiplication with grouped accumulation
        def one_row(xr):
            return cconv.samd_conv_grouped(xr, kj, bits)
    else:
        def one_row(xr):
            def body(acc, ck):
                xc, kc = ck
                return acc + cconv.conv_by_scale(xc, kc, bits, True), None

            first = cconv.conv_by_scale(xr[0], kj[0], bits, True)
            out, _ = jax.lax.scan(body, first, (xr[1:], kj[1:]))
            return out

    f = jax.jit(jax.vmap(one_row))
    return time_fn(f, x, repeats=repeats)


# analytic TPU roofline (~v5e): the decode-regime crossover model.
TPU_BF16_FLOPS = 1.97e14   # MXU bf16
TPU_INT8_OPS = 3.94e14     # MXU int8 (2x bf16)
TPU_HBM_BYTES = 8.19e11    # HBM bandwidth


def tpu_decode_model(layers, bit_list=(2, 4, 8), m_decode=8):
    """Analytic TPU rows: the layer's weights as a decode-time matmul.

    At serving decode the batch is tiny (``m_decode`` rows) and each
    layer's weight matrix [K=9*C_in, N=C_out] must stream from HBM every
    step — the memory-bound regime the paper's packing targets. Native
    int8 moves 1 byte/value; SAMD-packed b-bit moves b/8 bytes/value and
    contracts in bf16 after the in-VMEM unpack (the unpack is VPU work
    overlapped with the DMA, not modeled). Both paths' times are
    max(compute, memory) rooflines; the speedup column is
    t_int8 / t_packed — > 1 means the packed path wins on TPU (the
    crossover the CPU measurement cannot show directly).
    """
    rows = []
    for (name, c_in, c_out, h, w) in layers:
        k, n = 9 * c_in, c_out
        flops = 2.0 * m_decode * k * n
        t_int8 = max(flops / TPU_INT8_OPS, (k * n) / TPU_HBM_BYTES)
        for bits in bit_list:
            t_packed = max(flops / TPU_BF16_FLOPS,
                           (k * n * bits / 8) / TPU_HBM_BYTES)
            bound = ("memory" if (k * n * bits / 8) / TPU_HBM_BYTES
                     >= flops / TPU_BF16_FLOPS else "compute")
            rows.append({
                "name": f"tpu-model/{name}/decode-b{bits}",
                "us": t_packed * 1e6,
                "speedup_vs_native_int8": t_int8 / t_packed,
                "bound": bound,
                "m_decode": m_decode,
            })
    return rows


def run(layers=None, bit_list=(8, 6, 4, 3, 2), regimes=("temporary",),
        quick=False, repeats=REPEATS, blocked_bits=(2, 4, 8),
        full_refs=True):
    """Returns json rows (dicts with name/us/speedup[s]/runs).

    ``quick`` caps spatial extent at 34 (CI-sized); the committed
    artifact is generated WITHOUT quick so conv3_1/conv5_1 carry their
    real shapes. ``full_refs=False`` skips the full-layer reference and
    blocked rows (the seed-compatible 1-channel sweep only).
    """
    rng = np.random.default_rng(0)
    layers = layers or VGGB_LAYERS
    rows = []
    for (name, c_in, c_out, h, w) in layers:
        if quick:
            h = min(h, 34)
            w = min(w, 34)
        t_native, nat_runs = bench_layer_native(c_in, h, w, rng,
                                                repeats=repeats)
        t_native *= 1e6
        rows.append({"name": f"vggb/{name}/native-int8", "us": t_native,
                     "speedup_vs_native": 1.0, "runs_s": nat_runs,
                     "repeats": repeats})
        scalar_us = {}
        for bits in bit_list:
            for regime in regimes:
                t, runs = bench_layer_samd(c_in, h, w, bits, regime, rng,
                                           repeats=repeats)
                t *= 1e6
                scalar_us[bits] = t
                rows.append({
                    "name": f"vggb/{name}/samd{bits}-{regime[:4]}",
                    "us": t, "speedup_vs_native": t_native / t,
                    "runs_s": runs, "repeats": repeats,
                })
        if not full_refs:
            continue
        t_i8, i8_runs = bench_layer_native_full(c_in, c_out, h, w, rng,
                                                jnp.int8, repeats=repeats)
        t_i8 *= 1e6
        rows.append({"name": f"vggb/{name}/native-int8-full", "us": t_i8,
                     "runs_s": i8_runs, "repeats": repeats,
                     "c_out": c_out})
        t_f32, f32_runs = bench_layer_native_full(c_in, c_out, h, w, rng,
                                                  jnp.float32,
                                                  repeats=repeats)
        t_f32 *= 1e6
        rows.append({"name": f"vggb/{name}/native-f32-full", "us": t_f32,
                     "runs_s": f32_runs, "repeats": repeats,
                     "c_out": c_out})
        for bits in blocked_bits:
            t, runs = bench_layer_blocked(c_in, c_out, h, w, bits, rng,
                                          repeats=repeats)
            t *= 1e6
            row = {
                "name": f"vggb/{name}/blocked{bits}",
                "us": t,
                "speedup_vs_native_int8_full": t_i8 / t,
                "speedup_vs_native_f32_full": t_f32 / t,
                "us_per_out_channel": t / c_out,
                "runs_s": runs, "repeats": repeats, "c_out": c_out,
            }
            if bits in scalar_us:
                # pre-PR scalar kernel measured one channel; the blocked
                # kernel does the whole layer — compare per channel
                row["speedup_vs_scalar_kernel"] = (
                    scalar_us[bits] * c_out / t
                )
            rows.append(row)
    return rows


def op_count_model(bit_list=(8, 6, 4, 3, 2), word_bits=64):
    """Cortex-A57 analogue (paper Figs. 17/18): modeled ops/value.

    Two variants per configuration:
      * 'extract' — our general implementation, which unpacks every output
        lane with shift/mask (what the JAX/TPU port does);
      * 'packed'  — the paper's C code generator, which keeps results in
        the packed domain and resolves the overlapping parallelogram
        regions with ONE shift + ONE SAMD-add per word (§5.1), unpacking
        only at the network boundary. This variant reproduces the paper's
        reported 6x/10x speedups at 2-bit.

    native baseline = 1 load + 1 mul + 1 add per (tap x value) = Fig. 14.
    """
    from repro.core.samd import conv_lane_width
    from repro.core.codegen import (
        FIXUP_PERM, FIXUP_TEMP, GRYS_ADJUST, OpCounts, SIGN_EXTEND,
        WIDE_MUL_NATIVE, WIDE_MUL_TPU32,
    )

    rows = []
    taps = 3
    native_per_val = taps * 3.0  # load + mul + add per tap
    wide = WIDE_MUL_NATIVE if word_bits == 64 else WIDE_MUL_TPU32
    for bits in bit_list:
        for regime in ("temporary", "permanent"):
            lane = (
                conv_lane_width(bits, taps, True)
                if bits * 2 + 2 <= word_bits // taps
                else None
            )
            fixup = FIXUP_PERM if regime == "permanent" else FIXUP_TEMP
            if lane is not None and taps * lane <= word_bits:
                vals = word_bits // lane
                out_lanes = vals + taps - 1
                base = (wide + GRYS_ADJUST + fixup + SIGN_EXTEND
                        + OpCounts(bitwise=1)).total + 1  # +load
                extract = base + 4 * out_lanes
                packed = base + 3       # one shift + add + mask per word
            else:  # vector-scale fallback (one mul per tap per word)
                fmt = scale_format(bits, True, word_bits)
                vals = fmt.lanes_per_word
                extract = taps * 3 + 4 * vals + 1
                packed = taps * 3 + 3 + 1
            for variant, ops in (("extract", extract), ("packed", packed)):
                per_val = ops / vals
                rows.append((
                    f"a57-model/samd{bits}-{regime[:4]}-{variant}",
                    per_val, native_per_val / per_val,
                ))
    return rows


def main() -> None:
    from benchmarks.jsonio import write_bench_json

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all 10 VGG-B layers at full spatial extent "
                         "(default: conv1_1/conv3_1/conv5_1)")
    ap.add_argument("--layers", default=None,
                    help="comma-separated layer names "
                         "(e.g. conv3_1,conv5_1) — overrides --full")
    ap.add_argument("--bits", default="2,4,8",
                    help="blocked-kernel bit widths (comma-separated)")
    ap.add_argument("--quick", action="store_true",
                    help="cap spatial extent at 34 (CI-sized layers)")
    ap.add_argument("--repeats", type=int, default=REPEATS,
                    help="best-of-N timed runs per row")
    ap.add_argument("--out-dir", default=".")
    args = ap.parse_args()

    if args.layers:
        wanted = set(args.layers.split(","))
        layers = [l for l in VGGB_LAYERS if l[0] in wanted]
        missing = wanted - {l[0] for l in layers}
        assert not missing, f"unknown layers: {sorted(missing)}"
    elif args.full:
        layers = VGGB_LAYERS
    else:
        layers = [VGGB_LAYERS[0], VGGB_LAYERS[4], VGGB_LAYERS[8]]
    bit_list = tuple(int(b) for b in args.bits.split(","))

    rows = run(layers=layers, bit_list=bit_list, quick=args.quick,
               repeats=args.repeats, blocked_bits=bit_list)
    rows += tpu_decode_model(layers, bit_list)

    print("name,us,speedup")
    for row in rows:
        speed = (row.get("speedup_vs_native_int8_full")
                 or row.get("speedup_vs_native_int8")
                 or row.get("speedup_vs_native") or 0.0)
        print(f"{row['name']},{row['us']:.1f},{speed:.2f}")
    path = write_bench_json("vggb", rows, out_dir=args.out_dir)
    print(f"# wrote {path}")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
