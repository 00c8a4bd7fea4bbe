"""Quickstart: the paper's core technique in five minutes.

1. Bit-precise SAMD lane arithmetic embedded in uint32 words.
2. The novel op: 1D convolution computed by ONE widening multiply.
3. Constant-kernel overflow analysis choosing minimal lane widths.
4. A quantized matmul with SAMD-packed weights (the TPU serving path).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp
import numpy as np

from repro.core import (
    conv_output_bits, dense_format, make_plan, pack, plan_for_kernel,
    samd_add, samd_conv_full, samd_mul, unpack,
)
from repro.quant import QuantConfig, pack_weights, qmatmul


def main():
    rng = np.random.default_rng(0)

    # -- 1. lane-wise arithmetic on 3-bit signed integers ------------------
    fmt = dense_format(bits=3, signed=True)
    a = jnp.asarray(rng.integers(-4, 4, size=10))
    b = jnp.asarray(rng.integers(-4, 4, size=10))
    aw, bw = pack(a, fmt), pack(b, fmt)
    print("10 x 3-bit lanes fit in", aw.size, "uint32 word(s)")
    s = unpack(samd_add(aw, bw, fmt), fmt, 10)
    m = unpack(samd_mul(aw, bw, fmt), fmt, 10)
    print("  a      =", np.asarray(a))
    print("  b      =", np.asarray(b))
    print("  a+b    =", np.asarray(s), "(mod 2^3, signed)")
    print("  a*b    =", np.asarray(m), "(mod 2^3, signed)")

    # -- 2. convolution as long multiplication ----------------------------
    plan = make_plan(bits=2, taps=3, signed=True)
    x = jnp.asarray(rng.integers(-2, 2, size=12))
    k = jnp.asarray(rng.integers(-2, 2, size=3))
    out = samd_conv_full(x, k, plan)
    print("\nconv-as-multiplication (2-bit, 3 taps, "
          f"lane={plan.fmt.lane_width}b, {plan.fmt.lanes_per_word} "
          "values/multiply):")
    print("  samd :", np.asarray(out))
    print("  numpy:", np.convolve(np.asarray(x), np.asarray(k)))

    # -- 3. deploy-time overflow analysis (paper §7) ----------------------
    kernel = np.array([[4, 3, 9, 6]])
    bits = conv_output_bits(kernel, input_bits=4, input_signed=False)
    print(f"\nknown kernel {kernel.tolist()} on 4-bit unsigned input "
          f"needs only {bits} output bits (paper's b+5 example)")
    plan = plan_for_kernel(np.array([[1, -2, 1]]), 3, True, 3)
    print(f"kernel [1,-2,1] at 3-bit: lane width {plan.fmt.lane_width} "
          f"-> {plan.fmt.lanes_per_word} outputs per multiply")

    # -- 4. SAMD-packed quantized matmul (the serving path) ---------------
    w = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
    xx = jnp.asarray(rng.normal(size=(4, 512)), jnp.float32)
    exact = xx @ w
    for bit in (8, 4, 2):
        cfg = QuantConfig(bits=bit)
        packed, scale = pack_weights(w, cfg)
        y = qmatmul(xx, packed, scale, 512, cfg)
        err = float(jnp.mean(jnp.abs(y - exact)) / jnp.mean(jnp.abs(exact)))
        ratio = w.size * 2 / (packed.size * 4)
        print(f"  {bit}-bit packed weights: {ratio:.1f}x smaller than "
              f"bf16, rel-err {err:.3f}")


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
