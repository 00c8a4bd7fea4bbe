"""Kernel/layout lane-safety contracts built on the abstract interpreter.

Three consumers run these at static points:

* ``kernels/ops.py`` — ``verify=True`` dispatch: the checks run at trace
  time (pure Python over static shapes/configs; zero runtime ops) and
  raise :class:`~repro.analysis.lanes.LaneSafetyError` on unsafe configs;
* ``serving/engine.py`` — admission: every packed weight's (bits, K)
  tuple is validated against the model's actual reduction depths;
* ``benchmarks/hillclimb.py`` / ``tools/samd_lint.py`` — ladder cells and
  CI certify against the same functions, so the autotuner can never
  recommend a config the checker would refuse.

Two kinds of checks live here:

1. **Unpacked-accumulation paths** (the blocked ``samd_matmul`` /
   ``samd_conv2d`` kernels): lanes are storage only — codes are unpacked
   to int32 before the MXU contraction — so the lane program is
   ``Pack -> ReadValue``. The reduction depth K still matters when
   activations are themselves quantized (``cfg.act_bits``): raw-code
   products accumulate in float32, whose 24-bit mantissa bounds the
   depth at which integer accumulation stays exact.
2. **Packed-domain paths** (``ConvPlan`` conv-as-multiplication,
   vector-scale): the full pipeline runs inside lanes, so the canonical
   accumulation program applies — including borrow-fixup tracking.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np

from repro.analysis.lanes import (
    NEEDS_SPACER,
    LaneSafetyError,
    Pack,
    ReadValue,
    Verdict,
    check_accumulation,
    interpret,
)
from repro.core import overflow
from repro.core.conv import ConvPlan
from repro.core.samd import SAMDFormat
from repro.quant.config import QuantConfig

# float32 keeps integers exact up to 2^24 (mantissa incl. implicit bit)
F32_MANTISSA_BITS = 24

# per-backend VMEM budget for one grid step's blocks + scratch. Mosaic's
# scoped VMEM limit on a v5e is 16 MiB (the figure the TPU compiler
# reports when a kernel exceeds it); leave headroom for its own double
# buffering.
VMEM_LIMIT_BYTES = {
    "tpu": 12 * 2**20,
    "default": 12 * 2**20,
}


def assert_safe(verdict: Verdict) -> Verdict:
    """Raise :class:`LaneSafetyError` on any non-safe verdict."""
    if not verdict.ok:
        raise LaneSafetyError(verdict)
    return verdict


def _storage_format(cfg: QuantConfig, signed: bool) -> SAMDFormat:
    return SAMDFormat(cfg.bits, cfg.lane_width, signed=signed, word_bits=32)


def _f32_exact_depth(cfg: QuantConfig, signed: bool) -> Optional[int]:
    """Max reduction depth at which raw-code x quantized-activation
    products stay integer-exact in a float32 accumulator; None when
    activations are float (no integer-exactness contract applies)."""
    if not cfg.act_bits:
        return None
    code_hi = 1 << (cfg.bits - 1) if signed else (1 << cfg.bits) - 1
    act_hi = 1 << (cfg.act_bits - 1)
    # every integer of magnitude <= 2^24 is exactly representable; the
    # worst single product is |(-2^(b-1)) * (-2^(a-1))| = code_hi * act_hi
    return max(1, (1 << F32_MANTISSA_BITS) // max(1, code_hi * act_hi))


@functools.lru_cache(maxsize=None)
def _check_unpacked_acc(cfg: QuantConfig, k: int, signed: bool) -> Verdict:
    fmt = _storage_format(cfg, signed)
    storage = interpret(fmt, [Pack(), ReadValue()], depth=k)
    if not storage.ok:
        return storage
    exact_depth = _f32_exact_depth(cfg, signed)
    if exact_depth is None:
        return dataclasses.replace(
            storage,
            detail=(
                "storage-only lanes (codes unpack to int32 before the "
                f"f32 contraction); depth K={k} accumulates out of the "
                "packed domain in float"
            ),
        )
    code_lo, code_hi = overflow.input_range(cfg.bits, signed)
    act_lo, act_hi = overflow.input_range(cfg.act_bits, True)
    cross = (
        code_lo * act_lo,
        code_lo * act_hi,
        code_hi * act_lo,
        code_hi * act_hi,
    )
    acc_lo, acc_hi = k * min(cross), k * max(cross)
    # exactness criterion is MAGNITUDE <= 2^24 (every such integer is
    # representable, and partial sums are bounded by the endpoints), not
    # bit width: 2^24 itself needs 26 signed bits yet is exact.
    if max(-acc_lo, acc_hi) > (1 << F32_MANTISSA_BITS):
        need = overflow.bits_required_signed(acc_lo, acc_hi)
        return dataclasses.replace(
            storage,
            status=NEEDS_SPACER,
            required_lane_width=need,
            spacer_bits_needed=max(1, need - F32_MANTISSA_BITS - 1),
            lane_lo=acc_lo,
            lane_hi=acc_hi,
            detail=(
                f"f32 accumulator: K={k} products of {cfg.bits}-bit codes "
                f"x {cfg.act_bits}-bit activations span [{acc_lo}, "
                f"{acc_hi}] but float32 is integer-exact only to "
                f"2^{F32_MANTISSA_BITS} — lower bits/act_bits or split "
                f"the reduction (exact to depth {exact_depth})"
            ),
        )
    return dataclasses.replace(
        storage,
        detail=(
            f"f32 accumulator integer-exact at K={k} "
            f"(exact to depth {exact_depth})"
        ),
    )


def check_matmul_config(
    cfg: QuantConfig, k: int, *, signed: bool = True
) -> Verdict:
    """Lane-safety verdict for ``samd_matmul`` at reduction depth ``k``
    under quantization policy ``cfg`` (storage lanes + f32-accumulator
    exactness when ``cfg.act_bits`` is set)."""
    return _check_unpacked_acc(cfg, int(k), bool(signed))


def check_conv2d_config(
    cfg: QuantConfig,
    kh: int,
    kw: int,
    c_in: int,
    *,
    signed: bool = True,
) -> Verdict:
    """Lane-safety verdict for the blocked ``samd_conv2d``: reduction
    depth is the whole filter fan-in KH*KW*C_in (one accumulator per
    output point, per-output-channel scale applied once)."""
    return _check_unpacked_acc(cfg, int(kh) * int(kw) * int(c_in), signed)


def check_conv_plan(
    plan: ConvPlan,
    channels: int = 1,
    *,
    kernel: Optional[np.ndarray] = None,
    input_bits: Optional[int] = None,
) -> Verdict:
    """Lane-safety verdict for the packed-domain conv-as-multiplication
    pipeline (``samd_conv_chunks`` / ``samd_conv_multichannel``):
    ``plan.taps`` products per lane, accumulated across ``channels``
    words before extraction. ``kernel`` (known constants, flattened
    [channels * taps]) applies the §7 tap-sum bound instead of the
    generic worst case."""
    plan.validate()
    if kernel is not None:
        return check_accumulation(
            plan.fmt,
            1,
            kernel=np.asarray(kernel).reshape(-1),
            input_bits=input_bits,
        )
    return check_accumulation(
        plan.fmt,
        int(channels),
        taps=plan.taps,
        input_bits=input_bits,
    )


# ---------------------------------------------------------------------------
# VMEM block-budget estimates (per grid step, bytes)
# ---------------------------------------------------------------------------


def matmul_vmem_bytes(
    cfg: QuantConfig,
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_kw: int = 128,
    x_bytes: int = 4,
) -> int:
    """Estimated VMEM bytes one ``samd_matmul`` grid step holds: x block,
    packed weight block, unpacked int32 codes, scale, output block and
    the f32 accumulator scratch."""
    vpw = cfg.values_per_word
    x_block = block_m * block_kw * vpw * x_bytes
    w_block = block_kw * block_n * 4
    codes = block_kw * vpw * block_n * 4
    scale = block_n * 4
    out = block_m * block_n * x_bytes
    acc = block_m * block_n * 4
    return x_block + w_block + codes + scale + out + acc


def conv2d_vmem_bytes(
    cfg: QuantConfig,
    *,
    w_img: int,
    kh: int = 3,
    kw: int = 3,
    block_cw: int = 64,
    block_n: int = 256,
    padding: int = 1,
    x_bytes: int = 4,
) -> int:
    """Estimated VMEM bytes one ``samd_conv2d`` grid step holds: KH input
    rows of the channel block, the packed weight block, one unpacked code
    block, scale, output row and the f32 accumulator scratch."""
    vpw = cfg.values_per_word
    bc = block_cw * vpw
    wp = w_img + 2 * padding
    ow = w_img + 2 * padding - kw + 1
    x_rows = kh * bc * wp * x_bytes
    w_block = kh * kw * block_cw * block_n * 4
    codes = bc * block_n * 4
    scale = block_n * 4
    out = ow * block_n * x_bytes
    acc = ow * block_n * 4
    return x_rows + w_block + codes + scale + out + acc


def vmem_limit(backend: str = "tpu") -> int:
    return VMEM_LIMIT_BYTES.get(backend, VMEM_LIMIT_BYTES["default"])


# ---------------------------------------------------------------------------
# model reduction depths (what the serving engine validates at admission)
# ---------------------------------------------------------------------------


def model_reduction_depths(
    template,
    qcfg: Optional[QuantConfig] = None,
    *,
    respect_min_size: bool = False,
) -> list[int]:
    """Reduction depths (K) of every quantizable weight in a TensorSpec
    template — the depths a packed matmul will accumulate over.

    ``respect_min_size=True`` mirrors ``quantize_params``' size floor
    (only leaves that would actually be packed); the default returns
    every quantizable depth, which is the conservative superset the
    certification sweep wants."""
    from repro.models.quantize import _MIN_QUANT_SIZE
    from repro.models.spec import TensorSpec

    import jax

    depths = set()
    for spec in jax.tree.leaves(
        template, is_leaf=lambda x: isinstance(x, TensorSpec)
    ):
        if not isinstance(spec, TensorSpec) or spec.quant_axis is None:
            continue
        if respect_min_size and (
            int(np.prod(spec.shape)) < _MIN_QUANT_SIZE
        ):
            continue
        if (
            qcfg is not None
            and "vocab" in (spec.axes or ())
            and not qcfg.quantize_embeddings
        ):
            continue
        depths.add(int(spec.shape[spec.quant_axis]))
    return sorted(depths)


def packed_reduction_depths(params) -> list[int]:
    """Reduction depths of the QuantizedTensor leaves actually present in
    a packed parameter tree (exact truth for an engine's weights)."""
    from repro.models.layers import QuantizedTensor

    import jax

    return sorted(
        {
            int(leaf.k)
            for leaf in jax.tree.leaves(
                params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
            )
            if isinstance(leaf, QuantizedTensor)
        }
    )
