"""Pallas TPU kernels for SAMD convolution.

Two generations live here:

1. :func:`samd_conv_chunks` — the faithful port of the paper's novel op
   (conv-as-long-multiplication, §5-6): per-chunk 32x32->64 widening
   multiplies from 16-bit limbs, Grys signed adjustment, Fig. 12 borrow
   fixup, lane extraction. It demonstrates the paper's arithmetic on the
   VPU but is scalar-per-chunk — each output needs a synthesized wide
   multiply, and the MXU sits idle.

2. :func:`samd_conv2d` — the production blocked kernel (this PR). SAMD is
   kept where it pays on TPU: *storage*. Conv weights stay packed in HBM
   as b-bit lanes along C_in; each grid step copies a packed block to
   VMEM, unpacks in-register on the VPU, and contracts on the MXU. The
   im2col is fused into the BlockSpec index maps — the input, relaid as
   rows [H, W, C], is passed KH times with a leading row block of 1, so
   block index == exact input row (``oh + kh``), and the KW taps are
   static sublane-offset loads of that row; NO patch matrix is ever
   materialized. The C_in reduction is blocked with
   a float32 accumulator scratch carried across grid steps (online
   accumulation; ragged C_in zero-padded to whole blocks per the PR 2
   K-block fix), and the per-output-channel scale is applied once at the
   final store.

The chunk kernel emits per-chunk extracted lanes [nc, out_lanes]; the
final overlap-add of the parallelogram regions runs as XLA ops in ops.py.
:func:`samd_conv2d_xla` is the unrolled-jnp lowering of the blocked loop
for CPU (the PR 3 pattern — the Pallas interpreter stays test-only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv import ConvPlan
from repro.core import masks as masks_mod
from repro.kernels.samd_matmul import lane_major, unpack_codes
from repro.quant.config import QuantConfig


def _wide_mul_u32(a, b):
    mask16 = jnp.uint32(0xFFFF)
    a0, a1 = a & mask16, a >> 16
    b0, b1 = b & mask16, b >> 16
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> 16) + (p01 & mask16) + (p10 & mask16)
    lo = (p00 & mask16) | (mid << 16)
    hi = p11 + (p01 >> 16) + (p10 >> 16) + (mid >> 16)
    return hi, lo


def _conv_kernel(x_ref, k_ref, o_ref, *, plan: ConvPlan):
    fmt = plan.fmt
    L = fmt.lane_width
    xw = x_ref[...]            # [block, 1] uint32 chunk words
    kw = k_ref[0, 0]           # scalar kernel word
    hi, lo = _wide_mul_u32(xw, kw)
    if fmt.signed:
        # Grys high-half adjustment for signed operands
        sx = (xw >> 31).astype(bool)
        sk = (kw >> 31).astype(bool)
        hi = hi - jnp.where(sx, kw, jnp.uint32(0))
        hi = hi - jnp.where(sk, xw, jnp.uint32(0))
        # Fig. 12 borrow fixup across the 64-bit pair
        msb_full = masks_mod.build_mask(L - 1, 1, L, 64)
        m_lo = jnp.uint32(msb_full & 0xFFFFFFFF)
        m_hi = jnp.uint32(msb_full >> 32)
        s_lo = lo & m_lo
        s_hi = hi & m_hi
        q_lo = lo + s_lo
        carry = (q_lo < lo).astype(jnp.uint32)
        q_hi = hi + s_hi + carry
        hi, lo = q_hi ^ s_hi, q_lo ^ s_lo
    # extract all output lanes with one broadcasted shift over a lane-offset
    # vector (single shift/mask chain; trace size independent of lane count)
    lane_mask = jnp.uint32((1 << L) - 1)
    nt = plan.out_lanes_per_chunk
    offs = jax.lax.broadcasted_iota(jnp.int32, (1, nt), 1) * L   # [1, nt]
    # three sources per lane: fully in lo, fully in hi, or straddling the
    # 32-bit boundary; shift amounts are clamped so every branch is defined
    sh_lo = jnp.minimum(offs, 31).astype(jnp.uint32)
    sh_hi = jnp.clip(offs - 32, 0, 31).astype(jnp.uint32)
    sh_left = jnp.clip(32 - offs, 1, 31).astype(jnp.uint32)
    lo_part = lo >> sh_lo                                        # [blk, nt]
    hi_part = hi >> sh_hi
    straddle = lo_part | (hi << sh_left)
    v = jnp.where(
        offs + L <= 32, lo_part, jnp.where(offs >= 32, hi_part, straddle)
    )
    v = (v & lane_mask).astype(jnp.int32)
    if fmt.signed:
        sign = (v >> (L - 1)) & 1
        v = v - (sign << L)
    o_ref[...] = v


@functools.partial(jax.jit, static_argnames=("plan", "block", "interpret"))
def samd_conv_chunks(
    x_words: jax.Array,
    k_word: jax.Array,
    plan: ConvPlan,
    *,
    block: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """[nc] packed chunk words x kernel word -> [nc, out_lanes] int32."""
    nc = x_words.shape[0]
    blk = min(block, nc)
    grid = (pl.cdiv(nc, blk),)
    return pl.pallas_call(
        functools.partial(_conv_kernel, plan=plan),
        grid=grid,
        in_specs=[
            pl.BlockSpec((blk, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (blk, plan.out_lanes_per_chunk), lambda i: (i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(
            (nc, plan.out_lanes_per_chunk), jnp.int32
        ),
        interpret=interpret,
    )(x_words[:, None], k_word.reshape(1, 1))


# ---------------------------------------------------------------------------
# blocked 2D conv over SAMD-packed weights (fused im2col, MXU contraction)
# ---------------------------------------------------------------------------

def _conv2d_kernel(*refs, kh_taps, kw_taps, ow, bits, lane_width, vpw,
                   signed, n_ci_steps):
    # refs: x_ref x KH, w_ref, s_ref, o_ref, acc_ref
    x_refs = refs[:kh_taps]
    w_ref, s_ref, o_ref, acc_ref = refs[kh_taps:]
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc = acc_ref[...]
    for kh in range(kh_taps):
        for kw in range(kw_taps):
            codes = unpack_codes(
                w_ref[kh, kw], bits, lane_width, vpw, signed
            )                                            # [bc, bn]
            patch = x_refs[kh][0, pl.ds(kw, ow), :]      # [OW, bc]
            acc = acc + jnp.dot(
                patch, codes.astype(patch.dtype),
                preferred_element_type=jnp.float32,
            )
    acc_ref[...] = acc

    @pl.when(ci == n_ci_steps - 1)
    def _store():
        o_ref[...] = (
            acc_ref[...] * s_ref[...].astype(jnp.float32)
        )[None].astype(o_ref.dtype)


def _pad_conv_operands(x, packed, padding, vpw, bcw):
    """SAME-style spatial padding + zero-padding of the channel reduction
    to whole word-blocks (ragged C_in blocks would read undefined words)."""
    c_in, h, w = x.shape
    cw = packed.shape[2]
    cw_pad = pl.cdiv(cw, bcw) * bcw - cw
    if cw_pad:
        packed = jnp.pad(packed, ((0, 0), (0, 0), (0, cw_pad), (0, 0)))
    cwp = cw + cw_pad
    x = jnp.pad(
        x,
        ((0, cwp * vpw - c_in), (padding, padding), (padding, padding)),
    )
    return x, packed, cwp


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "padding", "block_cw", "block_n", "signed",
                     "interpret"),
)
def samd_conv2d(
    x: jax.Array,
    packed: jax.Array,
    scale: jax.Array,
    cfg: QuantConfig,
    *,
    padding: int = 1,
    block_cw: int = 64,
    block_n: int = 256,
    signed: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """out[OH, OW, C_out] = conv2d(x[C_in, H, W], dequant(packed), stride 1).

    ``packed``/``scale`` come from :func:`repro.quant.packing.pack_conv_weights`
    — uint32 [KH, KW, ceil(C_in/vpw), C_out] with lanes along C_in and one
    float32 scale per output channel.

    Grid: (OH, N-blocks, C_in-blocks) with the channel reduction innermost
    so the f32 accumulator scratch survives across reduction steps. The
    fused im2col: x is relaid once as rows [H, W, C] (channels on lanes,
    in :func:`unpack_codes`' lane-major order) and passed KH times, each
    alias blocked to a single input row picked by the index map
    ``(oh + kh, 0, ci)`` (a leading-axis block of 1 makes the block index
    an exact row index — the trick that lets BlockSpecs express
    overlapping windows), and the KW taps are static sublane-offset
    loads of that row. One weight-block unpack feeds KH*KW MXU
    contractions.
    """
    c_in, h, w = x.shape
    kh_taps, kw_taps, cw, n = packed.shape
    vpw = cfg.values_per_word
    assert cw * vpw >= c_in, (cw, vpw, c_in)
    oh = h + 2 * padding - kh_taps + 1
    ow = w + 2 * padding - kw_taps + 1
    bn = min(block_n, n)
    bcw = min(block_cw, cw)
    x, packed, cwp = _pad_conv_operands(x, packed, padding, vpw, bcw)
    x = jnp.transpose(lane_major(x, vpw, bcw, axis=0), (1, 2, 0))
    wp = x.shape[1]
    bc = bcw * vpw
    grid = (oh, pl.cdiv(n, bn), cwp // bcw)

    x_specs = [
        pl.BlockSpec((1, wp, bc), functools.partial(
            lambda i, j, ci, kh: (i + kh, 0, ci), kh=kh))
        for kh in range(kh_taps)
    ]
    out = pl.pallas_call(
        functools.partial(
            _conv2d_kernel, kh_taps=kh_taps, kw_taps=kw_taps, ow=ow,
            bits=cfg.bits, lane_width=cfg.lane_width, vpw=vpw,
            signed=signed, n_ci_steps=grid[2],
        ),
        grid=grid,
        in_specs=x_specs + [
            pl.BlockSpec((kh_taps, kw_taps, bcw, bn),
                         lambda i, j, ci: (0, 0, ci, j)),
            pl.BlockSpec((1, bn), lambda i, j, ci: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, ow, bn), lambda i, j, ci: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((oh, ow, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((ow, bn), jnp.float32)],
        interpret=interpret,
        # the trace names the kernel's op by this name
        name="samd_conv2d",
    )(*([x] * kh_taps), packed, scale)
    return out


@functools.partial(
    jax.jit, static_argnames=("cfg", "padding", "block_cw", "signed"),
)
def samd_conv2d_xla(
    x: jax.Array,
    packed: jax.Array,
    scale: jax.Array,
    cfg: QuantConfig,
    *,
    padding: int = 1,
    block_cw: int = 128,
    signed: bool = True,
) -> jax.Array:
    """Unrolled-jnp lowering of the blocked conv loop (the CPU backend).

    Identical math to :func:`samd_conv2d`: per (C_in-block, kh, kw) step,
    unpack the packed weight block to integer codes and contract the
    shifted input window against them in float32 — an implicit im2col as
    KH*KW strided views, never a materialized patch matrix. XLA fuses the
    unpack into the matmul prologue and runs the contraction on the native
    matmul path, which is what makes the packed bench rows beat
    ``lax.conv`` int8 on CPU hosts.
    """
    c_in, h, w = x.shape
    kh_taps, kw_taps, cw, n = packed.shape
    vpw = cfg.values_per_word
    assert cw * vpw >= c_in, (cw, vpw, c_in)
    oh = h + 2 * padding - kh_taps + 1
    ow = w + 2 * padding - kw_taps + 1
    bcw = min(block_cw, cw)
    x, packed, cwp = _pad_conv_operands(x, packed, padding, vpw, bcw)
    x = lane_major(x, vpw, bcw, axis=0)
    bc = bcw * vpw
    acc = jnp.zeros((oh * ow, n), jnp.float32)
    for cb in range(cwp // bcw):
        xb = x[cb * bc:(cb + 1) * bc]
        for kh in range(kh_taps):
            for kw in range(kw_taps):
                codes = unpack_codes(
                    packed[kh, kw, cb * bcw:(cb + 1) * bcw],
                    cfg.bits, cfg.lane_width, vpw, signed,
                )                                        # [bc, n]
                patch = jax.lax.dynamic_slice(
                    xb, (0, kh, kw), (bc, oh, ow)
                ).reshape(bc, oh * ow)
                acc = acc + jax.lax.dot_general(
                    patch, codes.astype(x.dtype),
                    (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
    out = acc * scale.astype(jnp.float32)
    return out.reshape(oh, ow, n).astype(x.dtype)
