"""Pallas TPU kernel: packed-weight matmul (SAMD storage -> MXU compute).

The production form of the paper's technique on TPU: weights are stored in
HBM as SAMD-packed uint32 words (b-bit lanes along the reduction axis).
Each grid step copies a *packed* block HBM->VMEM (32/lane_width x fewer
bytes than bf16), unpacks on the VPU inside VMEM, and feeds the MXU. The
HBM side therefore sees only packed bytes — the memory-roofline term drops
by the packing factor, which is exactly the paper's claim ("quantization
reduces memory traffic") mapped onto the TPU hierarchy.

Blocking discipline (ported back from the paged-attention kernels of the
serving push):

  * the reduction axis is BLOCKED (``block_kw`` packed words per grid
    step) with a float32 accumulator scratch that lives across grid
    steps — online accumulation, one output store per (m, n) tile;
  * ragged K extents are zero-padded to whole K-blocks before launch
    (zero words dequantize to exact zeros), because a ragged last
    K-block would read UNDEFINED out-of-bounds words that contaminate
    real outputs through the accumulator;
  * the per-output-channel scale is applied ONCE at the final store —
    grid steps accumulate raw integer-code products, so the unpack path
    is a pure shift/mask chain with no float multiply per lane;
  * codes unpack LANE-MAJOR (all words' lane 0, then lane 1, ...) and the
    activation's reduction axis is permuted to match before launch
    (:func:`lane_major`): Mosaic cannot interleave the lanes back into
    word order in-register;
  * signed lanes sign-extend with a two-op mask/subtract; ``signed=False``
    lanes (codes that fit the lane headroom with no sign bit) skip the
    correction entirely — the fast path.

Block shapes are MXU-aligned by default: the unpacked K-block
(block_kw * values_per_word) and N-block are multiples of 128 for the
shapes used by the framework; ``block_m`` adapts to small decode batches.
Defaults were selected by the ``benchmarks/hillclimb.py`` ladder over the
VGG-B layer shapes at bits in {2, 4, 8} (re-run it on real TPU hardware
to retune — CPU CI times the jnp lowering below).

Two lowerings share the block-loop algorithm:

  * :func:`samd_matmul` — the Pallas kernel (Mosaic on TPU; the
    interpreter is test-only, CI equivalence suites pass
    ``interpret=True``);
  * :func:`samd_matmul_xla` — the same K-block loop unrolled as plain
    jnp ops, the CPU serving/benchmark backend (the PR 3 dispatch
    pattern: the interpreter walks the grid sequentially and loses to
    XLA's native matmul, while the unrolled loop vectorizes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.quant.config import QuantConfig


def unpack_codes(words, bits: int, lane_width: int, vpw: int,
                 signed: bool = True):
    """uint32 [bk, bn] -> int32 codes [vpw * bk, bn] (VPU shift/mask ops).

    Codes come out LANE-MAJOR: row ``i * bk + w`` holds lane ``i`` of
    word ``w``, i.e. reduction index ``w * vpw + i`` of the block. Each
    lane is one shift/mask over the whole [bk, bn] word tile and the
    lanes stack along the sublane axis, so Mosaic never has to interleave
    rows (a [vpw, bk, bn] -> [bk * vpw, bn] transpose-and-merge, which it
    refuses). The matching activation columns come from
    :func:`lane_major`. Signed lanes append a two-op sign correction
    (extract the sign bit, subtract ``sign << bits``); unsigned lanes
    skip it — their codes already fit the lane headroom. The correction
    is applied HERE, inside the kernels, so no caller ever has to
    remember the wide-lane fixup by hand.
    """
    vmask = jnp.uint32((1 << bits) - 1)
    v = jnp.concatenate(
        [(words >> jnp.uint32(i * lane_width)) & vmask for i in range(vpw)],
        axis=0,
    ).astype(jnp.int32)
    if signed:
        sign = (v >> (bits - 1)) & 1
        v = v - (sign << bits)
    return v


def lane_major(x, vpw: int, bkw: int, axis: int):
    """Permute ``x``'s reduction ``axis`` (whole blocks of ``bkw`` packed
    words, ``bkw * vpw`` values each) into the order
    :func:`unpack_codes` emits codes in: within every block, position
    ``i * bkw + w`` takes reduction index ``w * vpw + i``. A pure
    relabelling of the reduction, so the contraction is unchanged."""
    n = x.shape[axis] // (bkw * vpw)
    lead, tail = x.shape[:axis], x.shape[axis + 1:]
    x = x.reshape(lead + (n, bkw, vpw) + tail)
    x = jnp.swapaxes(x, axis + 1, axis + 2)
    return x.reshape(lead + (n * vpw * bkw,) + tail)


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, bits, lane_width, vpw,
            signed, n_k_steps):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = unpack_codes(w_ref[...], bits, lane_width, vpw, signed)
    # accumulate RAW code products; the per-channel scale lands once at
    # the final store (cheaper than a float multiply per unpacked lane)
    acc_ref[...] += jnp.dot(
        x_ref[...], codes.astype(x_ref.dtype),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k_steps - 1)
    def _store():
        o_ref[...] = (
            acc_ref[...] * s_ref[...].astype(jnp.float32)
        ).astype(o_ref.dtype)


def _pad_packed_operands(x, packed, k, vpw, bkw):
    """Zero-pad the reduction axis to whole K-blocks (and x to match the
    padded word extent) — the PR 2 ragged-K fix. Zero words unpack to
    code 0 and contribute nothing to the accumulator."""
    kw = packed.shape[0]
    kw_pad = pl.cdiv(kw, bkw) * bkw - kw
    if kw_pad:
        packed = jnp.pad(packed, ((0, kw_pad), (0, 0)))
    if (kw + kw_pad) * vpw != k:
        x = jnp.pad(x, ((0, 0), (0, (kw + kw_pad) * vpw - k)))
    return x, packed, kw + kw_pad


@functools.partial(
    jax.jit,
    static_argnames=("k", "cfg", "block_m", "block_n", "block_kw", "signed",
                     "interpret"),
)
def samd_matmul(
    x: jax.Array,
    packed: jax.Array,
    scale: jax.Array,
    k: int,
    cfg: QuantConfig,
    *,
    block_m: int = 128,
    block_n: int = 256,
    block_kw: int = 128,
    signed: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """out[M, N] = x[M, K] @ dequant(packed[K/vpw, N], scale[1, N]).

    ``block_n`` covers multiple 128-wide MXU tiles per grid step (one
    unpack feeds several MXU passes) and ``block_kw`` keeps the unpacked
    K-block at 1024+ values — both defaults from the hillclimb ladder.
    Ragged K is handled by zero-padding the packed words to whole blocks.
    """
    if cfg.group_size is not None:
        raise NotImplementedError("pallas path supports per-channel scales")
    m, kx = x.shape
    assert kx == k, (kx, k)
    kw, n = packed.shape
    vpw = cfg.values_per_word
    assert kw * vpw >= k, (kw, vpw, k)
    bm = min(block_m, m)
    bn = min(block_n, n)
    bkw = min(block_kw, kw)
    x, packed, kw = _pad_packed_operands(x, packed, k, vpw, bkw)
    x = lane_major(x, vpw, bkw, axis=1)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(kw, bkw))

    out = pl.pallas_call(
        functools.partial(
            _kernel, bits=cfg.bits, lane_width=cfg.lane_width, vpw=vpw,
            signed=signed, n_k_steps=grid[2],
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bkw * vpw), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkw, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        # the trace names the kernel's op by this name
        name="samd_matmul",
    )(x, packed, scale)
    return out


@functools.partial(
    jax.jit, static_argnames=("k", "cfg", "block_kw", "signed"),
)
def samd_matmul_xla(
    x: jax.Array,
    packed: jax.Array,
    scale: jax.Array,
    k: int,
    cfg: QuantConfig,
    *,
    block_kw: int = 128,
    signed: bool = True,
) -> jax.Array:
    """Unrolled-jnp lowering of the SAME K-block loop (the CPU backend).

    Per K-block: unpack ``block_kw`` packed words to integer codes,
    accumulate the raw-code product in float32, and apply the per-channel
    scale once at the end — identical math to the Pallas kernel, traced
    as plain XLA ops so the CPU serving draft path and the VGG-B bench
    run it at native matmul speed (the Pallas interpreter stays
    test-only).
    """
    if cfg.group_size is not None:
        raise NotImplementedError("per-channel scales only (as the kernel)")
    m, kx = x.shape
    assert kx == k, (kx, k)
    kw, n = packed.shape
    vpw = cfg.values_per_word
    assert kw * vpw >= k, (kw, vpw, k)
    bkw = min(block_kw, kw)
    x, packed, kw = _pad_packed_operands(x, packed, k, vpw, bkw)
    x = lane_major(x, vpw, bkw, axis=1)
    acc = jnp.zeros((m, n), jnp.float32)
    for kb in range(kw // bkw):
        words = packed[kb * bkw:(kb + 1) * bkw]
        codes = unpack_codes(words, cfg.bits, cfg.lane_width, vpw, signed)
        xb = x[:, kb * bkw * vpw:(kb + 1) * bkw * vpw]
        acc = acc + jnp.dot(xb, codes.astype(x.dtype),
                            preferred_element_type=jnp.float32)
    return (acc * scale.astype(jnp.float32)).astype(x.dtype)
