"""SAMD packing of quantized weights + the quantized matmul entry point.

Layout: a weight W[K, N] quantized to b bits is stored as uint32 words of
``values_per_word`` lanes packed along the *reduction* axis K:

    packed[K // vpw, N]  uint32,   scale[1 or K//group, N]  float32

so a (bk, bn) kernel block unpacks to (bk * vpw, bn) weight values with
contiguous lane extraction — the layout the Pallas kernel wants, and the
layout that minimizes HBM traffic at decode time (the paper's central
claim, re-targeted at the TPU memory hierarchy).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import samd
from repro.quant.config import QuantConfig
from repro.quant.quantizer import quantize_symmetric


def _fmt(cfg: QuantConfig) -> samd.SAMDFormat:
    return samd.SAMDFormat(cfg.bits, cfg.lane_width, signed=True, word_bits=32)


def packed_shape(shape: tuple[int, ...], cfg: QuantConfig) -> tuple[int, ...]:
    k = shape[0]
    vpw = cfg.values_per_word
    return (-(-k // vpw),) + tuple(shape[1:])


def pack_weights(w: jax.Array, cfg: QuantConfig):
    """Quantize + SAMD-pack a [K, ...] weight along axis 0.

    Returns (packed uint32 [ceil(K/vpw), ...], scale f32).
    """
    q, scale = quantize_symmetric(
        w, cfg.bits, axis=0, group_size=cfg.group_size
    )
    fmt = _fmt(cfg)
    # move K last, pack it, move back
    qt = jnp.moveaxis(q, 0, -1)
    words = samd.pack(qt, fmt)
    packed = jnp.moveaxis(words, -1, 0)
    return packed, scale


def unpack_weights(packed: jax.Array, k: int, cfg: QuantConfig) -> jax.Array:
    """Unpack to int32 [K, ...] (XLA shifts/masks — VPU-friendly on TPU)."""
    fmt = _fmt(cfg)
    pt = jnp.moveaxis(packed, 0, -1)
    vals = samd.unpack(pt, fmt, k)
    return jnp.moveaxis(vals, -1, 0)


def dequant_weights(packed: jax.Array, scale: jax.Array, k: int,
                    cfg: QuantConfig, dtype=jnp.bfloat16) -> jax.Array:
    q = unpack_weights(packed, k, cfg)
    if cfg.group_size is not None:
        g = cfg.group_size
        qg = q.reshape((k // g, g) + q.shape[1:])
        w = qg.astype(jnp.float32) * scale[:, None]
        return w.reshape(q.shape).astype(dtype)
    return (q.astype(jnp.float32) * scale).astype(dtype)


def pack_conv_weights(w: jax.Array, cfg: QuantConfig):
    """Quantize + SAMD-pack a conv weight W[KH, KW, C_in, C_out].

    The reduction axis of a conv is (KH, KW, C_in); scales are per OUTPUT
    channel, so the whole (KH * KW * C_in) fan-in of a filter shares one
    scale and the blocked kernel can accumulate raw codes across every
    (kh, kw, ci) grid step and dequantize once at the store. Lanes pack
    along C_in — the innermost reduction axis, so a (bcw, bn) weight block
    unpacks to contiguous (bcw * vpw, bn) values exactly like the matmul
    layout.

    Returns (packed uint32 [KH, KW, ceil(C_in/vpw), C_out], scale f32
    [1, C_out]).
    """
    if cfg.group_size is not None:
        raise NotImplementedError("conv packing is per-output-channel only")
    kh, kw, c_in, c_out = w.shape
    q, scale = quantize_symmetric(
        w.reshape(kh * kw * c_in, c_out), cfg.bits, axis=0
    )
    fmt = _fmt(cfg)
    q = q.reshape(kh, kw, c_in, c_out)
    words = samd.pack(jnp.moveaxis(q, 2, -1), fmt)      # [kh, kw, c_out, cw]
    packed = jnp.moveaxis(words, -1, 2)
    return packed, scale


def unpack_conv_weights(packed: jax.Array, c_in: int,
                        cfg: QuantConfig) -> jax.Array:
    """Inverse of :func:`pack_conv_weights` (codes only): int32
    [KH, KW, C_in, C_out]."""
    fmt = _fmt(cfg)
    pt = jnp.moveaxis(packed, 2, -1)
    vals = samd.unpack(pt, fmt, c_in)
    return jnp.moveaxis(vals, -1, 2)


def dequant_conv_weights(packed: jax.Array, scale: jax.Array, c_in: int,
                         cfg: QuantConfig, dtype=jnp.float32) -> jax.Array:
    """Dense [KH, KW, C_in, C_out] conv weight from the packed form."""
    q = unpack_conv_weights(packed, c_in, cfg)
    return (q.astype(jnp.float32) * scale.reshape(1, 1, 1, -1)).astype(dtype)


def pack_int8_lanes(vals: jax.Array) -> jax.Array:
    """int8 [..., D] -> uint32 [..., D//4]: four 8-bit lanes per word along
    the trailing axis. This is the SAMD storage format of the paged KV pool
    (b=8, lane_width=8, word_bits=32): quantized K/V stay packed in HBM and
    are unpacked lane-wise inside the paged-attention kernel."""
    d = vals.shape[-1]
    assert d % 4 == 0, f"trailing dim {d} must pack into whole uint32 words"
    u = (vals.astype(jnp.int32) & 0xFF).astype(jnp.uint32)
    u = u.reshape(vals.shape[:-1] + (d // 4, 4))
    shifts = jnp.arange(4, dtype=jnp.uint32) * jnp.uint32(8)
    return jnp.sum(u << shifts, axis=-1, dtype=jnp.uint32)


def int8_lane(words: jax.Array, lane: int) -> jax.Array:
    """Lane ``lane`` (0-3) of uint32 words as sign-extended int32, same
    shape as ``words``: element ``4 * w + lane`` of the vector that
    ``pack_int8_lanes`` packed. The paged-attention kernel reads one lane
    of a whole row of words at a time, so the row never changes shape."""
    v = ((words >> jnp.uint32(8 * lane)) & jnp.uint32(0xFF)).astype(jnp.int32)
    return v - ((v >> 7) & 1) * 256


def unpack_int8_lanes(words: jax.Array) -> jax.Array:
    """uint32 [..., W] -> sign-extended int32 [..., W*4] (inverse of
    ``pack_int8_lanes``)."""
    v = jnp.stack([int8_lane(words, j) for j in range(4)], axis=-1)
    return v.reshape(words.shape[:-1] + (words.shape[-1] * 4,))


def qmatmul(x: jax.Array, packed: jax.Array, scale: jax.Array, k: int,
            cfg: QuantConfig, precision=None) -> jax.Array:
    """x[..., K] @ dequant(packed)[K, N] with backend dispatch."""
    if cfg.backend == "pallas":
        from repro.kernels import ops as kops

        return kops.samd_matmul(x, packed, scale, k, cfg)
    if cfg.backend != "xla":
        raise ValueError(
            f"unknown QuantConfig backend {cfg.backend!r}; known "
            "backends: xla, pallas"
        )
    w = dequant_weights(packed, scale, k, cfg, dtype=x.dtype)
    return jnp.matmul(x, w, precision=precision)
