"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.conv import ConvPlan
from repro.quant.config import QuantConfig
from repro.quant.packing import (
    dequant_conv_weights,
    dequant_weights,
    unpack_int8_lanes,
)


def samd_matmul_ref(x: jax.Array, packed: jax.Array, scale: jax.Array,
                    k: int, cfg: QuantConfig) -> jax.Array:
    """Unpack the whole weight and matmul at once."""
    w = dequant_weights(packed, scale, k, cfg, dtype=x.dtype)
    return jnp.matmul(x, w)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array,
                        v_pages: jax.Array, page_table: jax.Array,
                        q_pos: jax.Array, k_scale=None,
                        v_scale=None) -> jax.Array:
    """Gather-then-attend oracle with ``layers._paged_gather`` /
    ``_paged_key_positions`` semantics: each row's pages are copied into a
    dense [n_pp * page_size] view, unallocated blocks are masked via
    derived key positions, softmax runs in f32 over the whole view. This
    is exactly the dense copy the fused kernel exists to delete."""
    b, n_pp = page_table.shape
    p, page_size, lanes = k_pages.shape
    h, dh = q.shape[1:]
    packed = k_pages.dtype == jnp.uint32
    hkv = lanes * (4 if packed else 1) // dh
    g = h // hkv

    safe = jnp.clip(page_table.astype(jnp.int32), 0, p - 1).reshape(-1)

    def gather(pool, scale):
        # folded [P, page_size, Hkv * w] pages: heads split after the gather
        gathered = jnp.take(pool, safe, axis=0).reshape(
            b, n_pp * page_size, hkv, -1
        )
        if packed:
            gathered = unpack_int8_lanes(gathered).astype(jnp.float32)
            gathered = gathered * jnp.take(scale, safe, axis=0).reshape(
                b, n_pp * page_size, hkv
            )[..., None]
        return gathered.astype(jnp.float32)

    kg = gather(k_pages, k_scale)
    vg = gather(v_pages, v_scale)

    iota = jnp.arange(n_pp * page_size, dtype=jnp.int32)[None, :]
    valid = jnp.repeat(page_table >= 0, page_size, axis=1)
    k_pos = jnp.where(valid, iota, -1)

    qg = q.reshape(b, hkv, g, -1).astype(jnp.float32)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, kg) * scale
    mask = (k_pos[:, None, None, :] >= 0) & (
        k_pos[:, None, None, :] <= q_pos[:, None, None, None]
    )
    s = jnp.where(mask, s, -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs, vg)
    return out.reshape(b, h, -1).astype(q.dtype)


def samd_conv2d_ref(x: jax.Array, packed: jax.Array, scale: jax.Array,
                    cfg: QuantConfig, padding: int = 1) -> jax.Array:
    """Dense dequant + XLA conv oracle for the blocked conv2d kernel."""
    c_in = x.shape[0]
    w = dequant_conv_weights(packed, scale, c_in, cfg, dtype=jnp.float32)
    out = jax.lax.conv_general_dilated(
        x[None].astype(jnp.float32), w, window_strides=(1, 1),
        padding=[(padding, padding)] * 2,
        dimension_numbers=("NCHW", "HWIO", "NHWC"),
    )
    return out[0].astype(x.dtype)


def samd_conv_chunks_ref(x_words: jax.Array, k_word: jax.Array,
                         plan: ConvPlan) -> jax.Array:
    """Chunk products via the core library (already numpy-validated)."""
    from repro.core.conv import chunk_products, extract_outputs

    hi, lo = chunk_products(x_words, k_word, plan)
    return extract_outputs(hi, lo, plan)


def conv1d_int_ref(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """Integer full convolution, direct dot products."""
    taps = kernel.shape[-1]
    n = x.shape[-1]
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(taps - 1, taps - 1)])
    out = jnp.zeros(x.shape[:-1] + (n + taps - 1,), jnp.int32)
    for j in range(taps):
        idx = taps - 1 - j + jnp.arange(n + taps - 1)
        out = out + kernel[..., j] * xp[..., idx]
    return out
