"""Pallas TPU kernel: fused decode attention over the paged KV pool.

The serving engine's paged KV cache (PR 2) stores every layer's K/V as a
global pool of fixed-size pages indexed by a host-side page table.
Before this kernel, every decode tick gathered each slot's pages into a
dense [B, n_pp * page_size] copy (``layers._paged_gather``) and ran
plain attention over it — an O(B * max_len * d) HBM round trip per layer
per token that exists purely to satisfy the dense-attention API. This
kernel deletes that copy: attention reads the pool THROUGH the page
table, one page at a time, with an online-softmax accumulator, so the
only KV bytes touched are the pages a slot actually owns.

Structure (one grid program per (slot, kv-head block), pages innermost):

  * the page table and the per-slot query positions ride scalar prefetch
    (``pltpu.PrefetchScalarGridSpec``) so the K/V BlockSpec index maps
    can resolve ``page_table[b, j]`` to a physical pool page before the
    DMA for grid step (b, hb, j) is issued — the kernel body never sees
    an unresolved logical block index;
  * unallocated blocks (table entry -1) clamp to page 0 for the copy and
    are skipped by ``pl.when``; within a live page, offsets beyond the
    slot's position are masked to ``mask_value`` — exactly the validity
    semantics of ``layers._paged_key_positions`` (allocation +
    causality, no per-token pos buffer);
  * m/l/acc online-softmax state lives in VMEM scratch and persists
    across the page grid dimension; the output block is written once, at
    the last page step.

Two operand paths share the accumulator:

  * bf16 (or f32) pages — read as-is;
  * SAMD-packed int8 pages — uint32 words of four 8-bit lanes along
    head_dim plus per-(token, head) scales, unpacked lane-wise on the
    VPU inside VMEM with the same broadcasted shift/mask idiom as
    ``samd_matmul`` (the paper's technique applied to the KV operand:
    HBM sees only packed words, the unpack rides the compute).

``interpret=True`` runs the same kernel body under the Pallas
interpreter so CPU CI exercises both paths; on TPU the call compiles to
Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# plain jnp shifts/reshapes, traceable inside the kernel body — the ONE
# definition of the lane format, shared with the pack/gather-ref paths
from repro.quant.packing import unpack_int8_lanes as _unpack_lanes

DEFAULT_MASK_VALUE = -1e30


def _online_update(
    q, k, v, base, q_pos, page_size, mask_value, m_ref, l_ref, acc_ref
):
    """Fold one page of K/V into the online-softmax state.

    q [hkv, g, dh] f32; k/v [page_size, hkv, dh] f32. Offsets past the
    slot's current position are causally masked (they belong to pages
    granted ahead of the write cursor, or to a previous page occupant).
    """
    s = jnp.einsum("hgd,phd->hgp", q, k)  # [hkv, g, page_size]
    offs = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page_size), 2)
    s = jnp.where(offs <= q_pos, s, mask_value)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
        "hgp,phd->hgd", p, v
    )
    m_ref[...] = m_new


def _init_scratch(j, m_ref, l_ref, acc_ref, mask_value):
    """Reset the online-softmax state at the first page step of a
    (slot, head-block) program. MUST run before the page accumulation —
    the scratch carries the previous program's state otherwise."""

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, mask_value)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)


def _store_out(j, o_ref, m_ref, l_ref, acc_ref):
    """Emit the normalized output at the last page step.

    A slot with no valid key at all (inactive: page table row all -1)
    keeps l == 0 and yields zeros — its logits are discarded by the
    engine, and unlike the gather path it never averages pool garbage.
    """

    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = out.astype(o_ref.dtype)


def _kernel_bf16(
    pt_ref,
    pos_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    page_size,
    sm_scale,
    mask_value,
):
    b, j = pl.program_id(0), pl.program_id(2)
    page = pt_ref[b, j]
    q_pos = pos_ref[b]
    base = j * page_size
    _init_scratch(j, m_ref, l_ref, acc_ref, mask_value)

    @pl.when((page >= 0) & (base <= q_pos))
    def _accum():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        _online_update(
            q, k, v, base, q_pos, page_size, mask_value, m_ref, l_ref, acc_ref
        )

    _store_out(j, o_ref, m_ref, l_ref, acc_ref)


def _kernel_packed(
    pt_ref,
    pos_ref,
    q_ref,
    k_ref,
    ks_ref,
    v_ref,
    vs_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    page_size,
    sm_scale,
    mask_value,
):
    b, j = pl.program_id(0), pl.program_id(2)
    page = pt_ref[b, j]
    q_pos = pos_ref[b]
    base = j * page_size
    _init_scratch(j, m_ref, l_ref, acc_ref, mask_value)

    @pl.when((page >= 0) & (base <= q_pos))
    def _accum():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        # lane-unpack + dequantize in VMEM: HBM only saw packed words
        ks = ks_ref[0][..., None]
        vs = vs_ref[0][..., None]
        k = _unpack_lanes(k_ref[0]).astype(jnp.float32) * ks
        v = _unpack_lanes(v_ref[0]).astype(jnp.float32) * vs
        _online_update(
            q, k, v, base, q_pos, page_size, mask_value, m_ref, l_ref, acc_ref
        )

    _store_out(j, o_ref, m_ref, l_ref, acc_ref)


def paged_decode_attention_xla(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    q_pos: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    mask_value: float = DEFAULT_MASK_VALUE,
) -> jax.Array:
    """The SAME page-loop algorithm lowered to straight-line jnp — the
    non-TPU backend of ``ops.paged_decode_attention``.

    One unrolled step per page column, batched over slots (the Pallas
    interpreter runs the grid sequentially, which on CPU costs more than
    the gather it replaces; this lowering keeps the algorithm — online
    softmax, per-page reads, no [B, n_pp * page_size] copy — and lets
    XLA vectorize across the batch). The page loop is a Python loop, not
    a ``lax.scan``: n_pp is a static shape (and small — the engine
    truncates the table to the pow2 used-width), and unrolling deletes
    the ~100us/step while-loop overhead XLA pays on CPU. Numerics match
    the kernel: f32 accumulation, pages folded in ascending order.
    """
    b, h, dh = q.shape
    packed = k_pages.dtype == jnp.uint32
    p, page_size, hkv = k_pages.shape[:3]
    g = h // hkv
    sm_scale = 1.0 / (dh**0.5)
    qg = q.reshape(b, hkv, g, dh).astype(jnp.float32) * sm_scale
    pt = page_table.astype(jnp.int32)
    pos = q_pos.astype(jnp.int32)
    n_pp = pt.shape[1]

    def body(carry, page, base):
        m, l_sum, acc = carry
        safe = jnp.clip(page, 0, p - 1)
        k = jnp.take(k_pages, safe, axis=0)  # [B, ps, hkv, w]
        v = jnp.take(v_pages, safe, axis=0)
        if packed:
            ks = jnp.take(k_scale, safe, axis=0)[..., None]
            vs = jnp.take(v_scale, safe, axis=0)[..., None]
            k = _unpack_lanes(k).astype(jnp.float32) * ks
            v = _unpack_lanes(v).astype(jnp.float32) * vs
        else:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        s = jnp.einsum("bhgd,bphd->bhgp", qg, k)
        offs = base + jnp.arange(page_size, dtype=jnp.int32)
        valid = (page[:, None] >= 0) & (offs[None, :] <= pos[:, None])
        s = jnp.where(valid[:, None, None, :], s, mask_value)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_new = l_sum * alpha + jnp.sum(pexp, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgp,bphd->bhgd", pexp, v
        )
        # rows whose page is invalid keep their carry untouched — the
        # scan-lowering twin of the kernel's pl.when page skip. Without
        # this, a row with NO valid key ever (inactive slot) would see
        # exp(mask - mask) == 1 at every position and average garbage;
        # skipping keeps l == 0 there, so the epilogue emits zeros.
        keep = ((page >= 0) & (base <= pos))[:, None, None]
        m_new = jnp.where(keep, m_new, m)
        l_new = jnp.where(keep, l_new, l_sum)
        acc_new = jnp.where(keep[..., None], acc_new, acc)
        return m_new, l_new, acc_new

    carry = (
        jnp.full((b, hkv, g), mask_value, jnp.float32),
        jnp.zeros((b, hkv, g), jnp.float32),
        jnp.zeros((b, hkv, g, dh), jnp.float32),
    )
    for j in range(n_pp):
        carry = body(carry, pt[:, j], j * page_size)
    _, l_sum, acc = carry
    out = acc / jnp.maximum(l_sum, 1e-30)[..., None]
    return out.reshape(b, h, dh).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_kv_heads", "interpret", "mask_value")
)
def paged_decode_attention(
    q: jax.Array,  # [B, H, dh] current-token queries (post-rope)
    k_pages: jax.Array,  # [P, page_size, Hkv, dh] bf16/f32, or packed
    v_pages: jax.Array,  # ...[P, page_size, Hkv, dh//4] uint32 (4 lanes)
    page_table: jax.Array,  # [B, n_pp] int32; -1 = unallocated block
    q_pos: jax.Array,  # [B] int32 logical position of each query
    *,
    k_scale: jax.Array | None = None,  # [P, page_size, Hkv] f32 (packed)
    v_scale: jax.Array | None = None,
    block_kv_heads: int | None = None,
    interpret: bool = False,
    mask_value: float = DEFAULT_MASK_VALUE,
) -> jax.Array:
    """Decode attention straight off the page pool; returns [B, H, dh].

    No [B, n_pp * page_size] gathered KV copy is ever materialized: each
    grid step reads exactly one physical page, resolved from the scalar-
    prefetched page table. Pass ``k_scale``/``v_scale`` iff the pools
    are SAMD-packed uint32 (four int8 lanes per word along head_dim).
    """
    b, h, dh = q.shape
    packed = k_pages.dtype == jnp.uint32
    if packed:
        assert (
            k_scale is not None and v_scale is not None
        ), "packed int8 pools need per-(token, head) scales"
        assert k_pages.shape[-1] * 4 == dh, (k_pages.shape, dh)
    else:
        assert k_pages.shape[-1] == dh, (k_pages.shape, dh)
    _, page_size, hkv = k_pages.shape[:3]
    g = h // hkv
    assert g * hkv == h, (h, hkv)
    n_pp = page_table.shape[1]
    bh = block_kv_heads or hkv
    assert hkv % bh == 0, (hkv, bh)
    sm_scale = 1.0 / (dh**0.5)

    qg = q.reshape(b, hkv, g, dh)
    pt = page_table.astype(jnp.int32)
    pos = q_pos.astype(jnp.int32)
    grid = (b, hkv // bh, n_pp)

    # index maps receive the scalar-prefetch refs after the grid indices;
    # -1 pages clamp to 0 (their copy lands in VMEM but pl.when skips the
    # compute, so the values never reach the accumulator)
    def q_map(i, hb, j, pt_s, pos_s):
        return (i, hb, 0, 0)

    def kv_map(i, hb, j, pt_s, pos_s):
        return (jnp.maximum(pt_s[i, j], 0), 0, hb, 0)

    def scale_map(i, hb, j, pt_s, pos_s):
        return (jnp.maximum(pt_s[i, j], 0), 0, hb)

    kv_width = k_pages.shape[-1]
    if packed:
        kernel = functools.partial(
            _kernel_packed,
            page_size=page_size,
            sm_scale=sm_scale,
            mask_value=mask_value,
        )
        in_specs = [
            pl.BlockSpec((1, bh, g, dh), q_map),
            pl.BlockSpec((1, page_size, bh, kv_width), kv_map),
            pl.BlockSpec((1, page_size, bh), scale_map),
            pl.BlockSpec((1, page_size, bh, kv_width), kv_map),
            pl.BlockSpec((1, page_size, bh), scale_map),
        ]
        operands = (pt, pos, qg, k_pages, k_scale, v_pages, v_scale)
    else:
        kernel = functools.partial(
            _kernel_bf16,
            page_size=page_size,
            sm_scale=sm_scale,
            mask_value=mask_value,
        )
        in_specs = [
            pl.BlockSpec((1, bh, g, dh), q_map),
            pl.BlockSpec((1, page_size, bh, kv_width), kv_map),
            pl.BlockSpec((1, page_size, bh, kv_width), kv_map),
        ]
        operands = (pt, pos, qg, k_pages, v_pages)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bh, g, dh), q_map),
            scratch_shapes=[
                pltpu.VMEM((bh, g), jnp.float32),  # running max
                pltpu.VMEM((bh, g), jnp.float32),  # running denom
                pltpu.VMEM((bh, g, dh), jnp.float32),  # weighted V acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        interpret=interpret,
        # the trace names the kernel's op by this name
        name="paged_decode_attention",
    )(*operands)
    return out.reshape(b, h, dh)


# ---------------------------------------------------------------------------
# multi-token-query block: speculative verify (and multi-page amortization)
# ---------------------------------------------------------------------------
#
# The speculative-decoding verify step scores a q-block of S = K+1 tokens
# per slot (the pending token plus K draft proposals) against the same
# paged pool in ONE pass. Each grid step now folds a whole page into S*G
# query rows instead of G, amortizing the page DMA and the grid overhead
# across the block — the ROADMAP's "multi-page compute blocks" follow-up
# realized along the query axis. Per-query causal masking (offset <=
# q_pos[s]) keeps every row token-identical to S independent decode
# calls; rows whose position is -1 (slots past their draft budget) match
# nothing and emit zeros.


def _query_positions(pos_ref, b, sq):
    """Slot ``b``'s per-query positions as an [sq, 1, 1, 1] int32 vector
    plus their maximum as a scalar. Mosaic loads only scalars from SMEM,
    so the vector is assembled from ``sq`` scalar reads (sq = K+1 is a
    handful) instead of one row load of the prefetched [B, S] table."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (sq, 1, 1, 1), 0)
    vec = jnp.full((sq, 1, 1, 1), pos_ref[b, 0], jnp.int32)
    top = pos_ref[b, 0]
    for i in range(1, sq):
        vec = jnp.where(iota == i, pos_ref[b, i], vec)
        top = jnp.maximum(top, pos_ref[b, i])
    return vec, top


def _online_update_mq(
    q, k, v, base, q_pos, page_size, mask_value, m_ref, l_ref, acc_ref
):
    """Fold one page of K/V into the q-block online-softmax state.

    q [s, hkv, g, dh] f32 (pre-scaled); q_pos [s, 1, 1, 1] per-query
    positions (-1 = fully masked row); k/v [page_size, hkv, dh] f32.
    """
    s = jnp.einsum("qhgd,phd->qhgp", q, k)  # [s, hkv, g, page_size]
    offs = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, page_size), 3)
    s = jnp.where(offs <= q_pos, s, mask_value)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., None])
    # a fully-masked query row (q_pos -1: past the slot's draft budget)
    # would see exp(mask - mask) == 1 everywhere and average page noise;
    # zeroing its mass keeps l == 0 so the epilogue emits exact zeros
    p = jnp.where(q_pos >= 0, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + jnp.einsum(
        "qhgp,phd->qhgd", p, v
    )
    m_ref[...] = m_new


def _kernel_bf16_mq(
    pt_ref,
    pos_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    page_size,
    sm_scale,
    mask_value,
):
    b, j = pl.program_id(0), pl.program_id(2)
    page = pt_ref[b, j]
    q_pos, last = _query_positions(pos_ref, b, q_ref.shape[1])
    base = j * page_size
    _init_scratch(j, m_ref, l_ref, acc_ref, mask_value)

    @pl.when((page >= 0) & (base <= last))
    def _accum():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        _online_update_mq(
            q, k, v, base, q_pos, page_size, mask_value, m_ref, l_ref, acc_ref
        )

    _store_out(j, o_ref, m_ref, l_ref, acc_ref)


def _kernel_packed_mq(
    pt_ref,
    pos_ref,
    q_ref,
    k_ref,
    ks_ref,
    v_ref,
    vs_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    page_size,
    sm_scale,
    mask_value,
):
    b, j = pl.program_id(0), pl.program_id(2)
    page = pt_ref[b, j]
    q_pos, last = _query_positions(pos_ref, b, q_ref.shape[1])
    base = j * page_size
    _init_scratch(j, m_ref, l_ref, acc_ref, mask_value)

    @pl.when((page >= 0) & (base <= last))
    def _accum():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        ks = ks_ref[0][..., None]
        vs = vs_ref[0][..., None]
        k = _unpack_lanes(k_ref[0]).astype(jnp.float32) * ks
        v = _unpack_lanes(v_ref[0]).astype(jnp.float32) * vs
        _online_update_mq(
            q, k, v, base, q_pos, page_size, mask_value, m_ref, l_ref, acc_ref
        )

    _store_out(j, o_ref, m_ref, l_ref, acc_ref)


def paged_verify_attention_xla(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    page_table: jax.Array,
    q_pos: jax.Array,
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    mask_value: float = DEFAULT_MASK_VALUE,
) -> jax.Array:
    """Unrolled-jnp lowering of the multi-token-query page loop — the
    non-TPU backend of ``ops.paged_verify_attention``. Same algorithm and
    numerics as the q-block kernel: f32 accumulation, pages folded in
    ascending order, per-query causal masks."""
    b, sq, h, dh = q.shape
    packed = k_pages.dtype == jnp.uint32
    p, page_size, hkv = k_pages.shape[:3]
    g = h // hkv
    sm_scale = 1.0 / (dh**0.5)
    qg = q.reshape(b, sq, hkv, g, dh).astype(jnp.float32) * sm_scale
    pt = page_table.astype(jnp.int32)
    pos = q_pos.astype(jnp.int32)  # [B, S]
    row_max = jnp.max(pos, axis=1)  # last valid query per slot
    n_pp = pt.shape[1]

    def body(carry, page, base):
        m, l_sum, acc = carry
        safe = jnp.clip(page, 0, p - 1)
        k = jnp.take(k_pages, safe, axis=0)
        v = jnp.take(v_pages, safe, axis=0)
        if packed:
            ks = jnp.take(k_scale, safe, axis=0)[..., None]
            vs = jnp.take(v_scale, safe, axis=0)[..., None]
            k = _unpack_lanes(k).astype(jnp.float32) * ks
            v = _unpack_lanes(v).astype(jnp.float32) * vs
        else:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        s = jnp.einsum("bqhgd,bphd->bqhgp", qg, k)
        offs = base + jnp.arange(page_size, dtype=jnp.int32)
        valid = (page[:, None, None] >= 0) & (
            offs[None, None, :] <= pos[:, :, None]
        )  # [B, S, page_size]
        s = jnp.where(valid[:, :, None, None, :], s, mask_value)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        # fully-masked query rows (position -1) keep zero mass — the
        # kernel-twin of the q-block's budget masking
        pexp = jnp.where(pos[:, :, None, None, None] >= 0, pexp, 0.0)
        l_new = l_sum * alpha + jnp.sum(pexp, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bqhgp,bphd->bqhgd", pexp, v
        )
        keep = ((page >= 0) & (base <= row_max))[:, None, None, None]
        m_new = jnp.where(keep, m_new, m)
        l_new = jnp.where(keep, l_new, l_sum)
        acc_new = jnp.where(keep[..., None], acc_new, acc)
        return m_new, l_new, acc_new

    carry = (
        jnp.full((b, sq, hkv, g), mask_value, jnp.float32),
        jnp.zeros((b, sq, hkv, g), jnp.float32),
        jnp.zeros((b, sq, hkv, g, dh), jnp.float32),
    )
    for j in range(n_pp):
        carry = body(carry, pt[:, j], j * page_size)
    _, l_sum, acc = carry
    out = acc / jnp.maximum(l_sum, 1e-30)[..., None]
    return out.reshape(b, sq, h, dh).astype(q.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_kv_heads", "interpret", "mask_value")
)
def paged_verify_attention(
    q: jax.Array,  # [B, S, H, dh] q-block (post-rope): pending + drafts
    k_pages: jax.Array,  # [P, page_size, Hkv, dh] bf16/f32, or packed
    v_pages: jax.Array,  # ...[P, page_size, Hkv, dh//4] uint32 (4 lanes)
    page_table: jax.Array,  # [B, n_pp] int32; -1 = unallocated block
    q_pos: jax.Array,  # [B, S] logical position per query; -1 = masked
    *,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    block_kv_heads: int | None = None,
    interpret: bool = False,
    mask_value: float = DEFAULT_MASK_VALUE,
) -> jax.Array:
    """Multi-token-query decode attention off the page pool: [B, S, H, dh].

    The speculative-verify sibling of ``paged_decode_attention``: one grid
    step folds a whole page into all S query rows of a slot (same scalar-
    prefetched page resolution, same online-softmax scratch, now carrying
    a leading query axis), so the page DMA and grid overhead are
    amortized across the verify block instead of paid per token.
    """
    b, sq, h, dh = q.shape
    packed = k_pages.dtype == jnp.uint32
    if packed:
        assert (
            k_scale is not None and v_scale is not None
        ), "packed int8 pools need per-(token, head) scales"
        assert k_pages.shape[-1] * 4 == dh, (k_pages.shape, dh)
    else:
        assert k_pages.shape[-1] == dh, (k_pages.shape, dh)
    _, page_size, hkv = k_pages.shape[:3]
    g = h // hkv
    assert g * hkv == h, (h, hkv)
    n_pp = page_table.shape[1]
    bh = block_kv_heads or hkv
    assert hkv % bh == 0, (hkv, bh)
    sm_scale = 1.0 / (dh**0.5)

    qg = q.reshape(b, sq, hkv, g, dh)
    pt = page_table.astype(jnp.int32)
    pos = q_pos.astype(jnp.int32)
    grid = (b, hkv // bh, n_pp)

    def q_map(i, hb, j, pt_s, pos_s):
        return (i, 0, hb, 0, 0)

    def kv_map(i, hb, j, pt_s, pos_s):
        return (jnp.maximum(pt_s[i, j], 0), 0, hb, 0)

    def scale_map(i, hb, j, pt_s, pos_s):
        return (jnp.maximum(pt_s[i, j], 0), 0, hb)

    kv_width = k_pages.shape[-1]
    if packed:
        kernel = functools.partial(
            _kernel_packed_mq,
            page_size=page_size,
            sm_scale=sm_scale,
            mask_value=mask_value,
        )
        in_specs = [
            pl.BlockSpec((1, sq, bh, g, dh), q_map),
            pl.BlockSpec((1, page_size, bh, kv_width), kv_map),
            pl.BlockSpec((1, page_size, bh), scale_map),
            pl.BlockSpec((1, page_size, bh, kv_width), kv_map),
            pl.BlockSpec((1, page_size, bh), scale_map),
        ]
        operands = (pt, pos, qg, k_pages, k_scale, v_pages, v_scale)
    else:
        kernel = functools.partial(
            _kernel_bf16_mq,
            page_size=page_size,
            sm_scale=sm_scale,
            mask_value=mask_value,
        )
        in_specs = [
            pl.BlockSpec((1, sq, bh, g, dh), q_map),
            pl.BlockSpec((1, page_size, bh, kv_width), kv_map),
            pl.BlockSpec((1, page_size, bh, kv_width), kv_map),
        ]
        operands = (pt, pos, qg, k_pages, v_pages)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, sq, bh, g, dh), q_map),
            scratch_shapes=[
                pltpu.VMEM((sq, bh, g), jnp.float32),  # running max
                pltpu.VMEM((sq, bh, g), jnp.float32),  # running denom
                pltpu.VMEM((sq, bh, g, dh), jnp.float32),  # weighted V acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, sq, hkv, g, dh), q.dtype),
        interpret=interpret,
        # the trace names the kernel's op by this name
        name="paged_verify_attention",
    )(*operands)
    return out.reshape(b, sq, h, dh)
