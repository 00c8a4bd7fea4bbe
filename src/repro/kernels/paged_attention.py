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

Pool layout: ``[P, page_size, Hkv * w]``, heads folded into the minor
(lane) dim, where ``w`` is head_dim for bf16/f32 pages and head_dim // 4
for SAMD-packed uint32 pages. At real widths the minor dim is a whole
number of 128-lane tiles, so XLA keeps the pool in its default layout
and the KV scatter, the page gather and this kernel all take it as it
is stored. (A ``[..., Hkv, dh]`` pool with dh = 64 would pad half of
every tile; XLA then lays the donated pool out with P on the lanes and
converts every pool in and out of each step program.)

Structure (one grid program per (slot, kv-head block), pages innermost):

  * the page table and the per-slot query positions ride scalar prefetch
    (``pltpu.PrefetchScalarGridSpec``) so the K/V BlockSpec index maps
    can resolve ``page_table[b, j]`` to a physical pool page before the
    DMA for grid step (b, hb, j) is issued — the kernel body never sees
    an unresolved logical block index;
  * unallocated blocks (table entry -1) clamp to page 0 for the copy and
    are skipped by ``pl.when``; within a live page, offsets beyond a
    query's position are masked — exactly the validity semantics of
    ``layers._paged_key_positions`` (allocation + causality, no
    per-token pos buffer);
  * heads are split in VMEM without reshaping the lane dim: a 0/1
    indicator matrix sums each head's lanes of ``k * q`` into its score
    (``[page_size, heads]``) and copies each head's softmax weights back
    onto its lanes for the PV product;
  * m/l/acc online-softmax state lives in VMEM scratch and persists
    across the page grid dimension; the output block is written once, at
    the last page step.

Two operand paths share the accumulator:

  * bf16 (or f32) pages — read as-is;
  * SAMD-packed int8 pages — uint32 words of four 8-bit lanes along
    head_dim plus per-(token, head) scales. Each of the four lanes is
    shifted out of a whole row of words on the VPU inside VMEM (the
    paper's technique applied to the KV operand: HBM sees only packed
    words, the unpack rides the compute), and the query is split into
    the matching four planes outside the kernel.

Decode (one query per slot) and speculative verify (a block of S queries
per slot) run the same kernel body under different names.

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

# plain jnp shifts/masks, traceable inside the kernel body — the ONE
# definition of the lane format, shared with the pack/gather-ref paths
from repro.quant.packing import int8_lane
from repro.quant.packing import unpack_int8_lanes as _unpack_lanes

DEFAULT_MASK_VALUE = -1e30


def _pool_geometry(q, k_pages, k_scale, v_scale):
    """(planes, w, hkv) of a folded pool for queries of head_dim
    ``q.shape[-1]``: packed pools hold four lanes (planes) per word."""
    dh = q.shape[-1]
    packed = k_pages.dtype == jnp.uint32
    planes = 4 if packed else 1
    if packed:
        assert (
            k_scale is not None and v_scale is not None
        ), "packed int8 pools need per-(token, head) scales"
    assert dh % planes == 0, (dh, planes)
    w = dh // planes
    lanes = k_pages.shape[-1]
    assert k_pages.ndim == 3 and lanes % w == 0, (k_pages.shape, dh)
    return planes, w, lanes // w


def _head_indicator(lanes, heads, width, transpose=False):
    """0/1 f32 matrix [lanes, heads] (or its transpose) with entry (l, h)
    set iff lane l of a folded row belongs to head h. ``x @ E`` sums each
    head's lanes; ``y @ E.T`` copies a per-head value onto its lanes."""
    shape = (heads, lanes) if transpose else (lanes, heads)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if transpose else 0)
    head = jax.lax.broadcasted_iota(jnp.int32, shape, 0 if transpose else 1)
    inside = (lane >= head * width) & (lane < head * width + width)
    return inside.astype(jnp.float32)


def _dot(a, b):
    # f32 at full precision: the indicator products must not round the
    # scores or the softmax weights to bf16
    return jax.lax.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _kernel(pt_ref, pos_ref, q_ref, *refs, page_size, width, groups,
            sm_scale, mask_value):
    """Fold page ``page_table[b, j]`` into the online-softmax state of
    every query row of slot ``b``'s head block.

    q_ref [1, planes, rows, L]: row r is query r // groups, group member
    r % groups; L = (heads in block) * width lanes. k/v refs
    [1, page_size, L] (packed: uint32 words, plus [1, page_size, heads]
    scale refs). m/l scratch [rows, heads]; acc [planes, rows, L].
    """
    if len(refs) == 8:
        k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        ks_ref = vs_ref = None
    b, j = pl.program_id(0), pl.program_id(2)
    page = pt_ref[b, j]
    # Mosaic loads only scalars from SMEM: one read per query position
    q_pos = [pos_ref[b, i] for i in range(pos_ref.shape[1])]
    last = functools.reduce(jnp.maximum, q_pos)
    base = j * page_size
    rows, heads = m_ref.shape
    lanes = k_ref.shape[-1]
    expand = _head_indicator(lanes, heads, width, transpose=True)

    # reset at the first page step of a (slot, head-block) program: the
    # scratch carries the previous program's state otherwise
    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, mask_value)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((page >= 0) & (base <= last))
    def _accum():
        reduce = _head_indicator(lanes, heads, width)
        if ks_ref is None:
            k = [k_ref[0].astype(jnp.float32)]
            v = [v_ref[0].astype(jnp.float32)]
        else:
            # lane-unpack in VMEM: HBM only saw packed words
            k = [int8_lane(k_ref[0], i).astype(jnp.float32) for i in range(4)]
            v = [int8_lane(v_ref[0], i).astype(jnp.float32) for i in range(4)]
        offs = base + jax.lax.broadcasted_iota(jnp.int32, (page_size, 1), 0)
        for r in range(rows):
            # offsets past the query's position are causally masked (pages
            # granted ahead of the write cursor, a previous occupant's
            # tokens, or every offset of a query at position -1)
            valid = offs <= q_pos[r // groups]
            s = _dot(k[0] * q_ref[0, 0, r:r + 1, :].astype(jnp.float32),
                     reduce)
            for i in range(1, len(k)):
                s = s + _dot(k[i] * q_ref[0, i, r:r + 1, :].astype(
                    jnp.float32), reduce)
            s = s * sm_scale  # [page_size, heads]
            if ks_ref is not None:
                s = s * ks_ref[0]
            s = jnp.where(valid, s, mask_value)
            m_prev = m_ref[r:r + 1, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # masked offsets carry no mass, so a fully masked query keeps
            # l == 0 and the epilogue emits exact zeros
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[r:r + 1, :] = (l_ref[r:r + 1, :] * alpha
                                 + jnp.sum(p, axis=0, keepdims=True))
            m_ref[r:r + 1, :] = m_new
            if vs_ref is not None:
                p = p * vs_ref[0]
            p = _dot(p, expand)  # [page_size, L]
            alpha = _dot(alpha, expand)  # [1, L]
            for i, vi in enumerate(v):
                acc_ref[i, r:r + 1, :] = (
                    acc_ref[i, r:r + 1, :] * alpha
                    + jnp.sum(p * vi, axis=0, keepdims=True))

    # a slot with no valid key at all (inactive: page table row all -1)
    # keeps l == 0 and yields zeros — its logits are discarded by the
    # engine, and unlike the gather path it never averages pool garbage
    @pl.when(j == pl.num_programs(2) - 1)
    def _store():
        denom = jnp.maximum(_dot(l_ref[...], expand), 1e-30)  # [rows, L]
        for i in range(acc_ref.shape[0]):
            o_ref[0, i] = (acc_ref[i] / denom).astype(o_ref.dtype)


def _query_rows(q, hkv, planes):
    """[B, S, H, dh] -> [B, planes, S * g, hkv * w]: one row per (query,
    group member), the kv heads folded into lanes as the pool folds them;
    plane i holds head_dim elements ``w' * planes + i`` (the packed lane
    order of ``pack_int8_lanes``)."""
    b, sq, h, dh = q.shape
    g, w = h // hkv, dh // planes
    x = q.reshape(b, sq, hkv, g, w, planes)
    return x.transpose(0, 5, 1, 3, 2, 4).reshape(b, planes, sq * g, hkv * w)


def _from_rows(x, sq, hkv):
    """Inverse of ``_query_rows``: [B, planes, S * g, hkv * w] ->
    [B, S, H, dh]."""
    b, planes, rows, lanes = x.shape
    g, w = rows // sq, lanes // hkv
    x = x.reshape(b, planes, sq, g, hkv, w)
    return x.transpose(0, 2, 4, 3, 5, 1).reshape(b, sq, hkv * g, w * planes)


def _paged_attention(q, k_pages, v_pages, page_table, q_pos, k_scale,
                     v_scale, block_kv_heads, interpret, mask_value, name):
    """q [B, S, H, dh], q_pos [B, S] -> [B, S, H, dh] through the kernel
    named ``name``."""
    b, sq, h, dh = q.shape
    planes, w, hkv = _pool_geometry(q, k_pages, k_scale, v_scale)
    g = h // hkv
    assert g * hkv == h, (h, hkv)
    page_size = k_pages.shape[1]
    n_pp = page_table.shape[1]
    bh = block_kv_heads or hkv
    assert hkv % bh == 0, (hkv, bh)
    rows, lanes = sq * g, bh * w

    qr = _query_rows(q, hkv, planes)
    pt = page_table.astype(jnp.int32)
    pos = q_pos.astype(jnp.int32)

    # index maps receive the scalar-prefetch refs after the grid indices;
    # -1 pages clamp to 0 (their copy lands in VMEM but pl.when skips the
    # compute, so the values never reach the accumulator)
    def q_map(i, hb, j, pt_s, pos_s):
        return (i, 0, 0, hb)

    def kv_map(i, hb, j, pt_s, pos_s):
        return (jnp.maximum(pt_s[i, j], 0), 0, hb)

    q_spec = pl.BlockSpec((1, planes, rows, lanes), q_map)
    kv_spec = pl.BlockSpec((1, page_size, lanes), kv_map)
    if planes > 1:
        scale_spec = pl.BlockSpec((1, page_size, bh), kv_map)
        in_specs = [q_spec, kv_spec, scale_spec, kv_spec, scale_spec]
        operands = (pt, pos, qr, k_pages, k_scale, v_pages, v_scale)
    else:
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = (pt, pos, qr, k_pages, v_pages)

    out = pl.pallas_call(
        functools.partial(
            _kernel, page_size=page_size, width=w, groups=g,
            sm_scale=1.0 / (dh**0.5), mask_value=mask_value,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv // bh, n_pp),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, bh), jnp.float32),  # running max
                pltpu.VMEM((rows, bh), jnp.float32),  # running denom
                pltpu.VMEM((planes, rows, lanes), jnp.float32),  # PV acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        interpret=interpret,
        # the trace names the kernel's op by this name
        name=name,
    )(*operands)
    return _from_rows(out, sq, hkv)


@functools.partial(
    jax.jit, static_argnames=("block_kv_heads", "interpret", "mask_value")
)
def paged_decode_attention(
    q: jax.Array,  # [B, H, dh] current-token queries (post-rope)
    k_pages: jax.Array,  # [P, page_size, Hkv * dh] bf16/f32, or packed
    v_pages: jax.Array,  # ...[P, page_size, Hkv * dh//4] uint32 (4 lanes)
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
    prefetched page table. Pools are folded ``[P, page_size, Hkv * w]``
    (module docstring). Pass ``k_scale``/``v_scale`` iff the pools are
    SAMD-packed uint32 (four int8 lanes per word along head_dim).
    """
    return _paged_attention(
        q[:, None], k_pages, v_pages, page_table, q_pos[:, None], k_scale,
        v_scale, block_kv_heads, interpret, mask_value,
        "paged_decode_attention",
    )[:, 0]


@functools.partial(
    jax.jit, static_argnames=("block_kv_heads", "interpret", "mask_value")
)
def paged_verify_attention(
    q: jax.Array,  # [B, S, H, dh] q-block (post-rope): pending + drafts
    k_pages: jax.Array,  # [P, page_size, Hkv * dh] bf16/f32, or packed
    v_pages: jax.Array,  # ...[P, page_size, Hkv * dh//4] uint32 (4 lanes)
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
    prefetched page resolution, same online-softmax scratch), so the page
    DMA and grid overhead are amortized across the verify block instead
    of paid per token. Per-query causal masks keep every row equal to an
    independent decode call; rows at position -1 (slots past their draft
    budget) match nothing and emit zeros.
    """
    return _paged_attention(
        q, k_pages, v_pages, page_table, q_pos, k_scale, v_scale,
        block_kv_heads, interpret, mask_value, "paged_verify_attention",
    )


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
    """The SAME page-loop algorithm lowered to straight-line jnp — the
    non-TPU backend of ``ops.paged_verify_attention`` (and, with S = 1,
    of ``ops.paged_decode_attention``).

    One unrolled step per page column, batched over slots (the Pallas
    interpreter runs the grid sequentially, which on CPU costs more than
    the gather it replaces; this lowering keeps the algorithm — online
    softmax, per-page reads, no [B, n_pp * page_size] copy — and lets
    XLA vectorize across the batch). The page loop is a Python loop, not
    a ``lax.scan``: n_pp is a static shape (and small — the engine
    truncates the table to the pow2 used-width), and unrolling deletes
    the ~100us/step while-loop overhead XLA pays on CPU. Numerics match
    the kernel: f32 accumulation, pages folded in ascending order,
    per-query causal masks.
    """
    b, sq, h, dh = q.shape
    planes, _, hkv = _pool_geometry(q, k_pages, k_scale, v_scale)
    p, page_size = k_pages.shape[:2]
    g = h // hkv
    sm_scale = 1.0 / (dh**0.5)
    qg = q.reshape(b, sq, hkv, g, dh).astype(jnp.float32) * sm_scale
    pt = page_table.astype(jnp.int32)
    pos = q_pos.astype(jnp.int32)  # [B, S]
    row_max = jnp.max(pos, axis=1)  # last valid query per slot
    n_pp = pt.shape[1]

    def read(pool, scale, safe):
        # heads split only in the gathered page, never on the pool
        x = jnp.take(pool, safe, axis=0).reshape(b, page_size, hkv, -1)
        if planes == 1:
            return x.astype(jnp.float32)
        s = jnp.take(scale, safe, axis=0)[..., None]
        return _unpack_lanes(x).astype(jnp.float32) * s

    def body(carry, page, base):
        m, l_sum, acc = carry
        safe = jnp.clip(page, 0, p - 1)
        k = read(k_pages, k_scale, safe)  # [B, ps, hkv, dh]
        v = read(v_pages, v_scale, safe)
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
        # rows whose page is invalid keep their carry untouched — the
        # twin of the kernel's pl.when page skip. Without this, a row
        # with NO valid key ever (inactive slot) would see exp(mask -
        # mask) == 1 at every position and average garbage; skipping
        # keeps l == 0 there, so the epilogue emits zeros.
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


def paged_decode_attention_xla(q, k_pages, v_pages, page_table, q_pos, *,
                               k_scale=None, v_scale=None,
                               mask_value: float = DEFAULT_MASK_VALUE):
    """Unrolled-jnp lowering of single-query decode: the verify lowering
    with one query per slot. q [B, H, dh], q_pos [B] -> [B, H, dh]."""
    return paged_verify_attention_xla(
        q[:, None], k_pages, v_pages, page_table, q_pos[:, None],
        k_scale=k_scale, v_scale=v_scale, mask_value=mask_value,
    )[:, 0]
