"""Transformer building blocks: norms, RoPE, GQA attention, MLPs, MoE.

Design notes:
  * All matmuls go through ``apply_linear`` so the SAMD quantization backend
    can swap packed weights in transparently.
  * Attention is query-chunked (lax.map over chunks) so 32k-token prefill
    never materializes an [S, S] score tensor — peak live memory is
    [B, H, chunk, S] per chunk.
  * MoE uses grouped capacity-based dispatch (GShard-style einsums) with
    ~2k-token groups so the one-hot dispatch tensor stays ~tens of MB per
    device at 32k sequence lengths.
  * Norms and softmax run in f32; matmul outputs stay bf16.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.quant.config import QuantConfig
from repro.quant.packing import pack_int8_lanes, qmatmul, unpack_int8_lanes


# ---------------------------------------------------------------------------
# linear (+ quantized linear) application
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """SAMD-packed weight: uint32 words + per-channel scale (+ static meta).

    The weight is packed along its reduction axis, stored 2D as
    [K/values_per_word, prod(rest)]. ``orig_shape``/``axis`` restore the
    full layout for non-matmul consumers (einsum sites materialize).
    """

    packed: jax.Array
    scale: jax.Array
    orig_shape: tuple  # static
    axis: int          # static: reduction axis in orig_shape
    cfg: QuantConfig   # static

    @property
    def k(self) -> int:
        return self.orig_shape[self.axis]

    def tree_flatten(self):
        children = (self.packed, self.scale)
        return children, (self.orig_shape, self.axis, self.cfg)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


def materialize(w, dtype=jnp.bfloat16) -> jax.Array:
    """Dense view of a (possibly SAMD-packed) weight."""
    if not isinstance(w, QuantizedTensor):
        return w
    from repro.quant.packing import dequant_weights

    k = w.k
    rest = tuple(s for i, s in enumerate(w.orig_shape) if i != w.axis)
    dense2d = dequant_weights(w.packed, w.scale, k, w.cfg, dtype=dtype)
    dense = dense2d.reshape((k,) + rest)
    return jnp.moveaxis(dense, 0, w.axis)


def apply_linear(w, x: jax.Array, precision=None) -> jax.Array:
    """x[..., K] @ w[K, N] where w is an array or a QuantizedTensor."""
    if isinstance(w, QuantizedTensor):
        if len(w.orig_shape) == 2 and w.axis == 0:
            return qmatmul(x, w.packed, w.scale, w.k, w.cfg)
        return jnp.matmul(x, materialize(w, x.dtype), precision=precision)
    return jnp.matmul(x, w, precision=precision)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def rope_tables(positions: jax.Array, head_dim: int, theta: float):
    """positions [..., S] -> (sin, cos) [..., S, head_dim//2] f32."""
    half = head_dim // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x [..., S, H, D]; sin/cos [..., S, D//2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    s = sin[..., None, :]  # broadcast over heads
    c = cos[..., None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _attend_chunk(q, k, v, q_pos, k_pos, scale):
    """q [B,Cq,Hkv,G,dh]; k/v [B,S,Hkv,dh] -> [B,Cq,Hkv,G,dh].

    Masks keys with k_pos > q_pos (causal) or k_pos < 0 (unfilled cache).

    Probs stay f32 through the PV product (rounding only the output):
    the fused paged-attention kernel accumulates in f32, so greedy
    token-identity between the serving paths needs matching precision
    here — and it must hold UNCONDITIONALLY, not per call site: the
    cached-decode-vs-full-forward consistency check (test_models.
    test_decode_consistency at 1e-3) fails if cached and uncached
    attention round at different points.
    """
    scores = jnp.einsum(
        "bqhgd,bshd->bhgqs", q, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale
    mask = (k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]) & (
        k_pos[:, None, None, None, :] >= 0
    )
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqs,bshd->bqhgd", probs, v.astype(jnp.float32))
    return out.astype(v.dtype)


def attention(
    q: jax.Array,        # [B, Sq, H, dh]
    k: jax.Array,        # [B, Sk, Hkv, dh]
    v: jax.Array,        # [B, Sk, Hkv, dh]
    q_pos: jax.Array,    # [B, Sq] int32
    k_pos: jax.Array,    # [B, Sk] int32 (negative = masked/unfilled)
    chunk: int = 1024,
) -> jax.Array:
    """Causal GQA attention, query-chunked to bound live memory."""
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = 1.0 / (dh ** 0.5)
    qg = q.reshape(b, sq, hkv, g, dh)

    if sq <= chunk:
        out = _attend_chunk(qg, k, v, q_pos, k_pos, scale)
        return out.reshape(b, sq, h, dh)

    if sq % chunk:  # pad queries to a whole number of chunks, slice after
        pad = chunk - sq % chunk
        qg = jnp.pad(qg, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)))
        out = attention(
            qg.reshape(b, sq + pad, h, dh), k, v, q_pos, k_pos, chunk
        )
        return out[:, :sq]
    nchunks = sq // chunk
    qc = qg.reshape(b, nchunks, chunk, hkv, g, dh)
    pc = q_pos.reshape(b, nchunks, chunk)

    def body(args):
        qi, pi = args
        return _attend_chunk(qi, k, v, pi, k_pos, scale)

    out = jax.lax.map(
        body,
        (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(pc, 1, 0)),
    )  # [nchunks, B, chunk, hkv, g, dh]
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq, h, dh)
    return out


def _cache_write(buf: jax.Array, val: jax.Array, cache_index, s: int):
    """Write ``val`` [B, S, ...] into ``buf`` [B, T, ...] at time offset
    ``cache_index`` — a scalar (lockstep batch) or a [B] vector (ragged
    batch: row i writes at its own offset). Offsets must be in-range and
    non-negative (the serving engine clamps)."""
    val = val.astype(buf.dtype)
    if getattr(cache_index, "ndim", 0) == 1:
        b = buf.shape[0]
        rows = jnp.arange(b, dtype=jnp.int32)[:, None]
        cols = (
            cache_index.astype(jnp.int32)[:, None]
            + jnp.arange(s, dtype=jnp.int32)[None, :]
        )
        return buf.at[rows, cols].set(val, mode="drop")
    starts = (0, cache_index) + (0,) * (buf.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, val, starts)


# ---------------------------------------------------------------------------
# paged KV cache (vLLM-style block tables over a global page pool)
# ---------------------------------------------------------------------------
#
# Pool layout: each attention layer owns K and V pools [P, page_size,
# Hkv * w] (P pages shared by ALL slots), heads folded into the minor dim:
# w = head_dim for bf16/f32 pools, head_dim // 4 for SAMD-packed uint32
# pools (packed pools add per-(token, head) scale pools [P, page_size,
# Hkv]). The folded minor dim is a whole number of 128-lane tiles at real
# widths, so the step programs keep the pool in XLA's default layout and
# the scatter, the gather and the kernel take it as stored; heads are
# split only in gathered [B, T, Hkv, w] views. A host-managed page table
# [B, n_pp] maps a slot's logical block index to a pool page; -1 marks an
# unallocated block.
# Token at logical position t of slot b lives at pool page
# ``page_table[b, t // page_size]``, offset ``t % page_size``.
#
# Validity is derived, not stored: a gathered key at logical position t is
# valid iff its block is allocated, and causality (k_pos <= q_pos) masks
# allocated-but-not-yet-written offsets — every position <= the row's
# current position has been written either by the CURRENT occupant or, for
# refcount-shared prefix pages, by a DONOR request whose token prefix is
# identical up to that position (same tokens + same positions => same KV,
# so shared reads are indistinguishable from own writes). This holds
# because pages are granted before the write that needs them, a shared
# page is copy-on-write forked before any occupant-specific write lands in
# it, and freed pages re-enter the pool only when their refcount drops to
# zero. No per-token ``pos`` buffer is needed.

def _paged_flat_index(page_table: jax.Array, positions: jax.Array,
                      page_size: int, oob: int) -> jax.Array:
    """Map logical ``positions`` [B, S] to flat pool indices [B, S] through
    ``page_table`` [B, n_pp]. Invalid entries (negative position, block
    beyond the table, unallocated page) map to ``oob`` — an index one past
    the pool end, so ``mode='drop'``/``'fill'`` discards them. (A -1
    sentinel would silently WRAP to the last pool slot: jax .at[] indexing
    normalizes negative indices before applying the OOB mode.)"""
    n_pp = page_table.shape[1]
    pos = positions.astype(jnp.int32)
    block = pos // page_size
    page = jnp.take_along_axis(
        page_table.astype(jnp.int32), jnp.clip(block, 0, n_pp - 1), axis=1
    )
    ok = (pos >= 0) & (block < n_pp) & (page >= 0)
    return jnp.where(ok, page * page_size + pos % page_size, oob)


def _paged_write(pool: jax.Array, val: jax.Array, page_table: jax.Array,
                 positions: jax.Array, page_size: int) -> jax.Array:
    """Scatter ``val`` [B, S, ...] into ``pool`` [P, page_size, ...] at the
    slots named by (page_table, positions) — the paged generalization of
    the ragged ``_cache_write``. Each token's trailing dims are folded to
    the pool's row shape (``[Hkv, w]`` -> ``Hkv * w``). Invalid positions
    are dropped."""
    p = pool.shape[0]
    with jax.named_scope("kv_write"):
        flat = pool.reshape((p * page_size,) + pool.shape[2:])
        idx = _paged_flat_index(page_table, positions, page_size,
                                p * page_size)
        out = flat.at[idx.reshape(-1)].set(
            val.astype(pool.dtype).reshape((-1,) + pool.shape[2:]),
            mode="drop",
        )
        return out.reshape(pool.shape)


def _paged_gather(pool: jax.Array, page_table: jax.Array,
                  page_size: int) -> jax.Array:
    """Gather each row's pages into a contiguous [B, n_pp * page_size, ...]
    view (logical token order). PAGE-granular take — one contiguous block
    copy per page, far cheaper than an elementwise gather. Unallocated
    blocks read an arbitrary (clamped) page: their contents never reach
    attention, because _paged_key_positions marks them -1 and the score
    mask zeroes them (stored values are always finite, so no NaN risk)."""
    b, n_pp = page_table.shape
    safe = jnp.clip(page_table.astype(jnp.int32), 0, pool.shape[0] - 1)
    pages = jnp.take(pool, safe.reshape(-1), axis=0)
    return pages.reshape((b, n_pp * page_size) + pool.shape[2:])


def _paged_key_positions(page_table: jax.Array, page_size: int) -> jax.Array:
    """k_pos [B, n_pp * page_size] for the gathered view: the logical
    position for allocated blocks, -1 (masked) for unallocated ones."""
    b, n_pp = page_table.shape
    length = n_pp * page_size
    iota = jnp.arange(length, dtype=jnp.int32)[None, :]
    valid = jnp.repeat(page_table >= 0, page_size, axis=1)
    return jnp.where(valid, iota, -1)


def _gathered_pool_kv(pool: dict, page_table: jax.Array, page_size: int,
                      n_kv_heads: int, dtype) -> tuple:
    """Dense per-row gather of a KV pool into contiguous
    [B, n_pp * page_size, Hkv, dh] K/V views. Heads are split from the
    folded rows after the gather; SAMD-packed uint32 pools are then
    lane-unpacked and rescaled — the ONE reference view shared by the
    gather decode path and the speculative draft's pool read, so the
    page layout is interpreted in one place."""

    def gather(x):
        g = _paged_gather(x, page_table, page_size)
        return g.reshape(g.shape[:2] + (n_kv_heads, -1))

    if pool["k"].dtype in (jnp.int8, jnp.uint32):
        kg = gather(pool["k"])
        vg = gather(pool["v"])
        ksg = _paged_gather(pool["k_scale"], page_table, page_size)
        vsg = _paged_gather(pool["v_scale"], page_table, page_size)
        k_full = (unpack_int8_lanes(kg).astype(jnp.float32)
                  * ksg[..., None]).astype(dtype)
        v_full = (unpack_int8_lanes(vg).astype(jnp.float32)
                  * vsg[..., None]).astype(dtype)
        return k_full, v_full
    return gather(pool["k"]).astype(dtype), gather(pool["v"]).astype(dtype)


def attention_block(
    p: dict,
    x: jax.Array,            # [B, S, D]
    positions: jax.Array,    # [B, S]
    cfg,
    *,
    kv_cache=None,           # dict(k=[B,T,Hkv,dh], v=..., pos=[B,T]) or None
    cache_index=None,        # cache write offset: scalar, or [B] per-row
    page_table=None,         # [B, n_pp] int32: paged KV (pool-shaped cache)
    page_size: int = 0,
    paged_attn: str = "gather",  # "fused" (Pallas kernel) | "gather" (ref)
    pool_kv=None,            # read-only page pools (speculative draft path)
    pool_bound=None,         # [B] last pool position the draft may read
    chunk: int = 1024,
):
    """Full attention sub-block: norm -> qkv -> rope -> attend -> out.

    Returns (residual_delta, updated_cache_or_None).

    ``cache_index`` may be a per-row vector [B] (ragged decode: every batch
    row sits at its own position); writes then go through one vectorized
    scatter instead of a lockstep dynamic_update_slice, so mixed-position
    serving batches stay inside a single compiled step.

    When ``page_table`` is given, ``kv_cache`` leaves are page pools
    [P, page_size, Hkv * w] (heads folded into the minor dim; see the
    pool-layout comment above ``_paged_flat_index``) instead of per-slot
    rings [B, T, ...]: writes scatter through the table at each token's
    logical position (the ``(page, offset)`` generalization of the ragged
    ``(row, offset)`` writes). ``cache_index`` is ignored — ``positions``
    already names every written token's offset. With ``paged_attn="fused"`` (decode
    only, S == 1) attention runs the Pallas paged-attention kernel
    straight off the pool — no gathered [B, n_pp * page_size] copy;
    ``paged_attn="gather"`` keeps the per-row page gather as the
    reference path (and serves prefill, whose queries span many
    positions); multi-token decode blocks (``paged_attn="fused"``,
    S > 1 — the speculative verify) run the multi-token-query sibling
    kernel. Quantized pools (``kv_bits=8``) are stored SAMD-packed:
    uint32 words of four int8 lanes along head_dim, unpacked lane-wise
    inside the kernel (fused) or after the gather (reference).

    ``pool_kv`` switches to the speculative DRAFT layout: ``kv_cache``
    is then a tick-local bf16 ring that is written here (the draft's
    in-flight proposals), while the paged pool in ``pool_kv`` is READ
    ONLY, truncated to positions <= ``pool_bound`` — the pool may hold a
    previous tick's rejected-draft KV above the window base, which must
    never reach the draft's attention.
    """
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    q = apply_linear(p["wq"], xn)
    k = apply_linear(p["wk"], xn)
    v = apply_linear(p["wv"], xn)
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, hkv, dh)
    v = v.reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    sin, cos = rope_tables(positions, dh, cfg.rope_theta)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)

    new_cache = None
    if pool_kv is not None:
        # speculative DRAFT path: write this token's K/V into the tick-
        # local bf16 ring, attend over (pool pages <= pool_bound) + ring.
        ck = _cache_write(kv_cache["k"], k, cache_index, s)
        cv = _cache_write(kv_cache["v"], v, cache_index, s)
        cpos = _cache_write(
            kv_cache["pos"], positions.astype(jnp.int32), cache_index, s)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        # pool pages <= pool_bound plus the ring, one dense softmax over
        # the per-row page gather, on every backend: an unrolled jnp page
        # loop here (one per layer and draft step) took the TPU compiler
        # over 20 minutes for a 24-layer model at 64 pages per slot
        k_pos_pool = _paged_key_positions(page_table, page_size)
        k_pos_pool = jnp.where(
            k_pos_pool <= pool_bound[:, None], k_pos_pool, -1)
        pool_k, pool_v = _gathered_pool_kv(pool_kv, page_table,
                                           page_size, hkv, q.dtype)
        k_full = jnp.concatenate([pool_k, ck.astype(q.dtype)], axis=1)
        v_full = jnp.concatenate([pool_v, cv.astype(q.dtype)], axis=1)
        k_pos = jnp.concatenate([k_pos_pool, cpos], axis=1)
        att = attention(q, k_full, v_full, positions, k_pos, chunk=chunk)
    elif kv_cache is not None:
        # int8 ring rows, or SAMD-packed uint32 page pools (kv_bits=8)
        quantized_kv = kv_cache["k"].dtype in (jnp.int8, jnp.uint32)

        def _quant(t):
            """int8 cache write: per-(token, kv-head) symmetric scale —
            the paper's packing trick applied to the KV cache."""
            tf = t.astype(jnp.float32)
            amax = jnp.max(jnp.abs(tf), axis=-1)
            scale = jnp.maximum(amax, 1e-6) / 127.0
            qv = jnp.clip(
                jnp.round(tf / scale[..., None]), -127, 127
            ).astype(jnp.int8)
            return qv, scale

        if page_table is not None:
            if quantized_kv:
                kq, ks = _quant(k)
                vq, vs = _quant(v)
                # SAMD-pack the int8 lanes into uint32 words along head_dim
                # BEFORE the scatter: the pool only ever holds packed words
                new_cache = {
                    "k": _paged_write(kv_cache["k"], pack_int8_lanes(kq),
                                      page_table, positions, page_size),
                    "v": _paged_write(kv_cache["v"], pack_int8_lanes(vq),
                                      page_table, positions, page_size),
                    "k_scale": _paged_write(kv_cache["k_scale"], ks,
                                            page_table, positions, page_size),
                    "v_scale": _paged_write(kv_cache["v_scale"], vs,
                                            page_table, positions, page_size),
                }
            else:
                new_cache = {
                    "k": _paged_write(kv_cache["k"], k, page_table,
                                      positions, page_size),
                    "v": _paged_write(kv_cache["v"], v, page_table,
                                      positions, page_size),
                }
            if paged_attn == "fused" and s == 1:
                # decode hot path: attend per page straight off the pool —
                # the [B, n_pp * page_size] gathered copy never exists
                att = kernel_ops.paged_decode_attention(
                    q[:, 0], new_cache["k"], new_cache["v"], page_table,
                    positions[:, 0],
                    k_scale=new_cache.get("k_scale"),
                    v_scale=new_cache.get("v_scale"),
                )[:, None]
            elif paged_attn == "fused":
                # speculative verify: a q-block of S tokens per slot
                # attends causally over the pool through the multi-
                # token-query kernel (per-query positions; -1 = masked)
                att = kernel_ops.paged_verify_attention(
                    q, new_cache["k"], new_cache["v"], page_table,
                    positions,
                    k_scale=new_cache.get("k_scale"),
                    v_scale=new_cache.get("v_scale"),
                )
            else:
                k_pos = _paged_key_positions(page_table, page_size)
                k_full, v_full = _gathered_pool_kv(new_cache, page_table,
                                                   page_size, hkv, q.dtype)
                att = attention(q, k_full, v_full, positions, k_pos,
                                chunk=chunk)
        elif quantized_kv:
            kq, ks = _quant(k)
            vq, vs = _quant(v)
            ck = _cache_write(kv_cache["k"], kq, cache_index, s)
            cv = _cache_write(kv_cache["v"], vq, cache_index, s)
            cks = _cache_write(kv_cache["k_scale"], ks, cache_index, s)
            cvs = _cache_write(kv_cache["v_scale"], vs, cache_index, s)
            cpos = _cache_write(
                kv_cache["pos"], positions.astype(jnp.int32), cache_index, s)
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs,
                         "pos": cpos}
            k_full = (ck.astype(jnp.float32)
                      * cks[..., None]).astype(q.dtype)
            v_full = (cv.astype(jnp.float32)
                      * cvs[..., None]).astype(q.dtype)
            att = attention(q, k_full, v_full, positions, cpos, chunk=chunk)
        else:
            ck = _cache_write(kv_cache["k"], k, cache_index, s)
            cv = _cache_write(kv_cache["v"], v, cache_index, s)
            cpos = _cache_write(
                kv_cache["pos"], positions.astype(jnp.int32), cache_index, s)
            new_cache = {"k": ck, "v": cv, "pos": cpos}
            att = attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                            positions, cpos, chunk=chunk)
    else:
        att = attention(q, k, v, positions, positions, chunk=chunk)

    out = apply_linear(p["wo"], att.reshape(b, s, h * dh))
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_block(p: dict, x: jax.Array, cfg) -> jax.Array:
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    if cfg.activation == "swiglu":
        gate = apply_linear(p["wg"], xn)
        up = apply_linear(p["wu"], xn)
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    elif cfg.activation == "sq_relu":
        up = apply_linear(p["wu"], xn)
        r = jax.nn.relu(up)
        h = r * r
    elif cfg.activation == "gelu":
        up = apply_linear(p["wu"], xn)
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    else:
        raise ValueError(cfg.activation)
    return apply_linear(p["wd"], h)


# ---------------------------------------------------------------------------
# MoE (grouped capacity-based dispatch)
# ---------------------------------------------------------------------------

def moe_capacity(group_tokens: int, n_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    c = int(group_tokens * top_k * capacity_factor / n_experts)
    return max(c, 1)


def moe_block(p: dict, x: jax.Array, cfg, *, group_tokens: int = 2048):
    """Top-k routed experts with per-group capacity (GShard-style).

    x: [B, S, D]. Groups are contiguous token spans of ``group_tokens`` so
    the dispatch one-hots stay small and shard cleanly along batch.
    Returns (out [B,S,D], aux_loss scalar).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    gt = min(group_tokens, s)
    assert s % gt == 0, (s, gt)
    ng = b * (s // gt)
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    xg = xn.reshape(ng, gt, d)

    router_logits = jnp.einsum(
        "gtd,de->gte", xg.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [ng, gt, k]
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=1)                                   # [ng, e]
    ce = jnp.mean(
        jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32), axis=1
    )
    aux = jnp.mean(me * ce) * (e * e)

    cap = moe_capacity(gt, e, k, cfg.capacity_factor)
    # position of each token within its expert, k-slot priority order
    dispatch = jnp.zeros((ng, gt, e, cap), jnp.bfloat16)
    combine = jnp.zeros((ng, gt, e, cap), jnp.float32)
    counts = jnp.zeros((ng, e), jnp.int32)
    for slot in range(k):
        # [ng,gt,e]
        mask = jax.nn.one_hot(gate_idx[..., slot], e, dtype=jnp.int32)
        pos = jnp.cumsum(mask, axis=1) - 1 + counts[:, None, :]
        counts = counts + jnp.sum(mask, axis=1)
        keep = (pos < cap) & (mask > 0)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                                dtype=jnp.bfloat16)[..., :cap]  # [ng,gt,e,cap]
        sel = pos_oh * mask[..., None].astype(jnp.bfloat16)
        dispatch = dispatch + sel
        combine = combine + sel.astype(jnp.float32) * gate_vals[
            ..., slot
        ][..., None, None]

    xin = jnp.einsum("gtec,gtd->gecd", dispatch, xg.astype(jnp.bfloat16))
    h1 = jnp.einsum("gecd,edf->gecf", xin, materialize(p["w_up"]))
    if cfg.activation == "swiglu":
        hg = jnp.einsum("gecd,edf->gecf", xin, materialize(p["w_gate"]))
        h = jax.nn.silu(hg.astype(jnp.float32)).astype(jnp.bfloat16) * h1
    else:
        h = jax.nn.silu(h1.astype(jnp.float32)).astype(jnp.bfloat16)
    y = jnp.einsum("gecf,efd->gecd", h, materialize(p["w_down"]))
    out = jnp.einsum("gtec,gecd->gtd", combine.astype(jnp.bfloat16), y)
    out = out.reshape(b, s, d).astype(x.dtype)

    if cfg.dense_residual:
        out = out + mlp_block(p["dense"], x, cfg)
    return out, aux
