"""Pallas kernels (interpret mode) vs pure-jnp oracles: shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv as cconv
from repro.core import overflow
from repro.kernels import ops, ref
from repro.quant import QuantConfig, pack_weights


@pytest.mark.parametrize("bits", [2, 3, 4, 8])
@pytest.mark.parametrize("shape", [(16, 128, 128), (64, 256, 384),
                                   (8, 512, 256)])
def test_samd_matmul_vs_ref(bits, shape):
    m, k, n = shape
    rng = np.random.default_rng(bits)
    cfg = QuantConfig(bits=bits)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    packed, scale = pack_weights(w, cfg)
    got = ops.samd_matmul(x, packed, scale, k, cfg, interpret=True)
    want = ref.samd_matmul_ref(x, packed, scale, k, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("k", [704, 576, 200])
@pytest.mark.parametrize("bits", [4, 8])
def test_samd_matmul_ragged_k_blocks(bits, k):
    """Regression: K whose packed word count is NOT a multiple of the
    kernel's K-block (e.g. K=704, bits=4 -> 88 words vs block 64) used to
    read undefined out-of-bounds words in the last K-block — NaN in
    interpret mode, silent garbage on TPU. The reduction axis must be
    zero-padded to whole blocks."""
    rng = np.random.default_rng(k + bits)
    cfg = QuantConfig(bits=bits)
    n, m = 96, 4
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    packed, scale = pack_weights(w, cfg)
    got = np.asarray(ops.samd_matmul(x, packed, scale, k, cfg,
                                     interpret=True))
    assert not np.isnan(got).any()
    want = ref.samd_matmul_ref(x, packed, scale, k, cfg)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_samd_matmul_dtypes(dtype):
    rng = np.random.default_rng(0)
    cfg = QuantConfig(bits=4)
    k, n, m = 256, 128, 32
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    packed, scale = pack_weights(w, cfg)
    got = ops.samd_matmul(x, packed, scale, k, cfg, interpret=True)
    want = ref.samd_matmul_ref(x, packed, scale, k, cfg)
    assert got.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-1,
    )


def test_samd_matmul_batched_lead_dims():
    rng = np.random.default_rng(1)
    cfg = QuantConfig(bits=4)
    k, n = 128, 128
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 3, k)), jnp.float32)
    packed, scale = pack_weights(w, cfg)
    got = ops.samd_matmul(x, packed, scale, k, cfg, interpret=True)
    assert got.shape == (2, 3, n)


# ---------------------------------------------------------------------------
# fused paged-attention kernel vs the gather reference
# ---------------------------------------------------------------------------

def _paged_pools(rng, P, ps, hkv, dh, packed):
    """Random pools in either operand layout, heads folded into the minor
    dim as ``init_paged_cache`` stores them: bf16 pages [P, ps, hkv * dh],
    or SAMD-packed uint32 pages [P, ps, hkv * dh // 4] (+ per-(token,
    head) scales [P, ps, hkv])."""
    if not packed:
        kp = jnp.asarray(rng.normal(size=(P, ps, hkv * dh)), jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=(P, ps, hkv * dh)), jnp.bfloat16)
        return kp, vp, None, None
    from repro.quant.packing import pack_int8_lanes

    k8 = rng.integers(-127, 128, size=(P, ps, hkv, dh)).astype(np.int8)
    v8 = rng.integers(-127, 128, size=(P, ps, hkv, dh)).astype(np.int8)
    ks = jnp.asarray(np.abs(rng.normal(size=(P, ps, hkv))) * 0.01 + 1e-4,
                     jnp.float32)
    vs = jnp.asarray(np.abs(rng.normal(size=(P, ps, hkv))) * 0.01 + 1e-4,
                     jnp.float32)
    return (pack_int8_lanes(jnp.asarray(k8)).reshape(P, ps, -1),
            pack_int8_lanes(jnp.asarray(v8)).reshape(P, ps, -1), ks, vs)


@pytest.mark.parametrize("lowering", ["pallas", "xla"])
@pytest.mark.parametrize("packed", [False, True],
                         ids=["bf16", "int8_packed"])
@pytest.mark.parametrize("b", [1, 4])  # B=1 and B=max_batch
def test_paged_attention_fused_vs_gather_ref(packed, b, lowering):
    """The fused kernel must match the gather-then-attend oracle on a
    ragged batch: shuffled page tables, per-row positions, partially
    filled last pages, and fully unallocated tail blocks.

    ``lowering`` covers both backends of ops.paged_decode_attention: the
    Pallas kernel body under the interpreter (interpret=True) and the
    unrolled-jnp lowering CPU serving uses (the default here)."""
    P, ps, hkv, dh, n_pp, g = 16, 8, 2, 16, 4, 2
    rng = np.random.default_rng(b + 10 * packed)
    kp, vp, ks, vs = _paged_pools(rng, P, ps, hkv, dh, packed)
    q = jnp.asarray(rng.normal(size=(b, hkv * g, dh)), jnp.bfloat16)
    # every row gets a distinct allocation pattern: row i holds i+1 blocks
    # of pages drawn without replacement, sits mid-way through its LAST
    # page (partial fill), and leaves the remaining blocks unallocated
    perm = rng.permutation(P)
    pt = np.full((b, n_pp), -1, np.int32)
    pos = np.zeros(b, np.int32)
    take = 0
    for i in range(b):
        nblk = min(i + 1, n_pp)
        pt[i, :nblk] = perm[take:take + nblk]
        take += nblk
        pos[i] = (nblk - 1) * ps + int(rng.integers(0, ps))  # partial last
    got = ops.paged_decode_attention(
        q, kp, vp, jnp.asarray(pt), jnp.asarray(pos),
        k_scale=ks, v_scale=vs,
        interpret=True if lowering == "pallas" else None,
    )
    want = ref.paged_attention_ref(
        q, kp, vp, jnp.asarray(pt), jnp.asarray(pos),
        k_scale=ks, v_scale=vs,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_paged_attention_first_token_single_key():
    """q_pos = 0: exactly one valid key — softmax must collapse to it."""
    P, ps, hkv, dh = 4, 8, 2, 16
    rng = np.random.default_rng(0)
    kp, vp, _, _ = _paged_pools(rng, P, ps, hkv, dh, packed=False)
    q = jnp.asarray(rng.normal(size=(1, hkv, dh)), jnp.bfloat16)
    pt = jnp.asarray([[2, -1]], jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, pt,
                                     jnp.asarray([0], jnp.int32))
    # page 2 offset 0, heads split from the folded row
    want = np.asarray(vp, np.float32)[2, 0].reshape(hkv, dh)
    np.testing.assert_allclose(np.asarray(got[0], np.float32), want,
                               rtol=2e-2, atol=2e-2)


def test_paged_attention_unallocated_row_yields_zeros():
    """A page-table row of all -1 (inactive slot) must produce zeros, not
    an average of arbitrary pool contents."""
    P, ps, hkv, dh = 4, 8, 2, 16
    rng = np.random.default_rng(1)
    kp, vp, _, _ = _paged_pools(rng, P, ps, hkv, dh, packed=False)
    q = jnp.asarray(rng.normal(size=(2, hkv, dh)), jnp.bfloat16)
    pt = jnp.asarray([[1, 3], [-1, -1]], jnp.int32)
    got = np.asarray(ops.paged_decode_attention(
        q, kp, vp, pt, jnp.asarray([9, 9], jnp.int32)), np.float32)
    assert np.all(got[1] == 0.0)
    assert np.any(got[0] != 0.0)


@pytest.mark.parametrize("block_kv_heads", [1, 2])
def test_paged_attention_kv_head_blocking(block_kv_heads):
    """Grid over kv-head blocks: any block size must give the same answer
    as the oracle (one program per (slot, head-block))."""
    P, ps, hkv, dh, n_pp = 8, 4, 4, 8, 3
    rng = np.random.default_rng(block_kv_heads)
    kp = jnp.asarray(rng.normal(size=(P, ps, hkv * dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, ps, hkv * dh)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, hkv, dh)), jnp.float32)
    pt = jnp.asarray([[0, 5, 2], [7, -1, -1]], jnp.int32)
    pos = jnp.asarray([10, 3], jnp.int32)
    got = ops.paged_decode_attention(q, kp, vp, pt, pos,
                                     block_kv_heads=block_kv_heads,
                                     interpret=True)
    want = ref.paged_attention_ref(q, kp, vp, pt, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lowering", ["pallas", "xla"])
@pytest.mark.parametrize("packed", [False, True],
                         ids=["bf16", "int8_packed"])
def test_paged_attention_folded_pool_matches_unfolded_kv(packed, lowering):
    """The folded pool [P, ps, hkv * w] is only a storage order: decode
    attention over it must equal plain softmax attention computed in
    numpy from the same K/V held as [P, ps, hkv, dh] (grouped queries,
    g = 2, and a kv-head count whose lanes do not fill a tile)."""
    from repro.quant.packing import pack_int8_lanes

    P, ps, hkv, dh, g = 6, 8, 3, 16, 2
    rng = np.random.default_rng(31 + packed)
    if packed:
        k8 = rng.integers(-127, 128, size=(P, ps, hkv, dh))
        v8 = rng.integers(-127, 128, size=(P, ps, hkv, dh))
        ks = rng.uniform(0.002, 0.02, size=(P, ps, hkv))
        vs = rng.uniform(0.002, 0.02, size=(P, ps, hkv))
        k4, v4 = k8 * ks[..., None], v8 * vs[..., None]

        def fold(x8):
            words = pack_int8_lanes(jnp.asarray(x8, jnp.int8))
            return words.reshape(P, ps, hkv * dh // 4)

        kp, vp = fold(k8), fold(v8)
        ks, vs = jnp.asarray(ks, jnp.float32), jnp.asarray(vs, jnp.float32)
    else:
        k4 = rng.normal(size=(P, ps, hkv, dh)).astype(np.float32)
        v4 = rng.normal(size=(P, ps, hkv, dh)).astype(np.float32)
        kp = jnp.asarray(k4.reshape(P, ps, hkv * dh))
        vp = jnp.asarray(v4.reshape(P, ps, hkv * dh))
        ks = vs = None
    q = rng.normal(size=(2, hkv * g, dh)).astype(np.float32)
    table = np.asarray([[4, 1, 3], [2, 5, -1]], np.int32)
    pos = np.asarray([19, 10], np.int32)
    got = np.asarray(ops.paged_decode_attention(
        jnp.asarray(q), kp, vp, jnp.asarray(table), jnp.asarray(pos),
        k_scale=ks, v_scale=vs,
        interpret=True if lowering == "pallas" else None,
    ), np.float32)
    for i in range(2):
        keys = np.concatenate([k4[p] for p in table[i] if p >= 0])
        vals = np.concatenate([v4[p] for p in table[i] if p >= 0])
        keys, vals = keys[:pos[i] + 1], vals[:pos[i] + 1]  # [T, hkv, dh]
        for h in range(hkv * g):
            s = keys[:, h // g] @ q[i, h] / np.sqrt(dh)
            w = np.exp(s - s.max())
            want = (w / w.sum()) @ vals[:, h // g]
            np.testing.assert_allclose(got[i, h], want, rtol=1e-4,
                                       atol=1e-4)


# ---------------------------------------------------------------------------
# multi-token-query paged attention (speculative verify block)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("lowering", ["pallas", "xla"])
@pytest.mark.parametrize("packed", [False, True],
                         ids=["bf16", "int8_packed"])
def test_paged_verify_attention_matches_per_token_decode(packed, lowering):
    """The q-block kernel must equal S independent single-token decode
    calls at the same positions — the property speculative verify relies
    on for greedy token-identity. Rows past a slot's draft budget carry
    position -1 and must come back all-zero."""
    P, ps, hkv, dh, n_pp, g, b, s = 12, 8, 2, 16, 3, 2, 3, 3
    rng = np.random.default_rng(17 + packed)
    kp, vp, ks, vs = _paged_pools(rng, P, ps, hkv, dh, packed)
    q = jnp.asarray(rng.normal(size=(b, s, hkv * g, dh)), jnp.bfloat16)
    perm = rng.permutation(P)
    pt = np.full((b, n_pp), -1, np.int32)
    pos = np.full((b, s), -1, np.int32)
    take = 0
    for i in range(b):
        nblk = min(i + 1, n_pp)
        pt[i, :nblk] = perm[take:take + nblk]
        take += nblk
        base = (nblk - 1) * ps + int(rng.integers(0, ps - s))
        budget = int(rng.integers(0, s))  # some queries masked per row
        for j in range(budget + 1):
            pos[i, j] = base + j
    got = np.asarray(ops.paged_verify_attention(
        q, kp, vp, jnp.asarray(pt), jnp.asarray(pos),
        k_scale=ks, v_scale=vs,
        interpret=True if lowering == "pallas" else None,
    ), np.float32)
    for j in range(s):
        want = np.asarray(ops.paged_decode_attention(
            q[:, j], kp, vp, jnp.asarray(pt), jnp.asarray(pos[:, j]),
            k_scale=ks, v_scale=vs,
        ), np.float32)
        for i in range(b):
            if pos[i, j] >= 0:
                np.testing.assert_allclose(got[i, j], want[i],
                                           rtol=2e-2, atol=2e-2)
            else:
                assert np.all(got[i, j] == 0.0), (i, j)


@pytest.mark.parametrize("bits", [2, 3, 4])
@pytest.mark.parametrize("signed", [False, True])
@pytest.mark.parametrize("n", [50, 333, 1024])
def test_samd_conv_kernel_vs_ref(bits, signed, n):
    rng = np.random.default_rng(n + bits)
    plan = cconv.make_plan(bits, 3, signed)
    lo, hi = overflow.input_range(bits, signed)
    x = jnp.asarray(rng.integers(lo, hi + 1, size=n), jnp.int32)
    k = jnp.asarray(rng.integers(lo, hi + 1, size=3), jnp.int32)
    got = ops.samd_conv1d(x, k, plan, interpret=True)
    want = np.convolve(np.asarray(x), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_samd_conv_chunks_against_core_ref():
    """Kernel-internal chunk products match the numpy-validated core path."""
    rng = np.random.default_rng(9)
    plan = cconv.make_plan(3, 3, True)
    x = jnp.asarray(rng.integers(-4, 4, size=120), jnp.int32)
    k = jnp.asarray(rng.integers(-4, 4, size=3), jnp.int32)
    xw = cconv.pack_conv_operand(x, plan)
    kw = cconv.pack_conv_kernel(k, plan)
    from repro.kernels.samd_conv import samd_conv_chunks

    got = samd_conv_chunks(xw, kw, plan, interpret=True)
    want = ref.samd_conv_chunks_ref(xw, kw, plan)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
