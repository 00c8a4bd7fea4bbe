"""Public jit'd wrappers for the Pallas kernels.

``interpret`` defaults to True on CPU backends (this container) so the same
call sites run the kernel bodies in Python for validation, and compile to
Mosaic on a real TPU.

Every packed-weight entry point takes ``verify=True``: the lane-safety
checker (:mod:`repro.analysis`) runs over the *static* configuration at
trace time — pure Python on hashable args, zero runtime ops, cached per
(cfg, K, signedness) — and raises ``LaneSafetyError`` before an unsafe
config can lower. Under ``jax.jit`` this costs once per trace cache
entry and nothing per call.
"""
from __future__ import annotations

import functools

import jax

from repro.analysis import (
    assert_safe,
    check_conv_plan,
    check_conv2d_config,
    check_matmul_config,
)
from repro.core.conv import (
    ConvPlan,
    overlap_add,
    pack_conv_kernel,
    pack_conv_operand,
)
from repro.quant.config import QuantConfig
from repro.kernels import paged_attention as _pa
from repro.kernels import samd_conv as _conv
from repro.kernels import samd_matmul as _mm

# 'auto' picks per jax.default_backend(): Mosaic on TPU, the unrolled-jnp
# XLA lowering elsewhere. 'interpret' forces the Pallas interpreter.
KNOWN_BACKENDS = ("auto", "xla", "pallas", "interpret")


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _resolve_backend(backend: str | None) -> str:
    if backend is None:
        backend = "auto"
    if backend not in KNOWN_BACKENDS:
        raise ValueError(
            f"unknown kernel backend {backend!r}; known backends: "
            f"{', '.join(KNOWN_BACKENDS)}"
        )
    return backend


@functools.lru_cache(maxsize=None)
def _verify_matmul(cfg: QuantConfig, k: int, signed: bool) -> None:
    assert_safe(check_matmul_config(cfg, k, signed=signed))


@functools.lru_cache(maxsize=None)
def _verify_conv2d(
    cfg: QuantConfig, kh: int, kw: int, c_in: int, signed: bool
) -> None:
    assert_safe(check_conv2d_config(cfg, kh, kw, c_in, signed=signed))


@functools.lru_cache(maxsize=None)
def _verify_plan(plan: ConvPlan) -> None:
    assert_safe(check_conv_plan(plan))


def _pick_backend(backend: str | None, interpret: bool | None) -> str:
    """Resolve the dispatch target. An explicit ``backend=`` wins; the
    legacy ``interpret=`` flag keeps its PR 3 meaning; 'auto' follows
    ``jax.default_backend()``. Unknown strings raise (never fall through
    to a default lowering)."""
    if backend is not None:
        be = _resolve_backend(backend)
    elif interpret is not None:
        be = "interpret" if interpret else "pallas"
    else:
        be = "auto"
    if be == "auto":
        be = "xla" if _default_interpret() else "pallas"
    return be


def samd_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array, k: int,
                cfg: QuantConfig, *, block_m: int = 128, block_n: int = 256,
                block_kw: int = 128, signed: bool = True,
                interpret: bool | None = None,
                backend: str | None = None,
                verify: bool = True) -> jax.Array:
    """Packed-weight matmul: x[..., K] @ dequant(packed)[K, N].

    Backend dispatch (the PR 3 pattern): TPU compiles the Pallas kernel
    to Mosaic; the CPU default is ``samd_matmul_xla`` — the unrolled-jnp
    lowering of the same K-block loop (the serving draft path and the
    benchmarks run this); ``interpret=True`` (or ``backend='interpret'``)
    forces the Pallas interpreter (test-only coverage of the kernel
    body). ``verify=True`` runs the lane-safety checker on the static
    (cfg, K, signed) tuple at trace time and raises ``LaneSafetyError``
    on unsafe configs.
    """
    if verify:
        _verify_matmul(cfg, int(k), bool(signed))
    be = _pick_backend(backend, interpret)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if be == "xla":
        out = _mm.samd_matmul_xla(
            x2, packed, scale, k, cfg, block_kw=block_kw, signed=signed,
        )
    else:
        out = _mm.samd_matmul(
            x2, packed, scale, k, cfg,
            block_m=block_m, block_n=block_n, block_kw=block_kw,
            signed=signed, interpret=(be == "interpret"),
        )
    return out.reshape(lead + (out.shape[-1],))


def samd_conv2d(x: jax.Array, packed: jax.Array, scale: jax.Array,
                cfg: QuantConfig, *, padding: int = 1, block_cw: int = 64,
                block_n: int = 256, signed: bool = True,
                interpret: bool | None = None,
                backend: str | None = None,
                verify: bool = True) -> jax.Array:
    """Blocked 2D conv over SAMD-packed weights (fused im2col).

    x [C_in, H, W] x packed [KH, KW, ceil(C_in/vpw), C_out] ->
    [OH, OW, C_out]. Dispatch mirrors ``samd_matmul``: TPU -> Mosaic
    kernel, CPU default -> unrolled-jnp lowering of the same blocked
    loop, ``interpret=True`` -> Pallas interpreter (tests).
    ``verify=True`` checks the static (cfg, KH*KW*C_in, signed) tuple at
    trace time.
    """
    if verify:
        kh, kw_, c_in = packed.shape[0], packed.shape[1], x.shape[0]
        _verify_conv2d(cfg, int(kh), int(kw_), int(c_in), bool(signed))
    be = _pick_backend(backend, interpret)
    if be == "xla":
        return _conv.samd_conv2d_xla(
            x, packed, scale, cfg, padding=padding,
            block_cw=max(block_cw, 128), signed=signed,
        )
    return _conv.samd_conv2d(
        x, packed, scale, cfg, padding=padding, block_cw=block_cw,
        block_n=block_n, signed=signed, interpret=(be == "interpret"),
    )


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           q_pos: jax.Array, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           block_kv_heads: int | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Fused decode attention over the paged KV pool (no gathered copy).

    q [B, H, dh] -> [B, H, dh]. Pools are folded [P, page_size, Hkv * w]
    bf16/f32 pages (w = dh), or SAMD-packed uint32 pages (w = dh // 4,
    + per-(token, head) scales) unpacked inside the kernel.

    Backend dispatch differs from the other kernels here: on TPU the
    Pallas kernel compiles to Mosaic, but on CPU the default is the
    unrolled-jnp lowering of the same page-loop algorithm rather than
    the Pallas interpreter — the interpreter walks the (slot, page) grid
    sequentially, which costs more than the gather this kernel replaces,
    while the unrolled lowering vectorizes across slots. Pass
    ``interpret=True`` to force the Pallas interpreter (the CI
    equivalence tests do, so the kernel body itself stays covered).
    """
    if interpret is None:
        if _default_interpret():
            return _pa.paged_decode_attention_xla(
                q, k_pages, v_pages, page_table, q_pos,
                k_scale=k_scale, v_scale=v_scale,
            )
        interpret = False
    return _pa.paged_decode_attention(
        q, k_pages, v_pages, page_table, q_pos,
        k_scale=k_scale, v_scale=v_scale, block_kv_heads=block_kv_heads,
        interpret=interpret,
    )


def paged_verify_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           q_pos: jax.Array, *,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           block_kv_heads: int | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """Multi-token-query paged attention (speculative verify block).

    q [B, S, H, dh] with per-query positions q_pos [B, S] -> [B, S, H,
    dh]. One grid step folds a whole pool page into all S query rows of
    a slot, amortizing the page DMA/grid overhead across the verify
    block. Backend dispatch mirrors ``paged_decode_attention``: TPU ->
    Mosaic q-block kernel, CPU default -> unrolled-jnp lowering of the
    same loop, ``interpret=True`` -> Pallas interpreter (CI coverage of
    the kernel body).
    """
    if interpret is None:
        if _default_interpret():
            return _pa.paged_verify_attention_xla(
                q, k_pages, v_pages, page_table, q_pos,
                k_scale=k_scale, v_scale=v_scale,
            )
        interpret = False
    return _pa.paged_verify_attention(
        q, k_pages, v_pages, page_table, q_pos,
        k_scale=k_scale, v_scale=v_scale, block_kv_heads=block_kv_heads,
        interpret=interpret,
    )


def samd_conv1d(x: jax.Array, kernel: jax.Array, plan: ConvPlan,
                *, interpret: bool | None = None,
                verify: bool = True) -> jax.Array:
    """Full 1D integer convolution via the Pallas conv-as-multiply kernel.

    x: [n] int, kernel: [taps] int -> [n + taps - 1] int32. This is the
    true packed-domain pipeline, so ``verify=True`` runs the full lane
    program (pack -> sign-extend -> multiply -> borrow-fixup -> wide
    read) over ``plan.fmt``.
    """
    if verify:
        _verify_plan(plan)
    if interpret is None:
        interpret = _default_interpret()
    n = x.shape[-1]
    xw = pack_conv_operand(x, plan)
    kw = pack_conv_kernel(kernel, plan)
    ext = _conv.samd_conv_chunks(xw, kw, plan, interpret=interpret)
    return overlap_add(ext, plan, n + plan.taps - 1)
