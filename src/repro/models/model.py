"""Composable decoder assembly: template -> init -> forward/prefill/decode.

One code path serves all four families ('dense', 'moe', 'rwkv6',
'hybrid_mamba2'); the per-layer block kind is derived from the ArchConfig.
Parameters are plain nested dicts whose leaves are declared once as
TensorSpecs (see spec.py), so sharding specs and SAMD quantization are
derived from the same source of truth.
"""
from __future__ import annotations

import contextvars
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.spec import TensorSpec

# Optional activation-sharding hint (sequence parallelism): when set to a
# PartitionSpec for the [B, S, D] residual stream, it is applied between
# blocks with with_sharding_constraint. Megatron-SP style: sharding S on
# 'model' turns the per-block activation all-reduces into
# reduce-scatter/all-gather pairs (half the bytes, 1/model_size residents).
_ACT_SHARDING: contextvars.ContextVar = contextvars.ContextVar(
    "repro_act_sharding", default=None
)


def set_activation_sharding(pspec) -> None:
    _ACT_SHARDING.set(pspec)


def _constrain(x: jax.Array) -> jax.Array:
    ps = _ACT_SHARDING.get()
    if ps is not None:
        x = jax.lax.with_sharding_constraint(x, ps)
    return x


# ---------------------------------------------------------------------------
# templates
# ---------------------------------------------------------------------------

def _attn_template(cfg: ArchConfig) -> dict:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    t = {
        "ln": TensorSpec((d,), (None,), init="ones"),
        "wq": TensorSpec((d, h * dh), ("embed", "heads"), quant_axis=0),
        "wk": TensorSpec((d, hkv * dh), ("embed", "kv_heads"), quant_axis=0),
        "wv": TensorSpec((d, hkv * dh), ("embed", "kv_heads"), quant_axis=0),
        "wo": TensorSpec((h * dh, d), ("heads", "embed"), quant_axis=0),
    }
    if cfg.qkv_bias:
        t["bq"] = TensorSpec((h * dh,), ("heads",), init="zeros")
        t["bk"] = TensorSpec((hkv * dh,), ("kv_heads",), init="zeros")
        t["bv"] = TensorSpec((hkv * dh,), ("kv_heads",), init="zeros")
    if cfg.qk_norm:
        t["q_norm"] = TensorSpec((dh,), (None,), init="ones")
        t["k_norm"] = TensorSpec((dh,), (None,), init="ones")
    return t


def _mlp_template(cfg: ArchConfig, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    t = {
        "ln": TensorSpec((d,), (None,), init="ones"),
        "wu": TensorSpec((d, f), ("embed", "ff"), quant_axis=0),
        "wd": TensorSpec((f, d), ("ff", "embed"), quant_axis=0),
    }
    if cfg.activation == "swiglu":
        t["wg"] = TensorSpec((d, f), ("embed", "ff"), quant_axis=0)
    return t


def _moe_template(cfg: ArchConfig) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    t = {
        "ln": TensorSpec((d,), (None,), init="ones"),
        "router": TensorSpec((d, e), ("embed", None), dtype=jnp.float32),
        "w_up": TensorSpec((e, d, f), ("experts", "embed", "ff"),
                           quant_axis=1),
        "w_down": TensorSpec((e, f, d), ("experts", "ff", "embed"),
                             quant_axis=1),
    }
    if cfg.activation == "swiglu":
        t["w_gate"] = TensorSpec((e, d, f), ("experts", "embed", "ff"),
                                 quant_axis=1)
    if cfg.dense_residual:
        t["dense"] = _mlp_template(cfg, cfg.expert_d_ff)
    return t


def _mamba2_template(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    d_inner, n_heads, conv_dim = S.mamba2_dims(cfg)
    n = cfg.ssm_state
    return {
        "ln": TensorSpec((d,), (None,), init="ones"),
        "in_proj": TensorSpec(
            (d, 2 * d_inner + 2 * n + n_heads), ("embed", "ssm_inner"),
            quant_axis=0,
        ),
        "conv_w": TensorSpec((conv_dim, cfg.ssm_conv), ("ssm_inner", None)),
        "dt_bias": TensorSpec((n_heads,), (None,), init="zeros"),
        "a_log": TensorSpec((n_heads,), (None,), init="decay"),
        "d_skip": TensorSpec((n_heads,), (None,), init="ones"),
        "out_norm": TensorSpec((d_inner,), ("ssm_inner",), init="ones"),
        "out_proj": TensorSpec((d_inner, d), ("ssm_inner", "embed"),
                               quant_axis=0),
    }


def _rwkv6_template(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    h, hd = S.rwkv6_dims(cfg)
    r = cfg.lora_rank
    tm = {
        "ln": TensorSpec((d,), (None,), init="ones"),
        "w0": TensorSpec((d,), (None,), init="decay"),
        "u_bonus": TensorSpec((h, hd), (None, None), init="zeros"),
        "gn": TensorSpec((hd,), (None,), init="ones"),
        "wr": TensorSpec((d, d), ("embed", "rwkv_att"), quant_axis=0),
        "wk": TensorSpec((d, d), ("embed", "rwkv_att"), quant_axis=0),
        "wv": TensorSpec((d, d), ("embed", "rwkv_att"), quant_axis=0),
        "wg": TensorSpec((d, d), ("embed", "rwkv_att"), quant_axis=0),
        "wo": TensorSpec((d, d), ("rwkv_att", "embed"), quant_axis=0),
        "w_lora_a": TensorSpec((d, r), ("embed", None)),
        "w_lora_b": TensorSpec((r, d), (None, "rwkv_att")),
    }
    for nm in ("r", "k", "v", "w", "g"):
        tm[f"mu_{nm}"] = TensorSpec((d,), (None,), init="zeros")
        tm[f"lora_{nm}_a"] = TensorSpec((d, r // 2), ("embed", None))
        tm[f"lora_{nm}_b"] = TensorSpec((r // 2, d), (None, "rwkv_att"))
    cm = {
        "ln": TensorSpec((d,), (None,), init="ones"),
        "mu_ck": TensorSpec((d,), (None,), init="zeros"),
        "mu_cr": TensorSpec((d,), (None,), init="zeros"),
        "wk_c": TensorSpec((d, cfg.d_ff), ("embed", "ff"), quant_axis=0),
        "wv_c": TensorSpec((cfg.d_ff, d), ("ff", "embed"), quant_axis=0),
        "wr_c": TensorSpec((d, d), ("embed", "rwkv_att"), quant_axis=0),
    }
    return {"tm": tm, "cm": cm}


def _layer_template(cfg: ArchConfig) -> dict:
    if cfg.family == "dense":
        return {"attn": _attn_template(cfg), "mlp": _mlp_template(cfg)}
    if cfg.family == "moe":
        return {"attn": _attn_template(cfg), "moe": _moe_template(cfg)}
    if cfg.family == "rwkv6":
        return _rwkv6_template(cfg)
    if cfg.family == "hybrid_mamba2":
        return {"m": _mamba2_template(cfg)}
    raise ValueError(cfg.family)


def _stack_spec(sp: TensorSpec, n: int) -> TensorSpec:
    return TensorSpec(
        (n,) + sp.shape, (None,) + sp.axes, sp.dtype, sp.init,
        sp.init_scale,
        None if sp.quant_axis is None else sp.quant_axis + 1,
    )


def build_template(cfg: ArchConfig, stacked: bool | None = None) -> dict:
    """Parameter template. ``stacked`` (default: cfg.scan_layers) makes
    ``blocks`` a single pytree whose leaves carry a leading layer dim, for
    the scan-over-layers forward path."""
    if stacked is None:
        stacked = cfg.scan_layers
    d, v = cfg.d_model, cfg.vocab
    t: dict = {
        "embed": TensorSpec((v, d), ("vocab", "embed"), init_scale=0.01),
        "final_ln": TensorSpec((d,), (None,), init="ones"),
    }
    if not cfg.tie_embeddings:
        t["lm_head"] = TensorSpec((d, v), ("embed", "vocab"), quant_axis=0)

    layer = _layer_template(cfg)
    if stacked:
        t["blocks"] = jax.tree.map(
            lambda sp: _stack_spec(sp, cfg.n_layers), layer,
            is_leaf=lambda x: isinstance(x, TensorSpec),
        )
    else:
        t["blocks"] = [
            jax.tree.map(lambda sp: sp, layer,
                         is_leaf=lambda x: isinstance(x, TensorSpec))
            for _ in range(cfg.n_layers)
        ]
    if cfg.family == "hybrid_mamba2":
        t["shared_attn"] = _attn_template(cfg)
        t["shared_mlp"] = _mlp_template(cfg)
    return t


def stack_blocks(params_list_blocks):
    """[per-layer dict, ...] -> stacked dict (checkpoint layout converter)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list_blocks)


def unstack_blocks(stacked, n_layers: int):
    return [
        jax.tree.map(lambda x: x[i], stacked) for i in range(n_layers)
    ]


# ---------------------------------------------------------------------------
# caches / recurrent state
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, stacked: bool = False,
               kv_bits: Optional[int] = None) -> dict:
    """Decode-time state for every layer. For attention layers this is a
    KV ring buffer; for SSM/RWKV layers the O(1) recurrent state.

    ``stacked=True`` (uniform families only) returns one tree whose leaves
    carry a leading layer dim — the layout the scan-over-layers prefill
    path emits. ``kv_bits=8`` stores the KV cache int8 with per-(token,
    head) scales (beyond-paper memory-term optimization).
    """

    def kv(b):
        if kv_bits == 8:
            return {
                "k": jnp.zeros(
                    (b, max_len, cfg.n_kv_heads, cfg.head_dim), jnp.int8),
                "v": jnp.zeros(
                    (b, max_len, cfg.n_kv_heads, cfg.head_dim), jnp.int8),
                "k_scale": jnp.zeros(
                    (b, max_len, cfg.n_kv_heads), jnp.float32),
                "v_scale": jnp.zeros(
                    (b, max_len, cfg.n_kv_heads), jnp.float32),
                "pos": jnp.full((b, max_len), -1, jnp.int32),
            }
        return {
            "k": jnp.zeros((b, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((b, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
            "pos": jnp.full((b, max_len), -1, jnp.int32),
        }

    if stacked:
        if cfg.family in ("dense", "moe"):
            one = kv(batch)
        elif cfg.family == "rwkv6":
            from repro.models import ssm as _ssm

            h, hd = _ssm.rwkv6_dims(cfg)
            one = {
                "wkv": jnp.zeros((batch, h, hd, hd), jnp.float32),
                "shift_tm": jnp.zeros((batch, cfg.d_model), jnp.float32),
                "shift_cm": jnp.zeros((batch, cfg.d_model), jnp.float32),
            }
        else:
            raise ValueError(
                f"stacked cache unsupported for family {cfg.family}"
            )
        return {
            "layers_stacked": jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x, (cfg.n_layers,) + x.shape
                ).copy() if x.dtype != jnp.int32 else jnp.tile(
                    x[None], (cfg.n_layers,) + (1,) * x.ndim
                ),
                one,
            )
        }

    cache: dict = {"layers": []}
    if cfg.family in ("dense", "moe"):
        cache["layers"] = [kv(batch) for _ in range(cfg.n_layers)]
    elif cfg.family == "rwkv6":
        h, hd = S.rwkv6_dims(cfg)
        cache["layers"] = [
            {
                "wkv": jnp.zeros((batch, h, hd, hd), jnp.float32),
                "shift_tm": jnp.zeros((batch, cfg.d_model), jnp.float32),
                "shift_cm": jnp.zeros((batch, cfg.d_model), jnp.float32),
            }
            for _ in range(cfg.n_layers)
        ]
    elif cfg.family == "hybrid_mamba2":
        d_inner, n_heads, conv_dim = S.mamba2_dims(cfg)
        for i in range(cfg.n_layers):
            st = {
                "conv": jnp.zeros((batch, conv_dim, cfg.ssm_conv - 1), dtype),
                "ssd": jnp.zeros(
                    (batch, n_heads, cfg.ssm_head_dim, cfg.ssm_state),
                    jnp.float32,
                ),
            }
            if cfg.attn_every and (i + 1) % cfg.attn_every == 0:
                st["attn_kv"] = kv(batch)
            cache["layers"].append(st)
    return cache


def init_paged_cache(cfg: ArchConfig, num_pages: int, page_size: int,
                     dtype=jnp.bfloat16, stacked: bool = False,
                     kv_bits: Optional[int] = None) -> dict:
    """Decode-time KV state as a global page pool (vLLM-style paging).

    Every attention layer owns ``num_pages`` pages of ``page_size`` tokens;
    which slot owns which page is a host-side page table passed to
    ``forward`` per call, NOT part of this pytree — long and short requests
    share the pool, so resident KV memory is ``num_pages * page_size``
    tokens per layer instead of ``max_batch * max_len``. Attention families
    only (recurrent state is O(1) per slot — nothing to page). No per-token
    ``pos`` buffer: key validity is derived from the page table plus
    causality (see layers._paged_key_positions).

    K and V pools are ``[num_pages, page_size, n_kv_heads * w]``: heads
    folded into the minor dim, ``w`` = head_dim for ``dtype`` pools and
    head_dim // 4 for SAMD-packed uint32 pools (``kv_bits=8``), whose
    per-(token, head) scale pools are ``[num_pages, page_size,
    n_kv_heads]``. At real widths the minor dim is a whole number of
    128-lane tiles, so XLA stores the pool unpadded in its default layout
    and the step programs never convert it (see layers' pool-layout
    comment). ``stacked`` adds a leading layer axis.
    """
    if cfg.family not in ("dense", "moe"):
        raise ValueError(
            f"paged KV cache needs an attention family, got {cfg.family}"
        )

    def kv_pool():
        heads = (num_pages, page_size, cfg.n_kv_heads)
        if kv_bits == 8:
            # SAMD-packed int8 pages: uint32 words of four 8-bit lanes
            # along head_dim (same bytes as int8, but the paged-attention
            # kernel reads whole words and unpacks lanes on the VPU)
            assert cfg.head_dim % 4 == 0, cfg.head_dim
            packed = heads[:2] + (cfg.n_kv_heads * cfg.head_dim // 4,)
            return {
                "k": jnp.zeros(packed, jnp.uint32),
                "v": jnp.zeros(packed, jnp.uint32),
                "k_scale": jnp.zeros(heads, jnp.float32),
                "v_scale": jnp.zeros(heads, jnp.float32),
            }
        shape = heads[:2] + (cfg.n_kv_heads * cfg.head_dim,)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    if stacked:
        one = kv_pool()
        return {
            "layers_stacked": jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x, (cfg.n_layers,) + x.shape
                ).copy(),
                one,
            )
        }
    return {"layers": [kv_pool() for _ in range(cfg.n_layers)]}


def copy_paged_page(cache: dict, src, dst) -> dict:
    """Device-side copy of pool page ``src`` into page ``dst`` across every
    layer's KV pools (k/v and, for packed int8 pools, the scale pages).

    This is the copy-on-write fork primitive for prefix sharing: when a
    request maps a donor's partially-relevant page and must write into it
    (the prefill/decode cursor lands inside the block), the engine forks
    the page with one fused device op instead of re-prefilling the
    block's tokens through every layer. ``src``/``dst`` may be traced
    scalars, so a single jit of this function serves every fork.

    Unrolled ``{'layers': [...]}`` pools only: a stacked pool's leading
    axis is LAYERS, so indexing it by page id would overwrite a whole
    layer's pool instead of forking one page.
    """
    if "layers_stacked" in cache:
        raise ValueError(
            "copy_paged_page needs the unrolled {'layers': [...]} cache "
            "layout; a stacked pool's leading axis is layers, not pages"
        )
    return jax.tree.map(lambda pool: pool.at[dst].set(pool[src]), cache)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _scan_blocks(params, x, positions, cfg, remat, cache=None,
                 cache_index=0, page_table=None, page_size=0,
                 paged_attn="gather"):
    """lax.scan over stacked layer params (compile time O(1) in depth).

    remat='block' composes naturally: jax.checkpoint wraps the scan body,
    so backward recomputes one layer at a time — peak activation memory is
    one layer's activations plus the per-layer residual stream.

    When ``cache`` carries 'layers_stacked' (prefill), the per-layer cache
    rides the scan xs/ys: layer i consumes slice i and emits the filled
    slice — the whole prefill is one scan regardless of depth.
    """
    blocks = params["blocks"]
    aux0 = jnp.zeros((), jnp.float32)
    stacked_cache = cache["layers_stacked"] if cache is not None else None

    if cfg.family == "dense":
        def body(xc, inp):
            p, kv_c = inp
            delta, new_kv = L.attention_block(
                p["attn"], xc, positions, cfg,
                kv_cache=kv_c, cache_index=cache_index,
                page_table=page_table, page_size=page_size,
                paged_attn=paged_attn, chunk=cfg.attn_chunk,
            )
            xc = xc + delta
            return _constrain(xc + L.mlp_block(p["mlp"], xc, cfg)), new_kv

        body = jax.checkpoint(body) if remat else body
        x, new_kvs = jax.lax.scan(body, x, (blocks, stacked_cache))
        return x, aux0, new_kvs

    if cfg.family == "moe":
        def body(carry, inp):
            p, kv_c = inp
            xc, aux = carry
            delta, new_kv = L.attention_block(
                p["attn"], xc, positions, cfg,
                kv_cache=kv_c, cache_index=cache_index,
                page_table=page_table, page_size=page_size,
                paged_attn=paged_attn, chunk=cfg.attn_chunk,
            )
            xc = xc + delta
            mo, a = L.moe_block(p["moe"], xc, cfg,
                                group_tokens=cfg.moe_group_tokens)
            return (_constrain(xc + mo), aux + a), new_kv

        body = jax.checkpoint(body) if remat else body
        (x, aux), new_kvs = jax.lax.scan(body, (x, aux0),
                                         (blocks, stacked_cache))
        return x, aux, new_kvs

    if cfg.family == "rwkv6":
        def body(xc, inp):
            p, st = inp
            delta, st_tm = S.rwkv6_time_mix(p["tm"], xc, cfg, st)
            xc = xc + delta
            delta, st_cm = S.rwkv6_channel_mix(p["cm"], xc, cfg, st)
            return _constrain(xc + delta), {**st_tm, **st_cm}

        body = jax.checkpoint(body) if remat else body
        x, new_states = jax.lax.scan(body, x, (blocks, stacked_cache))
        return x, aux0, new_states

    if cfg.family == "hybrid_mamba2":
        assert stacked_cache is None, (
            "hybrid prefill uses the unrolled layout (shared-attn caches "
            "exist only every attn_every layers)"
        )
        idx = jnp.arange(cfg.n_layers)

        def body(xc, inp):
            p, i = inp
            delta, _ = S.mamba2_block(p["m"], xc, cfg, None)
            xc = xc + delta
            if cfg.attn_every:
                def with_attn(xa):
                    d2, _ = L.attention_block(
                        params["shared_attn"], xa, positions, cfg,
                        chunk=cfg.attn_chunk,
                    )
                    xa = xa + d2
                    return xa + L.mlp_block(params["shared_mlp"], xa, cfg)

                xc = jax.lax.cond(
                    (i + 1) % cfg.attn_every == 0, with_attn,
                    lambda xa: xa, xc,
                )
            return _constrain(xc), None

        body = jax.checkpoint(body) if remat else body
        x, _ = jax.lax.scan(body, x, (blocks, idx))
        return x, aux0, None

    raise ValueError(cfg.family)


def forward(
    params: dict,
    tokens: jax.Array,                  # [B, S] int32
    cfg: ArchConfig,
    *,
    positions: Optional[jax.Array] = None,
    cache: Optional[dict] = None,
    cache_index=0,
    page_table: Optional[jax.Array] = None,
    page_size: int = 0,
    paged_attn: str = "gather",
    pool_cache: Optional[dict] = None,
    pool_bound: Optional[jax.Array] = None,
    prefix_embeds: Optional[jax.Array] = None,
    remat: bool = False,
):
    """Returns (logits [B, S(+P), vocab] bf16, new_cache, aux_loss f32).

    ``page_table`` [B, n_pp] switches attention KV caching to the paged
    pool layout (``init_paged_cache``); ``cache_index`` is then unused —
    every token's cache slot is derived from its logical position.
    ``paged_attn="fused"`` runs single-token decode attention through the
    Pallas paged-attention kernel (no gathered KV copy) and multi-token
    decode blocks (the speculative verify) through its multi-token-query
    sibling; ``"gather"`` keeps the dense per-row page gather as the
    reference path.

    ``pool_cache`` switches to the speculative DRAFT layout: ``cache``
    is then a tick-local KV ring written at ``cache_index`` while the
    paged pools in ``pool_cache`` are read-only, truncated to positions
    <= ``pool_bound`` [B] (unrolled layer layout only — the draft runs
    at decode time, which never uses the scan path).
    """
    b, s = tokens.shape
    # gather THEN cast: the backward scatter-add into the embedding table
    # accumulates in f32 (casting first would accumulate in bf16, whose
    # rounding depends on XLA fusion — remat vs no-remat would disagree)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.bfloat16)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        s = x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    aux_total = jnp.zeros((), jnp.float32)
    new_layers = []

    if isinstance(params["blocks"], dict):  # stacked params -> scan path
        assert cache is None or "layers_stacked" in cache, (
            "scan-over-layers needs no cache (train) or a stacked cache "
            "(prefill); decode uses the unrolled list layout"
        )
        assert pool_cache is None, (
            "the speculative draft path needs the unrolled layer layout"
        )
        x, aux_total, new_stacked = _scan_blocks(
            params, x, positions, cfg, remat, cache, cache_index,
            page_table, page_size, paged_attn,
        )
        new_cache = (
            {"layers_stacked": new_stacked} if cache is not None else None
        )
        return _lm_head(params, x, cfg), new_cache, aux_total

    def attn(p, x, kv_c, pool_c):
        with jax.named_scope("attn"):
            return L.attention_block(
                p["attn"], x, positions, cfg,
                kv_cache=kv_c, cache_index=cache_index,
                page_table=page_table, page_size=page_size,
                paged_attn=paged_attn, pool_kv=pool_c,
                pool_bound=pool_bound, chunk=cfg.attn_chunk,
            )

    def dense_block(p, x, kv_c, pool_c):
        delta, new_kv = attn(p, x, kv_c, pool_c)
        x = x + delta
        with jax.named_scope("mlp"):
            x = x + L.mlp_block(p["mlp"], x, cfg)
        return x, new_kv

    def moe_layer(p, x, kv_c, pool_c):
        delta, new_kv = attn(p, x, kv_c, pool_c)
        x = x + delta
        mo, aux = L.moe_block(p["moe"], x, cfg,
                              group_tokens=cfg.moe_group_tokens)
        return x + mo, new_kv, aux

    for i, p in enumerate(params["blocks"]):
        layer_cache = cache["layers"][i] if cache is not None else None
        pool_layer = (
            pool_cache["layers"][i] if pool_cache is not None else None
        )
        if cfg.family == "dense":
            fn = jax.checkpoint(dense_block) if remat else dense_block
            x, new_kv = fn(p, x, layer_cache, pool_layer)
            new_layers.append(new_kv)
        elif cfg.family == "moe":
            fn = jax.checkpoint(moe_layer) if remat else moe_layer
            x, new_kv, aux = fn(p, x, layer_cache, pool_layer)
            aux_total = aux_total + aux
            new_layers.append(new_kv)
        elif cfg.family == "rwkv6":
            def rwkv_block(p, x, st):
                delta, st_tm = S.rwkv6_time_mix(p["tm"], x, cfg, st)
                x = x + delta
                delta, st_cm = S.rwkv6_channel_mix(p["cm"], x, cfg, st)
                return x + delta, {**st_tm, **st_cm}
            fn = jax.checkpoint(rwkv_block) if remat else rwkv_block
            x, new_state = fn(p, x, layer_cache)
            new_layers.append(new_state)
        elif cfg.family == "hybrid_mamba2":
            def mamba_block(p, x, st):
                delta, new_st = S.mamba2_block(p["m"], x, cfg, st)
                return x + delta, new_st
            fn = jax.checkpoint(mamba_block) if remat else mamba_block
            x, new_state = fn(p, x, layer_cache)
            if cfg.attn_every and (i + 1) % cfg.attn_every == 0:
                kv_c = (
                    layer_cache.get("attn_kv") if layer_cache is not None
                    else None
                )
                delta, new_kv = L.attention_block(
                    params["shared_attn"], x, positions, cfg,
                    kv_cache=kv_c, cache_index=cache_index,
                    chunk=cfg.attn_chunk,
                )
                x = x + delta
                x = x + L.mlp_block(params["shared_mlp"], x, cfg)
                if new_kv is not None:
                    new_state["attn_kv"] = new_kv
            new_layers.append(new_state)
        x = _constrain(x)  # optional seq-parallel activation sharding

    new_cache = {"layers": new_layers} if cache is not None else None
    return _lm_head(params, x, cfg), new_cache, aux_total


def _lm_head(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Final norm, then logits against the (tied) output embedding."""
    with jax.named_scope("lm_head"):
        x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
        if cfg.tie_embeddings:
            return L.apply_linear(
                jnp.transpose(params["embed"]).astype(x.dtype), x
            )
        return L.apply_linear(params["lm_head"], x)
