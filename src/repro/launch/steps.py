"""Step functions: the jit/lower targets for training and serving.

``train_*`` cells lower ``train_step`` (fwd + bwd + AdamW); ``prefill_*``
cells lower ``prefill_step``; ``decode_*`` / ``long_*`` cells lower
``serve_step`` (ONE new token against a seq_len KV cache / recurrent
state), per the assignment spec.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, RunConfig, ShapeConfig
from repro.models import forward, init_cache
from repro.optim import adamw_update, cosine_warmup


def lm_loss(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Token-mean cross entropy in f32."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    tgt = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def make_loss_fn(cfg: ArchConfig, run: RunConfig):
    def loss_fn(params, batch):
        prefix = batch.get("prefix_embeds")
        logits, _, aux = forward(
            params, batch["tokens"], cfg,
            prefix_embeds=prefix, remat=(run.remat == "block"),
        )
        if prefix is not None:  # frontend stub tokens carry no LM targets
            logits = logits[:, prefix.shape[1]:]
        loss = lm_loss(logits, batch["targets"])
        return loss + 0.01 * aux, loss

    return loss_fn


def make_train_step(cfg: ArchConfig, run: RunConfig):
    loss_fn = make_loss_fn(cfg, run)

    def train_step(params, opt_state, batch):
        lr = cosine_warmup(opt_state.step, peak_lr=run.learning_rate,
                           warmup=run.lr_warmup)

        if run.grad_accum > 1:
            b = batch["tokens"].shape[0]
            mb = b // run.grad_accum

            def micro(acc, i):
                sl = jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(x, i * mb, mb, 0),
                    batch,
                )
                (_, raw), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, sl
                )
                acc_g, acc_l = acc
                return (
                    jax.tree.map(jnp.add, acc_g, g),
                    acc_l + raw / run.grad_accum,
                ), None

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (gsum, loss), _ = jax.lax.scan(
                micro, (zero, jnp.zeros((), jnp.float32)),
                jnp.arange(run.grad_accum),
            )
            grads = jax.tree.map(lambda g: g / run.grad_accum, gsum)
        else:
            (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )

        new_params, new_opt, metrics = adamw_update(
            grads, opt_state, params, lr,
            weight_decay=run.weight_decay, grad_clip=run.grad_clip,
        )
        return new_params, new_opt, {"loss": loss, "lr": lr, **metrics}

    return train_step


def make_prefill_step(cfg: ArchConfig, run: RunConfig):
    def prefill_step(params, batch, cache):
        prefix = batch.get("prefix_embeds")
        logits, new_cache, _ = forward(
            params, batch["tokens"], cfg,
            cache=cache, cache_index=0, prefix_embeds=prefix,
        )
        next_tok = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return next_tok.astype(jnp.int32), new_cache

    return prefill_step


def make_serve_step(cfg: ArchConfig, run: RunConfig):
    def serve_step(params, tokens, cache, pos):
        """One decode step: tokens [B,1] at scalar position ``pos``."""
        b = tokens.shape[0]
        positions = jnp.broadcast_to(
            pos.astype(jnp.int32), (b, 1)
        )
        logits, new_cache, _ = forward(
            params, tokens, cfg,
            positions=positions, cache=cache, cache_index=pos,
        )
        next_tok = jnp.argmax(logits[:, -1].astype(jnp.float32), axis=-1)
        return next_tok.astype(jnp.int32), new_cache

    return serve_step


def _fold_row_keys(key: jax.Array, fold: jax.Array) -> jax.Array:
    """Per-row sampling keys: ``fold_in(fold_in(key, fold[row]), row)``.

    The ONE definition of the noise-stream derivation the serving paths
    share: folding by the token's logical position makes every
    (key, position) draw its own stream (so a jit that samples several
    times — the speculative tick — never reuses noise, and a fixed
    engine seed stays reproducible), and the extra row fold keeps two
    slots that sit at the SAME position (identical prompts admitted
    together) sampling independently.
    """
    rows = jnp.arange(fold.shape[0], dtype=jnp.int32)
    return jax.vmap(
        lambda r, f: jax.random.fold_in(jax.random.fold_in(key, f), r)
    )(rows, fold.astype(jnp.int32))


def sample_tokens(logits: jax.Array, key: jax.Array,
                  temperature: jax.Array,
                  fold: Optional[jax.Array] = None) -> jax.Array:
    """In-jit sampling: greedy at temperature == 0, Gumbel-max otherwise.

    One trace covers both (``temperature`` is a traced scalar), so the
    serving engine never recompiles when the sampling policy changes.

    ``fold`` [B] (optional) derives each row's Gumbel noise from the
    per-row streams of ``_fold_row_keys`` instead of one shared
    [B, vocab] draw. Bugfix: a jit that samples MORE THAN ONCE from the
    same key (the speculative tick: K draft samples + a verify resample)
    would otherwise reuse IDENTICAL noise per call — with the same
    logits that degenerates into repeating the same token. See
    ``_fold_row_keys`` for the stream-derivation contract.
    """
    lf = logits.astype(jnp.float32)

    def greedy(_):
        return jnp.argmax(lf, axis=-1)

    def sample(k):
        if fold is None:
            g = jax.random.gumbel(k, lf.shape, jnp.float32)
        else:
            g = jax.vmap(
                lambda kk: jax.random.gumbel(kk, lf.shape[-1:], jnp.float32)
            )(_fold_row_keys(k, fold))
        return jnp.argmax(lf / jnp.maximum(temperature, 1e-6) + g, axis=-1)

    # lax.cond: the greedy branch never pays for the [B, vocab] Gumbel draw
    with jax.named_scope("sample"):
        return jax.lax.cond(temperature > 0, sample, greedy, key).astype(
            jnp.int32
        )


def make_ragged_serve_step(cfg: ArchConfig, run: RunConfig):
    """Position-ragged decode: every slot advances at its OWN position.

    The returned function is the serving hot path — one compiled step that
    decodes a continuous-batching slot set where each row sits at a
    different sequence position (the normal state right after a refill).
    All per-row KV reads/writes are vectorized scatters/gathers inside the
    jit (see layers._cache_write); sampling also happens in-jit so only the
    [B] token-id vector ever crosses the device boundary.
    """
    max_len = run.shape.seq_len

    def ragged_serve_step(params, tokens, cache, positions, active, key,
                          temperature):
        """tokens [B,1] int32; positions [B] int32 per-slot write offsets;
        active [B] bool. Returns (next ids [B] int32 (-1 where inactive),
        new cache). Inactive rows still write to their own cache row at a
        clamped offset — harmless, since a slot's row is fully reset when a
        new request is admitted into it."""
        pos = jnp.clip(positions.astype(jnp.int32), 0, max_len - 1)
        logits, new_cache, _ = forward(
            params, tokens, cfg,
            positions=pos[:, None], cache=cache, cache_index=pos,
        )
        next_tok = sample_tokens(logits[:, -1], key, temperature, fold=pos)
        return jnp.where(active, next_tok, -1), new_cache

    return ragged_serve_step


def make_paged_ragged_serve_step(cfg: ArchConfig, run: RunConfig,
                                 page_size: int,
                                 paged_attn: str = "fused"):
    """Position-ragged decode against the PAGED KV pool.

    Same contract as ``make_ragged_serve_step`` plus a ``page_table``
    [B, n_pp] argument: row i's token is written at pool page
    ``page_table[i, pos_i // page_size]``, offset ``pos_i % page_size`` —
    the (page, offset) generalization of the ragged (row, offset) scatter.
    Rows whose page-table row is all -1 (inactive slots) write nowhere and
    read an all-masked key set, so no reset of retired slots is needed.

    ``paged_attn="fused"`` (the serving default) attends per page through
    the Pallas paged-attention kernel — no [B, max_len] gathered KV copy
    inside the step; ``"gather"`` keeps the dense page gather as the
    token-identity reference path.
    """
    max_len = run.shape.seq_len
    assert paged_attn in ("fused", "gather"), paged_attn

    def paged_ragged_serve_step(params, tokens, cache, positions, active,
                                page_table, key, temperature):
        pos = jnp.clip(positions.astype(jnp.int32), 0, max_len - 1)
        logits, new_cache, _ = forward(
            params, tokens, cfg,
            positions=pos[:, None], cache=cache,
            page_table=page_table, page_size=page_size,
            paged_attn=paged_attn,
        )
        next_tok = sample_tokens(logits[:, -1], key, temperature, fold=pos)
        return jnp.where(active, next_tok, -1), new_cache

    return paged_ragged_serve_step


# ---------------------------------------------------------------------------
# self-speculative decoding: low-bit draft + multi-token paged verify
# ---------------------------------------------------------------------------
#
# One compiled tick: the DRAFT model (the same weights SAMD-packed to a
# lower bit width — the paper's ~6-10x-cheaper arithmetic is exactly the
# cost profile a speculative draft wants) proposes K tokens per slot with
# K unrolled single-token steps, then the full-precision TARGET model
# verifies all K in ONE multi-token forward and per-slot accept lengths
# come back to the host. Greedy verification is token-identical to plain
# decode; temperature > 0 uses standard rejection sampling (accept d with
# prob min(1, p_t(d)/p_d(d)), resample the first reject from the residual
# (p_t - p_d)+), so the output distribution is the target's.
#
# Draft KV never touches the page pool: each draft step writes its K/V
# into a K-slot bf16 ring that lives only inside the tick, and reads the
# pool STRICTLY BELOW the tick's window base (the pool may hold a
# previous tick's rejected-draft KV at >= the base). The verify forward
# paged-writes all K+1 tokens in bulk through the page table; positions
# past a slot's ``spec_len`` budget are masked to -1 (no write, no valid
# logits), so partially-budgeted slots stay correct.

# distinct per-purpose streams derived from the tick key, so no two
# draws inside one compiled tick share Gumbel/uniform noise
_SPEC_ACCEPT_STREAM = 0x5A
_SPEC_RESAMPLE_STREAM = 0x5B


def speculative_accept(logits: jax.Array, draft_tok: jax.Array,
                       draft_logits: jax.Array, spec_len: jax.Array,
                       key: jax.Array, temperature: jax.Array,
                       pos: jax.Array):
    """Per-slot accept lengths + output tokens for one speculative tick.

    logits [B, K+1, V] target logits at window positions ``pos..pos+K``
    (index j > spec_len[b] is garbage — masked by the budget);
    draft_tok [B, K] proposed tokens; draft_logits [B, K, V]; spec_len
    [B] per-slot draft budget (0..K); pos [B] window base positions.

    Returns (out [B, K+1] int32, n_acc [B] int32): the tick emits
    ``out[b, : n_acc[b] + 1]``. Greedy: out is the target argmax at every
    position, and n_acc counts the drafts that matched it — emitted
    tokens are exactly what non-speculative greedy decode would produce.
    Sampled: accepted drafts followed by the rejection-resample (or the
    bonus sample when every budgeted draft was accepted).
    """
    b, k1, v = logits.shape
    k = k1 - 1
    lf = logits.astype(jnp.float32)
    j_idx = jnp.arange(1, k + 1, dtype=jnp.int32)[None, :]
    in_budget = j_idx <= spec_len[:, None]

    def greedy(_):
        tgt = jnp.argmax(lf, axis=-1).astype(jnp.int32)
        match = (draft_tok == tgt[:, :k]) & in_budget
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        return tgt, n_acc.astype(jnp.int32)

    def sampled(kk):
        t = jnp.maximum(temperature, 1e-6)
        pt = jax.nn.softmax(lf[:, :k] / t, axis=-1)
        pd = jax.nn.softmax(draft_logits.astype(jnp.float32) / t, axis=-1)
        pt_d = jnp.take_along_axis(pt, draft_tok[..., None], axis=-1)[..., 0]
        pd_d = jnp.take_along_axis(pd, draft_tok[..., None], axis=-1)[..., 0]
        ratio = pt_d / jnp.maximum(pd_d, 1e-30)
        ukeys = _fold_row_keys(jax.random.fold_in(kk, _SPEC_ACCEPT_STREAM),
                               pos)
        u = jax.vmap(lambda kr: jax.random.uniform(kr, (k,), jnp.float32))(
            ukeys
        )
        ok = (u <= jnp.minimum(ratio, 1.0)) & in_budget
        n_acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
        n_acc = n_acc.astype(jnp.int32)
        # replacement token at emit index n_acc: residual resample at the
        # first reject, the target's own (bonus) sample when every
        # budgeted draft was accepted
        j_rep = jnp.clip(n_acc, 0, k - 1)[:, None, None]
        pt_rep = jnp.take_along_axis(pt, j_rep, axis=1)[:, 0]
        pd_rep = jnp.take_along_axis(pd, j_rep, axis=1)[:, 0]
        resid = jnp.maximum(pt_rep - pd_rep, 0.0)
        resid = jnp.where(
            jnp.sum(resid, axis=-1, keepdims=True) > 0, resid, pt_rep
        )
        lg_bonus = jnp.take_along_axis(
            lf, n_acc[:, None, None], axis=1
        )[:, 0]
        rkeys = _fold_row_keys(
            jax.random.fold_in(kk, _SPEC_RESAMPLE_STREAM), pos + n_acc
        )
        g = jax.vmap(lambda kr: jax.random.gumbel(kr, (v,), jnp.float32))(
            rkeys
        )
        resample = jnp.argmax(jnp.log(jnp.maximum(resid, 1e-30)) + g, axis=-1)
        bonus = jnp.argmax(lg_bonus / t + g, axis=-1)
        repl = jnp.where(n_acc >= spec_len, bonus, resample).astype(jnp.int32)
        j_grid = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
        drafts_pad = jnp.concatenate([draft_tok, draft_tok[:, -1:]], axis=1)
        out = jnp.where(j_grid < n_acc[:, None], drafts_pad, repl[:, None])
        return out.astype(jnp.int32), n_acc

    return jax.lax.cond(temperature > 0, sampled, greedy, key)


def make_draft_step(cfg: ArchConfig, run: RunConfig, page_size: int,
                    k_spec: int):
    """Draft half of the speculative tick: ``k_spec`` unrolled low-bit
    autoregressive steps per slot. Each step's K/V lands in a tick-local
    bf16 ring (``init_cache(cfg, B, k_spec)`` built in-trace — never the
    pool), while pool history is read read-only STRICTLY BELOW the
    window base, through the dense page gather. Returns (draft_tok
    [B, K], draft_logits [B, K, V])."""
    max_len = run.shape.seq_len
    assert k_spec >= 1, k_spec

    def draft_step(draft_params, tokens, cache, positions, page_table,
                   key, temperature):
        b = tokens.shape[0]
        pos = jnp.clip(positions.astype(jnp.int32), 0, max_len - 1)
        pool_bound = pos - 1  # pool history strictly below the window
        ring = init_cache(cfg, b, k_spec)
        cur = tokens
        drafts, dlogits = [], []
        for j in range(k_spec):
            lg, ring, _ = forward(
                draft_params, cur, cfg,
                positions=(pos + j)[:, None], cache=ring, cache_index=j,
                page_table=page_table, page_size=page_size,
                pool_cache=cache, pool_bound=pool_bound,
            )
            lgj = lg[:, -1]
            d = sample_tokens(lgj, jax.random.fold_in(key, j + 1),
                              temperature, fold=pos + j)
            drafts.append(d)
            dlogits.append(lgj)
            cur = d[:, None]
        return jnp.stack(drafts, axis=1), jnp.stack(dlogits, axis=1)

    return draft_step


def make_speculative_verify_step(cfg: ArchConfig, run: RunConfig,
                                 page_size: int, k_spec: int,
                                 paged_attn: str = "fused"):
    """Verify half: ONE multi-token target forward over ``[t0, d_1..d_K]``
    at positions ``pos..pos+K`` — all K+1 KV entries paged-written in
    bulk, attention through the multi-token-query paged block — followed
    by the accept rule. Returns (out [B, K+1], n_acc [B], new_cache)."""
    max_len = run.shape.seq_len

    def verify_step(params, tokens, draft_tok, draft_lg, cache, positions,
                    active, page_table, spec_len, key, temperature):
        pos = jnp.clip(positions.astype(jnp.int32), 0, max_len - 1)
        seq = jnp.concatenate([tokens, draft_tok], axis=1)  # [B, K+1]
        steps_i = jnp.arange(k_spec + 1, dtype=jnp.int32)[None, :]
        qpos = jnp.where(steps_i <= spec_len[:, None],
                         pos[:, None] + steps_i, -1)
        logits, new_cache, _ = forward(
            params, seq, cfg, positions=qpos, cache=cache,
            page_table=page_table, page_size=page_size,
            paged_attn=paged_attn,
        )
        out, n_acc = speculative_accept(
            logits, draft_tok, draft_lg, spec_len, key, temperature, pos
        )
        out = jnp.where(active[:, None], out, -1)
        n_acc = jnp.where(active, n_acc, 0)
        return out, n_acc, new_cache

    return verify_step


def make_speculative_step(cfg: ArchConfig, run: RunConfig, page_size: int,
                          k_spec: int, paged_attn: str = "fused"):
    """One compiled speculative tick: draft + verify fused in a single
    trace (the serving hot path — one host sync per tick for up to K+1
    tokens per slot).

    ``spec_len`` [B] caps each slot's draft budget (0..k_spec): positions
    past it carry -1 (nothing written, logits ignored), so slots near
    their token budget, the cache end, or an unallocated page degrade
    gracefully down to plain one-token decode. Only the accepted prefix
    is ever consumed by the host; KV written past it is overwritten by
    the next tick's window before any query can attend to it (the write
    cursor resumes at the first unaccepted position).
    """
    assert paged_attn in ("fused", "gather"), paged_attn
    draft = make_draft_step(cfg, run, page_size, k_spec)
    verify = make_speculative_verify_step(cfg, run, page_size, k_spec,
                                          paged_attn)

    def speculative_step(params, draft_params, tokens, cache, positions,
                         active, page_table, spec_len, key, temperature):
        """tokens [B,1] int32 (each slot's pending last token); spec_len
        [B] int32 per-slot draft budgets. Returns (out [B, k_spec+1],
        n_acc [B], new_cache); rows of inactive slots are -1/0."""
        draft_tok, draft_lg = draft(
            draft_params, tokens, cache, positions, page_table, key,
            temperature,
        )
        return verify(
            params, tokens, draft_tok, draft_lg, cache, positions, active,
            page_table, spec_len, key, temperature,
        )

    return speculative_step


def make_paged_prefill_step(cfg: ArchConfig, run: RunConfig,
                            page_size: int):
    """Bucket-padded batched prefill writing straight into the page pool.

    Unlike the ring-cache variant there is no fresh-cache + blend-by-slot
    step: each admitted row's KV lands directly in the pages its table
    names, and padding rows (valid=False, page table all -1) write nothing.
    Attention-family only, like ``make_batched_prefill_step``.

    Prefix sharing rides on the per-row ``starts`` offsets: a row whose
    leading prompt blocks were mapped from already-resident shared pages
    carries only its UNSHARED suffix in ``tokens`` and its first unshared
    position in ``starts``. Queries then attend to the shared prefix KV
    through the page table (those blocks are in the row's table and
    ``_paged_key_positions`` marks them valid), while the ragged KV
    scatter starts at ``starts[row]`` — the shared pages are never
    rewritten. ``starts = 0`` everywhere reproduces the unshared PR 2
    behavior exactly.
    """

    def paged_prefill_step(params, tokens, lens, starts, page_table, valid,
                           cache, key, temperature):
        """tokens [Nb, Lb] right-padded UNSHARED suffixes; lens [Nb] suffix
        lengths; starts [Nb] first unshared logical position per row;
        page_table [Nb, n_pp] pool pages of each row's TARGET SLOT
        (including its shared prefix pages); valid [Nb] bool."""
        nb, lb = tokens.shape
        t_idx = jnp.arange(lb, dtype=jnp.int32)[None, :]
        pos = jnp.where(
            t_idx < lens[:, None], starts[:, None].astype(jnp.int32) + t_idx,
            -1,
        )
        logits, new_cache, _ = forward(
            params, tokens, cfg, positions=pos, cache=cache,
            page_table=page_table, page_size=page_size,
        )
        last = jnp.take_along_axis(
            logits, jnp.clip(lens - 1, 0)[:, None, None], axis=1
        )[:, 0]
        tok0 = sample_tokens(last, key, temperature,
                             fold=starts + jnp.clip(lens - 1, 0))
        return jnp.where(valid, tok0, -1), new_cache

    return paged_prefill_step


def make_batched_prefill_step(cfg: ArchConfig, run: RunConfig,
                              max_batch: int):
    """Bucket-padded batched prefill for continuous-batching admission.

    Prompts are right-padded to a shared bucket length; padded tokens carry
    position -1 so their cache entries stay marked unfilled and attention
    masks them out. The freshly-filled rows are blended into the engine
    cache by slot id inside the same jit (deterministic where/one-hot blend
    — no scatter with duplicate indices), and each admitted row's first
    generated token is sampled from its last *valid* logit row.

    Attention-family only (dense/moe): recurrent state (rwkv6/mamba2) has
    no position channel, so right-padding would pollute it; the engine
    falls back to per-slot exact-length prefill for those families.
    """
    max_len = run.shape.seq_len
    kv_bits = run.quant.kv_bits if run.quant.enabled else None

    def batched_prefill_step(params, tokens, lens, slot_map, valid, cache,
                             key, temperature):
        """tokens [Nb, Lb] right-padded; lens [Nb]; slot_map [Nb] target
        slot per row; valid [Nb] bool (padding rows false)."""
        nb, lb = tokens.shape
        t_idx = jnp.arange(lb, dtype=jnp.int32)[None, :]
        pos = jnp.where(t_idx < lens[:, None], t_idx, -1)
        fresh = init_cache(cfg, nb, max_len, kv_bits=kv_bits)
        logits, filled, _ = forward(
            params, tokens, cfg, positions=pos, cache=fresh, cache_index=0,
        )
        last = jnp.take_along_axis(
            logits, jnp.clip(lens - 1, 0)[:, None, None], axis=1
        )[:, 0]
        tok0 = sample_tokens(last, key, temperature,
                             fold=jnp.clip(lens - 1, 0))

        # slot b <- filled row r iff valid[r] and slot_map[r] == b
        match = valid[None, :] & (
            slot_map[None, :] == jnp.arange(max_batch)[:, None]
        )                                                  # [B, Nb]
        has = jnp.any(match, axis=1)
        src = jnp.argmax(match, axis=1)

        def blend(c, r):
            picked = jnp.take(r, src, axis=0)
            keep = has.reshape((max_batch,) + (1,) * (c.ndim - 1))
            return jnp.where(keep, picked.astype(c.dtype), c)

        new_cache = jax.tree.map(blend, cache, filled)
        return jnp.where(valid, tok0, -1), new_cache

    return batched_prefill_step


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig,
                kv_bits: Optional[int] = None) -> dict:
    """Stand-ins for every model input of this (arch x shape) cell.

    For decode cells the KV-cache/state tree is part of the inputs; for the
    modality-stub archs ([audio]/[vlm]) precomputed frame/patch embeddings
    are included on train/prefill.
    """
    b, s = shape.global_batch, shape.seq_len
    specs: dict = {}
    if shape.kind == "train":
        specs["batch"] = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "targets": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
    elif shape.kind == "prefill":
        specs["batch"] = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
        # uniform families prefill via scan-over-layers with a stacked
        # cache; the hybrid keeps per-layer caches (see model.init_cache)
        specs["cache"] = jax.eval_shape(
            functools.partial(
                init_cache, cfg, b, s + _prefix_len(cfg),
                stacked=(cfg.family != "hybrid_mamba2"),
            )
        )
    elif shape.kind == "decode":
        specs["tokens"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        specs["cache"] = jax.eval_shape(
            functools.partial(init_cache, cfg, b, s, kv_bits=kv_bits)
        )
        specs["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    if shape.kind in ("train", "prefill") and cfg.n_prefix_embeds:
        specs["batch"]["prefix_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_prefix_embeds, cfg.d_model), jnp.bfloat16
        )
    return specs


def _prefix_len(cfg: ArchConfig) -> int:
    return cfg.n_prefix_embeds
