"""Self-speculative decoding: low-bit draft + multi-token paged verify.

Acceptance contract (ISSUE 5): greedy speculative decode is
TOKEN-IDENTICAL to non-speculative fused paged decode across
fused/gather x bf16/int8-KV — including mid-run preemption and COW forks
landing inside an accepted run — because the verify step emits the
target argmax at every position and only the matching draft prefix is
consumed. ``speculative=0`` keeps the engine on the exact single-token
path. The accept-length bookkeeping is property-tested against a pure
python model, and temperature > 0 decode must be reproducible under a
fixed engine seed (the per-slot Gumbel-fold bugfix).

Engine construction and workloads come from the shared ``serving``
fixture (tests/conftest.py).
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.quant.config import QuantConfig
from repro.serving import Request


def _run(serving, n_reqs=6, seed=3, **kw):
    eng = serving.engine(**kw)
    got = serving.mixed_arrival_run(eng, n_reqs=n_reqs, seed=seed)
    return got, eng


# ---------------------------------------------------------------------------
# greedy token-identity to the non-speculative paths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [2, 4])
def test_greedy_spec_token_identical_fused(serving, k):
    """K=2 and K=4 speculative decode over the fused paged kernel path
    must reproduce plain fused decode token-for-token, in fewer ticks."""
    plain, eng_plain = _run(serving)
    spec, eng_spec = _run(serving, speculative=k)
    assert spec == plain
    assert eng_spec.stats["spec_ticks"] > 0
    assert eng_spec.stats["per_row_forward_calls"] == 0
    assert eng_spec.stats["decode_steps"] < eng_plain.stats["decode_steps"]


def test_greedy_spec_token_identical_gather(serving):
    """Same identity through the gather reference backend."""
    plain, _ = _run(serving, paged_attn="gather")
    spec, eng = _run(serving, paged_attn="gather", speculative=2)
    assert spec == plain
    assert eng.stats["spec_ticks"] > 0


@pytest.mark.parametrize("paged_attn", ["fused", "gather"])
def test_greedy_spec_token_identical_int8_kv(serving, paged_attn):
    """SAMD-packed int8 KV pages: the verify's bulk packed writes and the
    draft's packed-pool reads must stay token-identical to plain decode
    (the quantized target is its own draft here)."""
    q = QuantConfig(bits=8, kv_bits=8)
    plain, _ = _run(serving, n_reqs=4, quant=q, paged_attn=paged_attn)
    spec, eng = _run(
        serving, n_reqs=4, quant=q, paged_attn=paged_attn, speculative=2
    )
    assert spec == plain
    assert eng.stats["spec_ticks"] > 0


def test_spec_zero_keeps_single_token_path(serving):
    """speculative=0 (default) must never touch the speculative
    machinery — the current path stays byte-identical."""
    _, eng = _run(serving, n_reqs=3)
    assert eng.speculative == 0
    assert eng.stats["spec_ticks"] == 0
    assert eng.stats["draft_proposed"] == 0
    assert not hasattr(eng, "_spec_step")


def test_spec_requires_paged_ragged(serving):
    with pytest.raises(ValueError):
        serving.engine(kv_mode="ring", speculative=2)
    with pytest.raises(ValueError):
        serving.engine(decode_mode="per_row", speculative=2)


# ---------------------------------------------------------------------------
# draft quality / accept-rate accounting
# ---------------------------------------------------------------------------


def test_full_precision_draft_accepts_nearly_everything(serving):
    """Oracle: a draft sharing the full-precision target weights proposes
    exactly what greedy verify picks — the accept rate must be ~1 and
    the tick count must shrink accordingly."""
    spec, eng = _run(
        serving, speculative=2, draft_quant=QuantConfig(enabled=False)
    )
    plain, _ = _run(serving)
    assert spec == plain
    assert eng.stats["draft_proposed"] > 0
    rate = eng.stats["draft_accepted"] / eng.stats["draft_proposed"]
    assert rate >= 0.95, (rate, eng.stats)


def test_quantized_draft_still_token_identical(serving):
    """A deliberately lossy 2-bit draft may guess badly — the accept rate
    only costs speed, never output correctness."""
    spec, eng = _run(serving, speculative=2, draft_quant=QuantConfig(bits=2))
    plain, _ = _run(serving)
    assert spec == plain
    assert eng.stats["draft_proposed"] >= eng.stats["draft_accepted"] >= 0


def _successor_model(serving, vocab=64):
    """A model whose greedy continuation of token t is t + 1, built
    directly rather than searched for among random inits (whose greedy
    runs, and so the existence of a usable eos, depend on the PRNG).

    Embeddings are the identity, every block's output projection is zero
    (the residual stream stays ``embed[t]``), and the untied LM head maps
    row t to column t + 1. Attention still runs, reads and writes the
    paged KV, and the low-bit draft sees the same weights packed."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_template, init_from_spec

    cfg = serving.cfg(vocab=vocab, d_model=vocab, tie_embeddings=False)
    params = init_from_spec(build_template(cfg), jax.random.PRNGKey(0))
    eye = jnp.eye(vocab, dtype=jnp.bfloat16)
    params["embed"] = eye
    params["lm_head"] = jnp.roll(eye, 1, axis=1)
    for blk in params["blocks"]:
        blk["attn"]["wo"] = jnp.zeros_like(blk["attn"]["wo"])
        blk["mlp"]["wd"] = jnp.zeros_like(blk["mlp"]["wd"])
    return cfg, params


def test_spec_respects_eos_mid_accepted_run(serving):
    """An eos landing inside an accepted run must stop consumption there
    (tokens past it are discarded with their KV)."""
    cfg, params = _successor_model(serving)
    prompt = np.array([3, 1, 4, 1, 5], np.int32)
    ref_eng = serving.engine(cfg=cfg, params=params)
    ref_eng.submit(Request(rid=0, prompt=prompt.copy(), max_tokens=8))
    ref = ref_eng.run_to_completion()[0].generated
    assert ref == list(range(6, 14)), ref  # every token first occurs once
    # prefill emits ref[0]; the first speculative tick accepts every
    # draft and emits ref[1:k+2], so ref[2] sits strictly inside that
    # tick's accepted run for k=2 and k=4
    idx = 2
    for k in (2, 4):
        eng = serving.engine(cfg=cfg, params=params, speculative=k)
        eng.submit(
            Request(rid=0, prompt=prompt.copy(), max_tokens=8,
                    eos_id=ref[idx])
        )
        got = eng.run_to_completion()[0].generated
        assert got == ref[: idx + 1], (k, got, ref)
        assert eng.stats["spec_ticks"] == 1, eng.stats


# ---------------------------------------------------------------------------
# interplay with preemption, prefix sharing and COW forks
# ---------------------------------------------------------------------------


def test_spec_preemption_completes_untruncated(serving):
    """Pool pressure mid-speculation: the youngest slot is preempted and
    recompute-resumed; every feasible request still completes in full,
    token-identical to a pressure-free speculative run."""
    prompts = [(np.arange(12) + 17 * i) % 256 for i in range(3)]

    def run(**kw):
        eng = serving.engine(
            page_size=8, prefix_sharing=False, speculative=2, **kw
        )
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p.copy(), max_tokens=20))
        done = eng.run_to_completion()
        return {r.rid: r.generated for r in done}, eng

    pressured, eng = run(num_pages=6, admission="optimistic")
    assert eng.stats["preemptions"] > 0, eng.stats
    assert eng.stats["oop_retired"] == 0
    for r in eng.finished:
        assert not r.truncated and r.error is None
        assert len(r.generated) == 20
    roomy, _ = run()
    assert pressured == roomy


def test_cow_fork_inside_speculatively_written_block(serving):
    """A follower forks a page whose content was written by the donor's
    ACCEPTED speculative runs (multi-token bulk writes): the fork must
    copy exactly the accepted tokens' KV. A K=2 tick can advance a slot
    several positions and retire it mid-loop, so the donor's blocks are
    kept alive across its retirement with LRU retention."""
    prompt = (np.arange(12) * 3 + 5) % 256
    eng = serving.engine(page_size=8, speculative=2, prefix_retain=8)
    eng.submit(Request(rid=0, prompt=prompt, max_tokens=16))
    done0 = eng.run_to_completion()  # blocks 0..2 complete -> retained
    assert eng.stats["draft_accepted"] > 0
    written = np.concatenate(
        [prompt, np.asarray(done0[0].generated[:-1], np.int32)]
    )
    follow = written[:20].copy()  # ends inside retained block 2
    eng.submit(Request(rid=1, prompt=follow, max_tokens=4))
    done = {r.rid: r.generated for r in eng.run_to_completion()}
    assert eng.stats["cow_forks"] >= 1, eng.stats
    assert eng.stats["retained_hits"] >= 2, eng.stats
    fresh = serving.engine(page_size=8, speculative=2)
    fresh.submit(Request(rid=1, prompt=follow.copy(), max_tokens=4))
    assert done[1] == fresh.run_to_completion()[0].generated


def test_spec_multi_turn_continuation_shares_decoded_pages(serving):
    """Blocks completed BY ACCEPTED RUNS enter the prefix index: a
    follow-up prompt extending the donor's prompt + generation maps them
    (via retention — the donor has already retired) instead of
    re-prefilling."""
    prompt = (np.arange(10) * 7 + 1) % 256
    eng = serving.engine(page_size=8, speculative=2, prefix_retain=8)
    eng.submit(Request(rid=0, prompt=prompt, max_tokens=12))
    done0 = eng.run_to_completion()
    written = np.concatenate(
        [prompt, np.asarray(done0[0].generated[:-1], np.int32)]
    )
    follow = np.asarray(list(written[:16]) + [7, 9], np.int32)
    eng.submit(Request(rid=1, prompt=follow, max_tokens=4))
    got = {r.rid: r.generated for r in eng.run_to_completion()}
    assert eng.stats["prefix_hits"] >= 2, eng.stats
    assert eng.stats["retained_hits"] >= 2, eng.stats
    fresh = serving.engine(page_size=8, speculative=2)
    fresh.submit(Request(rid=1, prompt=follow.copy(), max_tokens=4))
    assert got[1] == fresh.run_to_completion()[0].generated


# ---------------------------------------------------------------------------
# accept-length bookkeeping vs a pure-python model (property test)
# ---------------------------------------------------------------------------


def _ref_accept(tgt_rows, draft_rows, spec_lens):
    """Pure-python greedy accept: longest draft prefix within budget that
    matches the target argmax chain; emit that prefix + one correction."""
    out = []
    for tgt, drafts, budget in zip(tgt_rows, draft_rows, spec_lens):
        n = 0
        for j in range(1, len(drafts) + 1):
            if j > budget or drafts[j - 1] != tgt[j - 1]:
                break
            n += 1
        out.append((n, list(tgt[: n + 1])))
    return out


@settings(max_examples=40, deadline=None)
@given(
    k=st.integers(1, 5),
    b=st.integers(1, 6),
    seed=st.integers(0, 2**16),
)
def test_greedy_accept_matches_python_model(k, b, seed):
    import jax
    import jax.numpy as jnp

    from repro.launch import steps as steps_mod

    rng = np.random.default_rng(seed)
    vocab = 7
    # one-hot logits force the target argmax chain; drafts agree with it
    # for a random-length prefix so every accept length is exercised
    tgt = rng.integers(0, vocab, size=(b, k + 1))
    drafts = np.where(
        rng.random((b, k)) < 0.6, tgt[:, :k], rng.integers(0, vocab, (b, k))
    ).astype(np.int32)
    spec_len = rng.integers(0, k + 1, size=b).astype(np.int32)
    logits = np.full((b, k + 1, vocab), -5.0, np.float32)
    np.put_along_axis(logits, tgt[..., None], 5.0, axis=-1)
    # positions past the budget carry garbage logits in the real step —
    # the accept rule must never read them
    for i in range(b):
        logits[i, spec_len[i] + 1 :] = rng.normal(
            size=(k - spec_len[i], vocab)
        )
    out, n_acc = steps_mod.speculative_accept(
        jnp.asarray(logits),
        jnp.asarray(drafts),
        jnp.asarray(logits[:, :k]),
        jnp.asarray(spec_len),
        jax.random.PRNGKey(0),
        jnp.float32(0.0),
        jnp.asarray(np.arange(b), np.int32),
    )
    out = np.asarray(out)
    n_acc = np.asarray(n_acc)
    for i, (n_ref, emit_ref) in enumerate(
        _ref_accept(tgt.tolist(), drafts.tolist(), spec_len.tolist())
    ):
        assert int(n_acc[i]) == n_ref, (i, n_acc[i], n_ref)
        assert int(n_acc[i]) <= int(spec_len[i])
        assert out[i, : n_ref + 1].tolist() == emit_ref, i


# ---------------------------------------------------------------------------
# per-slot Gumbel fold: temperature > 0 reproducibility (bugfix)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [0, 2])
def test_temperature_decode_reproducible_fixed_seed(serving, spec):
    """Regression (satellite bugfix): sampled decode under a fixed engine
    seed must be reproducible — every draw inside a tick now comes from
    a per-(key, position) folded stream instead of one shared key, so
    the speculative tick's multiple samples stay independent AND
    deterministic."""
    kw = dict(temperature=0.8, seed=11)
    if spec:
        kw["speculative"] = spec
    a, _ = _run(serving, n_reqs=4, **kw)
    b, _ = _run(serving, n_reqs=4, **kw)
    assert a == b
    assert any(len(v) > 0 for v in a.values())


def test_sampled_spec_serves_all_requests(serving):
    """Rejection-sampled verification (temperature > 0) must complete a
    mixed-arrival workload with well-formed outputs and nonzero accepted
    drafts (the oracle draft agrees with the target distribution)."""
    got, eng = _run(
        serving,
        n_reqs=5,
        temperature=0.6,
        seed=7,
        speculative=2,
        draft_quant=QuantConfig(enabled=False),
    )
    assert len(got) == 5
    assert all(0 <= t < 256 for toks in got.values() for t in toks)
    assert eng.stats["draft_accepted"] > 0
