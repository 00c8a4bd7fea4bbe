"""Paged prefill spreads each admitted suffix over the block's idle rows.

``engine._prefill_rows`` lays the suffixes into a ``[rows, lb]`` block;
the rows meet through the page pool, so a split prompt must prefill
exactly as if it held one row: same first tokens, same generated
tokens, same pool contents.
"""
import jax
import numpy as np
import pytest

from repro.quant import QuantConfig
from repro.serving import Request
from repro.serving import engine as engine_mod
from repro.serving.engine import _prefill_rows

# (suffix lengths, starts of the unshared suffix, rows, max_len, lb)
PLAN_CASES = {
    "lone_request": ([50], [0], 4, 64, 16),
    "two_uneven": ([40, 9], [0, 0], 4, 64, 16),
    "full_block_no_split": ([30, 5, 17, 2], [0, 0, 0, 0], 4, 64, 32),
    "shorter_than_8": ([3, 1], [0, 0], 4, 64, 8),
    "shared_prefix_start": ([20], [32], 4, 64, 8),
    "capped_at_max_len": ([90], [0], 2, 100, 64),
}


def _one_row_bucket(longest, max_len):
    """The bucket of one row per request: the least power of two (at
    least 8) holding the longest suffix, capped at the cache length."""
    lb = 8
    while lb < longest:
        lb *= 2
    return min(lb, max_len)


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_prefill_rows_plan(case):
    lens, starts, rows, max_len, want_lb = PLAN_CASES[case]
    lb, plan = _prefill_rows(lens, rows, max_len)
    assert lb == want_lb
    assert lb <= _one_row_bucket(max(lens), max_len)
    assert len(plan) <= rows
    assert len({row for row, *_ in plan}) == len(plan)
    k = len(lens)
    for i, n in enumerate(lens):
        mine = sorted((off, ln, row) for row, r, off, ln in plan if r == i)
        # the chunks cover the suffix once, in order, each at most lb
        assert [off for off, _, _ in mine] == list(range(0, n, lb))
        assert sum(ln for _, ln, _ in mine) == n
        assert all(1 <= ln <= lb for _, ln, _ in mine)
        # the last chunk sits on the request's own row; the rest lie
        # beyond the requests' rows, in request order
        assert mine[-1][2] == i
        assert all(row >= k for _, _, row in mine[:-1])
        # rows carry starts = start + j * lb, lens = min(lb, rest)
        assert [starts[i] + off for off, _, _ in mine] == list(
            range(starts[i], starts[i] + n, lb))
        assert [ln for _, ln, _ in mine] == [
            min(lb, n - off) for off, _, _ in mine]
    early = sorted((row, r, off) for row, r, off, _ in plan if row >= k)
    assert early == sorted(early, key=lambda e: (e[1], e[2]))
    # one row per request: the unsplit layout at today's bucket
    lb1, plan1 = _prefill_rows(lens, k, max_len)
    assert lb1 == _one_row_bucket(max(lens), max_len)
    assert sorted(plan1) == [(i, i, 0, n) for i, n in enumerate(lens)]


def _one_row_per_request(lens, rows, max_len):
    return _prefill_rows(lens, len(lens), max_len)


def _serve(serving, quant, monkeypatch, split):
    """A 40-token prompt admitted alone, then, while it decodes, a
    prompt sharing its first 32 tokens (start 32 at admission) and a
    third unrelated one; temperature 0.7."""
    if not split:
        monkeypatch.setattr(engine_mod, "_prefill_rows",
                            _one_row_per_request)
    eng = serving.engine(quant=quant, max_batch=4, max_len=96, page_size=8,
                         temperature=0.7, seed=5)
    rng = np.random.default_rng(11)
    a = rng.integers(0, 256, size=40).astype(np.int32)
    b = np.concatenate([a[:32], rng.integers(0, 256, size=20)]).astype(
        np.int32)
    c = rng.integers(0, 256, size=27).astype(np.int32)
    eng.submit(Request(rid=0, prompt=a, max_tokens=12))
    eng.step()
    eng.submit(Request(rid=1, prompt=b, max_tokens=8))
    eng.submit(Request(rid=2, prompt=c, max_tokens=6))
    eng.run_to_completion()
    assert eng.stats["prefix_tokens_saved"] == 32
    out = {r.rid: list(r.generated) for r in eng.finished}
    assert all(r.error is None for r in eng.finished)
    return out, eng


@pytest.mark.parametrize("quant", [None, QuantConfig(bits=8, kv_bits=8)],
                         ids=["bf16_pool", "packed_int8_pool"])
def test_split_prefill_matches_one_row_per_request(serving, quant,
                                                   monkeypatch):
    got, split = _serve(serving, quant, monkeypatch, split=True)
    ref, whole = _serve(serving, quant, monkeypatch, split=False)
    assert split.stats["prefill_split_rows"] > 0
    assert whole.stats["prefill_split_rows"] == 0
    assert split.stats["prefill_positions"] < whole.stats["prefill_positions"]
    assert split.stats["prefill_tokens"] == whole.stats["prefill_tokens"]
    # first tokens (sampled from the prefill) and every later token
    assert {k: v[0] for k, v in got.items()} == {
        k: v[0] for k, v in ref.items()}
    assert got == ref
    for x, y in zip(jax.tree.leaves(split.cache),
                    jax.tree.leaves(whole.cache)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_ring_prefill_keeps_one_row_per_request(serving):
    eng = serving.engine(max_batch=4, max_len=64, kv_mode="ring")
    seen = []
    step = eng._prefill_step

    def record(*args):
        seen.append([np.asarray(a) for a in args[1:5]])
        return step(*args)

    eng._prefill_step = record
    eng.submit(Request(rid=0, prompt=np.arange(30, dtype=np.int32),
                       max_tokens=2))
    eng.run_to_completion()
    tokens, lens, route, valid = seen[0]
    assert tokens.shape == (4, 32)
    np.testing.assert_array_equal(lens, [30, 0, 0, 0])
    np.testing.assert_array_equal(route, [0, 0, 0, 0])
    np.testing.assert_array_equal(valid, [True, False, False, False])
    assert eng.stats["prefill_split_rows"] == 0
