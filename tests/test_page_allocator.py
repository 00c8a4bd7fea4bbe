"""Property-based model test for the refcounted PageAllocator.

Random interleaved alloc / share / COW-fork / claim-reserved / free /
preempt sequences are driven against the real allocator AND a pure-Python
reference model; after every operation the two must agree and the pool
invariants must hold:

  * refcounts are never negative;
  * ``free + held == pool_size`` at every step (reserved pages stay in
    the free list — they hold no data);
  * no page is simultaneously free and mapped (held);
  * no double-grant: every page granted by alloc/claim_reserved was free
    and is returned at refcount exactly 1.
"""
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.serving import PageAllocator


class RefModel:
    """Pure-python mirror of the allocator contract (sets + dicts only).

    The model decides *whether* each operation must succeed from counts
    alone; the concrete page ids granted by the real allocator are fed
    back in, so the model independently tracks which pages are free and
    each page's refcount."""

    def __init__(self, num_pages):
        self.num_pages = num_pages
        self.free = set(range(num_pages))
        self.ref = {}          # page -> refcount >= 1
        self.reserved = 0

    @property
    def available(self):
        return len(self.free) - self.reserved

    def can_alloc(self, n, reserve):
        return n + reserve <= self.available

    def grant(self, pages, reserve=0):
        self.reserved += reserve
        for p in pages:
            assert p in self.free, f"double grant of page {p}"
            assert p not in self.ref, f"granted page {p} is still mapped"
            self.free.remove(p)
            self.ref[p] = 1

    def claim(self, pages):
        assert self.reserved >= len(pages)
        self.reserved -= len(pages)
        self.grant(pages)

    def share(self, page):
        assert self.ref.get(page, 0) >= 1
        self.ref[page] += 1

    def release(self, pages):
        freed = []
        for p in pages:
            assert self.ref.get(p, 0) >= 1, "refcount would go negative"
            self.ref[p] -= 1
            if self.ref[p] == 0:
                del self.ref[p]
                self.free.add(p)
                freed.append(p)
        return freed


def _check_agreement(alloc: PageAllocator, model: RefModel):
    held = {p for p, c in model.ref.items() if c >= 1}
    # refcounts agree and are never negative
    assert (alloc.refcount >= 0).all()
    for p in range(model.num_pages):
        assert int(alloc.refcount[p]) == model.ref.get(p, 0), p
    # free lists agree; free + held == pool_size
    free = set(alloc._free)
    assert free == model.free
    assert len(alloc._free) == alloc.free_pages
    assert alloc.free_pages + alloc.held_pages == alloc.num_pages
    assert len(model.free) + len(held) == model.num_pages
    # no page simultaneously free and mapped
    assert not (free & held)
    assert alloc.reserved == model.reserved
    assert 0 <= alloc.reserved <= alloc.free_pages


@settings(max_examples=60, deadline=None)
@given(
    num_pages=st.integers(1, 24),
    n_ops=st.integers(1, 80),
    seed=st.integers(0, 2**16),
)
def test_allocator_matches_reference_model(num_pages, n_ops, seed):
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(num_pages)
    model = RefModel(num_pages)
    # holders simulate engine slots: each holds page refs (possibly refs
    # to pages other holders also map — prefix sharing) + a reservation
    holders: list[dict] = []

    for _ in range(n_ops):
        op = rng.integers(0, 6)
        if op == 0:  # admission: alloc n pages + reserve growth
            n = int(rng.integers(0, 4))
            reserve = int(rng.integers(0, 3))
            pages = alloc.alloc(n, reserve=reserve)
            if model.can_alloc(n, reserve):
                assert pages is not None, (n, reserve)
                assert len(set(pages)) == n, "duplicate grant"
                model.grant(pages, reserve)
                for p in pages:
                    assert int(alloc.refcount[p]) == 1
                holders.append({"pages": list(pages), "reserved": reserve})
            else:
                assert pages is None, "alloc must fail atomically"
        elif op == 1 and holders:  # prefix share into another holder
            donor = holders[rng.integers(len(holders))]
            if donor["pages"]:
                page = donor["pages"][rng.integers(len(donor["pages"]))]
                alloc.share(page)
                model.share(page)
                taker = holders[rng.integers(len(holders))]
                taker["pages"].append(page)
        elif op == 2 and holders:  # COW fork: new page, drop shared ref
            h = holders[rng.integers(len(holders))]
            shared = [p for p in h["pages"] if model.ref.get(p, 0) > 1]
            if shared:
                page = shared[0]
                if h["reserved"] > 0:
                    new = alloc.claim_reserved(1)
                    model.claim(new)
                    h["reserved"] -= 1
                    h["pages"].extend(new)
                else:
                    new = alloc.alloc(1)
                    if model.can_alloc(1, 0):
                        assert new is not None
                        model.grant(new)
                        h["pages"].extend(new)
                    else:
                        assert new is None
                        new = None
                if new is not None:
                    freed = alloc.release([page])
                    assert freed == model.release([page])
                    h["pages"].remove(page)
        elif op == 3 and holders:  # mid-decode growth claim
            h = holders[rng.integers(len(holders))]
            if h["reserved"] > 0:
                pages = alloc.claim_reserved(1)
                assert len(pages) == 1
                model.claim(pages)
                h["reserved"] -= 1
                h["pages"].extend(pages)
        elif op == 4 and holders:  # retire or preempt: release everything
            h = holders.pop(rng.integers(len(holders)))
            freed = alloc.release(h["pages"])
            assert freed == model.release(h["pages"])
            # a freed page's refcount reached exactly zero, once
            assert len(set(freed)) == len(freed)
            if h["reserved"]:
                alloc.cancel_reservation(h["reserved"])
                model.reserved -= h["reserved"]
        elif op == 5 and holders:  # cancel part of a reservation
            h = holders[rng.integers(len(holders))]
            if h["reserved"] > 0:
                alloc.cancel_reservation(1)
                model.reserved -= 1
                h["reserved"] -= 1
        _check_agreement(alloc, model)

    # drain: releasing every holder returns the pool to fully-free
    for h in holders:
        alloc.release(h["pages"])
        model.release(h["pages"])
        if h["reserved"]:
            alloc.cancel_reservation(h["reserved"])
            model.reserved -= h["reserved"]
    _check_agreement(alloc, model)
    assert alloc.free_pages == num_pages
    assert alloc.reserved == 0


# ---------------------------------------------------------------------------
# LRU retention (refcount-0 pages parked for cross-residency prefix hits)
# ---------------------------------------------------------------------------


class RetainModel(RefModel):
    """RefModel extended with the retention contract: released pages may
    park in a bounded LRU pool; they count as available, any grant digs
    into them LRU-first (reporting evictions), and ``revive`` turns a
    retained page back into a refcount-1 holder."""

    def __init__(self, num_pages, retain_limit):
        super().__init__(num_pages)
        self.retain_limit = retain_limit
        self.retained = []  # LRU order: index 0 evicts first
        self.evicted_log = []

    @property
    def available(self):
        return len(self.free) + len(self.retained) - self.reserved

    def evict(self, n):
        pages, self.retained = self.retained[:n], self.retained[n:]
        self.free.update(pages)
        self.evicted_log.extend(pages)
        return pages

    def grant(self, pages, reserve=0):
        need = len(pages) - len(self.free)
        if need > 0:
            self.evict(need)
        super().grant(pages, reserve)

    def release_retain(self, pages):
        freed = []
        for p in pages:
            assert self.ref.get(p, 0) >= 1
            self.ref[p] -= 1
            if self.ref[p] == 0:
                del self.ref[p]
                if self.retain_limit > 0:
                    if len(self.retained) >= self.retain_limit:
                        self.evict(1)
                    self.retained.append(p)
                else:
                    self.free.add(p)
                    freed.append(p)
        return freed

    def revive(self, page):
        assert page in self.retained and page not in self.ref
        self.retained.remove(page)
        self.ref[page] = 1


def _check_retention_agreement(alloc: PageAllocator, model: RetainModel):
    assert set(alloc._free) == model.free
    assert list(alloc._retained) == model.retained
    for p in range(model.num_pages):
        assert int(alloc.refcount[p]) == model.ref.get(p, 0), p
    # a page is exactly one of: free, retained, held
    held = set(model.ref)
    assert not (model.free & set(model.retained))
    assert not (held & set(model.retained))
    assert not (model.free & held)
    assert (
        len(model.free) + len(model.retained) + len(held) == model.num_pages
    )
    assert alloc.available == model.available
    assert alloc.retained_pages == len(model.retained)
    assert alloc.held_pages == len(held)
    assert len(model.retained) <= model.retain_limit


@settings(max_examples=60, deadline=None)
@given(
    num_pages=st.integers(1, 24),
    retain_limit=st.integers(0, 8),
    n_ops=st.integers(1, 80),
    seed=st.integers(0, 2**16),
)
def test_allocator_retention_matches_reference_model(
    num_pages, retain_limit, n_ops, seed
):
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(num_pages, retain_limit=retain_limit)
    model = RetainModel(num_pages, retain_limit)
    evicted_log = []
    alloc.on_evict = evicted_log.extend
    holders: list[list] = []

    for _ in range(n_ops):
        op = rng.integers(0, 4)
        if op == 0:  # admission
            n = int(rng.integers(0, 4))
            pages = alloc.alloc(n)
            if model.can_alloc(n, 0):
                assert pages is not None
                model.grant(pages)
                holders.append(list(pages))
            else:
                assert pages is None
        elif op == 1 and holders:  # retire with retention
            h = holders.pop(rng.integers(len(holders)))
            freed = alloc.release(h, retain=True)
            assert freed == model.release_retain(h)
        elif op == 2 and holders:  # retire without retention
            h = holders.pop(rng.integers(len(holders)))
            freed = alloc.release(h)
            assert freed == model.release(h)
        elif op == 3 and model.retained:  # prefix hit on a retained page
            page = model.retained[rng.integers(len(model.retained))]
            assert alloc.is_retained(page)
            alloc.revive(page)
            model.revive(page)
            holders.append([page])
        # evictions surfaced to the owner must match the model exactly
        # (order included: the engine drops index entries from them)
        assert evicted_log == model.evicted_log
        _check_retention_agreement(alloc, model)

    for h in holders:
        assert alloc.release(h, retain=True) == model.release_retain(h)
    _check_retention_agreement(alloc, model)
    assert alloc.free_pages + alloc.retained_pages == num_pages
