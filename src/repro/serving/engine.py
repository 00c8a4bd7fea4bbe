"""Batched serving engine: paged KV cache + one compiled ragged decode step.

The inference-side integration of the paper: weights are SAMD-packed at
load time (``quantize_params``), requests are continuously batched into
fixed decode slots, and KV memory is a global pool of fixed-size pages
shared by all slots — a compact vLLM-style scheduler whose hot path is a
single jit.

Scheduling model (this module's contract):
  * fixed ``max_batch`` decode slots; host-side slot state (position, last
    token, active flag, page table) lives in numpy and is synced to the
    device once per tick;
  * admission runs ONE bucket-padded batched prefill over all admitted
    requests in a ``[max_batch, bucket]`` block (attention families;
    recurrent families fall back to per-slot exact-length prefill, since
    right-padding would pollute positionless recurrent state). Under
    paged KV each unshared suffix is cut into chunks spread over the
    rows no request holds, so the bucket is the smallest power of two
    at which the chunks fit the block, not the longest suffix's (a lone
    request fills the block instead of one row of it); the ring keeps
    one row per request. Prompts with ``len(prompt) >= max_len`` are
    REJECTED gracefully — the request lands in ``finished`` with
    ``error`` set and no tokens, and every other in-flight request keeps
    serving;
  * every engine tick runs ONE position-ragged fused decode step over the
    whole slot set: per-row KV reads/writes are vectorized scatters inside
    the jit, so mixed-position batches — the normal state right after a
    continuous-batching refill — never fall back to per-row Python
    forwards;
  * sampling (greedy or temperature/Gumbel-max) happens inside the jit;
    only the [max_batch] vector of next token ids crosses the device
    boundary each tick;
  * finished slots (eos or max_tokens) free immediately and are refilled
    from the queue — continuous batching. A slot that hits ``max_len``
    before finishing is force-retired with ``truncated=True`` so callers
    can tell truncation from completion.

Paged KV contract (``kv_mode="paged"``, the default for attention
families under ragged decode):
  * decode attention runs the FUSED Pallas paged-attention kernel by
    default (``paged_attn="fused"``): the step attends straight off the
    page pool through the page table with an online-softmax accumulator,
    so the per-tick [B, max_len] gathered KV copy of the old path never
    materializes. ``paged_attn="gather"`` keeps that dense gather as the
    token-identity reference path (prefill always gathers — its queries
    span many positions);
  * each attention layer owns a pool of ``num_pages`` KV pages of
    ``page_size`` tokens (SAMD-packed uint32 pages — four int8 lanes per
    head_dim word, unpacked lane-wise inside the kernel — when
    ``quant.kv_bits=8``);
    resident KV memory is ``num_pages * page_size`` tokens per layer, NOT
    ``max_batch * max_len`` — long and short requests share the pool;
  * pages are REFCOUNTED and PREFIX-SHARED (``prefix_sharing=True``, the
    default): the engine keeps a prefix index mapping the token content
    of each resident FULL page (keyed by the whole token prefix through
    that page, so two requests share a page only when everything before
    it matches too) to its pool page id. Admission matches a new prompt's
    leading full blocks against the index and maps hits straight into the
    slot's page table (refcount bumped) instead of re-prefilling them;
    prefill then runs only over the UNSHARED suffix, starting at the
    first unshared position. A page whose leading tokens match the
    prompt's partial tail block is copy-on-write FORKED (one device-side
    page copy, see ``models.copy_paged_page``) before the fork-holder's
    first write lands in it — shared pages are immutable while their
    refcount exceeds one. Pages whose last holder releases them
    (refcount -> 0) return to the free list and leave the index, so a
    recycled page can never leak stale KV into the index;
  * allocation lifecycle: admission takes ``ceil(len(prompt)/page_size)``
    pages (minus shared hits) from the host-side free list and — under
    the default ``admission="reserve"`` policy — additionally RESERVES
    the request's worst-case decode growth, ``ceil(min(len + max_tokens -
    1, max_len) / page_size)`` pages in total (the final sampled token is
    never written back), so mid-decode grants can never fail. A request
    whose pages are not available yet waits at the queue head; one that
    could never fit the pool is rejected with ``error``. Each decode tick
    grants one more page (claimed from the reservation) to any slot whose
    next write crosses a page boundary; ALL of a slot's page refs and
    unused reservations are dropped the moment its request retires
    (natural, truncated, preempted, or rejected-at-admission);
  * ``admission="optimistic"`` skips the growth reservation — higher
    admission concurrency, but the pool can run dry mid-decode.
    Out-of-pages behavior is page-level PREEMPTION, not truncation: when
    a grant finds the pool dry, the YOUNGEST resident request (latest
    admission) is preempted — its page refs are released and it is
    re-queued for recompute-resume, with every token it already generated
    becoming part of its re-prefill prompt — so feasible requests always
    complete token-identically, just later. Only a request that holds the
    ENTIRE pool and still needs more (i.e. one that can never fit, alone)
    is force-retired with ``truncated=True`` as a last resort — the
    engine never deadlocks and never crashes on pool pressure;
  * freed pages are NOT scrubbed: validity of a gathered key derives from
    the page table plus causal masking (plus prefix-donor identity for
    shared pages), so a new occupant can never attend to a previous
    occupant's KV (see layers._paged_key_positions).

Self-speculative decoding (``speculative=K`` > 0, paged + ragged only):
each tick runs ONE compiled draft+verify step instead of the plain
decode step. The draft is the SAME model with SAMD-packed low-bit
weights (``draft_quant``, default 4-bit — the paper's cheap-arithmetic
regime applied where it pays most: K extra forwards per tick); it
proposes up to K tokens per slot (tick-local ring KV, pool read-only
below the window), and the full-precision target verifies all of them
in one multi-token forward with per-slot accept lengths — between 1 and
K+1 tokens per slot cross the device boundary per tick. Greedy
verification is token-identical to plain decode; temperature > 0 uses
rejection sampling, so the output distribution stays the target's.
``speculative=0`` (default) keeps the single-token path byte-identical.
Page grants cover the verify window (``_spec_lens``); KV written past a
slot's accepted run is overwritten by the next tick's window before any
query can reach it.

Cached-prefix retention (``prefix_retain=N`` > 0): up to N refcount-0
prefix pages park in the allocator's LRU retention pool on release
instead of freeing, so prefix sharing survives NON-overlapping
residencies (request B reuses request A's pages after A fully retired).
Retained pages are evicted LRU-first whenever the free list runs short
— retention never causes preemption, admission failure, or footprint
growth in ``peak_pages_used`` (which counts refcount > 0 holders only).

``kv_mode="ring"`` keeps the PR 1 fixed per-slot KV ring (also the
automatic fallback for recurrent families and ``decode_mode="per_row"``);
``decode_mode="per_row"`` keeps the old per-row reference path (slow, one
``forward`` per slot per tick) for equivalence tests and as the benchmark
baseline. ``ServingEngine.stats`` counts compiled-step, per-row-forward,
page-grant, prefix-hit, COW-fork, preemption and OOP-retire events, the
prefill and decode work done against the work computed (prompt tokens
written vs positions, active rows vs rows, rows a split suffix took),
plus the peak page-pool occupancy, so tests can assert the hot path
stays fused and pool pressure (and the sharing win) is visible.
``serve.*`` trace spans (``serving/tracing.py``) mark each phase of a
tick while the JAX profiler records.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, RunConfig, ShapeConfig
from repro.launch import steps as steps_mod
from repro.models import (
    build_template, copy_paged_page, forward, init_cache, init_paged_cache,
    init_from_spec, quantize_params,
)
from repro.quant.config import QuantConfig
from repro.serving.tracing import span


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # [T] int32
    max_tokens: int = 16
    eos_id: Optional[int] = None
    generated: list = dataclasses.field(default_factory=list)
    # outcome flags (set by the engine):
    truncated: bool = False     # force-retired (cache/page-pool exhaustion)
    # error != None means the request did NOT complete normally: rejected
    # before prefill ("queue full ...", "prompt length ...", "request
    # needs ... pages"), or retired mid-flight when run_to_completion's
    # tick budget ran out ("tick budget exhausted" — may carry partial
    # ``generated`` tokens)
    error: Optional[str] = None
    # engine-internal: set while a preempted request waits for
    # recompute-resume (prompt + already-generated tokens, re-prefilled
    # verbatim), and the admission sequence used as preemption priority
    resume_prompt: Optional[np.ndarray] = None
    # observability timestamps (engine ``clock`` units, monotonic seconds
    # by default; None until the event happens). The serving front door's
    # metrics layer derives TTFT / TPOT / e2e latency from these:
    #   t_submit      stamped by ``submit`` (arrival at the engine)
    #   t_prefill     just before the first prefill program that holds
    #                 the request is launched: the end of its wait
    #   t_admit       first successful admission, stamped after that
    #                 prefill has run and its tokens were read back
    #   t_first_token first generated token (prefill's handoff sample)
    #   t_retire      retirement, any outcome (done/truncated/rejected)
    # t_prefill and t_admit survive preemption-resume unchanged, so
    # t_submit <= t_prefill <= t_admit
    t_submit: Optional[float] = None
    t_prefill: Optional[float] = None
    t_admit: Optional[float] = None
    t_first_token: Optional[float] = None
    t_retire: Optional[float] = None
    _seq: int = -1

    @property
    def done(self) -> bool:
        if len(self.generated) >= self.max_tokens:
            return True
        return bool(self.generated and self.eos_id is not None
                    and self.generated[-1] == self.eos_id)


class PageAllocator:
    """Host-side refcounted free list over the global KV page pool.

    O(1) alloc/free. Four kinds of bookkeeping:

    * ALLOCATION: ``alloc`` grants pages at refcount 1; ``release`` drops
      one ref per page and returns a page to the free list only when its
      refcount reaches zero (it also RETURNS the list of actually-freed
      pages so the owner can invalidate any content index entries).
    * SHARING: ``share`` bumps the refcount of an already-held page —
      prefix sharing maps one physical page into many page tables. A page
      is never simultaneously free and referenced, and a page granted by
      ``alloc``/``claim_reserved`` is never one that is still held.
    * RESERVATIONS: pages promised to admitted requests for their future
      decode growth but not yet bound to a page table. Reserved pages stay
      in the free list (they hold no data) yet are invisible to further
      admissions, so a reservation-admitted request can always claim its
      next page mid-decode.
    * RETENTION (``retain_limit`` > 0): up to ``retain_limit`` refcount-0
      pages released with ``retain=True`` park in an LRU pool instead of
      the free list, keeping their KV (and the owner's content-index
      entry) alive for prefix hits across NON-OVERLAPPING residencies.
      Retained pages count as ``available`` — any grant that outgrows the
      free list evicts LRU retained pages first (``on_evict`` tells the
      owner to drop its index entries), so retention can never cause a
      preemption or an admission failure. ``revive`` re-references a
      retained page on a prefix hit.
    """

    def __init__(self, num_pages: int, retain_limit: int = 0):
        self.num_pages = num_pages
        self.retain_limit = int(retain_limit)
        self._free = list(range(num_pages - 1, -1, -1))
        self._retained: collections.OrderedDict = collections.OrderedDict()
        self.refcount = np.zeros(num_pages, np.int32)
        self.reserved = 0
        self.on_evict = None  # callable(list[int]) -> None, or None

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def retained_pages(self) -> int:
        return len(self._retained)

    @property
    def held_pages(self) -> int:
        """Pages with at least one holder (unique-page footprint).
        Retained pages are refcount-0 — parked, not held."""
        return int((self.refcount > 0).sum())

    @property
    def available(self) -> int:
        """Pages an admission may take or reserve right now (retained
        pages are reclaimable, so they count)."""
        return len(self._free) + len(self._retained) - self.reserved

    def _evict(self, n: int) -> None:
        """Move the ``n`` least-recently-retained pages to the free list
        (the owner's index entries are dropped via ``on_evict``)."""
        pages = [self._retained.popitem(last=False)[0] for _ in range(n)]
        self._free.extend(pages)
        if self.on_evict is not None:
            self.on_evict(pages)

    def _grant(self, n: int) -> list:
        if len(self._free) < n:
            self._evict(n - len(self._free))
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            assert self.refcount[p] == 0, ("double grant", p)
            self.refcount[p] = 1
        return pages

    def alloc(self, n: int, reserve: int = 0) -> Optional[list]:
        """Take ``n`` pages and reserve ``reserve`` more, or None (and
        take nothing) unless all ``n + reserve`` are available."""
        if n + reserve > self.available:
            return None
        self.reserved += reserve
        return self._grant(n)

    def claim_reserved(self, n: int = 1) -> list:
        """Convert previously reserved pages into real ones (never fails:
        the reservation guarantees them)."""
        assert (
            0 <= n <= self.reserved
            <= len(self._free) + len(self._retained)
        )
        self.reserved -= n
        return self._grant(n)

    def cancel_reservation(self, n: int) -> None:
        self.reserved -= n
        assert self.reserved >= 0

    def share(self, page: int) -> None:
        """Add a reference to an already-held page (prefix sharing)."""
        assert self.refcount[page] >= 1, ("share of unheld page", page)
        self.refcount[page] += 1

    def is_retained(self, page: int) -> bool:
        return page in self._retained

    def revive(self, page: int) -> None:
        """Re-reference a retained refcount-0 page (prefix hit after its
        last holder left — the cross-residency sharing win)."""
        del self._retained[page]
        assert self.refcount[page] == 0, ("revive of held page", page)
        self.refcount[page] = 1

    def release(self, pages, retain: bool = False) -> list:
        """Drop one reference per page; pages whose refcount reaches zero
        return to the free list — or, with ``retain=True`` and retention
        configured, park in the LRU retention pool (evicting its oldest
        entry when full). Returns the actually-FREED pages (the owner
        must drop their index entries); retained pages are not freed."""
        freed = []
        for p in pages:
            p = int(p)
            assert self.refcount[p] >= 1, ("release of unheld page", p)
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                if retain and self.retain_limit > 0:
                    if len(self._retained) >= self.retain_limit:
                        self._evict(1)
                    self._retained[p] = None
                else:
                    self._free.append(p)
                    freed.append(p)
        return freed

    def reset(self) -> None:
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._retained.clear()
        self.refcount[:] = 0
        self.reserved = 0


def _prefill_rows(lens: list[int], rows: int, max_len: int):
    """Lay admitted suffixes of ``lens`` tokens into a ``[rows, lb]``
    prefill block, spreading each over rows no request holds.

    ``lb`` is the smallest power of two (floor 8, capped at the cache
    length; powers of two bound jit retraces to O(log max_len) shapes)
    at which cutting every suffix into consecutive chunks of ``lb``
    tokens needs at most ``rows`` rows; one row per request always
    fits, so ``lb`` never exceeds the longest suffix's own bucket.
    Request i's LAST chunk sits on row i, where its first token is
    sampled (folded by row i and its last position, as when it held the
    row alone); its earlier chunks fill rows ``len(lens)..`` in request
    order. Returns ``(lb, plan)``, plan a list of ``(row, request,
    offset, length)`` with offsets counted from the request's first
    unshared token. ``rows=len(lens)`` gives one row per request at the
    longest suffix's bucket."""
    assert 1 <= len(lens) <= rows, (lens, rows)

    def chunks(n, lb):
        return max(1, -(-n // lb))

    lb = 8
    while sum(chunks(n, min(lb, max_len)) for n in lens) > rows:
        lb *= 2
    lb = min(lb, max_len)
    plan, spare = [], len(lens)
    for i, n in enumerate(lens):
        c = chunks(n, lb)
        for j in range(c - 1):
            plan.append((spare, i, j * lb, lb))
            spare += 1
        plan.append((i, i, (c - 1) * lb, n - (c - 1) * lb))
    return lb, plan


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params=None, *,
                 quant: QuantConfig | None = None,
                 max_batch: int = 4, max_len: int = 512, seed: int = 0,
                 temperature: float = 0.0,
                 decode_mode: str = "ragged",
                 kv_mode: str = "auto",
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 admission: str = "reserve",
                 paged_attn: str = "fused",
                 prefix_sharing: bool = True,
                 prefix_retain: Optional[int] = None,
                 speculative: int = 0,
                 draft_quant: QuantConfig | None = None,
                 verify: bool = True,
                 max_queue: Optional[int] = None,
                 clock=None):
        assert decode_mode in ("ragged", "per_row"), decode_mode
        assert max_queue is None or max_queue >= 0, max_queue
        assert admission in ("reserve", "optimistic"), admission
        assert paged_attn in ("fused", "gather"), paged_attn
        assert speculative >= 0, speculative
        # paged KV needs the batched admission path and pool-shaped cache
        # inside the fused steps; the per-row reference path slices per-slot
        # cache rows and recurrent families have O(1) state — both fall
        # back to the ring.
        paged_capable = (
            decode_mode == "ragged" and cfg.family in ("dense", "moe")
        )
        if kv_mode == "auto":
            kv_mode = "paged" if paged_capable else "ring"
        assert kv_mode in ("paged", "ring"), kv_mode
        if kv_mode == "paged" and not paged_capable:
            raise ValueError(
                "kv_mode='paged' needs decode_mode='ragged' and an "
                f"attention family, got {decode_mode}/{cfg.family}"
            )
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len
        self.temperature = float(temperature)
        self.decode_mode = decode_mode
        self.kv_mode = kv_mode
        self.admission = admission
        self.paged_attn = paged_attn
        self.prefix_sharing = bool(prefix_sharing) and kv_mode == "paged"
        self.speculative = int(speculative)
        if self.speculative and (kv_mode != "paged"
                                 or decode_mode != "ragged"):
            raise ValueError(
                "speculative decoding needs kv_mode='paged' and "
                f"decode_mode='ragged', got {kv_mode}/{decode_mode}"
            )
        self.page_size = page_size
        self.pages_per_slot = -(-max_len // page_size)
        if num_pages is None:
            # full coverage by default: paged is then a drop-in for the
            # ring (token-identical, no truncation risk); size it smaller
            # to trade memory for preemption under pressure.
            num_pages = max_batch * self.pages_per_slot
        self.num_pages = num_pages
        # per-layer parameter list whatever ``cfg.scan_layers`` says: the
        # paged pools, the COW page copy and the speculative draft all use
        # the unrolled {'layers': [...]} cache layout
        template = build_template(cfg, stacked=False)
        if params is None:
            params = init_from_spec(template, jax.random.PRNGKey(seed))
        raw_params = params
        if quant is not None and quant.enabled:
            params = quantize_params(params, template, quant)
        self.params = params
        self.quant = quant or QuantConfig(enabled=False)
        self._kv_bits = self.quant.kv_bits if self.quant.enabled else None
        if self.speculative:
            # self-speculative draft: the SAME weights, SAMD-packed to a
            # low bit width (default 4-bit — the paper's cheap-arithmetic
            # regime). An already-quantized target is its own draft; an
            # explicitly disabled draft_quant shares the bf16 target
            # weights (the accept-rate-1 oracle used by tests).
            if self.quant.enabled:
                self.draft_quant = self.quant
                self._draft_params = self.params
            else:
                # backend="pallas" routes the draft's packed matmuls
                # through kernels.ops.samd_matmul (Mosaic on TPU, the
                # unrolled K-block lowering on CPU) instead of
                # dequantize-then-matmul — the draft reads packed bytes
                dq = (
                    draft_quant
                    if draft_quant is not None
                    else QuantConfig(bits=4, backend="pallas")
                )
                self.draft_quant = dq
                self._draft_params = (
                    quantize_params(raw_params, template, dq)
                    if dq.enabled else self.params
                )
        if verify:
            # admission-time lane safety: every (bits, K) tuple the packed
            # weights will actually accumulate over — target and draft —
            # must be certified safe before the engine serves a request.
            self._verify_lane_safety()
        run = RunConfig(arch=cfg,
                        shape=ShapeConfig("serve", max_len, max_batch,
                                          "decode"),
                        quant=self.quant)
        if kv_mode == "paged":
            self._ragged_step = jax.jit(
                steps_mod.make_paged_ragged_serve_step(
                    cfg, run, page_size, paged_attn=paged_attn),
                donate_argnums=(2,),
            )
            if self.speculative:
                self._spec_step = jax.jit(
                    steps_mod.make_speculative_step(
                        cfg, run, page_size, self.speculative,
                        paged_attn=paged_attn),
                    donate_argnums=(3,),
                )
            # COW fork primitive: one fused device op copies a pool page
            # across every layer (src/dst are traced, so one compile
            # serves every fork)
            self._copy_page = jax.jit(copy_paged_page, donate_argnums=(0,))
        else:
            self._ragged_step = jax.jit(
                steps_mod.make_ragged_serve_step(cfg, run),
                donate_argnums=(2,),
            )
        # batched prefill needs position-masked padding => attention only;
        # recurrent families (rwkv6 / hybrid_mamba2) prefill per slot —
        # exactly the paged-capability condition
        self._batched_prefill = paged_capable
        if kv_mode == "paged":
            self._prefill_step = jax.jit(
                steps_mod.make_paged_prefill_step(cfg, run, page_size),
                donate_argnums=(6,),
            )
        elif self._batched_prefill:
            self._prefill_step = jax.jit(
                steps_mod.make_batched_prefill_step(cfg, run, max_batch),
                donate_argnums=(5,),
            )
        self.cache = self._init_cache()
        self._key = jax.random.PRNGKey(seed ^ 0x5EED)
        # observability clock (injectable for deterministic tests) and
        # queue bound: ``submit`` REJECTS — machine-readably, via
        # ``Request.error`` — once ``max_queue`` requests wait, instead
        # of growing the queue (and every queued prompt's host memory)
        # without limit under open-loop overload. None = unbounded (the
        # pre-front-door behavior). Preemption re-queues bypass the
        # bound: an admitted request must never be bounced back out.
        self.clock = clock if clock is not None else time.monotonic
        self.max_queue = max_queue
        # host-side scheduler state (numpy; one device sync per tick)
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: list[Optional[Request]] = [None] * max_batch
        self.slot_pos = np.zeros(max_batch, np.int32)
        self.slot_next = np.zeros(max_batch, np.int32)
        self.active = np.zeros(max_batch, bool)
        self.finished: list[Request] = []
        # bounded LRU retention of refcount-0 prefix pages (0 = off):
        # sharing then survives non-overlapping residencies
        self.prefix_retain = (
            int(prefix_retain) if prefix_retain and self.prefix_sharing
            else 0
        )
        self._allocator = PageAllocator(num_pages,
                                        retain_limit=self.prefix_retain)
        self._allocator.on_evict = self._deregister
        self.page_table = np.full((max_batch, self.pages_per_slot), -1,
                                  np.int32)
        self.slot_pages = np.zeros(max_batch, np.int32)     # allocated count
        self.slot_reserved = np.zeros(max_batch, np.int32)  # growth pages
        self._slot_seq = np.zeros(max_batch, np.int64)      # admission order
        self._seq_counter = 0
        # prefix index: chain key (token prefix bytes through a FULL
        # block) -> resident pool page, plus the reverse maps needed to
        # deregister on free and to match partial tails for COW forks
        self._prefix_index: dict[bytes, int] = {}
        self._page_key: dict[int, bytes] = {}
        self._page_parent: dict[int, bytes] = {}
        self._page_block: dict[int, np.ndarray] = {}
        self._prefix_children: dict[bytes, set] = {}
        self._prefix_ready: set[int] = set()  # KV written on device
        self.stats = {
            "decode_steps": 0,          # fused ragged decode invocations
            "prefill_calls": 0,         # batched/fused prefill invocations
            "prefill_tokens": 0,        # prompt tokens prefill wrote
            "prefill_positions": 0,     # positions the prefill computed
            "prefill_split_rows": 0,    # prefill rows holding an early chunk
            "decode_rows": 0,           # active rows over decode steps
            "decode_slots": 0,          # rows the decode steps computed
            "per_row_prefill_calls": 0,
            "per_row_forward_calls": 0,  # reference decode path only
            "page_grants": 0,           # incremental mid-decode page allocs
            "prefix_hits": 0,           # pages mapped shared at admission
            "prefix_tokens_saved": 0,   # prompt tokens prefill skipped
            "retained_hits": 0,         # refcount-0 retained pages revived
            "cow_forks": 0,             # copy-on-write page copies
            "spec_ticks": 0,            # speculative draft+verify ticks
            "draft_proposed": 0,        # draft tokens offered to verify
            "draft_accepted": 0,        # draft tokens accepted by verify
            "preemptions": 0,           # slots preempted for recompute
            "oop_retired": 0,           # slots truncated on pool exhaustion
            "rejected": 0,              # requests refused before prefill
            "rejected_queue_full": 0,   # subset of rejected: queue bound
            "tick_budget_exhausted": 0,  # stragglers errored at max_ticks
            "peak_pages_used": 0,       # max pages with refcount > 0
        }

    def _verify_lane_safety(self):
        """Admission-time static check: walk the packed parameter trees
        (target and, when speculative, the draft) and certify every
        (QuantConfig, reduction-depth) tuple with the lane-safety
        analyzer. Raises ``LaneSafetyError`` — the engine refuses to
        come up on a quantization it cannot prove safe."""
        from repro.analysis import contracts

        checks = []
        if self.quant.enabled:
            checks.append((self.quant, self.params))
        dq = getattr(self, "draft_quant", None)
        if (
            self.speculative
            and dq is not None
            and dq.enabled
            and dq is not self.quant
        ):
            checks.append((dq, self._draft_params))
        for qcfg, tree in checks:
            for k in contracts.packed_reduction_depths(tree):
                contracts.assert_safe(
                    contracts.check_matmul_config(qcfg, k)
                )

    def _init_cache(self):
        if self.kv_mode == "paged":
            return init_paged_cache(self.cfg, self.num_pages, self.page_size,
                                    kv_bits=self._kv_bits)
        return init_cache(self.cfg, self.max_batch, self.max_len,
                          kv_bits=self._kv_bits)

    def kv_cache_bytes(self) -> int:
        """Resident bytes of the KV cache / recurrent-state pytree (for the
        paged mode this is the page pool — the memory the paging exists to
        shrink)."""
        return int(sum(x.nbytes for x in jax.tree.leaves(self.cache)))

    # -- rng ---------------------------------------------------------------
    def _next_key(self):
        if self.temperature <= 0.0:
            return self._key  # unused by greedy sampling; avoid split cost
        self._key, k = jax.random.split(self._key)
        return k

    # -- prefix index ------------------------------------------------------
    def _written_tokens(self, i: int) -> np.ndarray:
        """The token written at each logical position 0..slot_pos-1 of
        slot ``i``: the original prompt plus every generated token except
        the last (sampled, but written back only by the NEXT decode
        tick). The invariant ``slot_pos == len(prompt) + len(generated)
        - 1`` holds for every active slot — admission hands off with one
        sampled-unwritten token and each tick writes one and samples one
        — and survives preemption-resume unchanged, so the written-token
        record is always derivable from the request itself instead of
        being tracked as parallel per-slot state."""
        req = self.slots[i]
        toks = np.asarray(req.prompt, np.int32)
        if req.generated:
            toks = np.concatenate(
                [toks, np.asarray(req.generated[:-1], np.int32)])
        assert len(toks) == int(self.slot_pos[i]), (len(toks), i)
        return toks

    @staticmethod
    def _eff_prompt(req: Request) -> np.ndarray:
        """The tokens this admission must make resident: the original
        prompt, or (recompute-resume) prompt + already-generated tokens."""
        src = (
            req.resume_prompt
            if req.resume_prompt is not None
            else req.prompt
        )
        return np.asarray(src, np.int32)

    def _register_block(self, eff: np.ndarray, b: int, page: int) -> bool:
        """Index full block ``b`` of ``eff`` (its page now holds that
        content). Keys are the raw token-prefix bytes THROUGH the block —
        exact, no hash-collision risk — so a hit guarantees the donor's
        entire history matches. Returns False if equivalent content is
        already indexed."""
        ps = self.page_size
        key = eff[: (b + 1) * ps].tobytes()
        if key in self._prefix_index:
            return False
        parent = eff[: b * ps].tobytes()
        self._prefix_index[key] = page
        self._page_key[page] = key
        self._page_parent[page] = parent
        self._page_block[page] = eff[b * ps:(b + 1) * ps].copy()
        self._prefix_children.setdefault(parent, set()).add(page)
        return True

    def _deregister(self, freed_pages) -> None:
        """Drop index entries for pages whose refcount reached zero — a
        recycled page must never satisfy a future prefix match."""
        for p in freed_pages:
            key = self._page_key.pop(p, None)
            self._prefix_ready.discard(p)
            if key is None:
                continue
            if self._prefix_index.get(key) == p:
                del self._prefix_index[key]
            parent = self._page_parent.pop(p)
            kids = self._prefix_children.get(parent)
            if kids is not None:
                kids.discard(p)
                if not kids:
                    del self._prefix_children[parent]
            self._page_block.pop(p, None)

    def _match_prefix(self, eff: np.ndarray):
        """Match ``eff``'s leading blocks against resident pages.

        Returns (shared_pages, fork_src, prefill_start): ``shared_pages``
        are full-block hits to map refcounted; ``fork_src`` (may be None)
        is a resident page whose leading tokens equal the prompt's partial
        tail block — COW-forked so prefill only recomputes the LAST prompt
        token (its logits seed decoding). At least one token always
        remains to prefill."""
        t, ps = len(eff), self.page_size
        shared: list = []
        if not self.prefix_sharing or t == 0:
            return shared, None, 0
        m_max = (t - 1) // ps
        while len(shared) < m_max:
            page = self._prefix_index.get(
                eff[: (len(shared) + 1) * ps].tobytes())
            if page is None:
                break
            shared.append(page)
        m = len(shared)
        fork_src = None
        if m == m_max:
            # the full-block chain matched end to end; look for a resident
            # block extending it whose first r tokens equal the remaining
            # tail (r == ps when the prompt ends exactly on a page edge).
            # Only fork-ready pages: the copy reads the device pool NOW.
            r = t - m * ps
            tail = eff[m * ps: t]
            for page in self._prefix_children.get(
                    eff[: m * ps].tobytes(), ()):
                if page in self._prefix_ready and np.array_equal(
                        self._page_block[page][:r], tail):
                    fork_src = page
                    break
        start = (t - 1) if fork_src is not None else m * ps
        return shared, fork_src, start

    # -- admission ---------------------------------------------------------
    def submit(self, req: Request):
        """Enqueue ``req`` — or, when the queue already holds
        ``max_queue`` waiting requests, REJECT it with ``error`` set
        ("queue full ...") instead of queueing unboundedly. Explicit
        backpressure: under open-loop overload the pre-bound engine grew
        ``queue`` (and every queued prompt's host memory) without limit,
        and callers could not tell. In-flight requests and
        already-queued ones are untouched by the rejection."""
        if req.t_submit is None:
            req.t_submit = self.clock()
        if (self.max_queue is not None
                and len(self.queue) >= self.max_queue):
            self.stats["rejected_queue_full"] += 1
            self._reject(
                req,
                f"queue full ({len(self.queue)} waiting, "
                f"max_queue={self.max_queue})",
            )
            return
        self.queue.append(req)

    def _reject(self, req: Request, reason: str):
        """Finish a request without serving it (regression guard: a bad
        request must never take down in-flight traffic)."""
        req.error = reason
        if req.t_retire is None:
            req.t_retire = self.clock()
        self.finished.append(req)
        self.stats["rejected"] += 1

    def _paged_bind(self, slot: int, req: Request, eff: np.ndarray,
                    pending_ready: list):
        """Bind one request's pages to ``slot``: map shared prefix hits,
        COW-fork a matching partial tail, allocate the rest (plus the
        growth reservation). Returns ("ok", prefill_start) on success,
        ("wait", 0) on pool pressure, ("reject", 0) if infeasible."""
        ps = self.page_size
        t = len(eff)
        blocks = max(1, -(-t // ps))
        shared, fork_src, start = self._match_prefix(eff)
        m = len(shared)
        # worst-case decode growth: a fresh request's first generated
        # token comes from prefill without a cache write, so writes reach
        # at most position len + max_tokens - 2; a resumed request writes
        # its stored last token too, one more position
        gen_left = req.max_tokens - len(req.generated)
        future = gen_left - (0 if req.resume_prompt is not None else 1)
        horizon_tok = min(t + future, self.max_len)
        horizon = max(blocks, -(-horizon_tok // ps))
        reserve = horizon - blocks if self.admission == "reserve" else 0
        if blocks + reserve > self.num_pages:
            self._reject(
                req,
                f"request needs {blocks + reserve} KV pages; "
                f"pool holds {self.num_pages}",
            )
            return "reject", 0
        # take the shared refs BEFORE the alloc: the alloc may evict
        # refcount-0 RETAINED pages to satisfy itself, and an evicted
        # page must never be one we are about to map as a prefix hit
        retained_hits = 0
        for b, pg in enumerate(shared):
            if self._allocator.is_retained(pg):
                self._allocator.revive(pg)
                retained_hits += 1
            else:
                self._allocator.share(pg)
            self.page_table[slot, b] = pg
        pages = self._allocator.alloc(blocks - m, reserve=reserve)
        if pages is None:
            # pool pressure: wait at the queue head until a retirement
            # frees pages (undo the speculative shared refs; revived
            # retained pages re-park, still indexed)
            if shared:
                self._deregister(self._allocator.release(
                    shared, retain=self.prefix_retain > 0))
                self.page_table[slot, :m] = -1
            return "wait", 0
        self.stats["retained_hits"] += retained_hits
        nxt = m
        if fork_src is not None:
            # COW fork: the prefill write at position t-1 (and decode
            # right after it) lands inside this shared block, so the
            # holder gets a private device-side copy up front — one page
            # copy instead of re-prefilling the block through every layer
            dst = pages[0]
            self.cache = self._copy_page(
                self.cache, jnp.int32(fork_src), jnp.int32(dst))
            self.page_table[slot, m] = dst
            self.stats["cow_forks"] += 1
            pages = pages[1:]
            nxt = m + 1
        for j, pg in enumerate(pages):
            self.page_table[slot, nxt + j] = pg
        self.slot_pages[slot] = blocks
        self.slot_reserved[slot] = reserve
        if start:
            self.stats["prefix_hits"] += m + (fork_src is not None)
            self.stats["prefix_tokens_saved"] += start
        if self.prefix_sharing:
            # index this prompt's full blocks; every NEWLY registered one
            # is a page this batch's prefill is about to write (already-
            # resident blocks — shared hits and a full-hit fork's source
            # key — register as False), so ready flips after the prefill
            for b in range(t // ps):
                page = int(self.page_table[slot, b])
                if self._register_block(eff, b, page):
                    pending_ready.append(page)
        self._note_peak()
        return "ok", start

    def _admit(self):
        with span("serve.admit"):
            while self.queue:
                free = [i for i, r in enumerate(self.slots) if r is None]
                if not free:
                    return
                batch: list[Request] = []
                batch_slots: list[int] = []
                batch_effs: list[np.ndarray] = []
                batch_starts: list[int] = []
                pending_ready: list[int] = []  # fork-eligible after prefill
                stalled = False
                while self.queue and len(batch) < len(free):
                    req = self.queue.popleft()
                    eff = self._eff_prompt(req)
                    if len(eff) >= self.max_len:
                        # bugfix: this used to trip an assert inside
                        # prefill and kill the engine mid-tick, losing
                        # every in-flight request
                        self._reject(
                            req,
                            f"prompt length {len(eff)} >= max_len "
                            f"{self.max_len}",
                        )
                        continue
                    slot = free[len(batch)]
                    start = 0
                    if self.kv_mode == "paged":
                        status, start = self._paged_bind(slot, req, eff,
                                                         pending_ready)
                        if status == "wait":
                            self.queue.appendleft(req)
                            stalled = True
                            break
                        if status == "reject":
                            continue
                    batch.append(req)
                    batch_slots.append(slot)
                    batch_effs.append(eff)
                    batch_starts.append(start)
                if not batch:
                    return
                if self._batched_prefill:
                    self._prefill_batch(batch_slots, batch, batch_effs,
                                        batch_starts)
                    # freshly-written full blocks may now serve as COW fork
                    # sources (their KV is on device) — unless the batch
                    # already freed them again (done-at-admit requests)
                    self._prefix_ready.update(
                        p for p in pending_ready if p in self._page_key)
                else:
                    for slot, req in zip(batch_slots, batch):
                        self._prefill_one(slot, req)
                if stalled:
                    return

    def _prefill_batch(self, slots: list[int], reqs: list[Request],
                       effs: list[np.ndarray], starts: list[int]):
        """Admit N requests with ONE forward: each carries only its
        UNSHARED prompt suffix, right-padded to a shared bucket, written
        at positions ``start..len-1``. Ring mode blends the filled rows
        into the slots' cache rows inside the jit, one row per request.
        Paged mode writes straight into the slots' pages through their
        page tables (the tables also expose the shared prefix pages, so
        suffix queries attend across the whole prompt), and spreads each
        suffix over the rows no request holds (``_prefill_rows``): every
        row writes its chunk's K/V into the pool before any row gathers
        keys, so a chunk's queries see the earlier chunks' keys through
        the same page table, as in one row."""
        lens = [len(e) - s for e, s in zip(effs, starts)]
        assert all(
            ln >= 1 for ln, s in zip(lens, starts) if s
        ), "sharing must leave >= 1 token to prefill"
        assert (
            max(len(e) for e in effs) < self.max_len
        ), "admission rejects over-long prompts"
        nb = self.max_batch
        paged = self.kv_mode == "paged"
        lb, plan = _prefill_rows(lens, nb if paged else len(lens),
                                 self.max_len)
        split = len(plan) - len(lens)
        # rows write through their target slot's page table, truncated to
        # the admitted batch's used page columns (pow2-bucketed like the
        # decode table — prefill attention work then scales with the
        # prompts' pages, not pages_per_slot). Width covers the SHARED
        # prefix blocks too: suffix queries attend to them.
        width = self._pow2_width(max(
            -(-len(e) // self.page_size) for e in effs))

        def stats():
            out = {"rows": len(lens), "tokens": sum(lens),
                   "keys": sum(n * s + n * (n + 1) // 2
                               for n, s in zip(lens, starts)),
                   "bucket": lb}
            if paged:
                out["width"] = width
            return out

        with span("serve.prefill", stats):
            tokens = np.zeros((nb, lb), np.int32)
            lens_a = np.zeros(nb, np.int32)
            starts_a = np.zeros(nb, np.int32)
            valid = np.zeros(nb, bool)
            for row, i, off, n in plan:
                st = starts[i] + off
                tokens[row, :n] = effs[i][st:st + n]
                lens_a[row] = n
                starts_a[row] = st
                valid[row] = True
            if paged:
                route = np.full((nb, width), -1, np.int32)
                for row, i, _, _ in plan:
                    route[row] = self.page_table[slots[i], :width]
                args = (self.params, jnp.asarray(tokens),
                        jnp.asarray(lens_a), jnp.asarray(starts_a),
                        jnp.asarray(route), jnp.asarray(valid), self.cache)
            else:
                # rows are blended into their target slot's ring row
                # in-jit
                route = np.zeros(nb, np.int32)
                for row, slot in enumerate(slots):
                    route[row] = slot
                args = (self.params, jnp.asarray(tokens),
                        jnp.asarray(lens_a), jnp.asarray(route),
                        jnp.asarray(valid), self.cache)
            args += (self._next_key(), jnp.float32(self.temperature))
            self._stamp_prefill(reqs)
            tok0, self.cache = self._prefill_step(*args)
            self.stats["prefill_calls"] += 1
            self.stats["prefill_tokens"] += sum(lens)
            self.stats["prefill_positions"] += nb * lb
            self.stats["prefill_split_rows"] += split
            tok0 = np.asarray(tok0)
        for row, (slot, req) in enumerate(zip(slots, reqs)):
            self._finish_admit(slot, req, effs[row], int(tok0[row]))

    def _prefill_one(self, slot: int, req: Request):
        """Per-slot exact-length prefill (recurrent families / reference
        mode; ring cache only). The slot's cache row is reset first:
        recurrent state and the KV ``pos`` ring of the previous occupant
        must not leak."""
        eff = self._eff_prompt(req)
        t = len(eff)
        assert t < self.max_len, "admission rejects over-long prompts"
        with span("serve.prefill", lambda: {
                "rows": 1, "tokens": t, "keys": t * (t + 1) // 2,
                "bucket": t}):
            fresh = init_cache(self.cfg, 1, self.max_len,
                               kv_bits=self._kv_bits)
            self.cache = jax.tree.map(
                lambda c, f: c.at[slot:slot + 1].set(f.astype(c.dtype)),
                self.cache, fresh,
            )
            tokens = jnp.asarray(eff, jnp.int32)[None]
            positions = jnp.arange(t, dtype=jnp.int32)[None]
            row_cache = jax.tree.map(lambda c: c[slot:slot + 1], self.cache)
            self._stamp_prefill([req])
            logits, row_cache2, _ = forward(
                self.params, tokens, self.cfg,
                positions=positions, cache=row_cache, cache_index=0,
            )
            self.cache = jax.tree.map(
                lambda c, r: c.at[slot:slot + 1].set(r), self.cache,
                row_cache2,
            )
            self.stats["per_row_prefill_calls"] += 1
            self.stats["prefill_tokens"] += t
            self.stats["prefill_positions"] += t
            tok0 = int(steps_mod.sample_tokens(
                logits[:, -1], self._next_key(),
                jnp.float32(self.temperature),
                fold=jnp.asarray([t - 1], jnp.int32),
            )[0])
        self._finish_admit(slot, req, eff, tok0)

    def _stamp_prefill(self, reqs) -> None:
        """``t_prefill``: the first prefill launch that holds a request
        ends its wait (a preemption-resume keeps the first stamp)."""
        now = self.clock()
        for req in reqs:
            if req.t_prefill is None:
                req.t_prefill = now

    def _finish_admit(self, slot: int, req: Request, eff: np.ndarray,
                      tok0: int):
        """Prefill's last logits yield the FIRST generated token (standard
        prefill->decode handoff). A resumed request instead discards the
        handoff sample — every one of its tokens was already sampled
        before preemption (greedy makes the resample identical anyway) —
        and continues decoding from its stored last token."""
        prompt_len = len(eff)
        if req._seq < 0:
            self._seq_counter += 1
            req._seq = self._seq_counter
        if req.t_admit is None:  # resume keeps the FIRST admission stamp
            req.t_admit = self.clock()
        if req.resume_prompt is not None:
            req.resume_prompt = None
            self.slots[slot] = req
            self.slot_pos[slot] = prompt_len
            self.slot_next[slot] = req.generated[-1]
            self.active[slot] = True
            self._slot_seq[slot] = req._seq
            return
        req.generated.append(tok0)
        if req.t_first_token is None:
            req.t_first_token = self.clock()
        if req.done:
            self._release_pages(slot)
            req.t_retire = self.clock()
            self.finished.append(req)
            return
        self.slots[slot] = req
        self.slot_pos[slot] = prompt_len
        self.slot_next[slot] = tok0
        self.active[slot] = True
        self._slot_seq[slot] = req._seq

    # -- paged allocation --------------------------------------------------
    def _note_peak(self):
        used = self._allocator.held_pages
        if used > self.stats["peak_pages_used"]:
            self.stats["peak_pages_used"] = used

    def _release_pages(self, slot: int):
        """Drop every page reference a slot holds (and cancel its unused
        growth reservation); pages whose last reference this was return
        to the free list and leave the prefix index — the retire and
        preempt path. With retention configured, last-reference INDEXED
        pages park in the allocator's LRU retention pool instead (their
        index entries and device KV stay valid for later prefix hits);
        unindexed pages (partial tails, COW forks) free as before."""
        if self.kv_mode != "paged":
            return
        held = self.page_table[slot][self.page_table[slot] >= 0]
        if held.size:
            if self.prefix_retain > 0:
                indexed = [int(p) for p in held if int(p) in self._page_key]
                rest = [int(p) for p in held
                        if int(p) not in self._page_key]
                freed = self._allocator.release(indexed, retain=True)
                freed += self._allocator.release(rest)
            else:
                freed = self._allocator.release(held)
            self._deregister(freed)
        if self.slot_reserved[slot]:
            self._allocator.cancel_reservation(int(self.slot_reserved[slot]))
        self.page_table[slot] = -1
        self.slot_pages[slot] = 0
        self.slot_reserved[slot] = 0

    def _retire_slot(self, i: int, req: Request):
        self._release_pages(i)
        if req.t_retire is None:
            req.t_retire = self.clock()
        self.finished.append(req)
        self.slots[i] = None
        self.active[i] = False

    def _preempt(self, j: int):
        """Page-level preemption: release slot ``j``'s page refs and
        re-queue its request for recompute-resume. The tokens it already
        generated become part of the re-prefill prompt (the written-token
        sequence), so when pages free up it completes token-identically —
        preemption trades latency for correctness where force-retire
        traded away the output."""
        req = self.slots[j]
        req.resume_prompt = self._written_tokens(j)
        self._release_pages(j)
        self.slots[j] = None
        self.active[j] = False
        self.queue.appendleft(req)
        self.stats["preemptions"] += 1

    def _alloc_or_preempt(self, i: int) -> Optional[int]:
        """Allocate one page for slot ``i``'s next write. Under pool
        pressure, preempt the YOUNGEST resident request (latest admission
        sequence — its recompute costs the least and the oldest request
        keeps strictly progressing, so there is no livelock) until a page
        frees or slot ``i`` itself is the victim. A request that holds
        the whole pool alone and still needs more can never complete and
        is force-retired truncated — the only remaining truncation path.
        Returns the page, or None if slot ``i`` no longer needs it."""
        while True:
            pages = self._allocator.alloc(1)
            if pages is not None:
                return pages[0]
            active = np.nonzero(self.active)[0]
            if len(active) <= 1:
                req = self.slots[i]
                req.truncated = True
                self._retire_slot(i, req)
                self.stats["oop_retired"] += 1
                return None
            victim = max(active, key=lambda j: self._slot_seq[j])
            self._preempt(int(victim))
            if victim == i:
                return None

    def _claim_reserved_page(self, i: int) -> Optional[int]:
        """Claim one page from slot ``i``'s growth reservation, or None
        if it has none left. Never fails when it returns a page — the
        admission horizon guarantees the reservation covers every write
        the request can make (speculative lookahead included)."""
        if self.slot_reserved[i] <= 0:
            return None
        page = self._allocator.claim_reserved(1)[0]
        self.slot_reserved[i] -= 1
        return page

    def _bind_next_page(self, i: int, page: int) -> None:
        """Append ``page`` as slot ``i``'s next block — the ONE place the
        grant bookkeeping (table entry, allocated count, stat) lives, so
        plain-decode grants and speculative lookahead grants can never
        desynchronize."""
        blk = int(self.slot_pages[i])
        self.page_table[i, blk] = page
        self.slot_pages[i] = blk + 1
        self.stats["page_grants"] += 1

    def _grant_pages(self):
        """Before the tick's write at ``slot_pos[i]``, make sure the page
        covering it exists AND is exclusively held. Reservation-admitted
        slots claim from their reservation (never fails); otherwise the
        grant may preempt younger slots (see ``_alloc_or_preempt``).
        Copy-on-write happens at ADMISSION (``_paged_bind`` forks matched
        partial tails before the prefill write), so by the time decode
        runs, the cursor's page is always exclusive — asserted below."""
        with span("serve.grant"):
            for i in np.nonzero(self.active)[0]:
                if not self.active[i]:
                    continue  # preempted while serving an earlier grant
                block = int(self.slot_pos[i]) // self.page_size
                if block < int(self.slot_pages[i]):
                    # the cursor page must be exclusively held: shared full
                    # blocks always end at or before the prefill start (the
                    # cursor only moves forward from there), partial tails
                    # are COW-forked at admission, and decode-completed
                    # blocks are indexed only once the cursor has left them.
                    # Any future mapping path that breaks this must fork the
                    # page BEFORE the write (see _paged_bind) — fail loudly.
                    page = int(self.page_table[i, block])
                    assert self._allocator.refcount[page] == 1, (
                        "write cursor reached a shared page", i, block, page)
                    continue
                page = self._claim_reserved_page(int(i))
                if page is None:
                    page = self._alloc_or_preempt(int(i))
                    if page is None:
                        continue
                self._bind_next_page(int(i), page)
            self._note_peak()

    def _spec_lens(self) -> np.ndarray:
        """Per-slot draft budgets for this tick, with lookahead page
        grants: slot ``i`` may draft ``spec_len[i]`` tokens, so the
        verify writes positions ``pos..pos + spec_len[i]`` — every page
        covering that span must exist before the step runs. The budget
        is capped by the engine K, the request's remaining tokens (the
        reservation horizon already covers exactly that span), the cache
        end, and — under optimistic admission — by what the pool can
        grant WITHOUT preempting: lookahead is an optimization and must
        never evict a resident request to happen."""
        ps = self.page_size
        spec = np.zeros(self.max_batch, np.int32)
        for i in np.nonzero(self.active)[0]:
            req = self.slots[i]
            pos = int(self.slot_pos[i])
            want = min(self.speculative,
                       req.max_tokens - len(req.generated) - 1,
                       self.max_len - 1 - pos)
            want = max(0, want)
            last_block = (pos + want) // ps
            while int(self.slot_pages[i]) <= last_block:
                page = self._claim_reserved_page(int(i))
                if page is None:
                    got = self._allocator.alloc(1)  # lookahead: no preempt
                    if got is None:
                        break
                    page = got[0]
                self._bind_next_page(int(i), page)
            cap = int(self.slot_pages[i]) * ps - 1 - pos
            spec[i] = min(want, max(0, cap))
        self._note_peak()
        return spec

    def _pow2_width(self, pages: int) -> int:
        """Page-table width bucket covering ``pages``: next power of two,
        capped at pages_per_slot — bounds jit retraces to O(log) shapes.
        Shared by prefill routing and the decode table so both warm the
        same shapes."""
        width = 1
        while width < max(1, pages):
            width *= 2
        return min(width, self.pages_per_slot)

    def _active_table(self) -> np.ndarray:
        """Page table truncated to the page columns actually in use this
        tick (pow2-bucketed). Decode attention then scales with the
        pages slots HOLD, not with ``max_len`` — the ring and the
        full-width gather always pay for max_len keys. Dropped columns
        are unallocated (-1) or beyond every write cursor, so the
        attention result is unchanged."""
        width = self._pow2_width(int(self.slot_pages.max()))
        return self.page_table[:, :width]

    # -- decode ------------------------------------------------------------
    def _advance_slot(self, i: int, tok: int) -> bool:
        """Consume ONE generated token for slot ``i``: append, advance the
        write cursor, index any page the cursor just completed (so a
        follow-up request whose prompt extends this request's prompt +
        generation shares it — the multi-turn continuation pattern), and
        retire the slot when done or out of cache. Returns True if the
        slot retired — a speculative tick stops consuming its accepted
        run there. Bugfix kept from PR 2: forced retirement at cache
        exhaustion sets ``truncated`` so it stays distinguishable from
        natural completion."""
        req = self.slots[i]
        req.generated.append(tok)
        self.slot_pos[i] += 1
        self.slot_next[i] = tok
        pos = int(self.slot_pos[i])
        ps = self.page_size
        if self.prefix_sharing and pos % ps == 0:
            b = pos // ps - 1
            page = int(self.page_table[i, b])
            if page >= 0 and self._register_block(
                    self._written_tokens(i), b, page):
                self._prefix_ready.add(page)
        if req.done or pos >= self.max_len:
            if not req.done:
                req.truncated = True
            self._retire_slot(i, req)
            return True
        return False

    def step(self):
        """One engine tick: admit, grant pages, ONE fused decode (or one
        fused speculative draft+verify), retire."""
        with span("serve.tick", lambda: {
                "clock": self.clock(), "active": int(self.active.sum())}):
            self._admit()
            if not self.active.any():
                return False
            if self.kv_mode == "paged":
                self._grant_pages()
                if not self.active.any():
                    return True  # progress: slots were preempted or retired
            if self.decode_mode == "ragged" and self.speculative:
                return self._step_speculative()
            if self.decode_mode == "ragged":
                with span("serve.decode", lambda: self._decode_stats(0)):
                    next_ids, self.cache = self._ragged_step(
                        *self._decode_args(self._next_key()))
                    self._count_decode()
                    next_ids = np.asarray(next_ids)  # the ONE host sync
            else:
                next_ids = self._decode_rows_reference()
            with span("serve.advance"):
                for i in np.nonzero(self.active)[0]:
                    self._advance_slot(int(i), int(next_ids[i]))
            return True

    def _decode_stats(self, spec: int) -> dict:
        """``serve.decode`` span values: active rows, the keys they
        attend to (each row's position + 1), the page-table width."""
        act = self.active
        out = {"rows": int(act.sum()),
               "keys": int((self.slot_pos[act].astype(np.int64) + 1).sum()),
               "spec": spec}
        if self.kv_mode == "paged":
            out["width"] = self._active_table().shape[1]
        return out

    def _count_decode(self) -> None:
        """Count one decode step (plain or speculative) over the slots."""
        self.stats["decode_steps"] += 1
        self.stats["decode_rows"] += int(self.active.sum())
        self.stats["decode_slots"] += self.max_batch

    def _decode_args(self, key) -> list:
        """Arguments of the plain (non-speculative) ragged decode step
        for the current slot state."""
        args = [
            self.params,
            jnp.asarray(self.slot_next[:, None]), self.cache,
            jnp.asarray(self.slot_pos), jnp.asarray(self.active),
        ]
        if self.kv_mode == "paged":
            args.append(jnp.asarray(self._active_table()))
        return args + [key, jnp.float32(self.temperature)]

    def decode_program(self):
        """The plain decode tick's program, compiled for the current slot
        state (a ``jax.stages.Compiled``: ``as_text()`` shows which
        kernels the tick runs). Changes no engine state."""
        return self._ragged_step.lower(*self._decode_args(self._key)).compile()

    def _step_speculative(self) -> bool:
        """One speculative tick: grant lookahead pages, run the fused
        draft(K)+verify step, then consume each slot's accepted run plus
        the verify's own token — between 1 and K+1 tokens per slot per
        host sync. Greedy consumption is token-identical to plain decode
        (the verify emits the target argmax at every position)."""
        spec_len = self._spec_lens()
        if not self.active.any():
            return True
        with span("serve.decode", lambda: self._decode_stats(1)):
            out, n_acc, self.cache = self._spec_step(
                self.params, self._draft_params,
                jnp.asarray(self.slot_next[:, None]), self.cache,
                jnp.asarray(self.slot_pos), jnp.asarray(self.active),
                jnp.asarray(self._active_table()), jnp.asarray(spec_len),
                self._next_key(), jnp.float32(self.temperature),
            )
            self._count_decode()
            self.stats["spec_ticks"] += 1
            out = np.asarray(out)      # the ONE host sync per tick
            n_acc = np.asarray(n_acc)
        with span("serve.advance"):
            for i in np.nonzero(self.active)[0]:
                self.stats["draft_proposed"] += int(spec_len[i])
                used = 0
                for m in range(int(n_acc[i]) + 1):
                    used = m + 1
                    if self._advance_slot(int(i), int(out[i, m])):
                        break
                # accept rate counts drafts that became OUTPUT tokens: a
                # slot retiring mid-run (eos / max_len) discards the rest
                # of its accepted run, so the unconsumed tail must not
                # inflate the reported rate
                self.stats["draft_accepted"] += min(used, int(n_acc[i]))
        return True

    def _decode_rows_reference(self) -> np.ndarray:
        """Reference per-row decode (the old fallback): one ``forward`` per
        active slot. Kept for token-equivalence tests and as the benchmark
        baseline — never used by decode_mode='ragged'."""
        out = np.full(self.max_batch, -1, np.int64)
        temp = jnp.float32(self.temperature)
        for i in range(self.max_batch):
            if not self.active[i]:
                continue
            row_cache = jax.tree.map(lambda c: c[i:i + 1], self.cache)
            tok = jnp.asarray(self.slot_next[i:i + 1], jnp.int32)[None]
            pos = jnp.asarray(self.slot_pos[i:i + 1], jnp.int32)[None]
            lg, row_cache2, _ = forward(
                self.params, tok, self.cfg,
                positions=pos, cache=row_cache,
                cache_index=int(self.slot_pos[i]),
            )
            self.cache = jax.tree.map(
                lambda c, r: c.at[i:i + 1].set(r), self.cache, row_cache2
            )
            self.stats["per_row_forward_calls"] += 1
            out[i] = int(steps_mod.sample_tokens(
                lg[:, -1], self._next_key(), temp,
                fold=jnp.asarray(self.slot_pos[i:i + 1], jnp.int32),
            )[0])
        return out

    def reset(self):
        """Clear all scheduler + cache state but keep the compiled steps
        (benchmark warmup / epoch reuse without paying compilation twice)."""
        self.cache = self._init_cache()
        self.queue.clear()
        self.slots = [None] * self.max_batch
        self.slot_pos[:] = 0
        self.slot_next[:] = 0
        self.active[:] = False
        self.finished = []
        self._allocator.reset()
        self.page_table[:] = -1
        self.slot_pages[:] = 0
        self.slot_reserved[:] = 0
        self._slot_seq[:] = 0
        self._seq_counter = 0
        self._prefix_index.clear()
        self._page_key.clear()
        self._page_parent.clear()
        self._page_block.clear()
        self._prefix_children.clear()
        self._prefix_ready.clear()
        for k in self.stats:
            self.stats[k] = 0

    def run_to_completion(self, max_ticks: int = 10_000):
        """Tick until every submitted request retired, or ``max_ticks``.

        Bugfix: hitting the tick budget used to return ``self.finished``
        while SILENTLY DROPPING queued and in-flight requests — neither
        ``truncated`` nor ``error`` set, so a hung engine was
        indistinguishable from success. Stragglers are now retired with
        ``error="tick budget exhausted"`` (in-flight ones keep their
        partial ``generated`` tokens), counted in
        ``stats["tick_budget_exhausted"]``, and every submitted request
        is accounted for in the returned ``finished`` list."""
        ticks = 0
        while (
            self.queue or any(s is not None for s in self.slots)
        ) and ticks < max_ticks:
            self.step()
            ticks += 1
        if self.queue or any(s is not None for s in self.slots):
            self._exhaust_tick_budget()
        return self.finished

    def _exhaust_tick_budget(self):
        """Retire every straggler (in-flight slots first, then the
        queue) with ``error`` set — the tick budget ran out."""
        reason = "tick budget exhausted"
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            req.error = reason
            self.stats["tick_budget_exhausted"] += 1
            self._retire_slot(i, req)
        while self.queue:
            req = self.queue.popleft()
            req.error = reason
            self.stats["tick_budget_exhausted"] += 1
            if req.t_retire is None:
                req.t_retire = self.clock()
            self.finished.append(req)
