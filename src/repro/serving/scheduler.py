"""Continuous-batching constrained scheduler over a paged KV pool.

A fixed-capacity decode batch whose rows (KV "slots") are admitted and
evicted independently: finished requests free their slot immediately and
the next waiting request is prefilled into it, so the batch stays full
under load instead of draining to the slowest request.

The unit of admission is a :class:`~repro.serving.request.Request` —
``submit`` takes one (or a bare prompt string for the engine-default
request) and every per-row policy rides on the resulting Session, never
on an engine-global config.  One batch therefore freely mixes rows with
different grammars (each row's checker walks its own grammar's shared
TreeCache from the engine registry), different constraint modes
(domino/naive/online/unconstrained — unconstrained rows stage the
all-ones sentinel row in the same packed mask buffer), different EOS ids
and token budgets (checked per row in the tick loop), different
temperatures and seeds (greedy rows select through the fused device
kernel; sampled rows draw host-side from their own per-request RNG), and
different speculation knobs (the verify window is sized to the widest
resident row's ``spec_s``; non-speculative rows just skip proposing).
Per-row outputs are bitwise-identical to running each request alone on a
single-grammar engine.

Design points (ISSUE 1 tentpole):
 - admission prefills each request at B=1 and scatters the resulting row
   cache into the slot — this is what makes recurrent (SSM) and
   ring-buffer (SWA) rows correct: their state never sees pad tokens.
   Full-attention / MLA admissions are additionally padded to
   power-of-two length buckets (with a true-length validity marker) so
   the prefill compiles O(log max_len) programs under heavy traffic
   instead of one per distinct prompt length;
 - every decode step runs ONE batched forward over all slots; grammar
   masks are applied device-side through the fused
   ``kernels/masked_sample`` Pallas op.  Masks are PACKED end to end
   (ISSUE 4 tentpole): checkers assemble a ``ceil(V/32)``-word uint32
   bitset by OR-ing precomputed tree-node segments (memoized per
   immutable grammar state on the shared TreeCache — a recurring state
   is a dict lookup, counted in ``mask_cache_hits``), the scheduler
   stages rows into ONE persistent ``(capacity, ceil(V/32))`` uint32
   buffer (zero per-tick allocation; vacant slots keep a precomputed
   sentinel word row), and the kernel unpacks words in-register fused
   with the argmax — so the host ships V/8 mask bytes per slot per tick
   (8x less than the old (B, V) int8 staging array) and reads back (B,)
   token ids;
 - the forward is dispatched asynchronously and the host builds the NEXT
   step's grammar masks while the device executes (ISSUE 2 tentpole):
   mask_time moves off the step critical path — it still accrues
   per-session, with the hidden portion reported as ``mask_overlap_s``.
   Under ``opportunistic`` checking the prebuild is adaptive: it is
   skipped for slots whose previous tick's raw argmax passed the O(token)
   legality check (the mask would go unread), and resumes the tick after
   an intervention;
 - paged KV (ISSUE 3 tentpole): on pageable architectures (pure
   full-attention / MLA stacks) the slots do NOT own contiguous
   ``max_len`` cache stripes.  The cache is a shared pool of
   ``page_size``-token pages plus an (B, max_pages) block table per slot
   (models/kvcache.py); a host-side free-list allocator hands pages out
   at admission (``ceil((prompt+1)/page_size)`` — not a full-length
   stripe), grows rows page-by-page as they decode, shrinks them when
   speculative rollback rewinds the frontier, and frees them the moment
   a request finishes.  Every program that takes the pool donates it,
   and the decode step updates it in place (``kvcache.pool_inplace``),
   so no step copies a pool.  Admission blocks on pool exhaustion (the
   waiting queue provides backpressure), and mid-flight exhaustion falls
   back to vLLM-style recompute preemption: the youngest resident row
   returns its pages and re-enters the queue front, to be re-prefilled
   (prompt + generated prefix) when pages free up — the checker state
   rides along, so outputs are unchanged;
 - speculative decoding (paper §3.6) runs per-row: one (B, 1+s) decode
   verifies every row's proposal chain; rows on full-attention/MLA archs
   roll their per-row cache length back (returning now-empty pages),
   rows on SSM/SWA archs re-feed their accepted tokens from the
   pre-speculation cache — grouped by accepted length, so each group is
   one gather/decode/scatter round instead of a B=1 decode per row;
 - sessions on the same grammar share that grammar's TreeCache (and all
   sessions share the engine's count model); call ``warm()`` to run the
   offline ``precompute()`` pass over every registered grammar before
   serving;
 - fault tolerance (ISSUE 7 tentpole): every request ends in exactly one
   explicit terminal status (``GenerationResult.status``: ok | dead_end |
   deadline_exceeded | cancelled | rejected | internal_error) and one
   request's failure never perturbs its batch-mates.  Every tick starts
   with a lifecycle sweep (``_reap``): cancellation requested via
   ``cancel(rid)`` and per-request deadlines (``DecodeParams.deadline_s``,
   or the scheduler-wide ``default_deadline_s`` / ``queue_timeout_s``)
   take effect here, freeing the slot and pages immediately.  Failures
   are quarantined to the offending row: non-finite logits from the
   device step fail only that row (detected before selection), a
   checker / mask-build exception — including during the overlapped
   prebuild and speculative verification — evicts that session with
   ``internal_error`` while the tick completes for everyone else, an
   admission whose demand can NEVER be met (prompt pages > pool
   capacity, prompt > max_len) is rejected instead of blocking the FIFO
   queue forever, and ``queue_limit`` bounds the waiting queue by
   shedding overflow with ``rejected``.  A seeded
   :class:`~repro.serving.faults.FaultInjector` can be wired to the
   documented injection sites (one per tick phase), and
   ``debug_invariants=True`` audits free-list/block-table consistency
   and the slot<->session bijection at every tick boundary — surviving
   rows are asserted bitwise-identical to fault-free runs by the chaos
   suite (tests/test_faults.py).
 - tracing: :func:`~repro.serving.tracing.span` names each phase of a
   tick — ``serve.tick`` around the whole of ``step()``; ``reap``,
   ``admit`` (with a ``prefill`` per admitted request), ``readback``,
   ``select`` (with a ``mask`` per mask build), ``commit`` (with an
   ``advance`` per checker advance), ``dispatch``, ``prebuild`` and
   ``wait`` inside it — as host spans that a profiler session records
   on the device trace's clock.

Token selection is identical to the single-request engine path at
temperature 0 (greedy masked argmax, ties to the lowest index), so
per-request outputs match ``ServingEngine.generate`` token-for-token.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
import time
from typing import Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmask
from repro.core.analysis import OFF_FRONTIER
from repro.core.domino import DominoDecoder
from repro.kernels.masked_sample.kernel import masked_argmax_pallas_packed
from repro.kernels.masked_sample.ops import (masked_argmax,
                                             masked_sample_packed)
from repro.models import kvcache
from repro.serving.faults import (FaultInjector, InjectedFault,
                                  InvariantViolation, check_invariants)
from repro.serving.journal import JournalEntry, TokenJournal
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import (ConstraintSpec, DecodeParams, Request,
                                   select_token)
from repro.serving.session import GenerationResult, Session
from repro.serving.supervisor import DegradationSupervisor
from repro.serving.tracing import span


# -- page allocation -----------------------------------------------------------


class PagePool:
    """Host-side free-list allocator over pool page ids.

    Page 0 is the reserved trash page (vacant block-table entries point at
    it, so padded decode writes from empty slots land somewhere harmless);
    pages 1..n_pages-1 are allocatable.  LIFO reuse: a freed page is the
    next one handed out, which keeps the hot pages hot and makes
    stale-read bugs surface immediately under test.

    Pages carry refcounts so the prefix cache can share them: ``alloc``
    hands out pages at refcount 1, ``retain`` adds a reference (a radix
    node adopting the page, or a block table mapping a cached page) and
    ``release``/``free`` drops one — the page returns to the free list
    only when the LAST reference goes.  Exclusive ownership is the
    refcount-1 special case, so every pre-cache call site keeps its exact
    semantics.
    """

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self._free: List[int] = list(range(1, n_pages))
        self._ref = np.zeros(n_pages, np.int32)

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """All-or-nothing: n page ids, or None if the pool can't cover
        the request (partial grants would deadlock admission)."""
        if n > len(self._free):
            return None
        got = self._free[-n:][::-1] if n else []
        if n:
            del self._free[-n:]
        for p in got:
            self._ref[p] = 1
        return got

    def retain(self, pages) -> None:
        """Add one reference to already-allocated pages."""
        for p in pages:
            p = int(p)
            assert self._ref[p] > 0, f"retain of unallocated page {p}"
            self._ref[p] += 1

    def release(self, pages) -> None:
        """Drop one reference per page; last reference frees the page."""
        for p in pages:
            p = int(p)
            assert self._ref[p] > 0, f"release of unallocated page {p}"
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)
        assert len(self._free) <= self.n_pages - 1

    # historical name: exclusive owners "free" their pages (refcount 1).
    free = release

    def refcount(self, page: int) -> int:
        return int(self._ref[int(page)])


# -- per-slot cache surgery ----------------------------------------------------
#
# Cache pytree layout (models/kvcache.py): {"len", "head": [block...],
# "group": {"b#": stacked blocks (leading reps axis)}, "tail": [block...]}.
# Dense layouts carry batch on leaf axis 0 (head/tail) or 1 (group); paged
# layouts carry pool pages there instead, with the per-slot block table at
# cache["pages"].  "len" is (B,) in a ragged batch cache and scalar in a
# B=1 row cache.


def _scatter_row(dst, src, slot):
    """Write a B=1 row cache ``src`` into row ``slot`` of batch cache."""
    out = dict(dst)
    out["len"] = dst["len"].at[slot].set(src["len"])
    out["head"] = [jax.tree.map(lambda d, s: d.at[slot].set(s[0]), dc, sc)
                   for dc, sc in zip(dst["head"], src["head"])]
    out["tail"] = [jax.tree.map(lambda d, s: d.at[slot].set(s[0]), dc, sc)
                   for dc, sc in zip(dst["tail"], src["tail"])]
    out["group"] = {
        k: jax.tree.map(lambda d, s: d.at[:, slot].set(s[:, 0]),
                        dst["group"][k], src["group"][k])
        for k in dst["group"]}
    return out


def _scatter_row_paged(dst, src, slot, page_ids, page_size: int):
    """Write a dense B=1 row cache ``src`` into the pool pages
    ``page_ids`` ((max_pages,) int32) of paged batch cache ``dst``.

    ``page_ids`` is always padded to the full table width with trash-page
    zeros so this jit compiles ONCE (a (n_pg,)-shaped operand would
    recompile the whole-cache donating scatter per distinct admission
    page count): the row stripe is copied page-by-page into (generally
    non-contiguous) pool rows, and every stripe page beyond the
    allocation collapses onto pool row 0, whose contents are garbage by
    contract.  The block table itself is host-owned (the scheduler
    uploads it separately), so only ``len`` and the pool leaves change.
    """
    n_pg = page_ids.shape[0]

    def p0(d, s):          # head/tail: (P, ps, ...) <- (1, T, ...)
        blk = s[0, :n_pg * page_size].reshape(
            (n_pg, page_size) + s.shape[2:])
        return d.at[page_ids].set(kvcache.pad_lanes(blk, d.shape[-1]))

    def p1(d, s):          # group: (reps, P, ps, ...) <- (reps, 1, T, ...)
        blk = s[:, 0, :n_pg * page_size].reshape(
            (s.shape[0], n_pg, page_size) + s.shape[3:])
        return d.at[:, page_ids].set(kvcache.pad_lanes(blk, d.shape[-1]))

    out = dict(dst)
    out["len"] = dst["len"].at[slot].set(src["len"])
    out["head"] = [jax.tree.map(p0, dc, sc)
                   for dc, sc in zip(dst["head"], src["head"])]
    out["tail"] = [jax.tree.map(p0, dc, sc)
                   for dc, sc in zip(dst["tail"], src["tail"])]
    out["group"] = {k: jax.tree.map(p1, dst["group"][k], src["group"][k])
                    for k in dst["group"]}
    return out


def _gather_rows(src, idx):
    """Extract rows ``idx`` (traced (K,) int32) of a batch cache as a
    B=K ragged cache (``len`` stays a vector, so the refeed decode takes
    the per-row ragged write path)."""
    def g0(a):
        return jnp.take(a, idx, axis=0)

    def g1(a):
        return jnp.take(a, idx, axis=1)

    return {
        "len": jnp.take(src["len"], idx, axis=0),
        "head": [jax.tree.map(g0, c) for c in src["head"]],
        "tail": [jax.tree.map(g0, c) for c in src["tail"]],
        "group": {k: jax.tree.map(g1, v) for k, v in src["group"].items()},
    }


def _scatter_rows(dst, src, idx):
    """Write a B=K cache ``src`` back into rows ``idx`` of batch cache."""
    def s0(d, s):
        return d.at[idx].set(s)

    def s1(d, s):
        return d.at[:, idx].set(s)

    out = dict(dst)
    out["len"] = dst["len"].at[idx].set(src["len"])
    out["head"] = [jax.tree.map(s0, dc, sc)
                   for dc, sc in zip(dst["head"], src["head"])]
    out["tail"] = [jax.tree.map(s0, dc, sc)
                   for dc, sc in zip(dst["tail"], src["tail"])]
    out["group"] = {k: jax.tree.map(s1, dst["group"][k], src["group"][k])
                    for k in dst["group"]}
    return out


# admission: the old batch cache is dropped on assignment, so donate it —
# without donation every admission copies the whole B x max_len cache
_scatter_row_donate = jax.jit(_scatter_row, donate_argnums=(0,))
# refeed fixup: the pre-speculation snapshot may share untouched leaves
# (e.g. cross-attention xk/xv) with the current cache, so no donation
_scatter_rows_jit = jax.jit(_scatter_rows)
_gather_rows_jit = jax.jit(_gather_rows)


def _bucket_len(n: int, cap: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor), clamped to ``cap``.

    A prefix-cache hit's tail takes one page as ``floor`` and ``max_len``
    as ``cap``, not ``max_len - start``: a cap for each start would give
    every start near the end of the cache a program of its own, lowered
    while serving, and pads past ``max_len`` are written to the trash
    page (``transformer._page_translate``)."""
    p = 1
    while p < max(n, floor):
        p *= 2
    return min(p, cap)


def _window_buckets(floor: int, longest: int, cap: int) -> List[int]:
    """Every padded length ``_bucket_len`` gives a hit's tail of 1 to
    ``longest`` tokens."""
    out, n = [], 1
    while True:
        out.append(_bucket_len(n, cap, floor))
        if out[-1] >= min(longest, cap):
            return sorted(set(out))
        n = out[-1] + 1


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


class ContinuousBatchingScheduler:
    """Admits requests into a fixed-capacity constrained decode batch.

    ``overlap`` pipelines host mask construction with device execution;
    ``bucket_prefill`` pads full-attention/MLA admissions to power-of-two
    prompt lengths; ``adaptive_prebuild`` skips overlapped prebuilds for
    opportunistic-mode slots whose previous tick did not intervene.  All
    default on; they are observationally pure (token-for-token identical
    output) and exist as flags only so tests and benchmarks can measure
    them.

    Paged KV: ``paged`` defaults to auto — on for architectures whose
    every cache-bearing block is full-attention / MLA, off otherwise
    (ring/recurrent rows keep dense state).  ``page_size`` is the pool
    page length in tokens (the fused kernel's BLOCK_T); ``n_pages`` sizes
    the pool — default is capacity-equivalent
    (capacity * max_len / page_size + trash page), and sizing it SMALLER
    is the point: admission needs only each request's actual pages, so a
    sub-capacity pool still serves a full batch of short requests where
    the contiguous layout would hold ``pool_tokens / max_len`` rows.
    """

    def __init__(self, engine, capacity: int = 4, overlap: bool = True,
                 bucket_prefill: bool = True,
                 paged: Optional[bool] = None, page_size: int = 64,
                 n_pages: Optional[int] = None,
                 adaptive_prebuild: bool = True,
                 queue_limit: Optional[int] = None,
                 queue_timeout_s: Optional[float] = None,
                 default_deadline_s: Optional[float] = None,
                 fault_injector: Optional[FaultInjector] = None,
                 debug_invariants: bool = False,
                 device_loop: bool = False, sync_n: int = 8,
                 journal: Optional[TokenJournal] = None,
                 supervisor: Optional[DegradationSupervisor] = None,
                 prefix_cache: bool = False):
        self.eng = engine
        self.capacity = max(1, capacity)
        self.overlap = overlap
        self.bucket_prefill = bucket_prefill
        self.adaptive_prebuild = adaptive_prebuild
        # fault-tolerance policy: a bounded waiting queue sheds overflow
        # with `rejected` instead of growing without bound; queued
        # requests older than queue_timeout_s shed the same way; a
        # request's own DecodeParams.deadline_s (falling back to
        # default_deadline_s) bounds its total wall time
        self.queue_limit = queue_limit
        self.queue_timeout_s = queue_timeout_s
        self.default_deadline_s = default_deadline_s
        self.injector = fault_injector
        self.debug_invariants = debug_invariants
        self.waiting: "collections.deque[Session]" = collections.deque()
        self.slots: List[Optional[Session]] = [None] * self.capacity
        can_page = kvcache.pageable(engine.model.cfg)
        if paged and not can_page:
            # only the auto default may silently fall back to dense —
            # an explicit request with (possibly undersized) pool sizing
            # must not quietly allocate capacity x max_len stripes
            raise ValueError(
                f"{engine.model.cfg.arch_id}: paged KV requires a pure "
                "full-attention/MLA stack (ring/recurrent rows keep "
                "dense state); use paged=None for auto")
        self.paged = can_page if paged is None else bool(paged)
        if self.paged:
            ps = min(page_size, engine.max_len)
            self.page_size = ps
            self.max_pages = engine.max_len // ps
            self.n_pages = (kvcache.default_n_pages(
                self.capacity, engine.max_len, ps)
                if n_pages is None else int(n_pages))
            self.pool = PagePool(self.n_pages)
            self.cache = engine.model.init_cache(
                self.capacity, engine.max_len, page_size=ps,
                n_pages=self.n_pages)
            # host mirror of the device block table; uploaded (tiny
            # (B, max_pages) int32) whenever the allocator moves pages
            self._page_tbl = np.zeros((self.capacity, self.max_pages),
                                      np.int32)
            self._n_pages_row = np.zeros(self.capacity, np.int32)
            self._pages_dirty = False
            self._scatter_paged = jax.jit(
                functools.partial(_scatter_row_paged, page_size=ps),
                donate_argnums=(0,))
            # the batched decode donates the cache, dropped on assignment
            # (model.decode_step itself: the program stays jit_decode_step);
            # the engine's own _decode keeps its B=1 caches
            self._decode = jax.jit(engine.model.decode_step,
                                   donate_argnums=(1,))
            # a prefix-cache hit's tail: one window over a B=1 view of the
            # donated pool, a program of its own (jit_prefill_window)
            self._prefill_window = jax.jit(engine.model.prefill_window,
                                           donate_argnums=(1,))
            # per-slot count of block-table entries that map CACHED
            # (shared, read-only) pages — pages [0, n) of the row's
            # table.  The write frontier always sits strictly above the
            # shared region (lookup never matches the final page), so a
            # decode/rollback/refeed write can never touch a shared page.
            self._n_shared_row = np.zeros(self.capacity, np.int32)
        else:
            self.cache = engine.model.init_cache(self.capacity,
                                                 engine.max_len)
            # no donation: a spec tick's refeed re-reads its snapshot
            self._decode = engine._decode
        if prefix_cache and not self.paged:
            raise ValueError("prefix_cache=True requires paged KV "
                             "(pages are the sharing granularity)")
        self.prefix_cache = (PrefixCache(self.pool, self.page_size)
                             if prefix_cache else None)
        self.n_prefix_hits = 0         # admissions served >= 1 cached page
        self.n_prefix_tokens = 0       # prefill tokens skipped via cache
        self.n_prompt_tokens = 0       # prompt tokens admitted
        self.n_prefill_windows = 0     # hit tails prefilled as a window
        self.n_checker_clones = 0      # adopt() replays served by snapshot
        self._in_reset = False         # engine reset in flight: cached
        #                                pages are garbage, don't insert
        self.cache["len"] = jnp.zeros((self.capacity,), jnp.int32)  # ragged
        vpad = engine.model.padded_vocab
        self._logits = jnp.zeros((self.capacity, vpad), jnp.float32)
        v = engine._v
        # one fused readback per tick: raw argmax + per-row finiteness
        # (the device-fault detector — a NaN/Inf row is quarantined
        # BEFORE any selection consumes it; vocab-padded columns are
        # excluded, their values are unspecified by contract)
        self._raw_stats = jax.jit(lambda lg: (
            jnp.argmax(lg, axis=-1),
            jnp.all(jnp.isfinite(lg[:, :v]), axis=-1)))
        self._raw_argmax = jax.jit(lambda lg: jnp.argmax(lg, axis=-1))
        # persistent packed mask staging buffer: one (capacity, V/32)
        # uint32 row per slot, reused every tick (no per-tick (B, V) int8
        # allocation, 8x fewer host->device mask bytes).  Vacant slots
        # keep the precomputed sentinel row (token 0 legal — harmless,
        # their logits row is garbage by contract anyway).
        w = bitmask.n_words(engine._v)
        self._sentinel_row = np.zeros(w, np.uint32)
        bitmask.set_bit(self._sentinel_row, 0)
        self._allow_all_row = bitmask.pack_bool(
            np.ones(engine._v, bool))          # unconstrained rows
        self._mask_words = np.tile(self._sentinel_row, (self.capacity, 1))
        # packed masks prebuilt from each slot's current checker state
        # while the device executed the previous forward; dropped on any
        # checker advance / slot turnover (state changed -> mask stale)
        self._premask: Dict[int, np.ndarray] = {}
        # opportunistic-mode adaptive prebuild: build a slot's mask only
        # when its previous tick intervened (the O(token) legality check
        # failed and a full mask was consulted); fresh slots start False
        # because the opportunistic fast path usually wins
        self._opp_intervened = np.zeros(self.capacity, bool)
        self.premask_hits = 0          # selections served by a prebuild
        self.premask_skips = 0         # prebuilds adaptively skipped
        self.mask_cache_hits = 0       # mask builds served by the state-
        #                                keyed memo on the shared TreeCache
        self.n_fwd = 0                 # global forward count (all slots)
        self.n_preempt = 0             # paged recompute preemptions
        # device-resident decode loop (ISSUE 8): when enabled AND the
        # engine uploaded device tables (ServingEngine(device_tables=True)
        # + precompute()), ticks whose every resident row is certified
        # (DOMINO k=inf on a cleanly-certified grammar, greedy,
        # non-speculative) run sync_n decode steps in ONE fused device
        # call — mask gather, packed argmax, transition-table state
        # advance, KV append — and sync to the host once per block
        # instead of once per token.  Any host-path row in the batch
        # falls the whole tick back to the per-token path (those rows
        # need a host advance per token anyway), where certified rows
        # still gather their mask from the device table (stage 1).
        # Trade-off knobs documented in README: admission, cancellation,
        # deadline checks and EOS bookkeeping happen at block boundaries,
        # so sync_n bounds how stale they can go (<= sync_n tokens).
        self.device_loop = bool(device_loop)
        self.sync_n = max(1, int(sync_n))
        self._dts = engine.device_table_set if self.device_loop else None
        # per-slot device-table state id; OFF_FRONTIER (<0) = host path.
        # Maintained incrementally: computed from the checker at
        # admission (one abstract_key), advanced by O(1) host transition
        # lookups at every commit, resynced from the device after a
        # fused block, cleared on finish/preempt.
        self._dev_state = np.full(self.capacity, OFF_FRONTIER, np.int64)
        # tokens since the row's table state was last AUDITED against the
        # concrete checker's mask.  The key quotient is an abstraction of
        # a context-free state space, so a table walk can drift off the
        # concrete state (a QUOTIENT ESCAPE); every sync_n tokens — and
        # at every fused-block boundary — the mask row is compared to the
        # concrete mask and an escaped row demotes to the exact host
        # path.  Divergence from the host path is thereby bounded to one
        # audit interval; grammar validity is unconditional (every
        # committed token is validated by a concrete checker advance).
        self._dev_age = np.zeros(self.capacity, np.int64)
        self.n_quotient_escapes = 0    # audit demotions
        self.n_table_rejects = 0       # table-selected token rejected by
        #                                the checker -> recompute-preempt
        # per-tick device-gather plan: slot -> global state id (>=0) for
        # rows whose mask is gathered from the device table this tick
        self._dev_gather = np.full(self.capacity, OFF_FRONTIER, np.int64)
        # decode_nan fault plan for one fused block, consulted host-side
        # up front (persistent: tick funcs must not allocate dense rows)
        self._nan_plan = np.zeros((self.capacity, self.sync_n), bool)
        # device-sampler staging: per-row temperature + per-row
        # counter-based PRNG key (fold_in(PRNGKey(seed), n_draws)),
        # persistent so the tick path never allocates a dense buffer
        self._samp_temps = np.zeros(self.capacity, np.float32)
        self._samp_keys = np.zeros((self.capacity, 2), np.uint32)
        self._fused_fn = None          # built lazily on first device tick
        # mask-table gather: device rows take their table row, host rows
        # keep the staged packed buffer
        self._gather_masks = jax.jit(lambda tab, sid, staged: jnp.where(
            (sid >= 0)[:, None], tab[jnp.maximum(sid, 0).astype(jnp.int32)],
            staged))
        # decode-path host sync points (one blocking readback that gates
        # token commitment): +1 per host-path selection tick, +1 per
        # fused device block.  The benchmark reports syncs per committed
        # token — the quantity this PR drives from ~1 down to ~1/sync_n.
        self.n_host_syncs = 0
        self.n_device_tokens = 0       # tokens committed by fused blocks
        self._next_rid = 0
        # lifecycle bookkeeping: every terminal session in submit order
        # (`run()` reports from here, so submit-time rejections are never
        # lost); _finished_now accumulates between step() drains
        self.finished: List[Session] = []
        self._finished_now: List[Session] = []
        self.status_counts = collections.Counter()
        self._fail_log: List = []      # (rid, error) per quarantined row
        # durability + degradation (ISSUE 9 tentpole).  The journal only
        # BUFFERS during tick phases; all its file I/O happens in
        # _journal_tick at the tick boundary (lint rule R5 enforces
        # this).  _jmark[rid] = tokens already journaled for that rid, so
        # each tick writes a commit DELTA and replay merges idempotently.
        self.journal = journal
        self._jmark: Dict[int, int] = {}
        # supervisor: engine-wide fused -> host -> dense ladder for when
        # the DEVICE is sick (row-level faults stay quarantined per row).
        # A plain default supervisor never trips (no watchdogs, only
        # degrades on real dispatch errors / injected device faults).
        self.sup = supervisor or DegradationSupervisor()
        # effective capacity under HBM pressure: alloc_fail shrinks it
        # (preempting the excess to the queue) and each clean tick grows
        # it back toward the configured capacity
        self._cap_eff = self.capacity
        self.n_engine_resets = 0       # cache/logits re-inits after a
        #                                device error escaped a dispatch
        self.n_capacity_shrinks = 0    # alloc_fail-driven _cap_eff drops
        self.n_deadline_clamps = 0     # fused blocks clamped below
        #                                sync_n by a resident deadline
        self.n_replayed_tokens = 0     # journal-restored (not re-decoded)
        self._last_block_steps = 0     # steps the last fused block ran
        # committed-tokens-per-second EWMA over fused blocks; prices a
        # resident deadline into a block-step cap (0.0 = unprimed)
        self._tok_s_ema = 0.0
        self._shrunk_tick = False      # alloc_fail fired this tick

    # -- public API -------------------------------------------------------------

    def warm(self) -> Dict[str, float]:
        """Run the offline tree precomputation (paper Algorithm 2) over
        every grammar in the engine registry so mask construction never
        lands on the serving critical path, then prefill and PIN any
        engine-default prompts into the prefix cache."""
        stats = self.eng.precompute()
        self._pin_prompts()
        return stats

    def _pin_prompts(self) -> None:
        """Prefill each engine-registered default prompt once and park
        its full pages as PINNED radix nodes (never evicted): every
        future admission sharing the preamble skips its prefill."""
        if self.prefix_cache is None:
            return
        eng = self.eng
        for prompt in getattr(eng, "pinned_prompts", ()):
            ids = eng.tok.encode(prompt)
            n_full = len(ids) // self.page_size
            if n_full == 0:
                continue
            cut = ids[:n_full * self.page_size]
            probe = self.prefix_cache.lookup(cut, max_pages=n_full)
            if probe:
                self.pool.release(probe)     # drop the probe references
                if len(probe) == n_full:
                    continue                 # fully cached (re-warm)
            pages = self._alloc_pages(n_full)
            if pages is None:
                break
            row_cache = eng.model.init_cache(1, eng.max_len)
            _, row_cache = eng._prefill(
                eng.params, {"tokens": jnp.asarray(np.asarray([cut], np.int32))},
                row_cache)
            padded = np.zeros(self.max_pages, np.int32)
            padded[:n_full] = pages
            # slot 0 is scratch for the donating scatter; it must be
            # vacant (warm before serving) — restore its len afterwards
            assert self.slots[0] is None, "warm() after admission"
            self.cache = self._scatter_paged(self.cache, row_cache, 0,
                                             jnp.asarray(padded))
            cache = dict(self.cache)
            cache["len"] = cache["len"].at[0].set(0)
            self.cache = cache
            self.prefix_cache.insert(cut, pages, pin=True)
            self.pool.release(pages)   # ownership passes to the nodes
            self._warm_hits(pages)

    def _warm_hits(self, shared: List[int]) -> None:
        """Compile every hit program a prompt sharing the ``shared``
        pages can reach: one window per tail bucket, each of one real
        token into a private page that is freed afterwards (its pads
        land beyond the frontier, in that page or the trash page)."""
        start = len(shared) * self.page_size
        page = self._alloc_pages(1)
        if page is None:
            return
        table = np.zeros((1, self.max_pages), np.int32)
        table[0, :len(shared) + 1] = list(shared) + page
        for p in _window_buckets(self.page_size, self.eng.max_len - 1 - start,
                                 self.eng.max_len):
            _, self.cache = self._run_window(start, table, [0], p)
        jax.block_until_ready(self.cache)
        self.pool.free(page)

    def submit(self, request: Union[str, Request],
               extra_inputs=None) -> Session:
        """Queue one request.  ``request`` is a
        :class:`~repro.serving.request.Request` (per-row grammar, mode,
        EOS, budget, temperature, seed, speculation, deadline) or a bare
        prompt string, which submits the engine-default request.

        With a bounded queue (``queue_limit``) an overflowing submission
        is shed immediately: the returned session already carries a
        ``rejected`` result instead of growing the queue without bound.
        """
        sess = self.eng.make_session(self._next_rid, request, extra_inputs)
        self._next_rid += 1
        self._journal_submit(sess)
        if self.queue_limit is not None \
                and len(self.waiting) >= self.queue_limit:
            self._finish(sess, status="rejected",
                         error=f"waiting queue full "
                               f"(queue_limit={self.queue_limit})")
            return sess
        self.waiting.append(sess)
        return sess

    def cancel(self, rid: int) -> bool:
        """Request cancellation of a waiting or resident request by rid.
        Takes effect at the NEXT tick boundary: the session terminates
        with status ``cancelled`` and its slot and pages are freed for
        batch-mates.  Returns False when no live request has this rid
        (already finished, or never submitted)."""
        for sess in list(self.waiting) + self.slots:
            if sess is not None and sess.rid == rid \
                    and sess.result is None:
                sess.cancel_requested = True
                return True
        return False

    def run(self) -> List[GenerationResult]:
        """Drive all submitted sessions to a terminal status; results in
        rid order (including submit-time rejections)."""
        while self.waiting or any(s is not None for s in self.slots):
            self.step()
        done = sorted(self.finished, key=lambda s: s.rid)
        return [s.result for s in done]

    def stats(self) -> Dict[str, object]:
        """Operational counters for benchmarks and monitoring: the
        degradation-ladder state plus durability/pressure counters."""
        s = self.sup.stats()
        s.update(
            n_engine_resets=self.n_engine_resets,
            n_capacity_shrinks=self.n_capacity_shrinks,
            n_deadline_clamps=self.n_deadline_clamps,
            n_replayed_tokens=self.n_replayed_tokens,
            cap_eff=self._cap_eff,
            journal_syncs=(0 if self.journal is None
                           else self.journal.n_syncs),
            n_prefix_hits=self.n_prefix_hits,
            n_prefix_tokens=self.n_prefix_tokens,
            n_prompt_tokens=self.n_prompt_tokens,
            n_prefill_windows=self.n_prefill_windows,
            n_checker_clones=self.n_checker_clones,
            kv_inplace_layers=self.kv_inplace_layers,
        )
        if self.prefix_cache is not None:
            s.update({"prefix_" + k: v
                      for k, v in self.prefix_cache.stats().items()})
        return s

    @property
    def kv_inplace_layers(self) -> int:
        """Layers of the paged decode step that update the pool in place,
        as its last trace counted (0 for a dense cache)."""
        return self.eng.model.kv_inplace_layers if self.paged else 0

    def step(self) -> List[Session]:
        """One scheduler tick: reap -> admit -> select -> decode.
        Returns sessions that reached a terminal status since the last
        drain (tick casualties and submit-time rejections alike)."""
        with span("tick"):
            if self.injector is not None:
                self.injector.begin_tick()
            self._reap()
            self._admit()
            if any(s is not None for s in self.slots):
                width = self._verify_width()
                if width > 1:
                    self._spec_step(width)
                elif self._device_ready():
                    self._device_step()
                else:
                    self._plain_step()
            self._reset_vacant_lens()
            self._journal_tick()
            self._supervisor_tick()
            if self.debug_invariants:
                problems = check_invariants(self)
                if problems:
                    raise InvariantViolation("; ".join(problems))
            done, self._finished_now = self._finished_now, []
            return done

    # -- lifecycle: deadlines / cancellation ------------------------------------

    def _overdue(self, sess: Session, now: float, waiting: bool):
        """(status, reason) if the session must terminate at this tick
        boundary, else (None, None)."""
        if sess.cancel_requested:
            return "cancelled", ("cancelled while waiting" if waiting
                                 else "cancelled while decoding")
        deadline = sess.deadline_s
        if deadline is None:
            deadline = self.default_deadline_s
        waited = now - sess.t_submit
        if deadline is not None and waited > deadline:
            return "deadline_exceeded", (
                f"deadline {deadline:g}s exceeded after {waited:.3f}s"
                + (" in queue" if waiting else ""))
        if waiting and self.queue_timeout_s is not None \
                and waited > self.queue_timeout_s:
            return "rejected", (f"queue-wait timeout "
                                f"({self.queue_timeout_s:g}s) exceeded")
        return None, None

    def _reap(self) -> None:
        """Tick-boundary lifecycle sweep: honor cancellations, enforce
        per-request deadlines (waiting AND resident), and shed queued
        requests past the queue-wait timeout.  Freed slots and pages are
        available to this very tick's admission."""
        with span("reap"):
            now = time.perf_counter()
            if self.waiting:
                keep: "collections.deque[Session]" = collections.deque()
                while self.waiting:
                    sess = self.waiting.popleft()
                    status, why = self._overdue(sess, now, waiting=True)
                    if status is None:
                        keep.append(sess)
                    else:
                        self._finish(sess, status=status, error=why)
                self.waiting = keep
            for sess in list(self.slots):
                if sess is None:
                    continue
                status, why = self._overdue(sess, now, waiting=False)
                if status is not None:
                    self._finish(sess, status=status, error=why)

    def _verify_width(self) -> int:
        """Speculative verify width for this tick: 1 + the widest
        resident speculative row's ``spec_s`` (per-row policy — a batch
        mixing speculative and plain rows sizes the window to the rows
        that use it; plain rows ride along on pad positions).  1 means no
        resident row speculates and the tick takes the plain path."""
        widths = [1 + s.decode.spec_s for s in self.slots
                  if s is not None and s.speculator is not None]
        return max(widths) if widths else 1

    # -- admission / eviction ---------------------------------------------------

    def _admission_reject_reason(self, n_tokens: int) -> Optional[str]:
        """Reason string when a request's cache demand can NEVER be met
        (not even by an otherwise-empty engine), else None.  These must
        be rejected up front: the FIFO queue blocks behind its head, so
        an unsatisfiable head request would livelock every request
        behind it forever (the old behavior)."""
        if n_tokens + 1 > self.eng.max_len:
            return (f"prompt needs {n_tokens + 1} cache positions > "
                    f"engine max_len {self.eng.max_len}")
        if self.paged:
            n_pg = _ceil_div(n_tokens + 1, self.page_size)
            if n_pg > self.max_pages:
                return (f"prompt needs {n_pg} pages > per-row max_pages "
                        f"{self.max_pages}")
            if n_pg > self.n_pages - 1:
                return (f"prompt needs {n_pg} pages > total pool "
                        f"capacity {self.n_pages - 1}")
        return None

    def _admit(self) -> None:
        with span("admit"):
            while self.waiting and None in self.slots:
                if sum(s is not None for s in self.slots) >= self._cap_eff:
                    break      # capacity shrunk under allocation pressure
                slot = self.slots.index(None)
                sess = self.waiting[0]
                # re-admission after preemption re-prefills the generated
                # prefix too (the checker already advanced past it)
                ids = list(sess.prompt_ids) + list(sess.out_ids)
                reason = self._admission_reject_reason(len(ids))
                if reason is not None:
                    # unsatisfiable-by-construction: reject NOW (frees the
                    # queue head for admissible requests behind it) instead
                    # of waiting for pages that can never suffice
                    self.waiting.popleft()
                    self._finish(sess, status="rejected", error=reason)
                    continue
                page_ids = None
                cached: List[int] = []
                if self.paged:
                    # +1: the first decode write must fit without a new
                    # allocation, or a lone just-admitted row could preempt
                    # itself forever without committing a token
                    n_pg = _ceil_div(len(ids) + 1, self.page_size)
                    if self._inject("page_exhaustion", sess):
                        break      # injected dry pool: backpressure path
                    if self.prefix_cache is not None and not sess.extra_inputs:
                        # longest shared whole-page prefix, capped one token
                        # short of the sequence so the boundary page is
                        # always private (COW write barrier by construction)
                        cached = self.prefix_cache.lookup(
                            ids, max_pages=(len(ids) - 1) // self.page_size)
                    page_ids = self._alloc_pages(n_pg - len(cached))
                    if page_ids is None:
                        if cached:
                            self.pool.release(cached)
                        break          # backpressure: wait for frees (FIFO)
                    page_ids = cached + page_ids
                self.waiting.popleft()
                self._premask.pop(slot, None)
                self._opp_intervened[slot] = False
                t0 = time.perf_counter()
                try:
                    if cached:
                        logits_row = self._cached_prefill(sess, slot, ids,
                                                          page_ids,
                                                          len(cached))
                    else:
                        logits_row = self._fresh_prefill(sess, slot, ids,
                                                         page_ids)
                except Exception as e:   # quarantined: reject THIS request
                    if self.paged and page_ids:
                        self.pool.free(page_ids)
                    self._fail(sess, f"prefill failed: {e!r}")
                    continue
                self.n_prompt_tokens += len(ids)
                sess.hit_window = None
                if cached:
                    self.n_prefix_hits += 1
                    skipped = len(cached) * self.page_size
                    self.n_prefix_tokens += skipped
                    sess.n_cached_tokens += skipped
                    sess.hit_window = (skipped, len(ids) - skipped)
                if self.paged and self.prefix_cache is not None \
                        and not sess.extra_inputs:
                    # donate the row's full pages right away: requests later
                    # in this same admission sweep (and every future one)
                    # can share the prefix just prefilled
                    n_full = min(len(ids) // self.page_size, len(page_ids))
                    self.prefix_cache.insert(
                        ids[:n_full * self.page_size], page_ids[:n_full])
                self._logits = self._logits.at[slot].set(
                    logits_row.astype(jnp.float32))
                sess.model_time += time.perf_counter() - t0
                sess.n_fwd += 1
                self.n_fwd += 1
                sess.slot = slot
                sess.t_admit = time.perf_counter()
                self.slots[slot] = sess
                # device-table tracking starts (or resumes, after preemption:
                # the checker already advanced past the generated prefix) at
                # the checker's CURRENT abstract state
                self._dev_state[slot] = self._sid_for(sess)
                self._dev_age[slot] = 0
                if self.journal is not None:
                    # cache adoption is recorded for observability/auditing;
                    # replay does not need it (restored admissions re-acquire
                    # through the cache or fall back to a full re-prefill,
                    # identical either way by prefix determinism)
                    self.journal.append({"kind": "admit", "rid": sess.rid,
                                         "slot": slot,
                                         "cached_pages": len(cached),
                                         "cached_checker":
                                             sess.cached_checker})
                if self._inject("prefill_nan", sess):
                    self._logits = self._logits.at[slot].set(jnp.nan)

    def _fresh_prefill(self, sess: Session, slot: int, ids: List[int],
                       page_ids: Optional[List[int]]):
        """Admission without a prefix-cache hit: prefill the whole
        sequence at B=1 and scatter the row cache into ``slot`` (its
        pages, when paged).  Returns the last real token's logits row.
        NOT a tick function: runs only from ``_admit``."""
        eng = self.eng
        # power-of-two bucket: pads ride beyond the valid frontier (masked
        # by pos < len, overwritten by later decodes), the head reads the
        # true last token.  Gated off refeed archs: ring/recurrent state
        # would absorb the pads.
        bucket = self.bucket_prefill and not eng._needs_refeed \
            and not sess.extra_inputs
        p = _bucket_len(len(ids), eng.max_len) if bucket else len(ids)
        with span("prefill", rid=sess.rid, tokens=len(ids),
                              padded=p):
            row_cache = eng.model.init_cache(1, eng.max_len)
            # numpy first: a list would lower a conversion program per
            # prompt length
            feed = np.full((1, p), eng.tok.pad_id, np.int32)
            feed[0, :len(ids)] = ids
            inputs = {"tokens": jnp.asarray(feed)}
            if bucket:
                inputs["length"] = jnp.asarray(np.int32(len(ids)))
            if sess.extra_inputs:
                inputs.update(sess.extra_inputs)
            logits, row_cache = eng._prefill(eng.params, inputs, row_cache)
            if self.paged:
                padded = np.zeros(self.max_pages, np.int32)
                padded[:len(page_ids)] = page_ids
                self.cache = self._scatter_paged(
                    self.cache, row_cache, slot, jnp.asarray(padded))
                self._page_tbl[slot, :] = 0
                self._page_tbl[slot, :len(page_ids)] = page_ids
                self._n_pages_row[slot] = len(page_ids)
                self._n_shared_row[slot] = 0
                self._pages_dirty = True
            else:
                self.cache = _scatter_row_donate(self.cache, row_cache,
                                                 slot)
            return logits[0, -1]

    def _cached_prefill(self, sess: Session, slot: int, ids: List[int],
                        page_ids: List[int], n_cached: int):
        """Admission through a prefix-cache hit: the first ``n_cached``
        pages of the row's block table map shared pages whose K/V is
        already resident (bitwise-identical by prefix determinism), so
        only the tail ``ids[n_cached * page_size:]`` is prefilled — as
        one window (``Model.prefill_window``, one pass over the weights,
        attention tiled over the window) over a B=1 view of the donated
        pool, which reads the shared prefix through the block table and
        writes only private pages (every write position sits at or
        beyond the boundary page).  The span ``serve.prefill_hit`` runs
        from the dispatch to the host's read of the logits row: the
        stall the hit puts into every resident row.  Returns the last
        real token's logits row.

        NOT a tick function: runs only from ``_admit`` (lint rule R6
        keeps cache traffic off the per-token path).
        """
        eng = self.eng
        start = n_cached * self.page_size
        tail = list(ids[start:])
        assert tail, "cache hit must leave a non-empty private tail"
        # bucket the tail so the window compiles per size class, not per
        # length (``_warm_hits`` compiles each class up front)
        p = _bucket_len(len(tail), eng.max_len, self.page_size)
        table = np.zeros((1, self.max_pages), np.int32)
        table[0, :len(page_ids)] = page_ids
        with span("prefill_hit", rid=sess.rid, tokens=len(tail), window=p):
            row, self.cache = self._run_window(start, table, tail, p)
            jax.block_until_ready(row)
        cache = dict(self.cache)
        cache["len"] = cache["len"].at[slot].set(len(ids))
        self.cache = cache
        self.n_prefill_windows += 1
        self._page_tbl[slot, :] = table[0]
        self._n_pages_row[slot] = len(page_ids)
        self._n_shared_row[slot] = n_cached
        self._pages_dirty = True
        return row

    def _run_window(self, start: int, table: np.ndarray, tail: List[int],
                    p: int):
        """Prefill ``tail`` at positions ``start``.. of a B=1 view of the
        pool with block table ``table`` (1, max_pages), padded to ``p``
        tokens; pads write garbage above the final frontier into private
        or trash pages only.  Returns (the last real token's logits row,
        the batch cache with the written pool leaves)."""
        # numpy first: a list would lower a conversion program per shape
        feed = np.full((1, p), self.eng.tok.pad_id, np.int32)
        feed[0, :len(tail)] = tail
        view = {"len": jnp.asarray(np.asarray([start], np.int32)),
                "head": self.cache["head"], "tail": self.cache["tail"],
                "group": self.cache["group"], "pages": jnp.asarray(table)}
        lg, view = self._prefill_window(self.eng.params, view,
                                        jnp.asarray(feed),
                                        jnp.asarray(np.int32(len(tail))))
        # merge the written pool leaves back (the call donated the batch
        # cache's); other rows' pages are untouched (the window wrote
        # only this view's private pages)
        cache = dict(self.cache)
        cache["head"], cache["tail"] = view["head"], view["tail"]
        cache["group"] = view["group"]
        return lg[0], cache

    def _reset_vacant_lens(self) -> None:
        """Vacant slots' rows are garbage by contract, but every batched
        forward still advances their ragged ``len`` — left alone, the
        fused kernel would stream ever more dead cache tiles for freed
        rows.  Pin them to 0 so the per-row early-exit actually skips
        them (admission overwrites ``len`` when it scatters a new row)."""
        if all(s is not None for s in self.slots):
            return
        occ = jnp.asarray([0 if s is None else 1 for s in self.slots],
                          jnp.int32)
        cache = dict(self.cache)
        cache["len"] = cache["len"] * occ
        self.cache = cache

    def _finish(self, sess: Session, status: Optional[str] = None,
                error: Optional[str] = None) -> None:
        """Terminate one session: resolve its terminal status, free its
        slot and pages, and record it for ``step()``/``run()`` reporting.
        ``status=None`` resolves to ok/dead_end from the session flags."""
        if status is not None:
            sess.status = status
        if error is not None and sess.error is None:
            sess.error = error
        sess.finish(self.eng.tok.decode)
        if self.journal is not None:
            self._journal_commit(sess)
            self.journal.append({
                "kind": "terminal", "rid": sess.rid,
                "status": sess.result.status, "error": sess.result.error,
                "finished": sess.finished_eos,
                "dead_end": sess.dead_end})
            self._jmark.pop(sess.rid, None)
        if sess.slot >= 0:
            self._premask.pop(sess.slot, None)
            self._dev_state[sess.slot] = OFF_FRONTIER
            if self.paged:
                self._insert_prefix(sess)
                self._free_slot_pages(sess.slot)
            self.slots[sess.slot] = None
            sess.slot = -1
        self.status_counts[sess.result.status] += 1
        self.finished.append(sess)
        self._finished_now.append(sess)

    def _fail(self, sess: Session, error: str) -> None:
        """Quarantine a failure to this row: the session terminates with
        ``internal_error`` (never a silent swallow, never a crash that
        takes down batch-mates) and its slot/pages free immediately."""
        self._fail_log.append((sess.rid, error))
        self._finish(sess, status="internal_error", error=error)

    # -- fault injection sites --------------------------------------------------

    def _inject(self, site: str, sess: Optional[Session] = None) -> bool:
        """Consult the fault plan at one injection site (no-op without
        an injector)."""
        if self.injector is None:
            return False
        return self.injector.fire(site,
                                  rid=None if sess is None else sess.rid)

    def _inject_nan_rows(self, site: str) -> None:
        """Corrupt staged logits rows per the fault plan.  Detection is
        NOT short-circuited: the poisoned row flows into the next
        selection's finiteness check exactly like a real device fault."""
        if self.injector is None:
            return
        for slot, sess in enumerate(self.slots):
            if sess is not None and self._inject(site, sess):
                self._logits = self._logits.at[slot].set(jnp.nan)

    # -- durability: write-ahead journal (ISSUE 9 tentpole) ---------------------
    #
    # Tick phases only BUFFER records (journal.append is pure host
    # bookkeeping); the one place file I/O happens is _journal_tick at
    # the tick boundary, which lint rule R5 keeps off the tick functions.

    def _journal_submit(self, sess: Session) -> None:
        """Buffer the submit record: everything replay needs to rebuild
        the request (prompt + ConstraintSpec + DecodeParams fields).  A
        request that cannot be serialized (ad-hoc grammar object,
        extra_inputs pytrees) is journaled as non-recoverable so restore
        reports it explicitly instead of resurrecting it wrong."""
        if self.journal is None:
            return
        rec = {"kind": "submit", "rid": sess.rid, "prompt": sess.prompt}
        recoverable, reason = True, None
        spec = getattr(sess.request, "constraint", None)
        if spec is not None and spec.grammar is not None \
                and not isinstance(spec.grammar, str):
            recoverable = False
            reason = ("ad-hoc grammar object is not serializable; "
                      "register it by name to make the request "
                      "recoverable")
            rec["constraint"] = None
        else:
            rec["constraint"] = (None if spec is None
                                 else dataclasses.asdict(spec))
        dec = getattr(sess.request, "decode", None)
        rec["decode"] = None if dec is None else dataclasses.asdict(dec)
        if sess.extra_inputs:
            recoverable = False
            reason = "extra_inputs are not journaled"
        rec["recoverable"] = recoverable
        rec["reason"] = reason
        self.journal.append(rec)

    def _journal_commit(self, sess: Session) -> None:
        """Buffer a commit DELTA: the session's checker-validated tokens
        beyond what was already journaled, tagged with their offset so
        replay merges idempotently (a re-written delta contributes
        nothing).  The sampling-RNG state rides in the same record as
        the draws that advanced it, so a restored sampled row resumes
        its exact stream."""
        done = len(sess.out_ids)
        mark = self._jmark.get(sess.rid, 0)
        if done <= mark:
            return
        rec = {"kind": "commit", "rid": sess.rid, "off": mark,
               "toks": [int(t) for t in sess.out_ids[mark:]],
               "n_draws": sess.n_draws}
        if sess._rng is not None:
            rec["rng"] = sess._rng.bit_generator.state
        self.journal.append(rec)
        self._jmark[sess.rid] = done

    def _journal_tick(self) -> None:
        """The tick-boundary durability point: buffer commit deltas for
        every live session that gained tokens this tick (resident AND
        freshly-preempted), then let the journal do its batched
        write + fsync.  The ONLY tick-path call allowed to flush."""
        if self.journal is None:
            return
        for sess in list(self.slots) + list(self.waiting):
            if sess is not None and sess.result is None:
                self._journal_commit(sess)
        self.journal.commit_tick()

    # -- degradation supervisor (ISSUE 9 tentpole) ------------------------------

    def _supervisor_tick(self) -> None:
        """Close the tick for the degradation ladder: an alloc_fail-free
        tick regrows effective capacity one slot, and the supervisor
        counts clean ticks toward climbing fused <- host <- dense."""
        if self._shrunk_tick:
            self._shrunk_tick = False
        elif self._cap_eff < self.capacity:
            self._cap_eff += 1
        self.sup.tick_ok()

    def _engine_reset(self, reason: str) -> None:
        """The device surface is untrustworthy after an error escaped a
        dispatch (the fused call donates the cache, so its buffers may be
        gone): recompute-preempt every resident — validated prefixes ride
        along, so outputs are unchanged — and re-initialize the batch
        cache and the staged logits.  Youngest preempts first, so the
        oldest resident lands at the queue front for re-admission."""
        self.n_engine_resets += 1
        self._fail_log.append((None, f"engine reset: {reason}"))
        if self.prefix_cache is not None:
            # cached pages' contents die with the device cache: drop the
            # node references FIRST so the preempts below release the
            # last table references and the pages actually return
            self.prefix_cache.reset()
        self._in_reset = True
        try:
            for sess in sorted((s for s in self.slots if s is not None),
                               key=lambda s: s.t_admit, reverse=True):
                self._preempt(sess)
        finally:
            self._in_reset = False
        eng = self.eng
        self.cache = None      # two pools at once may not fit the device
        if self.paged:
            self.cache = eng.model.init_cache(
                self.capacity, eng.max_len, page_size=self.page_size,
                n_pages=self.n_pages)
            self._pages_dirty = True
        else:
            self.cache = eng.model.init_cache(self.capacity, eng.max_len)
        self.cache["len"] = jnp.zeros((self.capacity,), jnp.int32)
        self._logits = jnp.zeros(
            (self.capacity, eng.model.padded_vocab), jnp.float32)
        self._premask.clear()

    # -- restart recovery -------------------------------------------------------

    def adopt(self, entry: JournalEntry) -> Session:
        """Reconstruct one journal-replayed request (restart recovery).

        Terminal entries become finished shell sessions (their result is
        rebuilt from the journaled tokens/status, nothing re-decodes).
        Live entries rebuild the Request from the journaled spec fields,
        replay the validated committed prefix through a fresh concrete
        checker via ``advance()`` (a rejection is quarantined — the
        journal only ever holds validated tokens, so this means the
        grammar registry changed under us), restore the sampling RNG
        stream, and re-enter the waiting queue: admission re-prefills
        prompt + prefix exactly like a recompute preemption, which is
        what makes the resumed output bitwise-identical."""
        self._next_rid = max(self._next_rid, entry.rid + 1)
        if not entry.recoverable:
            sess = self.eng.make_session(entry.rid, entry.prompt)
            self._finish(sess, status="internal_error",
                         error=f"unrecoverable from journal: "
                               f"{entry.reason}")
            return sess
        req: Union[str, Request] = entry.prompt
        if entry.constraint is not None or entry.decode is not None:
            req = Request(
                prompt=entry.prompt,
                constraint=(ConstraintSpec(**entry.constraint)
                            if entry.constraint is not None
                            else ConstraintSpec(grammar=None,
                                                mode="unconstrained")),
                decode=(DecodeParams(**entry.decode)
                        if entry.decode is not None else DecodeParams()))
        sess = self.eng.make_session(entry.rid, req)
        if entry.terminal is not None:
            sess.out_ids = [int(t) for t in entry.toks]
            sess.n_replayed = len(entry.toks)
            sess.n_draws = entry.n_draws
            sess.finished_eos = entry.terminal["finished"]
            sess.dead_end = entry.terminal["dead_end"]
            st = entry.terminal["status"]
            self._jmark[entry.rid] = len(entry.toks)
            self._finish(sess,
                         status=(None if st in ("ok", "dead_end")
                                 else st),
                         error=entry.terminal["error"])
            return sess
        toks = [int(t) for t in entry.toks]
        n_adopted = 0
        sig = self._checker_sig(sess)
        if self.prefix_cache is not None and sig is not None and toks:
            # fork-point fast path: clone the longest stored checker
            # snapshot covering a prefix of the journaled tokens and
            # replay only the remainder through advance().  Exact-prefix
            # keying (grammar sig + prompt length + token ids) makes the
            # clone's state identical to what the replay would build.
            got = self.prefix_cache.get_checker(
                sig, len(sess.prompt_ids),
                list(sess.prompt_ids) + toks)
            if got is not None:
                n_cov, clone = got
                n_adopted = n_cov - len(sess.prompt_ids)
                sess.checker = clone
                sess.out_ids.extend(toks[:n_adopted])
                sess.budget -= n_adopted
                sess.cached_checker = True
                self.n_checker_clones += 1
        for tok in toks[n_adopted:]:
            try:
                ok = (sess.checker.advance(int(tok))
                      if sess.checker is not None else True)
            except Exception as e:
                self._fail(sess, f"journal replay: checker failed at "
                                 f"position {len(sess.out_ids)}: {e!r}")
                return sess
            if not ok:
                self._fail(sess, f"journal replay: checker rejected "
                                 f"validated token {int(tok)} at position "
                                 f"{len(sess.out_ids)} (grammar changed?)")
                return sess
            sess.out_ids.append(int(tok))
            sess.budget -= 1
        sess.n_replayed = len(entry.toks)
        self.n_replayed_tokens += len(entry.toks)
        if self.prefix_cache is not None and sig is not None \
                and sess.out_ids:
            # snapshot the fully-replayed state so later adopts in this
            # same restore (and their preemption re-admissions) clone it
            self.prefix_cache.put_checker(
                sig, len(sess.prompt_ids),
                list(sess.prompt_ids) + list(sess.out_ids), sess.checker)
        sess.n_draws = entry.n_draws
        if entry.rng_state is not None and sess.decode is not None:
            rng = sess.decode.make_rng()
            rng.bit_generator.state = entry.rng_state
            sess._rng = rng
        sess.n_preempt = entry.n_preempts
        self._jmark[entry.rid] = len(sess.out_ids)
        if sess.budget <= 0:
            self._finish(sess)
            return sess
        self.waiting.append(sess)
        return sess

    # -- page bookkeeping -------------------------------------------------------

    def _free_slot_pages(self, slot: int) -> None:
        n = int(self._n_pages_row[slot])
        if n:
            self.pool.free(self._page_tbl[slot, :n].tolist())
        self._page_tbl[slot, :] = 0         # vacant entries -> trash page
        self._n_pages_row[slot] = 0
        if self.prefix_cache is not None:
            self._n_shared_row[slot] = 0
        self._pages_dirty = True

    def _alloc_pages(self, n: int) -> Optional[List[int]]:
        """``pool.alloc`` with prefix-cache LRU eviction as the
        fallback: cache-only pages (refcount 1, unpinned) are reclaimed
        to cover the shortfall before admission backpressures or a
        resident row is preempted.  A page a live block table maps is
        never a candidate (its refcount is >= 2)."""
        got = self.pool.alloc(n)
        if got is None and self.prefix_cache is not None:
            self.prefix_cache.evict(n - self.pool.available)
            got = self.pool.alloc(n)
        return got

    def _checker_sig(self, sess: Session) -> Optional[tuple]:
        """Hashable signature of everything that shapes a session's
        checker state besides the advanced tokens, or None when the
        checker is not shareable (non-DOMINO modes, healed subclasses,
        ad-hoc grammar objects with no stable name)."""
        if sess.checker is None or type(sess.checker) is not DominoDecoder:
            return None
        req = sess.request
        spec = None if req is None else req.constraint
        if spec is None or not isinstance(spec.grammar, str):
            return None
        return (spec.grammar, spec.mode, spec.k, sess.eos_id)

    def _insert_prefix(self, sess: Session) -> None:
        """Donate a departing row's committed full pages to the radix
        tree and snapshot its checker at the fork point, so a future
        request sharing the prefix skips both the prefill and (on
        restart recovery) the ``advance()`` replay.  Teardown-boundary
        only (``_finish``/``_preempt``) — never from a tick function
        (lint R6), and never during an engine reset (the pool leaves'
        contents are untrustworthy)."""
        if self.prefix_cache is None or sess.slot < 0 or self._in_reset \
                or sess.extra_inputs:
            return
        if sess.status == "internal_error":
            return      # quarantined row: its device state is suspect
        slot = sess.slot
        ids = list(sess.prompt_ids) + list(sess.out_ids)
        n_full = min(len(ids) // self.page_size,
                     int(self._n_pages_row[slot]))
        if n_full > 0:
            self.prefix_cache.insert(
                ids[:n_full * self.page_size],
                self._page_tbl[slot, :n_full].tolist())
        sig = self._checker_sig(sess)
        if sig is not None and sess.out_ids:
            self.prefix_cache.put_checker(sig, len(sess.prompt_ids),
                                          ids, sess.checker)

    def _preempt(self, sess: Session) -> None:
        """Recompute preemption (pool exhausted mid-flight): reclaim the
        row's pages and return the request to the FRONT of the waiting
        queue.  On re-admission the prompt plus everything generated so
        far is re-prefilled; the checker state already reflects the
        generated prefix, so selection resumes exactly where it left off
        and outputs are unchanged."""
        slot = sess.slot
        self._premask.pop(slot, None)
        self._dev_state[slot] = OFF_FRONTIER
        # donate the committed prefix before releasing the table refs:
        # re-admission re-acquires these very pages through the cache,
        # so a recompute preemption re-prefills only the partial tail
        self._insert_prefix(sess)
        self._free_slot_pages(slot)
        self.slots[slot] = None
        sess.slot = -1
        sess.n_preempt += 1
        self.n_preempt += 1
        if self.journal is not None:
            self.journal.append({"kind": "preempt", "rid": sess.rid})
        self.waiting.appendleft(sess)

    def _ensure_pages(self, width: int) -> None:
        """Grow every resident row's block table to cover the ``width``
        cache positions this tick's decode will write.  If the pool can't
        cover everyone, preempt youngest-first until it can — the
        survivors keep decoding, the victims re-enter the queue."""
        if not self.paged:
            return
        lens = np.asarray(self.cache["len"])
        while True:
            need: Dict[int, int] = {}
            for slot, sess in enumerate(self.slots):
                if sess is None:
                    continue
                want = min(_ceil_div(int(lens[slot]) + width,
                                     self.page_size), self.max_pages)
                if want > int(self._n_pages_row[slot]):
                    need[slot] = want
            shortfall = sum(w - int(self._n_pages_row[s])
                            for s, w in need.items())
            if shortfall and self._inject("alloc_fail"):
                # simulated HBM allocation failure: this is pressure, not
                # a row fault — shrink effective capacity (admission
                # stops refilling the slot about to be reclaimed; clean
                # ticks grow it back) and preempt-to-queue below
                self._cap_eff = max(
                    1, sum(s is not None for s in self.slots) - 1)
                self.n_capacity_shrinks += 1
                self._shrunk_tick = True
            elif shortfall <= self.pool.available + (
                    0 if self.prefix_cache is None
                    else self.prefix_cache.evictable()) and not (
                    shortfall and self._inject("page_exhaustion")):
                # cache-only pages count as available: _alloc_pages
                # below reclaims them LRU-first before any preemption
                break
            victims = [s for s in self.slots if s is not None]
            if not victims:
                break
            self._preempt(max(victims, key=lambda s: s.t_admit))
        for slot, want in need.items():
            have = int(self._n_pages_row[slot])
            got = self._alloc_pages(want - have)
            self._page_tbl[slot, have:want] = got
            self._n_pages_row[slot] = want
            self._pages_dirty = True

    def _shrink_pages(self) -> None:
        """Speculative rollback rewound per-row frontiers; pages wholly
        beyond a row's new length hold only rejected-garbage and go back
        to the pool (the next ``_ensure_pages`` re-allocates on demand)."""
        if not self.paged:
            return
        lens = np.asarray(self.cache["len"])
        for slot, sess in enumerate(self.slots):
            if sess is None:
                continue
            keep = _ceil_div(int(lens[slot]), self.page_size)
            n = int(self._n_pages_row[slot])
            if n > keep:
                self.pool.free(self._page_tbl[slot, keep:n].tolist())
                self._page_tbl[slot, keep:n] = 0
                self._n_pages_row[slot] = keep
                self._pages_dirty = True

    def _sync_pages(self) -> None:
        """Upload the host block table to the device cache if the
        allocator moved pages since the last forward."""
        if self.paged and self._pages_dirty:
            cache = dict(self.cache)
            cache["pages"] = jnp.asarray(self._page_tbl)
            self.cache = cache
            self._pages_dirty = False

    # -- mask pipeline ----------------------------------------------------------

    def _checker_bits(self, sess: Session):
        """Build ``sess``'s packed mask row, attributing build time to the
        session and memo hits to ``mask_cache_hits``.  Checkers without a
        ``mask_bits`` API (e.g. test stubs) fall back to packing their
        bool mask."""
        ch = sess.checker
        with span("mask", rid=sess.rid):
            if self._inject("mask_delay", sess):
                time.sleep(self.injector.delay_s)
            if self._inject("mask_error", sess):
                raise InjectedFault(
                    f"injected mask-build failure (rid={sess.rid})")
            before = getattr(ch, "n_mask_memo_hits", 0)
            t0 = time.perf_counter()
            if hasattr(ch, "mask_bits"):
                m = ch.mask_bits()
            else:
                m = bitmask.pack_bool(np.asarray(ch.mask()))
            dt = time.perf_counter() - t0
        sess.mask_time += dt
        self.mask_cache_hits += getattr(ch, "n_mask_memo_hits", 0) - before
        return m, dt

    def _prebuild_masks(self):
        """Build the next selection's grammar masks from current checker
        state.  Called while the device executes the just-dispatched
        forward; build time accrues to per-session mask_time immediately,
        but the overlap credit is decided by the caller (``_run_decode``)
        once it knows whether the device actually outlasted the build.
        Returns [(session, build_seconds), ...] for that decision.

        Under opportunistic checking (a per-ROW mode now) the raw-argmax
        legality check usually makes the mask dead weight, so the
        prebuild is skipped for opportunistic slots whose previous tick
        did NOT intervene — accounting stays honest automatically: a
        skipped build adds no mask_time and can earn no overlap credit."""
        built = []
        for slot, sess in enumerate(self.slots):
            if sess is None or sess.checker is None \
                    or slot in self._premask:
                continue
            if self.device_loop and self._dev_state[slot] >= 0:
                continue   # mask comes from the uploaded device table
            if self.adaptive_prebuild and sess.opportunistic \
                    and sess.temperature <= 0.0 \
                    and not self._opp_intervened[slot]:
                self.premask_skips += 1
                continue
            try:
                m, dt = self._checker_bits(sess)
            except Exception as e:   # quarantined: evict THIS row only
                self._fail(sess, "checker failed during overlapped "
                                 f"prebuild: {e!r}")
                continue
            self._premask[slot] = m
            built.append((sess, dt))
        return built

    # -- token selection --------------------------------------------------------

    def _choose(self) -> Dict[int, int]:
        """Pick one token per occupied slot under that ROW's decode
        policy: greedy rows go through the device-side fused masked
        argmax over the shared packed staging buffer; sampled rows draw
        host-side from their own per-request RNG.  Finishes dead-ended
        sessions; updates intervention stats.  Returns {slot: token}."""
        # one fused readback: per-row raw argmax + per-row finiteness over
        # the real vocab columns (padded columns are legitimately -inf).
        # Guarded: a runtime error HERE is the device being sick, not a
        # row fault — bounded retry, then engine reset + ladder step.
        def _readback():
            raw_dev, fin_dev = self._raw_stats(self._logits)
            return np.asarray(raw_dev), np.asarray(fin_dev)

        with span("readback"):
            ok, got = self.sup.guard(
                "tick_readback", _readback,
                inject=lambda: self._inject("device_error"))
        if not ok:
            # every resident recompute-preempts with its validated prefix
            # intact, so outputs are unchanged; selection commits nothing
            # this tick and the next tick runs one ladder level down
            self._engine_reset(f"tick readback failed: {got!r}")
            self.sup.degrade("tick_readback", got)
            return {}
        self.n_host_syncs += 1         # per-token selection sync point
        with span("select"):
            return self._select(*got)

    def _select(self, raw: np.ndarray, finite: np.ndarray
                ) -> Dict[int, int]:
        """``_choose`` after its readback: stage each row's packed mask
        (``raw``: per-row raw argmax, ``finite``: per-row finiteness),
        then select on the device (greedy) or the host (sampled)."""
        v = self.eng._v
        masks = self._mask_words              # persistent staging buffer
        self._dev_gather[:] = OFF_FRONTIER
        row_bits: Dict[int, Optional[np.ndarray]] = {}
        for slot, sess in enumerate(self.slots):
            if sess is None:
                masks[slot] = self._sentinel_row
                continue
            if not finite[slot]:
                # device fault quarantined to THIS row: selection on NaN
                # logits would commit garbage, so evict it with an
                # explicit status while batch-mates keep decoding
                self._fail(sess, "non-finite logits from device step")
                masks[slot] = self._sentinel_row
                continue
            if self.device_loop and self._dev_state[slot] >= 0:
                # certified row (stage-1 device gather): its mask IS the
                # device table row — gathered device-side for selection,
                # host mirror staged for sampled rows.  No checker walk,
                # no opportunistic probe, no dead-end check (a clean
                # certificate has no trap states), ~zero mask_time.
                sid = int(self._dev_state[slot])
                self._dev_gather[slot] = sid
                row_bits[slot] = self._dts.mask_host[sid]
                continue
            ch = sess.checker
            if ch is None:
                # unconstrained row: the sentinel all-ones row shares the
                # one (capacity, V/32) buffer with the grammar rows
                masks[slot] = self._allow_all_row
                row_bits[slot] = None
                continue
            try:
                if sess.opportunistic and sess.temperature <= 0.0:
                    t0 = time.perf_counter()
                    ok = ch.check_token(int(raw[slot]))
                    sess.mask_time += time.perf_counter() - t0
                    if ok:
                        self._opp_intervened[slot] = False
                        masks[slot, :] = 0
                        bitmask.set_bit(masks[slot], int(raw[slot]))
                        row_bits[slot] = None
                        continue
                    # fast path lost: a full mask is needed this tick, so
                    # next tick's prebuild is worth building again
                    self._opp_intervened[slot] = True
                m = self._premask.pop(slot, None)   # overlapped prebuild
                if m is None:
                    m, _dt = self._checker_bits(sess)
                else:
                    self.premask_hits += 1
            except Exception as e:   # quarantined: evict THIS row only
                self._fail(sess, f"checker failed during mask build: "
                                 f"{e!r}")
                masks[slot] = self._sentinel_row
                continue
            if not m.any():
                sess.dead_end = True
                self._finish(sess)
                masks[slot] = self._sentinel_row
                continue
            masks[slot] = m
            row_bits[slot] = m
        occupied = [i for i, s in enumerate(self.slots) if s is not None]
        if not occupied:
            return {}
        toks = np.zeros(self.capacity, np.int64)
        # certified rows' mask rows are gathered ON DEVICE from the
        # uploaded table (the staged host buffer keeps everyone else);
        # without device tables this is exactly the staged buffer
        m_stage = jnp.asarray(masks)
        if self.device_loop and bool((self._dev_gather >= 0).any()):
            m_stage = self._gather_masks(
                self._dts.mask_dev, jnp.asarray(self._dev_gather), m_stage)
        greedy = [s for s in occupied if self.slots[s].temperature <= 0.0]
        if greedy:
            # ladder level >= 2 (dense): the jnp reference oracle — same
            # greedy selection bitwise (lowest-index ties), no pallas
            # dispatch at all, for when the kernel path itself is suspect
            idx, _ = masked_argmax(self._logits[:, :v], m_stage,
                                   use_ref=self.sup.level >= 2)
            toks[greedy] = np.asarray(idx)[greedy]
        sampled = [s for s in occupied if s not in greedy]
        if sampled and self.device_loop and self.sup.level < 2:
            # device sampler (Gumbel-max over the packed legal set):
            # per-row temperature, per-row counter-based keys — the
            # stream is a pure function of (seed, draw index), so output
            # never depends on batch composition.  NOT bit-identical to
            # the host np.random path below; distributionally identical.
            self._samp_temps[:] = 0.0
            for slot in sampled:
                sess = self.slots[slot]
                self._samp_temps[slot] = sess.temperature
                self._samp_keys[slot] = np.asarray(
                    jax.random.fold_in(jax.random.PRNGKey(sess.decode.seed),
                                       sess.n_draws))
                sess.n_draws += 1
            sel = np.asarray(masked_sample_packed(
                self._logits[:, :v], m_stage,
                jnp.asarray(self._samp_temps), jnp.asarray(self._samp_keys)))
            for slot in sampled:
                toks[slot] = sel[slot]
        elif sampled:
            lg_host = np.asarray(self._logits)[:, :v]
            for slot in sampled:
                sess = self.slots[slot]
                m = row_bits.get(slot)
                # sampling needs a bool mask for probability renormali-
                # zation; this unpack is per SAMPLED row only (greedy
                # rows stay packed through the fused kernel above)
                toks[slot] = select_token(
                    lg_host[slot],
                    None if m is None else bitmask.unpack(m, v),  # hotpath-lint: allow
                    sess.temperature, sess.rng)
        out: Dict[int, int] = {}
        for slot in occupied:
            sess = self.slots[slot]
            tok = int(toks[slot])
            sess.n_int += int(tok != int(raw[slot]))
            out[slot] = tok
        return out

    # -- plain decode tick ------------------------------------------------------

    def _commit_first(self, chosen: Dict[int, int]) -> Dict[int, int]:
        """Advance checkers / budgets for the chosen tokens; finish rows
        that hit their OWN EOS id or exhaust their own budget.  Returns
        {slot: token} for rows that still need a forward."""
        live: Dict[int, int] = {}
        with span("commit"):
            for slot, tok in chosen.items():
                sess = self.slots[slot]
                if sess is None or sess.slot != slot:
                    continue     # evicted between selection and commit
                ch = sess.checker
                # tracked rows select from the TABLE's mask row; a quotient
                # escape can therefore offer a token the concrete checker
                # refuses.  advance() leaves state unchanged on False, so the
                # validated prefix is intact: recompute-preempt the row — it
                # re-enters through _sid_for's exact entry audit and resumes
                # on the host path if still escaped.  Untracked rows selected
                # from the checker's own mask; their advance return keeps the
                # pre-device-loop (ignore) semantics.
                tracked = self._dev_state[slot] >= 0
                try:
                    if tok == sess.eos_id:
                        if ch is not None:
                            with span("advance", rid=sess.rid):
                                ok = ch.advance(tok)
                            if tracked and not ok:
                                self.n_table_rejects += 1
                                self._preempt(sess)
                                continue
                        sess.finished_eos = True
                        self._finish(sess)
                        continue
                    if ch is not None and sess.speculator is not None \
                            and hasattr(ch, "clone"):
                        sess.speculator.observe(ch.state_key(), tok)
                    if ch is not None:
                        if self._inject("advance_error", sess):
                            raise InjectedFault(
                                f"injected advance failure (rid={sess.rid})")
                        with span("advance", rid=sess.rid):
                            ok = ch.advance(tok)
                        self._premask.pop(slot, None)  # state moved: stale
                        if tracked:
                            if not ok:
                                self.n_table_rejects += 1
                                self._preempt(sess)
                                continue
                            self._advance_sid(slot, sess, tok)
                except Exception as e:   # quarantined: evict THIS row only
                    self._fail(sess, f"checker failed during advance: {e!r}")
                    continue
                sess.out_ids.append(tok)
                sess.budget -= 1
                if sess.budget <= 0:
                    self._finish(sess)
                    continue
                live[slot] = tok
        return live

    def _run_decode(self, feed: List[List[int]],
                    overlap_fn: Optional[Callable[[], None]] = None):
        """One batched forward over ``feed`` (a row of token ids per
        slot), attributing time and count to resident rows.  The
        forward is dispatched asynchronously; ``overlap_fn`` (next
        step's host-side mask construction) runs while the device
        executes, then we block so per-request model_time_s measures
        execution, not dispatch (the host would otherwise pay the wait
        inside the next tick's argmax readback, attributed to nothing)."""
        eng = self.eng
        with span("dispatch"):
            self._sync_pages()
            feed = jnp.asarray(feed, jnp.int32)
            t0 = time.perf_counter()
            lg, self.cache = self._decode(eng.params, self.cache, feed)
        built = []
        if overlap_fn is not None and self.overlap:
            with span("prebuild"):
                built = overlap_fn() or []
        t_mask_end = time.perf_counter()
        with span("wait"):
            lg.block_until_ready()
        wait = time.perf_counter() - t_mask_end
        # overlap credit only when the device provably outlasted the
        # prebuild (we still had to wait on it afterwards); if the build
        # outran the device, the excess sat on the critical path — it
        # stays in mask_time uncredited and is excluded from the model
        # wall below, so the two fields still decompose the step
        hidden = wait > 1e-5
        m_total = sum(b_dt for _, b_dt in built)
        if hidden:
            for b_sess, b_dt in built:
                b_sess.mask_overlap += b_dt
        dt = time.perf_counter() - t0 - (0.0 if hidden else m_total)
        self.n_fwd += 1
        for sess in self.slots:
            if sess is not None:
                sess.n_fwd += 1
                sess.model_time += dt
        return lg

    def _plain_step(self) -> None:
        eng = self.eng
        self._ensure_pages(1)
        live = self._commit_first(self._choose())
        if not any(s is not None for s in self.slots):
            return
        feed = [[eng.tok.pad_id]] * self.capacity
        for slot, tok in live.items():
            feed[slot] = [tok]
        lg = self._run_decode(feed, overlap_fn=self._prebuild_masks)
        self._logits = lg[:, -1].astype(jnp.float32)
        self._inject_nan_rows("decode_nan")

    # -- device-resident fused decode loop (tentpole) ---------------------------

    def _sid_for(self, sess: Session) -> int:
        """Global device-table state id for this session's CURRENT
        checker state, or OFF_FRONTIER when the row cannot be tracked:
        no uploaded tables, no constraint spec, a checker whose concrete
        type is not exactly DominoDecoder (healed / online / naive
        subclasses and stubs own semantics the table was not built
        from), bounded lookahead, a custom EOS id, an unregistered or
        uncertified grammar, or an abstract state outside the certified
        frontier."""
        dts = self._dts
        if dts is None or sess.checker is None or sess.request is None:
            return OFF_FRONTIER
        ch = sess.checker
        if type(ch) is not DominoDecoder or not ch.device_trackable:
            return OFF_FRONTIER
        spec = sess.request.constraint
        gname = getattr(spec, "grammar", None)
        if not isinstance(gname, str) or gname not in dts.offsets:
            return OFF_FRONTIER
        if sess.eos_id != dts.tables[gname].eos_id:
            return OFF_FRONTIER    # table EOS edges assume the engine EOS
        sid = dts.sid_for(gname, ch)
        if sid < 0:
            return OFF_FRONTIER
        # ENTRY AUDIT.  The abstract-key quotient of a context-free
        # grammar is not a bisimulation: two concrete states can share a
        # key yet disagree on their mask (a quotient escape — see
        # analysis.build_device_table).  Admission is the cheap place to
        # catch it: a fresh checker's mask_bits() hits the shared memo,
        # so this is a dict lookup + array compare, not a mask build.
        t0 = time.perf_counter()
        bits = ch.mask_bits()
        sess.mask_time += time.perf_counter() - t0
        if not np.array_equal(dts.mask_host[sid], bits):
            self.n_quotient_escapes += 1
            return OFF_FRONTIER
        return sid

    def _advance_sid(self, slot: int, sess: Session, tok: int) -> None:
        """Mirror a checker advance through the host transition table —
        O(1) incremental device-state tracking — auditing the landing
        state's mask row against the concrete checker every ``sync_n``
        advances so a quotient escape can't drift unbounded."""
        sid = int(self._dts.trans_host[self._dev_state[slot], tok])
        if sid < 0:
            self._dev_state[slot] = OFF_FRONTIER
            return
        self._dev_age[slot] += 1
        if self._dev_age[slot] < self.sync_n:
            self._dev_state[slot] = sid
            return
        self._dev_state[slot] = self._audit_sid(slot, sess, sid)

    def _audit_sid(self, slot: int, sess: Session, sid: int) -> int:
        """Compare the table's mask row against the concrete checker's
        packed mask.  Equal -> the table keeps selecting for this row;
        different -> a quotient escape: demote the row to the exact host
        path (the audit's mask build is kept as its premask, not
        wasted).  Bounds table/checker divergence to one audit
        interval at a cost of 1/sync_n mask builds per token."""
        t0 = time.perf_counter()
        bits = sess.checker.mask_bits()
        sess.mask_time += time.perf_counter() - t0
        self._dev_age[slot] = 0
        # table_corrupt simulates a corrupted device-table mask row; the
        # audit catches it exactly like a real quotient escape would
        corrupt = self._inject("table_corrupt", sess)
        if not corrupt and np.array_equal(self._dts.mask_host[sid], bits):
            return sid
        self.n_quotient_escapes += 1
        self._premask[slot] = bits
        if self.journal is not None:
            self.journal.append({
                "kind": "demote", "rid": sess.rid,
                "reason": ("injected table corruption" if corrupt
                           else "mask-row audit mismatch")})
        return OFF_FRONTIER

    def _device_ready(self) -> bool:
        """True when EVERY resident row can commit tokens without a host
        round-trip: greedy, non-speculative, constrained by a checker
        whose state sits inside an uploaded device table, with room for a
        full block.  All-or-nothing on purpose: one host-path row needs a
        host sync per token anyway, so fusing its batch-mates buys
        nothing and would split the batched forward — mixed ticks take
        the per-token path, where certified rows still gather their
        masks from the device table."""
        if not self.device_loop or self._dts is None or self.sync_n < 2 \
                or self.eng._needs_refeed or self.sup.level > 0:
            return False     # degraded: the ladder owns the path choice
        ready = False
        for slot, sess in enumerate(self.slots):
            if sess is None:
                continue
            if sess.checker is None or sess.speculator is not None \
                    or sess.temperature > 0.0 \
                    or self._dev_state[slot] < 0:
                return False
            ready = True
        if not ready:
            return False
        # a fused block writes up to sync_n new cache positions per row;
        # near max_len fall back to the per-token path (which stops at
        # the exact boundary) rather than write past the cache
        lens = np.asarray(self.cache["len"])
        return int(lens.max()) + self.sync_n <= self.eng.max_len

    def _build_fused(self):
        """Trace the fused N-step decode loop: forward, packed-mask
        argmax, transition-table state advance and KV append run entirely
        on device inside ``lax.while_loop``; the host syncs once per
        block.  Per-row early exit: EOS selection, budget exhaustion,
        off-frontier transition, or a non-finite logits row (fault) drop
        the row from ``active``; the loop ends when no row is active.

        Faithfulness to the per-token path (bitwise, for greedy rows):
        selection is the same ``masked_argmax_pallas_packed`` over the
        same table mask row; injected NaNs poison logits AFTER the
        forward, so detection happens at the NEXT selection's finiteness
        check exactly like the host path; the whole-block length rewind
        (``len = snap_len + n_fed``) is the speculative-rollback idiom —
        every iteration advances every row's ragged ``len`` by one, only
        the fed tokens are real, KV beyond ``len`` is masked by validity.
        """
        eng = self.eng
        n = self.sync_n
        v = eng._v
        pad_id = eng.tok.pad_id
        decode = eng.model.decode_step
        interpret = jax.default_backend() != "tpu"
        cap = self.capacity

        def fused(params, cache, lg, state, active, rem, eos_ids,
                  nan_plan, mask_tab, trans_tab, n_cap):
            snap_len = cache["len"]
            toks0 = jnp.full((cap, n), -1, jnp.int32)
            raws0 = jnp.full((cap, n), -1, jnp.int32)

            # n_cap is a TRACED operand (the deadline clamp changes it
            # block to block without recompiling); the static n still
            # bounds every buffer shape
            def cond(c):
                return (c[0] < jnp.minimum(n, n_cap)) & jnp.any(c[5])

            def body(c):
                (i, cache, lg, out_lg, state, active, rem, toks, raws,
                 n_fed, fault) = c
                finite = jnp.all(jnp.isfinite(lg[:, :v]), axis=-1)
                fault = fault | (active & ~finite)
                commit = active & finite
                masks = mask_tab[jnp.maximum(state, 0)]
                sel, _ = masked_argmax_pallas_packed(
                    lg[:, :v], masks, interpret=interpret)
                sel = sel.astype(jnp.int32)
                raw = jnp.argmax(lg[:, :v], axis=-1).astype(jnp.int32)
                eos_hit = commit & (sel == eos_ids)
                adv = commit & ~eos_hit
                rem = jnp.where(adv, rem - 1, rem)
                fed = adv & (rem > 0)
                nxt = trans_tab[jnp.maximum(state, 0),
                                jnp.maximum(sel, 0)]
                state = jnp.where(adv, nxt, state)
                toks = toks.at[:, i].set(jnp.where(commit, sel, -1))
                raws = raws.at[:, i].set(jnp.where(commit, raw, -1))
                feed = jnp.where(fed, sel, pad_id)[:, None]
                new_lg, cache = decode(params, cache, feed)
                new_lg = new_lg[:, -1, :].astype(jnp.float32)
                new_lg = jnp.where(nan_plan[:, i][:, None], jnp.nan,
                                   new_lg)
                out_lg = jnp.where(fed[:, None], new_lg, out_lg)
                n_fed = n_fed + fed.astype(jnp.int32)
                active = fed & (nxt >= 0)
                return (i + 1, cache, new_lg, out_lg, state, active, rem,
                        toks, raws, n_fed, fault)

            carry = (jnp.int32(0), cache, lg, lg, state, active, rem,
                     toks0, raws0, jnp.zeros((cap,), jnp.int32),
                     jnp.zeros((cap,), bool))
            (steps, cache, _lg, out_lg, state, _active, _rem, toks, raws,
             n_fed, fault) = jax.lax.while_loop(cond, body, carry)
            cache = dict(cache)
            cache["len"] = snap_len + n_fed
            return cache, out_lg, state, toks, raws, n_fed, fault, steps

        return jax.jit(fused, donate_argnums=(1,))

    def _deadline_cap(self) -> int:
        """Clamp the next fused block's step count to the nearest
        resident deadline: a full sync_n block can overshoot a deadline
        by up to sync_n tokens' wall time, so price the remaining budget
        of every deadline-carrying resident through the measured
        tokens/s EWMA and stop the block there (>= 1 step: lifecycle
        checks still only run at block boundaries, so the block must
        make progress).  Unprimed EWMA (first block) -> no clamp."""
        n_cap = self.sync_n
        if self._tok_s_ema <= 0.0:
            return n_cap
        now = time.perf_counter()
        for sess in self.slots:
            if sess is None:
                continue
            deadline = sess.deadline_s
            if deadline is None:
                deadline = self.default_deadline_s
            if deadline is None:
                continue
            left = deadline - (now - sess.t_submit)
            n_cap = min(n_cap, max(1, int(left * self._tok_s_ema)))
        if n_cap < self.sync_n:
            self.n_deadline_clamps += 1
        return n_cap

    def _device_step(self) -> None:
        """One fused tick: run up to ``sync_n`` decode steps in a single
        device call, then ONE host readback, then replay every committed
        token through the concrete checkers (``_resync_row``) so host
        state, statuses and results are exactly what the per-token path
        would have produced for the same tokens."""
        eng = self.eng
        # reserve the whole block's cache growth up front (may preempt —
        # preempted rows clear their _dev_state, survivors stay eligible)
        self._ensure_pages(self.sync_n)
        if not any(s is not None for s in self.slots):
            return
        self._sync_pages()
        active0 = np.asarray([s is not None for s in self.slots], bool)
        rem0 = np.asarray([0 if s is None else s.budget
                           for s in self.slots], np.int32)
        eos0 = np.asarray([-1 if s is None else s.eos_id
                           for s in self.slots], np.int32)
        state0 = np.where(active0, self._dev_state,
                          OFF_FRONTIER).astype(np.int32)
        # consult the decode_nan fault plan for the whole block up front
        # (same per-row consultation order as sync_n host ticks)
        self._nan_plan[:] = False
        if self.injector is not None:
            for j in range(self.sync_n):
                for slot, sess in enumerate(self.slots):
                    if sess is not None:
                        self._nan_plan[slot, j] = self._inject(
                            "decode_nan", sess)
        if self._fused_fn is None:
            self._fused_fn = self._build_fused()
        n_cap = self._deadline_cap()
        # the fused call DONATES the cache, so it must never be retried:
        # injected device_timeout is consulted PRE-dispatch (nothing
        # dispatched yet -> retry-safe no-op thunk), and a real exception
        # below resets the engine instead of re-running the block
        ok, err = self.sup.guard(
            "fused_dispatch", lambda: None,
            inject=lambda: self._inject("device_timeout"))
        if not ok:
            self.sup.degrade("fused_dispatch", err)
            return           # nothing ran; next tick takes the host path
        t0 = time.perf_counter()
        try:
            with span("dispatch"):
                (self.cache, out_lg, state_dev, toks_dev, raws_dev,
                 n_fed_dev, fault_dev, steps_dev) = self._fused_fn(
                    eng.params, self.cache, self._logits,
                    jnp.asarray(state0), jnp.asarray(active0),
                    jnp.asarray(rem0), jnp.asarray(eos0),
                    jnp.asarray(self._nan_plan),
                    self._dts.mask_dev, self._dts.trans_dev,
                    jnp.int32(n_cap))
            with span("wait"):
                out_lg.block_until_ready()
        except Exception as e:
            # an XLA/runtime error escaped the fused dispatch and the
            # donated cache is unrecoverable: reset the engine surface
            # (residents recompute-preempt, outputs unchanged) and step
            # down the ladder
            self._engine_reset(f"fused block failed: {e!r}")
            self.sup.degrade("fused_block", e)
            return
        dt = time.perf_counter() - t0
        self._logits = out_lg
        # the block's ONE host sync: tokens, states, counts, faults and
        # step count all come back in a single readback
        self.n_host_syncs += 1
        if self._inject("device_error"):
            # simulated corrupt readback: nothing from this block can be
            # trusted, so discard it wholesale — no token was committed
            # or journaled, so recompute-preemption keeps outputs exact
            self._engine_reset("device_error at fused-block readback")
            self.sup.degrade("fused_readback",
                             RuntimeError("injected device_error at "
                                          "fused-block readback"))
            return
        if self.sup.block_watchdog_s is not None \
                and dt > self.sup.block_watchdog_s:
            # the block FINISHED, just too slowly: its results are good
            # (commit them below) but the device is suspect — degrade
            self.sup.n_watchdog_trips += 1
            self.sup.degrade(
                "fused_block_watchdog",
                TimeoutError(f"fused block took {dt:.3f}s > watchdog "
                             f"{self.sup.block_watchdog_s:g}s"))
        with span("readback"):
            toks = np.asarray(toks_dev)
            raws = np.asarray(raws_dev)
            state_out = np.asarray(state_dev)
            n_fed = np.asarray(n_fed_dev)
            fault = np.asarray(fault_dev)
            steps_run = int(steps_dev)
        self._last_block_steps = steps_run
        fed_total = int(n_fed.sum())
        if fed_total and dt > 0:
            # committed-tokens/s EWMA: prices the next block's deadline
            # clamp (_deadline_cap).  alpha=0.3 — quick to prime, stable
            # against one slow (compile) block.
            rate = fed_total / dt
            self._tok_s_ema = (rate if self._tok_s_ema == 0.0
                               else 0.7 * self._tok_s_ema + 0.3 * rate)
        self.n_fwd += steps_run
        with span("commit"):
            for slot, sess in enumerate(list(self.slots)):
                if sess is None:
                    continue
                sess.n_fwd += int(n_fed[slot])
                sess.model_time += dt
                self._resync_row(slot, sess, toks[slot], raws[slot],
                                 bool(fault[slot]), int(state_out[slot]),
                                 steps_run)
        self._shrink_pages()   # rows that exited early rewound their len

    def _resync_row(self, slot: int, sess: Session, toks_row, raws_row,
                    faulted: bool, state_out: int,
                    steps_run: int) -> None:
        """Replay one row's device-committed token block through its
        CONCRETE checker, mirroring ``_commit_first`` token for token —
        grammar state, out_ids, budget, EOS/status taxonomy and
        intervention counts end up exactly as the per-token path would
        have left them.  A checker exception (injected or real)
        quarantines THIS row; a checker REJECTION (quotient escape)
        recompute-preempts it with the validated prefix intact; a device
        fault flag surfaces as the same ``internal_error`` the host
        finiteness check raises."""
        if sess.cancel_requested:
            # cancellation arrived while the block was in flight: honor
            # it at THIS block boundary — none of the block's tokens are
            # committed (or journaled) for this row, and the next tick's
            # lifecycle sweep terminates it with `cancelled`, so a
            # cancel never trails by more than one block
            return
        ch = sess.checker
        for j in range(steps_run):
            tok = int(toks_row[j])
            if tok < 0:
                break                  # row went inactive at step j
            sess.n_int += int(tok != int(raws_row[j]))
            try:
                if tok == sess.eos_id:
                    with span("advance", rid=sess.rid):
                        ok = ch.advance(tok)
                    if ok:
                        sess.finished_eos = True
                        self._finish(sess)
                    else:
                        # quotient escape: table offered EOS where the
                        # checker forbids it.  State is unchanged, the
                        # validated prefix intact: recompute-preempt;
                        # _sid_for's entry audit demotes the row to the
                        # host path on re-admission if still escaped.
                        self.n_table_rejects += 1
                        self._preempt(sess)
                    return
                if self._inject("advance_error", sess):
                    raise InjectedFault(
                        f"injected advance failure (rid={sess.rid})")
                with span("advance", rid=sess.rid):
                    ok = ch.advance(tok)
            except Exception as e:   # quarantined: evict THIS row only
                self._fail(sess, f"checker failed during advance: {e!r}")
                return
            self._premask.pop(slot, None)   # state moved: mask stale
            if not ok:
                # quotient escape surfaced as a concrete rejection (same
                # recovery as the EOS case above) — never silent
                # corruption, never a lost request
                self.n_table_rejects += 1
                self._preempt(sess)
                return
            sess.out_ids.append(tok)
            sess.budget -= 1
            sess.n_device_tokens += 1
            self.n_device_tokens += 1
            if sess.budget <= 0:
                self._finish(sess)
                return
        if faulted:
            self._fail(sess, "non-finite logits from device step")
            return
        if state_out < 0:
            self._dev_state[slot] = OFF_FRONTIER
            return
        # block boundary = audit point: the fused loop ran up to sync_n
        # table transitions with no concrete checker in the loop
        self._dev_state[slot] = self._audit_sid(slot, sess, int(state_out))

    # -- speculative decode tick (§3.6) -----------------------------------------

    def _spec_step(self, width: int) -> None:
        """One speculative tick.  ``width`` is 1 + the widest resident
        row's ``spec_s`` (per-row policy): rows with shorter chains — or
        no speculator at all — ride along on pad positions."""
        eng = self.eng
        pad = eng.tok.pad_id
        # reserve the full verify window up front: growing mid-tick could
        # preempt a row whose token was already committed into the feed
        self._ensure_pages(width)
        live = self._commit_first(self._choose())
        if not any(s is not None for s in self.slots):
            return
        proposals: Dict[int, List[int]] = {}
        for slot, tok in live.items():
            sess = self.slots[slot]
            ch = sess.checker
            props = []
            if ch is not None and sess.speculator is not None \
                    and hasattr(ch, "clone"):
                props = sess.speculator.propose(ch)
            sess.n_prop += len(props)
            proposals[slot] = props
        if all(len(p) == 0 for p in proposals.values()):
            # nothing to verify anywhere: plain-width forward, no rollback
            feed = [[pad]] * self.capacity
            for slot, tok in live.items():
                feed[slot] = [tok]
            lg = self._run_decode(feed, overlap_fn=self._prebuild_masks)
            self._logits = lg[:, -1].astype(jnp.float32)
            self._inject_nan_rows("decode_nan")
            self._shrink_pages()       # return the unused verify window
            return
        feed = [[pad] * width for _ in range(self.capacity)]
        for slot, tok in live.items():
            row = [tok] + proposals[slot]
            feed[slot][:len(row)] = row
        snapshot = self.cache          # for the refeed (dense: not donated)
        # overlapped prebuild: checker state is post-commit, i.e. exactly
        # the state verification position 0 selects from — _verify_row
        # consumes the mask, and untouched rows keep it for the next tick
        lg_dev = self._run_decode(feed, overlap_fn=self._prebuild_masks)
        with span("readback"):
            lg_host = np.asarray(lg_dev)[:, :, :eng._v]
        # rows not in `live` consumed the full pad width; "accepting" it
        # keeps their (garbage, to-be-overwritten) length bookkeeping
        # consistent with the decoded cache
        accepted_vec = np.full(self.capacity, width - 1, np.int32)
        with span("commit"):
            for slot, props in proposals.items():
                try:
                    accepted_vec[slot] = self._verify_row(slot, props,
                                                          lg_host[slot])
                except Exception as e:   # quarantined: evict THIS row only
                    accepted_vec[slot] = 0
                    if self.slots[slot] is not None:
                        self._fail(self.slots[slot],
                                   f"checker failed during speculative "
                                   f"verify: {e!r}")
        if eng._needs_refeed:
            self._fixup_refeed(snapshot, live, proposals, accepted_vec,
                               lg_dev, width)
        else:
            # per-row rollback: KV entries beyond `len` are masked by
            # validity, so rewinding the per-row length is the whole
            # rollback; pages now wholly beyond a frontier go back to the
            # pool right away.  The step advanced every len by `width`
            # (the paged decode donated the snapshot's)
            cache = dict(self.cache)
            cache["len"] = (self.cache["len"] - width + 1
                            + jnp.asarray(accepted_vec))
            self.cache = cache
            self._logits = lg_dev[
                jnp.arange(self.capacity), jnp.asarray(accepted_vec)
            ].astype(jnp.float32)
            self._shrink_pages()

    def _verify_row(self, slot: int, props: List[int],
                    lg_row: np.ndarray) -> int:
        """Greedy per-row verification, identical to the single-request
        path: accept the longest prefix where the proposal matches the
        (masked) selection at each position.  All policy — temperature,
        opportunistic checking, EOS id — is the row's own."""
        eng = self.eng
        sess = self.slots[slot]
        ch = sess.checker
        greedy = sess.temperature <= 0.0
        accepted = 0
        for i, prop in enumerate(props):
            if sess.budget <= 0:
                break
            if not np.all(np.isfinite(lg_row[i])):
                # surfaces as internal_error via the caller's quarantine
                raise RuntimeError(
                    "non-finite logits in speculative verify window")
            tok_i = None
            if greedy and int(lg_row[i].argmax()) == prop:
                t0 = time.perf_counter()
                ok = ch.check_token(prop)
                sess.mask_time += time.perf_counter() - t0
                if ok:
                    tok_i = prop
            if tok_i is None:
                # a full mask is needed at this position — worth
                # prebuilding again next tick under opportunistic mode
                self._opp_intervened[slot] = True
                # position 0 selects from the state the overlapped
                # prebuild saw; later positions advanced past it
                pre = self._premask.pop(slot, None) if i == 0 else None
                # under opportunistic mode _pick may accept the raw
                # argmax without reading the premask — don't count a hit
                # we can't attest
                if not (sess.opportunistic and greedy):
                    self.premask_hits += int(pre is not None)
                hits0 = getattr(ch, "n_mask_memo_hits", 0)
                tok_i, intervened, mask_dt = eng._pick(lg_row[i], ch,
                                                       premask=pre,
                                                       policy=sess)
                # _pick may have built a full mask (memo-eligible):
                # keep the scheduler aggregate consistent with the
                # per-session checker counters
                self.mask_cache_hits += \
                    getattr(ch, "n_mask_memo_hits", 0) - hits0
                sess.mask_time += mask_dt
                if tok_i is None:          # dead end mid-verification
                    sess.dead_end = True
                    break
                sess.n_int += intervened
            if tok_i != prop:
                break
            sess.speculator.observe(ch.state_key(), tok_i)
            if self._inject("advance_error", sess):
                raise InjectedFault(
                    f"injected advance failure (rid={sess.rid})")
            with span("advance", rid=sess.rid):
                ch.advance(tok_i)
            self._premask.pop(slot, None)   # state moved: mask stale
            if self._dev_state[slot] >= 0:
                # tok_i was checker-validated above; only the table state
                # needs mirroring (with its periodic escape audit)
                self._advance_sid(slot, sess, tok_i)
            accepted += 1
            if tok_i == sess.eos_id:
                sess.finished_eos = True
                break
            sess.out_ids.append(tok_i)
            sess.budget -= 1
        sess.n_acc += accepted
        if sess.finished_eos or sess.dead_end or sess.budget <= 0:
            self._finish(sess)
        return accepted

    def _fixup_refeed(self, snapshot, live, proposals, accepted_vec,
                      lg_dev, width: int) -> None:
        """SSM/SWA rows cannot rewind state: re-feed each partially-
        accepted row's committed tokens from the pre-speculation cache.
        Rows are grouped by committed length, so each group is ONE
        gather/decode/scatter round (B=K ragged refeed) instead of a B=1
        decode plus whole-cache scatter per row — one compile per
        (group size, width) pair, bounded by capacity x spec_s."""
        eng = self.eng
        groups: Dict[int, List[int]] = {}
        committed: Dict[int, List[int]] = {}
        for slot, tok in live.items():
            sess = self.slots[slot]
            if sess is None:
                # finished during verification: the slot is free and its
                # row state is overwritten at the next admission
                continue
            a = int(accepted_vec[slot])
            props = proposals[slot]
            if a == len(props) and len(props) == width - 1:
                # full accept, no pads: the batch-decoded row state is exact
                self._logits = self._logits.at[slot].set(
                    lg_dev[slot, -1].astype(jnp.float32))
                continue
            groups.setdefault(a, []).append(slot)
            committed[slot] = [tok] + props[:a]
        for a, slots in groups.items():
            idx = jnp.asarray(slots, jnp.int32)
            feed = jnp.asarray([committed[s] for s in slots], jnp.int32)
            t0 = time.perf_counter()
            with span("dispatch"):
                rows = _gather_rows_jit(snapshot, idx)
                lg_re, rows = eng._decode(eng.params, rows, feed)
                self.cache = _scatter_rows_jit(self.cache, rows, idx)
                self._logits = self._logits.at[idx].set(
                    lg_re[:, -1].astype(jnp.float32))
            # block so model_time measures execution, not dispatch (the
            # wait would otherwise hide in the next tick's argmax
            # readback, attributed to nothing)
            with span("wait"):
                lg_re.block_until_ready()
            dt = time.perf_counter() - t0
            self.n_fwd += 1
            for slot in slots:
                sess = self.slots[slot]
                sess.n_fwd += 1
                sess.model_time += dt
