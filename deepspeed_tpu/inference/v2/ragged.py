"""Paged KV-cache state.

Reference parity: the blocked KV cache of inference v2 —
``BlockedAllocator`` / ``KVCacheManager`` (inference/v2/ragged/,
ragged/csrc/fast_host_buffer.cpp and friends).  The reference manages
blocks with a C++ host allocator feeding CUDA ragged kernels; here the
allocator is host Python (it runs between jitted steps, off the hot
device path) and the cache is a dense page pool the decode program
indexes with page tables.

Layout: ``k``/``v`` are ``[L, num_pages + 1, page_size, KVH, D]``.  The
last page (index ``num_pages``) is the *trash page*: writes from inactive
slots and pad positions are routed there, keeping every device-side
scatter unconditional (no data-dependent control flow under jit).

A model whose layers keep fixed-size recurrent state has a second kind of
cache in the same manager: *state slots*, ``[L_state, max_seqs + 1, ...]``
per leaf, one slot per decode row and a trash slot last (``StateSlots`` is
the host's book of who holds which).  ``L`` of the page pool is then the
number of layers that keep pages, not the model's depth.
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

#: request priority classes (smaller = more urgent).  Priorities order
#: admission (the scheduler admits the highest class first), choose
#: preemption victims under KV-pool pressure (lowest class, then
#: youngest), and gate load shedding (``serving/admission.py`` sheds
#: only classes above the protected threshold under overload).
PRIORITY_INTERACTIVE = 0
PRIORITY_NORMAL = 1
PRIORITY_BATCH = 2


class RejectedError(RuntimeError):
    """A request refused by admission control (load shedding).

    Not a bug and not data loss: the submitter still holds the request
    and should back off ``retry_after_s`` seconds before resubmitting.
    Raised by ``InferenceEngineV2.put`` (bounded queue,
    ``max_queue_depth``) and by the fleet router's admission controller
    (queue bound / KV-pool occupancy shed threshold) — loudly, instead
    of queuing work into an OOM/preemption storm."""

    def __init__(self, reason: str, retry_after_s: float = 1.0,
                 priority: Optional[int] = None):
        super().__init__(
            f"request rejected ({reason}); retry after {retry_after_s:.2f}s")
        self.reason = reason
        self.retry_after_s = float(retry_after_s)
        self.priority = priority


@dataclasses.dataclass
class KVBlockConfig:
    page_size: int = 16
    num_pages: int = 256
    max_seqs: int = 8  # concurrent decode slots
    max_pages_per_seq: int = 16

    @property
    def max_seq_len(self) -> int:
        return self.page_size * self.max_pages_per_seq

    @property
    def trash_page(self) -> int:
        return self.num_pages


class BlockAllocator:
    """Ref-counted page allocator (reference inference/v2/ragged
    BlockedAllocator, grown for automatic prefix caching): O(1)
    alloc/share/free, host-side.

    Every live page carries a refcount: ``alloc`` hands out pages at
    refcount 1, ``share`` maps an already-written page into another
    sequence (+1), ``free`` drops a reference.  A page is *never* recycled
    while referenced.  Pages may additionally be **registered** under a
    content key (PrefixCache): when a registered page's refcount drops to
    0 it is parked in an LRU of cached-but-unreferenced pages instead of
    the raw free list, so later requests with the same prefix can re-map
    it.  ``alloc`` prefers truly-free pages and only then evicts from the
    LRU tail (unregistering the evicted key) — referenced pages are never
    eviction candidates because they are never in the LRU.
    """

    def __init__(self, num_pages: int, cache_pages: int = 0):
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._ref: List[int] = [0] * num_pages
        #: cap on cached-but-unreferenced pages retained (0 = pool-bounded)
        self.cache_cap = cache_pages
        self._by_key: Dict[Any, int] = {}   # content key -> page
        self._key_of: Dict[int, Any] = {}   # page -> content key
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # oldest first
        self.evictions = 0
        #: tiered KV cache (serving/kv_tier.py): called as
        #: ``hook(page, key) -> bool`` for every page evicted from the
        #: prefix-cache LRU.  Returning True CAPTURES the page for a
        #: host-RAM spill: the allocator pins it (refcount 1, tracked in
        #: ``_spill_pinned``) so it cannot be handed out — and therefore
        #: never overwritten — until the spill's D2H copy commits and
        #: the owner calls :meth:`release_spill_pin`.
        self.spill_hook = None
        self._spill_pinned: set = set()
        #: pin headroom for the CURRENT ``alloc`` call: each captured
        #: eviction consumes one unit of the capacity beyond the request,
        #: so capturing can never starve the allocation mid-loop.
        #: Outside ``alloc`` (cap trims) pinning is unconstrained.
        self._pin_slack = num_pages
        #: bumped on every registry change (register/evict) so match
        #: results can be memoized: a blocked head-of-queue request must
        #: not re-hash its whole prompt every engine step when nothing
        #: it could match against has changed
        self.generation = 0
        #: bumped only on unregister: registrations can only EXTEND an
        #: existing match, so while this is unchanged a memoized match
        #: prefix stays valid and the walk can RESUME from its end
        #: instead of re-hashing the whole prompt
        self.evict_generation = 0

    @property
    def free_pages(self) -> int:
        """Allocatable pages: truly free + cached-but-unreferenced."""
        return len(self._free) + len(self._lru)

    @property
    def used_pages(self) -> int:
        """Pages referenced by live sequences (refcount > 0)."""
        return self.num_pages - len(self._free) - len(self._lru)

    @property
    def uncached_free_pages(self) -> int:
        """Truly-free pages, excluding cached-but-unreferenced LRU pages.
        This is the budget speculative draft reservation spends: draft
        tokens may be rejected, so the engine never evicts prefix-cache
        content (guaranteed future savings) to reserve pages for them —
        only the base token may claim LRU pages, exactly like plain
        decode."""
        return len(self._free)

    @property
    def lru_pages(self) -> int:
        """Cached-but-unreferenced pages parked in the LRU: they occupy
        pool HBM purely for prefix reuse (the "pinned" occupancy the
        serving gauges and the memory ledger report)."""
        return len(self._lru)

    @property
    def cached_pages(self) -> int:
        return len(self._by_key)

    def refcount(self, page: int) -> int:
        return self._ref[page]

    def alloc(self, n: int) -> List[int]:
        if n > self.free_pages:
            raise MemoryError(f"KV pool exhausted: need {n} pages, "
                              f"{self.free_pages} free")
        # spill captures during the evictions below consume ONLY the
        # headroom beyond this request: free_pages was just proven >= n,
        # and every loop iteration takes one page from (free + LRU) for
        # the caller plus at most slack pages for pins — the request
        # itself can never fail mid-loop with refcounts half-mutated
        self._pin_slack = self.free_pages - n
        try:
            out = []
            for _ in range(n):
                if self._free:
                    p = self._free.pop()
                else:
                    p = self._evict_lru()
                self._ref[p] = 1
                out.append(p)
        finally:
            self._pin_slack = self.num_pages
        return out

    def try_alloc(self, n: int,
                  uncached_only: bool = False) -> Optional[List[int]]:
        """Headroom reservation (fused multi-step decode): allocate ``n``
        pages, or return ``None`` — allocator untouched — when the pool
        cannot cover them.  The engine pre-reserves each decode row's
        page headroom for the whole horizon before dispatch and SHRINKS
        the horizon on refusal instead of preempting mid-scan, so this
        is the non-raising twin of :meth:`alloc` for callers whose
        fallback is "ask for less", not "crash the step".

        ``uncached_only=True`` spends TRULY-free pages only: horizon
        headroom backs tokens a row may never produce (mid-horizon
        EOS), so — exactly like speculative draft reservation — it must
        never evict prefix-cache LRU content (guaranteed future
        savings) to cover it; ``alloc`` prefers the free list, so a
        grant within it never touches the LRU."""
        budget = self.uncached_free_pages if uncached_only \
            else self.free_pages
        if n > budget:
            return None
        return self.alloc(n)

    def share(self, page: int) -> int:
        """Map an already-written page into another sequence (+1 ref).
        A cached page at refcount 0 leaves the LRU: it is live again."""
        if not (0 <= page < self.num_pages):
            raise ValueError(f"sharing invalid page {page}")
        if self._ref[page] == 0:
            if page not in self._lru:
                raise ValueError(f"sharing unreferenced uncached page {page}")
            del self._lru[page]
        self._ref[page] += 1
        return page

    def free(self, pages: List[int]) -> None:
        # validate the WHOLE list before mutating (duplicate-aware): a
        # bad page mid-list must not leave earlier refcounts decremented
        counts: Dict[int, int] = {}
        for p in pages:
            if not (0 <= p < self.num_pages):
                raise ValueError(f"freeing invalid page {p}")
            counts[p] = counts.get(p, 0) + 1
        for p, c in counts.items():
            if self._ref[p] < c:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                if p in self._key_of:
                    # registered content survives: park in the LRU (MRU
                    # end) for prefix reuse instead of the free list
                    self._lru[p] = None
                    self._trim_cache()
                else:
                    self._free.append(p)

    # -- debug leak/invariant audit ------------------------------------------
    def check_invariants(
            self, live_pages: Optional[Sequence[Sequence[int]]] = None
    ) -> None:
        """Audit the allocator's internal invariants; raise
        ``AssertionError`` naming the first violation.  Cheap (O(pages))
        and read-only — tests and ``tools/fleet_drill.py`` run it after
        KV churn (speculative rollback, migration, preemption) so a
        leaked page or refcount can never pass silently.

        Structural invariants (always checked):

        * every page is in exactly one of {free list, LRU, referenced};
        * the free list has no duplicates and only refcount-0 pages;
        * every LRU page is refcount-0 AND registered;
        * ``_by_key``/``_key_of`` are a bijection over registered pages;
        * ``cache_cap`` (when set) bounds the LRU;
        * every spill-pinned page (host-tier capture awaiting its D2H
          commit) is referenced (its pin IS a reference) and
          unregistered — it sits in the "referenced" partition with no
          sequence owner.

        ``live_pages`` — one page list per live owner (e.g. every
        slotted sequence's ``seq.pages``) — additionally audits the
        refcounts *exactly*: each page's refcount must equal its total
        occurrence count across owners, PLUS one for an in-flight spill
        pin.  A surplus refcount is a leak (freed sequence still holding
        pages); a deficit is a use-after-free in waiting."""
        # explicit raises (not bare asserts) so ``python -O`` can't
        # compile the audit out and vacuously pass the leak gates
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            raise AssertionError(
                f"free list has duplicates: {sorted(self._free)}")
        if free_set & set(self._lru):
            raise AssertionError(
                f"pages in free list AND LRU: {sorted(free_set & set(self._lru))}")
        for p in self._free:
            if self._ref[p] != 0:
                raise AssertionError(
                    f"page {p} in free list with refcount {self._ref[p]}")
        for p in self._lru:
            if self._ref[p] != 0:
                raise AssertionError(
                    f"LRU page {p} has refcount {self._ref[p]}")
            if p not in self._key_of:
                raise AssertionError(f"LRU page {p} is not registered")
        referenced = {p for p in range(self.num_pages) if self._ref[p] > 0}
        if referenced & free_set:
            raise AssertionError(
                f"referenced pages in free list: {sorted(referenced & free_set)}")
        covered = len(free_set) + len(self._lru) + len(referenced)
        if covered != self.num_pages:
            raise AssertionError(
                f"page partition broken: {len(free_set)} free + "
                f"{len(self._lru)} LRU + {len(referenced)} referenced "
                f"!= {self.num_pages} pages (a refcount-0 page outside "
                "free/LRU is a leaked page)")
        if len(self._by_key) != len(self._key_of):
            raise AssertionError("registry maps disagree in size")
        for key, p in self._by_key.items():
            if self._key_of.get(p) != key:
                raise AssertionError(f"registry not a bijection at page {p}")
        if self.cache_cap > 0 and len(self._lru) > self.cache_cap:
            raise AssertionError(
                f"LRU {len(self._lru)} exceeds cache_cap {self.cache_cap}")
        for p in self._spill_pinned:
            if self._ref[p] < 1:
                raise AssertionError(
                    f"spill-pinned page {p} has refcount {self._ref[p]} "
                    "(the pin itself must hold a reference)")
            if p in self._key_of:
                raise AssertionError(
                    f"spill-pinned page {p} is still registered (eviction "
                    "must unregister before the capture)")
        if live_pages is not None:
            want: Dict[int, int] = {}
            for p in self._spill_pinned:
                want[p] = 1  # the in-flight spill's pin is a live ref
            for owner in live_pages:
                for p in owner:
                    want[p] = want.get(p, 0) + 1
            for p in range(self.num_pages):
                w = want.get(p, 0)
                if self._ref[p] != w:
                    raise AssertionError(
                        f"page {p}: refcount {self._ref[p]} != {w} live "
                        f"reference(s) — "
                        f"{'leak' if self._ref[p] > w else 'use-after-free'}")

    def assert_no_leaks(
            self, live_pages: Sequence[Sequence[int]] = ()) -> None:
        """``check_invariants`` with an exact refcount audit against the
        given live owners (default: none live, so every page must be
        free or LRU-parked).  The speculative-rollback / KV-churn gate."""
        self.check_invariants(list(live_pages))

    def adopt(self, keys: Sequence[Optional[Any]]
              ) -> Tuple[List[int], List[bool]]:
        """Import-side page placement with **ref-count adoption** (the KV
        migration refactor): for each position, when ``keys[j]`` is
        already registered locally the existing page is *shared* (+1 ref)
        instead of duplicated — content-chain keys are content
        addresses, so the local page holds bit-identical KV and the
        imported sequence can map it directly.  Unmatched positions (or
        ``None`` keys — partial tail pages, cache-off imports) get fresh
        pages for the caller to fill from the bundle's arrays.

        All-or-nothing: insufficient capacity raises ``MemoryError``
        BEFORE any refcount moves, so a failed import leaves the
        allocator untouched.  Returns ``(pages, reused)`` where
        ``reused[j]`` says position ``j`` adopted a local page (its
        content must NOT be overwritten)."""
        matched = [self._by_key.get(k) if k is not None else None
                   for k in keys]
        # matched pages at refcount 0 sit in the LRU: counted in
        # free_pages but claimed by share(), not alloc() (same exactness
        # rule as engine_v2._admit)
        lru_matched = sum(1 for p in matched
                          if p is not None and self._ref[p] == 0)
        need = sum(1 for p in matched if p is None)
        if need > self.free_pages - lru_matched:
            raise MemoryError(
                f"KV import needs {need} fresh pages "
                f"(+{lru_matched} adopted from the LRU), only "
                f"{self.free_pages - lru_matched} allocatable")
        # share FIRST: matched LRU pages must be protected from being
        # evicted by the alloc() calls below
        for p in matched:
            if p is not None:
                self.share(p)
        fresh = iter(self.alloc(need))
        pages = [p if p is not None else next(fresh) for p in matched]
        return pages, [p is not None for p in matched]

    def export_meta(self, pages: Sequence[int]) -> List[Dict[str, Any]]:
        """Block-table metadata for a page list (serialization side of
        KV migration): per page, its id, refcount, and registered
        content key (None for unregistered/private pages)."""
        return [{"page": int(p), "refcount": self._ref[p],
                 "key": self._key_of.get(p)} for p in pages]

    # -- prefix-cache registry ----------------------------------------------
    def register(self, page: int, key: Any) -> bool:
        """Publish ``page`` as the cached page for ``key``.  First writer
        wins: duplicate keys (concurrent identical prefills) and pages
        already registered under another key are skipped."""
        if key in self._by_key or page in self._key_of:
            return False
        self._by_key[key] = page
        self._key_of[page] = key
        self.generation += 1
        return True

    def lookup(self, key: Any) -> Optional[int]:
        return self._by_key.get(key)

    def _unregister(self, page: int) -> None:
        key = self._key_of.pop(page, None)
        if key is not None and self._by_key.get(key) == page:
            del self._by_key[key]
            self.generation += 1
            self.evict_generation += 1

    def _evict_one(self) -> Optional[int]:
        """Pop + unregister the LRU tail and offer it to the spill hook.
        Returns the page when it is immediately reusable, or None when
        the hook captured it for a host-RAM spill (pinned at refcount 1
        until :meth:`release_spill_pin` — never handed out, so the spill
        copy can never race a new writer)."""
        page, _ = self._lru.popitem(last=False)
        key = self._key_of.get(page)
        self._unregister(page)
        self.evictions += 1
        if (self.spill_hook is not None and self._pin_slack > 0
                and self.spill_hook(page, key)):
            self._ref[page] = 1
            self._spill_pinned.add(page)
            self._pin_slack -= 1
            return None
        return page

    def _evict_lru(self) -> int:
        """Evict LRU pages until one is NOT captured for spill; returns
        that (allocatable) page.  Bounded: captures are limited by
        ``_pin_slack``, so the loop always terminates with a page."""
        while True:
            p = self._evict_one()
            if p is not None:
                return p

    def _trim_cache(self) -> None:
        if self.cache_cap > 0:
            while len(self._lru) > self.cache_cap:
                # _evict_one, not _evict_lru: when the hook captures the
                # tail page the LRU already shrank by one — looping for a
                # returnable page here would over-evict content still
                # within the cap
                p = self._evict_one()
                if p is not None:
                    self._free.append(p)

    # -- host-tier spill pins -------------------------------------------------
    @property
    def spill_pinned_pages(self) -> int:
        """Pages pinned by in-flight host-tier spills: evicted from the
        prefix-cache LRU but held out of circulation until their D2H
        copy commits.  Counted in neither ``free_pages`` nor
        ``lru_pages`` — they are temporarily ``used``."""
        return len(self._spill_pinned)

    def release_spill_pin(self, page: int) -> None:
        """Drop a spill pin after its D2H copy committed (or was
        abandoned): the page returns to the truly-free list."""
        if page not in self._spill_pinned:
            raise ValueError(f"page {page} is not spill-pinned")
        self._spill_pinned.discard(page)
        self.free([page])


class PrefixCache:
    """Automatic prefix caching: a content-hash chain over FULL pages.

    Page ``j``'s key is ``hash((key[j-1], tokens[j*ps:(j+1)*ps]))`` — the
    chain makes a page's identity depend on its entire token prefix, so a
    lookup walk from the root finds the longest cached page-aligned
    prefix.  Only full pages are hashed: partial tail pages stay private
    to their sequence (the engine copy-on-writes the one case where a
    shared full page must be written — see engine_v2._admit).  Counters
    (``hits``/``misses`` here, ``evictions`` on the allocator) feed
    ``engine_v2.cache_stats()`` and the serving gauges.
    """

    def __init__(self, page_size: int, allocator: BlockAllocator):
        self.page_size = page_size
        self.allocator = allocator
        self.hits = 0    # page lookups that matched (counted on admission)
        self.misses = 0  # admission walks that ended on a missing page

    @staticmethod
    def chain_key(parent_key: Any, page_tokens: Sequence[int]) -> bytes:
        """sha256 digest chain, NOT Python hash(): registry lookups go by
        key equality alone, and a non-cryptographic 64-bit hash collision
        (or an offline-constructed colliding token sequence from another
        tenant) would silently map a request onto someone else's KV."""
        h = hashlib.sha256()
        if parent_key is not None:
            h.update(parent_key)
        h.update(",".join(str(int(t)) for t in page_tokens).encode())
        return h.digest()

    def page_keys(self, tokens: Sequence[int], n_pages: int,
                  prefix_keys: Sequence[Any] = ()) -> List[Any]:
        """Chain keys for full pages ``[len(prefix_keys), n_pages)``,
        extending an already-computed prefix of keys."""
        keys = list(prefix_keys)
        ps = self.page_size
        for j in range(len(keys), n_pages):
            parent = keys[j - 1] if j else None
            keys.append(self.chain_key(parent, tokens[j * ps:(j + 1) * ps]))
        return keys

    def match(self, tokens: Sequence[int],
              resume: Optional[Tuple[List[int], List[Any]]] = None,
              host_tier: Any = None):
        """Longest cached page-aligned prefix of ``tokens``: walks the
        hash chain over full pages until a key misses.  Pure — the caller
        bumps hits/misses only when an admission actually consumes the
        match (a blocked head-of-queue peek must not inflate the rate).

        ``resume``: a previous (pages, keys) match for the SAME tokens,
        known still valid (allocator.evict_generation unchanged since) —
        the walk continues from its end, so a blocked head of queue under
        heavy registration traffic re-hashes only the frontier page.

        ``host_tier``: a :class:`~...serving.kv_tier.HostKVTier` (or
        anything with ``has(key)``) consulted PAST the device hit: the
        walk continues into the host tier's spilled pages and the return
        grows a third element — the chain keys of consecutive host-held
        pages the engine can restore (H2D) before prefilling the rest.
        Without it the return stays the 2-tuple ``(pages, keys)``."""
        ps = self.page_size
        pages: List[int] = list(resume[0]) if resume else []
        keys: List[Any] = list(resume[1]) if resume else []
        parent = keys[-1] if keys else None
        for j in range(len(pages), len(tokens) // ps):
            key = self.chain_key(parent, tokens[j * ps:(j + 1) * ps])
            page = self.allocator.lookup(key)
            if page is None:
                break
            pages.append(page)
            keys.append(key)
            parent = key
        if host_tier is not None:
            return pages, keys, self.host_extend(tokens, keys, host_tier)
        return pages, keys

    def host_extend(self, tokens: Sequence[int], keys: Sequence[Any],
                    host_tier: Any) -> List[Any]:
        """Continue a device match's hash-chain walk into the HOST tier:
        chain keys for the consecutive full pages past the device hit
        that ``host_tier`` holds.  Pure — no counters, no restore (the
        engine restores and accounts when it consumes the extension)."""
        ps = self.page_size
        out: List[Any] = []
        parent = keys[-1] if keys else None
        for j in range(len(keys), len(tokens) // ps):
            key = self.chain_key(parent, tokens[j * ps:(j + 1) * ps])
            if not host_tier.has(key):
                break
            out.append(key)
            parent = key
        return out

    def count(self, matched_pages: int, n_full_pages: int) -> None:
        """Record a consumed match in the hit/miss counters."""
        self.hits += matched_pages
        if matched_pages < n_full_pages:
            self.misses += 1


class PagedKVCache:
    """Device arrays of the page pool.

    ``kv_quant``: store K/V as int8 codes + one fp32 scale per
    (page, slot, kv-head) — half the pool HBM of bf16, so twice the KV
    capacity (the reference's blocked-KV analogue of weight-only
    quantization, applied to the cache).  Quantize-on-write,
    dequantize-on-read; the paged Pallas kernel dequantizes in VMEM.

    K and V are ``[L, P+1, ps, KVH*D]`` — a page is the ``(ps, KVH*D)``
    block the decode kernel reads, so no program relayouts the pool —
    and the scales ``[L, P+1, ps, KVH]``.  ``KVPageBundle.arrays`` keeps
    ``[L, n, ps, KVH, D]``: ``model_runner.paged_gather_pages`` /
    ``paged_scatter_pages`` split and merge the last axis on page-sized
    data.

    The page format belongs to the layer types (``layer_types.page_leaves``):
    a model whose layers cache a latent (``mla``) has ONE leaf, ``latent``
    ``[L, P+1, ps, kv_lora_rank + qk_rope_head_dim]`` — the normalised latent
    and the rotated rotary key of a token side by side — and no K or V pool.
    At 256 + 64 bf16 values that row is 640 B; the device tiles the minor
    dimension by 128 lanes, so it occupies 384 lanes = 768 B as laid out
    (``tools/aot_serve_step.py`` prints it): one copy a page for the decode
    kernel, which reads the values as the first 256 lanes of the keys."""

    @staticmethod
    def init(n_layers: int, kv_heads: int, head_dim: int,
             block: KVBlockConfig, dtype=jnp.bfloat16,
             kv_quant: bool = False,
             state: Optional[Dict[str, Tuple[int, tuple, Any]]] = None,
             counters: Optional[Dict[str, int]] = None,
             pages: Optional[Dict[str, Tuple[int, int]]] = None
             ) -> Dict[str, Any]:
        """``n_layers``: the layers that keep pages.  ``pages``: ``{leaf:
        (layers, values a token)}`` where the format is not K and V of
        ``kv_heads * head_dim`` over ``n_layers``.  ``state``: ``{leaf:
        (layers, per-sequence shape, dtype or None for ``dtype``)}`` — each
        becomes ``[layers, max_seqs + 1, *shape]``, slot = decode row, the
        last slot the trash slot.  ``counters``: ``{leaf: n}``, int32 ``[n]``
        leaves the programs add to (read by the host, never reset on the
        device)."""
        if pages is None:
            pages = dict.fromkeys(("k", "v"), (n_layers, kv_heads * head_dim))
        rows = (block.num_pages + 1, block.page_size)
        if kv_quant:
            if set(pages) != {"k", "v"}:
                raise ValueError(
                    "kv_quant: int8 codes and scales exist for K and V "
                    f"pools, not for the page leaves {sorted(pages)}")
            pools = {}
            for name, (layers, width) in pages.items():
                pools[name] = jnp.zeros((layers, *rows, width), jnp.int8)
                pools[name + "_scale"] = jnp.zeros((layers, *rows, kv_heads),
                                                   jnp.float32)
        else:
            pools = {name: jnp.zeros((layers, *rows, width), dtype)
                     for name, (layers, width) in pages.items()}
        for name, (layers, sshape, sdtype) in (state or {}).items():
            pools[name] = jnp.zeros((layers, block.max_seqs + 1, *sshape),
                                    sdtype or dtype)
        for name, n in (counters or {}).items():
            pools[name] = jnp.zeros((n,), jnp.int32)
        return pools


@dataclasses.dataclass(frozen=True)
class PageRows:
    """How a sequence's pages grow, the plain case: position ``t`` is row ``t
    % page_size`` of page ``t // page_size``, and the host's table row is the
    sequence's pages in order.

    The engine always has one such object (``InferenceEngineV2.rows``, built
    by ``page_rows`` from what the stack's layer types declare) — this class
    or ``EvaRows``, two that share no logic under the same methods.  The
    object says how many pages and where; the engine allocates, frees and
    preempts.  ``admit_pages(length)``: ``(pages in front of SequenceState
    .n_sum, the others)`` a prompt is admitted with; ``needs`` / ``take``: the
    fresh pages position ``pos`` needs beyond what ``seq`` holds, and where
    they go in ``seq.pages`` and the host's table ``row``; ``chunk_tables``:
    the pages a chunk call writes and the table it attends, bucketed to few
    shapes; ``context`` / ``rows_attended``: the cached rows a chunk that
    starts at ``start``, and a decode query at ``pos``, read; ``give_back``:
    after a chunk or a decode step, the pages ``seq`` no longer needs (cut
    out of ``seq.pages``) and, where a window has just closed, how many
    have."""
    page_size: int
    max_pages_per_seq: int
    #: the pool's pages (0: not given — the arithmetic alone)
    num_pages: int = 0
    #: positions of the window layers' rings in a sequence's slot (0: none)
    ring: int = 0
    #: a chunk attends the whole table row, one shape (a cross-decoder's one
    #: query reads it through the decode kernel, which walks a row's pages)
    whole_row: bool = False
    CLOSED = None  # (no window ever closes)

    def __post_init__(self):
        if self.ring % self.page_size:
            raise ValueError(
                f"sliding_window {self.ring} is not a whole number of pages "
                f"of {self.page_size}: the decode kernel reads a window's "
                "ring as pages")
        if 0 < self.num_pages < self.max_pages_per_seq:
            raise ValueError(
                f"num_pages ({self.num_pages}) < max_pages_per_seq "
                f"({self.max_pages_per_seq}): one sequence could never run "
                "to completion even with the whole pool")

    @property
    def table_pages(self) -> int:
        return self.max_pages_per_seq

    @property
    def max_pages(self) -> int:
        return self.max_pages_per_seq

    def admit_pages(self, length: int) -> Tuple[int, int]:
        return 0, -(-length // self.page_size)

    def needs(self, seq: "SequenceState", pos: int) -> int:
        return int(pos // self.page_size == len(seq.pages))

    def take(self, seq: "SequenceState", pos: int, fresh: List[int], row,
             trash: int) -> None:
        row[len(seq.pages):len(seq.pages) + len(fresh)] = fresh
        seq.pages += fresh

    def write_table(self, seq: "SequenceState", row, trash: int) -> None:
        row[:] = trash
        row[:len(seq.pages)] = seq.pages

    def chunk_tables(self, seq: "SequenceState", row, start: int, c_n: int,
                     C: int, trash: int):
        ps = self.page_size
        rows = np.full((C // ps,), trash, np.int32)
        npg = -(-c_n // ps)
        rows[:npg] = seq.pages[start // ps:start // ps + npg]
        # bucket the window THROUGH this chunk (power-of-two page counts):
        # early chunks of a long prompt must not gather the full max window,
        # and the kernel path needs the chunk's own pages in the table
        # (pool-slot index == global position); few shapes -> few compiles
        b = 1
        while b < -(-(start + c_n) // ps):
            b *= 2
        if self.whole_row:
            b = self.max_pages_per_seq
        return rows, row[:min(b, self.max_pages_per_seq)]

    def context(self, start: int) -> int:
        return start

    def rows_attended(self, pos):
        return pos + 1

    def give_back(self, seq: "SequenceState", after_chunk: bool
                  ) -> Tuple[List[int], int]:
        return [], 0


@dataclasses.dataclass(frozen=True)
class EvaRows:
    """The page arithmetic of a sequence whose layers cache as EVA attention
    does (``models/layer_types.EVA``): of ``n`` cached positions, the open
    window's ``n % window`` exact rows, a row a position, and one summary row
    a whole chunk of ``chunk`` positions — rows that advance once a chunk and
    not once a token.  Both live in the same K and V page leaves.

    A sequence's ``pages`` list is ``[summary pages | open-window pages]``,
    the first ``SequenceState.n_sum`` of it the summary pages: summary ``c``
    (positions ``[c chunk, (c + 1) chunk)``) is row ``c % page_size`` of
    summary page ``c // page_size``; position ``t`` of the open window is row
    ``t % page_size`` of open page ``(t % window) // page_size``.  The host's
    table row has ``table_pages`` entries, ``[sum_cap summary pages | window
    / page_size open pages]`` (``sum_cap``: what ``max_positions`` positions'
    summaries take); the programs compose what a query attends —
    the *visible* summary pages (those of closed windows: ``visible(t) / page
    _size`` of them, whole pages because ``window / chunk`` is a whole number
    of pages) then the open pages — from it.  When a window closes its open
    pages go back to the allocator."""
    window: int
    chunk: int
    page_size: int
    #: the longest sequence, in positions (``KVBlockConfig.max_seq_len``)
    max_positions: int
    #: the pool's pages and the positions of a prefill chunk (0: not given —
    #: the arithmetic of positions alone)
    num_pages: int = 0
    prefill_chunk: int = 0
    #: what a window that closes leaves on a step: its count and its event
    CLOSED = ("eva_windows_closed", "eva_window_closed")

    def __post_init__(self):
        W, C, ps = self.window, self.chunk, self.page_size
        if ps != C:
            raise ValueError(
                f"page_size {ps} is not eva_chunk {C}: a page is one chunk "
                "of the open window's rows, which the decode program pools "
                "at the chunk's last position")
        if W % ps or (W // C) % ps:
            raise ValueError(
                f"page_size {ps} does not tile eva_window {W} and its "
                f"{W // C} summaries: the open window and a closed window's "
                "summaries are whole pages of the composed table")
        chunk = self.prefill_chunk
        if chunk and (W % chunk or chunk % (ps * C)):
            raise ValueError(
                f"prefill_chunk {chunk}: an 'eva' stack is prefilled through "
                f"the chunk program in chunks that tile eva_window {W} (a "
                "chunk never straddles a window) and are whole pages of "
                f"summaries ({ps} x eva_chunk {C} = {ps * C} positions)")
        if 0 < self.num_pages < self.max_pages:
            raise ValueError(
                f"num_pages ({self.num_pages}) < the {self.max_pages}"
                f" pages a sequence of {self.max_positions} positions "
                "holds (its summaries and an open window): one sequence "
                "could never run to completion even with the whole pool")

    @property
    def open_cap(self) -> int:
        return self.window // self.page_size

    @property
    def sum_cap(self) -> int:
        return -(-(-(-self.max_positions // self.chunk)) // self.page_size)

    @property
    def table_pages(self) -> int:
        return self.sum_cap + self.open_cap

    @property
    def max_pages(self) -> int:
        """Pages the longest sequence holds at once."""
        return self.summary_pages(self.max_positions) + self.open_cap

    def summary_pages(self, n: int) -> int:
        """Pages that hold the summaries of ``n`` cached positions' whole
        chunks."""
        return -(-(n // self.chunk) // self.page_size)

    def open_pages(self, n: int) -> int:
        """Pages that hold the open window's rows after ``n`` positions (a
        window that has just closed holds none)."""
        return -(-(n % self.window) // self.page_size)

    def visible(self, n: int) -> int:
        """Summary rows a query sees once ``n`` positions are cached: every
        chunk of every closed window."""
        return (n // self.window) * (self.window // self.chunk)

    def rows_held(self, n: int) -> Tuple[int, int]:
        """(summary rows written, open-window rows) after ``n`` positions."""
        return n // self.chunk, n % self.window

    def rows_attended(self, pos: int) -> int:
        """Rows a decode query at position ``pos`` reads, a layer."""
        return self.visible(pos) + pos % self.window + 1

    def context(self, start: int) -> int:
        """Rows a chunk that starts at ``start`` attends before itself: the
        visible summaries and the open window's earlier rows."""
        return self.visible(start) + start % self.window

    def admit_pages(self, length: int) -> Tuple[int, int]:
        """(summary pages, open pages) a sequence of ``length`` positions is
        admitted with: the summaries of its whole chunks, and the open pages
        its chunks write — its last window's, or where a chunk is shorter
        than a window and the prompt is not, a whole window's, which the
        chunks of every window reuse and the last chunk trims."""
        whole = length >= self.window and self.prefill_chunk < self.window
        return (self.summary_pages(length),
                self.open_cap if whole else self.open_pages(length))

    def needs(self, seq: "SequenceState", pos: int) -> int:
        """A page for the row at ``pos`` where it opens one, and a summary
        page where the chunk ``pos`` ends opens one."""
        return self.summary_pages(pos + 1) + (pos % self.window) \
            // self.page_size + 1 - len(seq.pages)

    def take(self, seq: "SequenceState", pos: int, fresh: List[int], row,
             trash: int) -> None:
        need_sum = self.summary_pages(pos + 1) - seq.n_sum
        seq.pages[seq.n_sum:seq.n_sum] = fresh[:need_sum]
        seq.n_sum += need_sum
        seq.pages += fresh[need_sum:]
        self.write_table(seq, row, trash)

    def write_table(self, seq: "SequenceState", row, trash: int) -> None:
        """``[summary pages | open pages]``, trash elsewhere."""
        opened = seq.pages[seq.n_sum:]
        row[:] = trash
        row[:seq.n_sum] = seq.pages[:seq.n_sum]
        row[self.sum_cap:self.sum_cap + len(opened)] = opened

    def chunk_tables(self, seq: "SequenceState", row, start: int, c_n: int,
                     C: int, trash: int):
        """The chunk's rows go to the open pages from ``(start % window) /
        ps`` on (the trash page where the chunk closes its window: nothing
        reads them again), its chunks' summaries to the summary pages from
        ``start / (ps chunk)`` on, and it attends ``[the closed windows'
        summary pages | the open window's earlier pages]`` right-aligned
        behind trash pages in a table bucketed to a power of two."""
        ps = self.page_size
        if start % C:
            raise RuntimeError(f"an 'eva' chunk starts at {start}, not at a "
                               f"multiple of prefill_chunk {C}")
        opened = seq.pages[seq.n_sum:]
        first = (start % self.window) // ps
        rows = np.full((C // ps + C // (ps * self.chunk),), trash, np.int32)
        if (start + c_n) % self.window:
            take = opened[first:first + C // ps]
            rows[:len(take)] = take
        take = seq.pages[:seq.n_sum][start // (ps * self.chunk):][
            :C // (ps * self.chunk)]
        rows[C // ps:C // ps + len(take)] = take
        before = seq.pages[:self.visible(start) // ps] + opened[:first]
        b = max(1, self.open_cap // 4)
        while b < len(before):
            b *= 2
        prev = np.full((b,), trash, np.int32)
        if before:
            prev[b - len(before):] = before
        return rows, prev

    def give_back(self, seq: "SequenceState", after_chunk: bool
                  ) -> Tuple[List[int], int]:
        """After a prompt's last chunk the open pages but the open window's
        go back (an earlier chunk that closes a window keeps them: they are
        the next window's chunks' too); after a decode step that closed the
        row's window, all of them."""
        n, drop = seq.prefilled, []
        closed = n % self.window == 0
        if n >= seq.length if after_chunk else closed:
            first = seq.n_sum + self.open_pages(n)
            drop = seq.pages[first:]
            del seq.pages[first:]
        return drop, n // self.window if closed else 0


def page_rows(cfg, block: KVBlockConfig, prefill_chunk: int):
    """The rows object the stack of ``cfg`` declares, over ``block``'s pool
    (it refuses a geometry it cannot keep)."""
    # (deferred: the engine imports models/, this module does not)
    from ...models.layer_types import (chunk_stops_early, pooled_rows,
                                       ring_positions)

    pooled = pooled_rows(cfg)
    if pooled:
        return EvaRows(*pooled, block.page_size, block.max_seq_len,
                       num_pages=block.num_pages, prefill_chunk=prefill_chunk)
    return PageRows(block.page_size, block.max_pages_per_seq,
                    num_pages=block.num_pages, ring=ring_positions(cfg),
                    whole_row=chunk_stops_early(cfg))


class StateSlots:
    """The host's book of the state slots: slot ``i`` belongs to decode row
    ``i`` and is held by the sequence scheduled there.  A slot is never
    zeroed on release: the chunk that starts a sequence starts from nothing
    (``model_runner.paged_prefill_chunk``), so a preempted sequence's state
    is dropped here and recomputed by its re-prefill."""

    def __init__(self, n_slots: int):
        self._owner: List[Optional[int]] = [None] * n_slots

    @property
    def in_use(self) -> int:
        return sum(o is not None for o in self._owner)

    def claim(self, slot: int, uid: int) -> None:
        if self._owner[slot] is not None:
            raise RuntimeError(f"state slot {slot} is held by uid "
                               f"{self._owner[slot]}; uid {uid} cannot "
                               "claim it")
        self._owner[slot] = uid

    def release(self, slot: int) -> None:
        self._owner[slot] = None

    def assert_no_leaks(self, live: Dict[int, int]) -> None:
        """``live``: {slot: uid} of the sequences scheduled now.  Every held
        slot must be one of them and every one of them must hold its slot."""
        held = {i: o for i, o in enumerate(self._owner) if o is not None}
        if held != live:
            raise AssertionError(
                f"state slots leaked or lost: held {held}, live {live}")


@dataclasses.dataclass
class KVPageBundle:
    """Serialized KV pages + block-table metadata of one in-flight
    sequence — the unit of **KV-page migration** between engines
    (prefill→decode disaggregation, replica drain) and, later, of
    host-RAM spill of cold pages.

    ``arrays`` holds one host array per pool leaf (``k``/``v`` and,
    under kv_quant, their scales), shaped ``[L, n_pages, page_size,
    KVH, D]`` in the pool's exact dtype — import is bit-identical by
    contract.  ``page_keys`` covers only the *immutable* leading full
    pages (index < ``prefilled // page_size``): those are the pages an
    importing engine may adopt by content key instead of copying; later
    pages (partial tails, copy-on-write duplicates about to be
    rewritten) are always transferred by value.  ``src_pages`` is the
    exporting allocator's block-table metadata (``export_meta``) —
    informational, page ids are meaningless across pools."""

    uid: int
    tokens: List[int]
    prompt_len: int
    max_new_tokens: int
    temperature: float
    eos_id: Optional[int]
    #: tokens of the prefix whose KV is already written in ``arrays``
    prefilled: int
    #: fully-cached prompt mid-handoff: enters through the decode program
    decode_entry: bool
    page_size: int
    page_keys: List[Any]
    src_pages: List[Dict[str, Any]]
    arrays: Dict[str, Any]
    #: (n_layers, kv_heads, head_dim) — pools must agree to import
    model_sig: Tuple[int, int, int]
    kv_quant: bool
    dtype: str
    #: SLO identity travels with the sequence: priority class and the
    #: absolute in-process deadline (``time.perf_counter`` clock, 0 =
    #: none).  The wire format re-bases the deadline as seconds-left so
    #: it survives a clock-domain change across processes.
    priority: int = PRIORITY_NORMAL
    deadline: float = 0.0
    #: fleet trace context (docs/OBSERVABILITY.md "Request tracing"):
    #: ``{"trace_id", "snapshot", "hops"}`` — the router-minted trace id,
    #: the sender's clock-free ledger snapshot, and per-hop wall stamps.
    #: None on legacy bundles and engine-standalone exports; the wire
    #: format carries it as an optional header block (tolerant parse).
    trace: Optional[Dict[str, Any]] = None

    @property
    def n_pages(self) -> int:
        return next(iter(self.arrays.values())).shape[1]

    @property
    def generated(self) -> int:
        return len(self.tokens) - self.prompt_len


@dataclasses.dataclass
class SequenceState:
    """Host-side descriptor of one in-flight sequence (reference
    DSSequenceDescriptor, inference/v2/ragged/sequence_descriptor.py)."""

    uid: int
    tokens: List[int]  # prompt + generated so far
    prompt_len: int
    max_new_tokens: int
    temperature: float
    eos_id: int | None
    slot: int = -1  # decode slot index, -1 = not scheduled
    pages: List[int] = dataclasses.field(default_factory=list)
    #: a model whose layers cache as EVA attention does: how many of
    #: ``pages``, from the front, hold summaries; the others are the open
    #: window's (``EvaRows``).  0 for every other model
    n_sum: int = 0
    done: bool = False
    admit_order: int = -1  # monotonic admission stamp (preemption policy)
    #: tokens of the prefix already prefilled (chunked prefill / cached
    #: prefix pages mapped at admission); a sequence decodes only once
    #: prefilled == length at chunk end
    prefilled: int = 0
    #: prefix-cache bookkeeping: chain keys of full pages computed so far,
    #: and how many leading pages have been offered to the registry
    page_keys: List[Any] = dataclasses.field(default_factory=list)
    registered_upto: int = 0
    #: fully-cached prompt: every prompt page was mapped from the cache
    #: (last one copy-on-write); the sequence enters through the decode
    #: program, which recomputes only the final prompt token
    decode_entry: bool = False
    #: memoized prefix-cache match for a QUEUED sequence, valid while
    #: the allocator's registry generation is unchanged; while only
    #: REGISTRATIONS happened (evict generation unchanged) the match is
    #: resumed from its end rather than recomputed
    cached_match: Any = None
    match_gen: int = -1
    match_evict_gen: int = -1
    #: priority class (PRIORITY_*): orders admission, picks preemption
    #: victims (lowest class evicted first), and gates load shedding
    priority: int = PRIORITY_NORMAL
    #: absolute expiry on the ``time.perf_counter`` clock (0 = none);
    #: past it the engine retires the sequence with
    #: ``finish_reason="deadline"`` at the next step boundary
    deadline: float = 0.0
    #: monotonic enqueue stamp: FCFS order within a priority class
    enqueue_order: int = -1
    #: perf_counter stamp of the LAST (re-)enqueue — queue-wait
    #: observations measure from here, so a preempted sequence's time
    #: spent RUNNING before eviction never counts as queueing
    queued_at: float = 0.0
    #: why the sequence finished: "length" (max_new_tokens), "eos",
    #: "max_seq_len", "deadline"; "" while running
    finish_reason: str = ""
    #: router-minted fleet trace id (None when the engine is used
    #: standalone): the cross-replica correlation key — uids are
    #: per-engine and collide across a fleet
    trace_id: Optional[str] = None
    #: generation by diffusion over blocks (``block_diffusion.BlockPolicy``):
    #: the request's passes a block (0 = the block length) and the block in
    #: progress (None between blocks, and for every other model)
    denoising_steps: int = 0
    block: Any = None

    @property
    def length(self) -> int:
        return len(self.tokens)

    @property
    def generated(self) -> int:
        return self.length - self.prompt_len
