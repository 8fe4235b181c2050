"""Automatic prefix caching over a KV page pool (vLLM-style, TPU-shaped).

The serve workload re-sends each conversation's whole history every turn
(`backend/service.build_prompt`), so prefill work grows quadratically with
conversation length and dominates decode ~15:1 on the round-4 profile. This
module caches the KV of PAGE-ALIGNED prompt prefixes across requests:

- Every full ``page_size``-token page of a prompt is identified by a CHAIN
  hash — a running blake2b over all tokens from position 0 through the end
  of that page — so equal chains imply equal token prefixes (the raw token
  window is stored and compared too, making collisions impossible rather
  than merely improbable).
- At admission the engine looks up the longest cached chain run, reuses
  those pages (attention reads them via ``ops.layers.gqa_attention_prefix``)
  and prefills ONLY the suffix. After prefill it registers the prompt's
  freshly-written full pages for future turns.
- Pages live in a dedicated pool (dense engine) or the main paged pool;
  eviction is LRU over pages no active slot depends on.

Host-side safety argument (single engine thread + device program order):
admission N's page reads are dispatched before admission N+1 is even
matched, so an entry evicted and re-registered by N+1 can only be
REWRITTEN by a dispatch that the device executes after N's reads. The
table never points a chain at a page whose (eventual) content differs from
that chain's tokens.

No reference counterpart (the reference has no model/serving layer —
SURVEY §5.7); the automatic-prefix-caching pattern is noted in PAPERS.md.
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np
from ..utils.sync import make_lock


def make_prefix_lru(num_pages: int, page_size: int,
                    manage_free: bool = True, pool: Any = None,
                    label: Optional[str] = None) -> "PrefixLRU":
    """Prefix-cache factory (the PrefixLRU half of the page sanitizer,
    ISSUE 13). Flag off: the plain :class:`PrefixLRU`, exactly as
    before (type identity pinned by tests/test_pagecheck.py).
    ``SWARMDB_PAGECHECK=1``: the checked subclass whose pin/unpin/
    register/evict events feed the shadow page registry — ``pool``
    (the engine's checked PageAllocator) shares its pool shadow in
    paged mode; dense mode registers its own."""
    if os.environ.get("SWARMDB_PAGECHECK", "0") not in ("", "0"):
        from ..obs import pagecheck

        return pagecheck.CheckedPrefixLRU(
            num_pages, page_size, manage_free=manage_free, pool=pool,
            label=label)
    return PrefixLRU(num_pages, page_size, manage_free=manage_free)


def page_chains(tokens: Sequence[int], page_size: int,
                max_pages: Optional[int] = None) -> List[bytes]:
    """Chain hashes for every FULL page of ``tokens``.

    chain[i] digests tokens[0 : (i+1)*page_size] — a prefix identity, not a
    page identity, so page i can only hit behind a hit of page i-1.
    """
    n_full = len(tokens) // page_size
    if max_pages is not None:
        n_full = min(n_full, max_pages)
    h = hashlib.blake2b(digest_size=16)
    out: List[bytes] = []
    # one vectorized serialization — this runs per admission on the single
    # engine thread; a per-int to_bytes loop was ~100x slower on long
    # prompts (review finding)
    raw = np.asarray(tokens[: n_full * page_size], dtype="<i4").tobytes()
    stride = 4 * page_size
    for i in range(n_full):
        h.update(raw[i * stride: (i + 1) * stride])
        out.append(h.digest())
    return out


class PrefixLRU:
    """Chain-hash → page-id table with LRU eviction over an id pool.

    Page ids are ``1..num_pages-1`` (0 is the trash page, never cached).
    ``pin``/``unpin`` guard pages that an ACTIVE slot's attention still
    reads every decode step (dense mode never needs this — the gathered
    prefix is copied into the slot's lane — but the paged engine reads
    shared pages in place until retirement).
    """

    def __init__(self, num_pages: int, page_size: int,
                 manage_free: bool = True) -> None:
        """``manage_free=False`` (paged-engine mode): this table does NOT
        own a free list — pages are borrowed from the engine's
        PageAllocator, ``acquire``/``evict_lru`` only evict entries, and
        the caller returns evicted ids to the allocator."""
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the trash page)")
        self.page_size = page_size
        self.num_pages = num_pages
        self._manage_free = manage_free
        self._free: List[int] = (
            list(range(num_pages - 1, 0, -1)) if manage_free else []
        )
        # chain -> (page_id, token window, routing rows or None);
        # insertion order == LRU order
        self._entries: "OrderedDict[bytes, Tuple[int, Tuple[int, ...], Any]]" = (
            OrderedDict()
        )
        # chain -> the caller's handle on the recurrent state at that
        # page's end, for the entries registered with one (``register``)
        # or given a snapshot slot (``take_state_slot``)
        self._states: dict = {}
        # snapshot slots (``keep_state_slots``; 0 = none kept): the free
        # ones, slot -> (the chain that owns it, its depth in pages), and
        # the slots whose sequence has a deeper one since
        self.state_slots = 0
        self._state_free: List[int] = []
        self._state_owner: dict = {}
        self._state_superseded: set = set()
        self._pins: dict = {}            # page_id -> pin count
        # the pages the entries hold: what ``evictable_count`` needs to
        # tell a pin on a cached page without walking every entry
        self._entry_pages: set = set()
        self._lock = make_lock("ops.prefix_cache.PrefixLRU._lock")
        self.hits = 0
        self.misses = 0
        # per-LOOKUP counters (vs the per-page hits/misses above):
        # a full-miss lookup on a prompt with cached-eligible pages is
        # the anchor-jump signature — the window re-anchored and every
        # previously cached page of the conversation went dark. The
        # ratio full_misses/lookups is the number the sink-anchored
        # window drives toward zero (PROFILE r6).
        self.lookups = 0
        self.full_misses = 0
        # pool generation (managed-free mode): bumped by reset(). Pages
        # held OUTSIDE the table (the serving layer's dense rolling-KV
        # registry acquires custody via acquire()) are only valid within
        # the generation they were taken in — reset() rebuilds the free
        # list, so a stale holder releasing or resuming them would alias
        # a later occupant's pages (same contract as
        # ops.paged_kv.PageAllocator.generation).
        self.generation = 0
        # swarmmem reuse-distance probe (ISSUE 17): every match() feeds
        # its chain accesses to the SHARDS sampler (flag off -> the
        # shared NullProbe; unsampled accesses cost one hash+compare).
        from ..obs.memprof import memprof

        self.mem = memprof().prefix_probe(self.stats)

    # ---------------------------------------------------------------- lookup

    def match(self, chains: Sequence[bytes], tokens: Sequence[int],
              routing: Optional[List[Any]] = None,
              states: Optional[List[Any]] = None) -> List[int]:
        """Longest cached run of ``chains`` (from page 0); returns its page
        ids and touches them MRU. ``tokens`` re-verifies content so a hash
        collision cannot alias two different prefixes. ``routing``, where
        the caller gives a list, receives what ``register`` was given as
        each hit page's routing, untouched: the page's keys and values
        were computed under those choices, so a request that reads the
        page starts its record with them. ``states`` likewise receives
        each hit page's state handle (None where it was registered
        without one): a caller whose sequences carry recurrent state can
        resume only behind a page that has it, and cuts the run back
        there itself (``unpin`` what it leaves)."""
        pages: List[int] = []
        ps = self.page_size
        with self._lock:
            for i, chain in enumerate(chains):
                entry = self._entries.get(chain)
                if entry is None:
                    break
                page_id, window, rows = entry
                if tuple(tokens[i * ps: (i + 1) * ps]) != window:
                    break  # collision — treat as miss
                self._entries.move_to_end(chain)
                pages.append(page_id)
                if routing is not None:
                    routing.append(rows)
                if states is not None:
                    states.append(self._states.get(chain))
            self.hits += len(pages)
            self.misses += max(0, len(chains) - len(pages))
            if chains:
                self.lookups += 1
                if not pages:
                    self.full_misses += 1
            m = self.mem
            if m.enabled:
                for chain in chains:
                    m.access(chain)
        return pages

    # ------------------------------------------------------------ allocation

    def acquire(self, n: int) -> List[int]:
        """Take UP TO ``n`` page ids for registration, evicting LRU
        unpinned entries as needed; returns what the pool can cover
        (possibly empty — the caller registers that much less)."""
        with self._lock:
            take: List[int] = []
            while len(take) < n and self._free:
                take.append(self._free.pop())
            if len(take) < n:
                take.extend(self._evict(n - len(take)))
            return take

    # swarmlint: holds[self._lock]
    def _evict(self, n: int, want=None) -> List[int]:
        """Drop up to ``n`` unpinned entries, oldest first, and return
        their pages. The walk stops at the ``n``-th victim: the entries
        are in LRU order, so it passes the pinned ones at the old end and
        no more (the gate and every allocation of a full pool come
        through here, at a session boundary)."""
        victims: List[bytes] = []
        for chain, (p, _, _) in self._entries.items():
            if len(victims) >= n:
                break
            if not self._pins.get(p) and (want is None or want(p)):
                victims.append(chain)
        out: List[int] = []
        for chain in victims:
            page = self._entries.pop(chain)[0]
            self._entry_pages.discard(page)
            self._drop_state(chain)
            out.append(page)
        return out

    # swarmlint: holds[self._lock]
    def _drop_state(self, chain: bytes) -> None:
        state = self._states.pop(chain, None)
        if self._state_owner.pop(state, None) is not None:
            self._state_superseded.discard(state)
            self._state_free.append(state)

    # -------------------------------------------------------- snapshot slots

    def keep_state_slots(self, n: int) -> None:
        """From here on a state handle is a slot ``1..n`` of a pool of
        SNAPSHOTS the caller keeps on the device (0 is its bin), for
        sequences whose recurrent state is hundreds of times a page's keys
        and values (models/nemotron_h.py): far fewer snapshots than pages,
        each the state as it stood at ONE cached page's end. A snapshot
        leaves with its page (an eviction, ``reset``) or apart from it,
        when ``take_state_slot`` finds no free slot: the page then stays,
        and a hit on it has nothing to resume behind and is forgone."""
        with self._lock:
            self.state_slots = n
            self._state_free = list(range(n, 0, -1))

    def take_state_slot(self, chain: bytes, depth: int, busy=()
                        ) -> Tuple[int, bool]:
        """A slot for the state at ``chain``'s end, ``depth`` pages deep,
        bound to the chain from here on (``match`` hands it out once the
        chain's page is registered): a free one; else one whose sequence
        has a deeper one since (``supersede``: no loss); else the
        SHALLOWEST's, if that is shallower than this one. A forgone hit
        computes again exactly the tokens its snapshot stood behind, so
        the shallowest is the one whose loss costs least, and a newcomer
        shallower than all that are kept takes none. (Age is the pages'
        matter: a sequence nobody comes back to loses its pages to their
        LRU, and its snapshot with them.) Never a slot in ``busy``, the
        ones this round's rows resume from: a wave reads them. Returns
        ``(slot, whether a snapshot that was still its sequence's deepest
        left for it)``; ``(0, False)`` where the chain has one already
        (the same prompt twice) or none is to be had."""
        with self._lock:
            if chain in self._states:
                return 0, False
            lost = False
            if self._state_free:
                slot = self._state_free.pop()
            else:
                slot = next((s for s in self._state_superseded
                             if s not in busy), 0)
                if not slot:
                    slot, shallowest = min(
                        ((s, d) for s, (_, d) in self._state_owner.items()
                         if s not in busy),
                        key=lambda sd: sd[1], default=(0, 0))
                    if not slot or shallowest >= depth:
                        return 0, False
                    lost = True
                self._drop_state(self._state_owner[slot][0])
                self._state_free.remove(slot)
            self._states[chain] = slot
            self._state_owner[slot] = (chain, depth)
            return slot, lost

    def supersede(self, slot: int) -> None:
        """The sequence that resumed from ``slot`` has a deeper snapshot
        since: ``slot`` is the first to leave."""
        with self._lock:
            if slot in self._state_owner:
                self._state_superseded.add(slot)

    def state_slots_live(self) -> int:
        with self._lock:
            return len(self._state_owner)

    def evict_lru(self, n: int, want=None) -> List[int]:
        """Evict up to ``n`` LRU unpinned entries, returning their page
        ids for the caller's free list (paged-engine mode — the returned
        pages are NOT retained here). ``want(page_id)`` filters the
        candidates: on a DP-sharded pool only same-shard pages can cover
        a slot's shortfall, and evicting foreign-shard entries would
        drain the whole cache without unblocking anything."""
        with self._lock:
            return self._evict(n, want)

    def match_and_pin(self, chains: Sequence[bytes], tokens: Sequence[int],
                      routing: Optional[List[Any]] = None,
                      states: Optional[List[Any]] = None) -> List[int]:
        """``match`` + pin the hit pages atomically (paged mode: a later
        admission in the same round must not evict pages this one is
        about to attach to a slot)."""
        pages = self.match(chains, tokens, routing, states)
        self.pin(pages)
        return pages

    def reset(self) -> None:
        """Forget everything (engine restart rebuilds the pool buffers, so
        every cached entry would point at zeroed pages)."""
        with self._lock:
            # bump BEFORE rebuilding the free list: a racing epoch check
            # must never observe (old generation, rebuilt pool)
            self.generation += 1
            self._free = (list(range(self.num_pages - 1, 0, -1))
                          if self._manage_free else [])
            self._entries.clear()
            self._entry_pages.clear()
            self._states.clear()
            self._state_owner.clear()
            self._state_superseded.clear()
            self._state_free = list(range(self.state_slots, 0, -1))
            self._pins.clear()

    def evictable_count(self) -> int:
        """How many cached pages could be evicted right now (cached and
        not pinned) — the page-pool backpressure gate counts these as
        headroom, since admission can always reclaim them via
        evict_lru."""
        with self._lock:
            # the entries less those whose page is pinned: a walk over
            # the pins (the live rows' pages), not over the cache
            return len(self._entries) - sum(
                1 for p in self._pins if p in self._entry_pages)

    def free_count(self) -> int:
        """Managed-free mode: pages immediately takeable without eviction
        (the dense rolling registry's headroom probe)."""
        with self._lock:
            return len(self._free)

    def register(self, chain: bytes, tokens: Tuple[int, ...],
                 page_id: int, routing: Any = None,
                 state: Any = None) -> bool:
        """Bind ``chain`` to ``page_id`` (whose device content a dispatched
        write is filling with exactly ``tokens``'s KV). Returns True if
        custody of ``page_id`` was accepted; False on a DUPLICATE chain
        (two slots prefilled the same new prefix in one round) — the old
        page is kept and the caller retains custody of the new one (in
        managed-free mode it is recycled here).

        A page's entry holds its id, its token window and, for a
        configuration that routes, ``routing``: the caller's handle on the
        rows [page_size, L_routed, k] (models.mixtral.encode_routing) of
        the forward that wrote the page — host memory, page_size *
        L_routed * k * 2 bytes once landed; this store keeps and returns
        it and never reads it. They leave with the entry: a duplicate
        keeps the old page WITH the old rows, an evicted and recomputed
        page is registered with the rows of its recomputation, so ``match``
        always hands out what the pool's page was really computed under.

        ``state``, for a configuration whose sequences carry recurrent
        state, is the caller's handle on the state as it stood at this
        page's end, kept and returned like the rows and never read. The
        paged engine keeps that state on the device beside the pool,
        under the page's own id (``cache["page_state"]``, models/lfm2.py),
        so its handle is ``True``; a page registered with ``None`` has
        keys and values a request can attend to and no state to resume
        behind. A snapshot slot (``take_state_slot``) is bound to the chain
        where it is taken, not here: the state is the chain's, whichever
        page holds the keys and values."""
        with self._lock:
            old = self._entries.pop(chain, None)
            if old is not None:
                self._entries[chain] = old
                self._entries.move_to_end(chain)
                if self._manage_free:
                    self._free.append(page_id)
                return False
            self._entries[chain] = (page_id, tuple(tokens), routing)
            self._entry_pages.add(page_id)
            if state is not None:
                self._states[chain] = state
            return True

    def release(self, page_id: int) -> None:
        """Return a page acquired but never registered (group failed).
        In paged mode (manage_free=False) the caller returns the page to
        the PageAllocator instead — appending here would fork custody."""
        with self._lock:
            if self._manage_free:
                self._free.append(page_id)

    # ---------------------------------------------------------------- pinning

    def pin(self, page_ids: Sequence[int]) -> None:
        with self._lock:
            for p in page_ids:
                self._pins[p] = self._pins.get(p, 0) + 1

    def unpin(self, page_ids: Sequence[int]) -> None:
        with self._lock:
            for p in page_ids:
                c = self._pins.get(p, 0) - 1
                if c <= 0:
                    self._pins.pop(p, None)
                else:
                    self._pins[p] = c

    # ----------------------------------------------------------- introspection

    def stats(self) -> dict:
        with self._lock:
            return {
                "num_pages": self.num_pages,
                "free_pages": len(self._free),
                "cached_pages": len(self._entries),
                "pinned_pages": len(self._pins),
                "page_size": self.page_size,
                "hit_tokens": self.hits * self.page_size,
                "miss_tokens": self.misses * self.page_size,
                "lookups": self.lookups,
                "full_misses": self.full_misses,
            }
