"""Paged KV cache for the serving tier.

vLLM-style paged attention state, TPU-shaped: the per-request KV cache
is not a contiguous ``(max_len, heads, d)`` buffer but a set of
fixed-size **pages** drawn from one shared pool, addressed through a
per-slot **block table**.  Continuous batching (``serving.batcher``)
needs exactly this: requests of wildly different lengths share one
compiled decode program (fixed slot count, fixed page pool) and memory
is bounded by the pool, not by ``capacity * max_len``.

Design points:

* **One stacked array per tensor.**  ``k_pages`` / ``v_pages`` are
  ``(n_layers, num_pages, page_size, n_heads, d_head)`` — a single
  pytree leaf, so the compiled decode step takes the whole cache as one
  donated operand and the checkpoint layer sees plain arrays.
* **Page 0 is the null page.**  Never allocated; inactive slots' block
  tables point at it, so the padded-slot decode program always reads
  and writes in-bounds (garbage it never uses) instead of branching.
* **Deterministic allocator.**  The free list is kept sorted ascending
  and admission reserves ``ceil(total_tokens / page_size)`` pages up
  front — the same request stream produces the same tables on every
  rank and every run (the block tables ride the compiled program's
  inputs, so nondeterminism here would desynchronize SPMD replicas).
  Reservation at admit also means a running request can never hit a
  mid-stream out-of-pages condition; the only failure point is
  admission, where the batcher can queue.  Pages are unit-granularity,
  so the pool cannot fragment: ``can_admit`` is exactly "enough free
  pages and a free slot" (pinned by test).
* **Copy-on-write prefix sharing.**  Pages carry refcounts.  A request
  whose prompt's page-aligned prefix hashes to an already-prefilled
  page run (``lookup_prefix`` over the ``register_prefix`` index) is
  admitted with its block table ALIASING those pages (refcount++) and
  only the tail freshly allocated — ``lengths`` starts at the shared
  length, so the batcher prefills only the remainder.  Writes into a
  still-shared page (the capped final page of a fully-matched prompt)
  go through ``cow_for_write``: the page is copied to a page reserved
  at admission, the writer's table entry swaps to the copy, and the
  original's refcount drops — a reader never observes another
  request's writes.  ``release``/``evict`` decrement and return a page
  to the free list only at refcount 0.  Sharing is pure host
  bookkeeping over the same deterministic allocator, so SPMD replicas
  stay in lockstep and the shared-prefix serve is bit-identical to the
  unshared oracle while ``used_pages`` (distinct pages) drops.
* **Deterministic eviction.**  ``choose_victim()`` names the most
  recently admitted active slot whose pages are ALL unshared
  (refcount 1) — LIFO over unshared slots only, so evicting the
  victim can never free or disturb a page a live request still reads
  (``check_invariants`` pins that a victim holds no refcount>1 page).
  ``evict()`` releases a slot's pages and returns them to the sorted
  free list; the batcher re-queues the request (greedy decode replays
  bit-identically from the prompt).
* **Checkpoint round-trip.**  ``state_dict()`` is a flat dict of
  arrays that the existing checkpoint layer
  (``extensions.checkpoint``) snapshots as-is; ``load_state_dict``
  reconstructs the allocator's host state (free list, per-slot page
  ownership) from the saved tables — a replica warm-starts with its
  pages and in-flight lengths intact.
* **TP resharding.**  Pages shard over the tensor-parallel axis by
  heads (dimension 3).  :func:`reshard_kv_state` re-splits a saved
  N-shard cache onto M shards bit-identically to a fresh split of the
  concatenated global cache — the serving analogue of
  ``resilience.elastic.reshard_state``'s ZeRO block rule.
* **Delta snapshots.**  Every mutation path marks the pages it touches
  dirty (``admit``'s fresh+CoW-reserve pages, ``cow_for_write``'s copy
  target, ``advance``'s written range, ``import_kv``'s copied pages);
  :meth:`delta_state_dict` ships ONLY the pages dirtied since the last
  marker — plus the full host accounting (tables, refcounts, CoW
  reserves), which is tiny — under a sha256 digest, and
  :meth:`apply_delta` installs it onto a replica at the same base
  marker, bit-identical to a full snapshot.  This is what rides the
  peer-RAM recovery tier (``resilience.peer_ckpt``): a serving replica
  re-replicates per drain window at delta cost, not pool cost.
"""

from __future__ import annotations

import hashlib
import math
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

NULL_PAGE = 0


class KVExport(NamedTuple):
    """One request's KV state, gathered out of the page pool into a
    dense contiguous handoff buffer (:meth:`PagedKVCache.export_kv`).

    ``k``/``v`` are page-major ``(n_layers, n_pages, page_size,
    n_heads, d_head)`` host arrays holding the slot's pages BY VALUE in
    table order — prefix-shared pages are copied like private ones, so
    the buffer is self-contained and the importer owes the exporter's
    pool nothing.  ``length`` is the valid cache positions (positions
    past it are prefill-bucket padding the masked attend never reads).
    ``prefix_chain`` is the page-aligned sha1 chain-hash run registered
    for this slot's prompt (possibly empty, always prefix-closed), so
    an importer can re-register sharing without re-hashing tokens."""

    k: "np.ndarray"
    v: "np.ndarray"
    length: int
    page_size: int
    dtype: str
    prefix_chain: Tuple[str, ...]


class PrefixMatch(NamedTuple):
    """A prefix-index hit: the page run to alias at admission.

    ``pages``: the existing pages, in table order.  ``shared_len``:
    cache positions the aliasing slot starts with (its ``lengths``
    value at admit — capped at one BELOW the new prompt's length so the
    tail prefill always has a token to produce logits from).  ``cow``:
    the cap landed mid-page, so the final aliased page will be written
    and a copy-on-write page must be reserved at admission."""

    pages: Tuple[int, ...]
    shared_len: int
    cow: bool


def _chain_hash(prev: str, chunk: Sequence[int]) -> str:
    """Deterministic cumulative hash of page-aligned token chunks
    (sha1, not ``hash()`` — PYTHONHASHSEED must not desynchronize SPMD
    replicas' admission schedules)."""
    data = prev + ":" + ",".join(str(int(t)) for t in chunk)
    return hashlib.sha1(data.encode()).hexdigest()


class CacheAdmissionError(RuntimeError):
    """A request was admitted past ``can_admit`` — pool or slots
    exhausted.  The batcher never triggers this (it checks first); a
    direct caller sees a loud error instead of a corrupted table."""


def pages_needed(total_tokens: int, page_size: int) -> int:
    """Pages a request occupying ``total_tokens`` cache positions needs
    (its prompt plus every generated token except the last, which is
    sampled but never written — callers pass prompt + max_new_tokens
    and over-reserve by at most one token's worth)."""
    return max(1, math.ceil(total_tokens / page_size))


class PagedKVCache:
    """The page pool, block tables, and allocator for one replica.

    ``capacity`` decode slots share ``num_pages`` pages of
    ``page_size`` tokens each (page 0 reserved as the null page).
    ``pages_per_slot`` bounds one request's table row — the static
    width of the compiled program's table operand.
    """

    def __init__(self, *, n_layers: int, n_heads: int, d_head: int,
                 capacity: int, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 pages_per_slot: Optional[int] = None,
                 dtype=jnp.bfloat16):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_layers = int(n_layers)
        self.n_heads = int(n_heads)
        self.d_head = int(d_head)
        self.capacity = int(capacity)
        self.page_size = int(page_size)
        if pages_per_slot is None:
            pages_per_slot = 8
        self.pages_per_slot = int(pages_per_slot)
        if num_pages is None:
            # enough for every slot to hold a full-length request, + null
            num_pages = capacity * self.pages_per_slot + 1
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is null)")
        self.num_pages = int(num_pages)
        self.dtype = dtype
        shape = (self.n_layers, self.num_pages, self.page_size,
                 self.n_heads, self.d_head)
        self.k_pages = jnp.zeros(shape, dtype)
        self.v_pages = jnp.zeros(shape, dtype)
        # host-side allocator state (numpy: tables ship as step inputs)
        self.block_tables = np.full(
            (self.capacity, self.pages_per_slot), NULL_PAGE, np.int32
        )
        self.lengths = np.zeros((self.capacity,), np.int32)
        self.active = np.zeros((self.capacity,), bool)
        self._free_pages: List[int] = list(range(1, self.num_pages))
        self._slot_pages: Dict[int, List[int]] = {}
        # admission order (slot ids, oldest first) — the deterministic
        # eviction victim is the tail
        self._admit_order: List[int] = []
        # per-page refcounts: 0 = free, 1 = privately owned, >1 =
        # prefix-shared across slots.  Pages return to the free list
        # only at refcount 0.
        self._refcounts = np.zeros((self.num_pages,), np.int32)
        # prefix index: chain hash of page-aligned prompt chunks ->
        # (page run, token count).  Entries drop when any of their
        # pages is freed (the content is gone).
        self._prefix_index: Dict[str, Tuple[Tuple[int, ...], int]] = {}
        # per-slot page reserved at a capped alias-admission for the
        # inevitable copy-on-write into the final shared page —
        # earmarked so a running request never hits mid-stream
        # out-of-pages (the allocator's no-midstream-failure contract)
        self._cow_reserve: Dict[int, int] = {}
        # delta-snapshot tracking: pages whose CONTENT may have changed
        # since the last delta marker.  Over-inclusive marking is safe
        # (a clean page shipped twice is wasted bytes); under-inclusive
        # is corruption — so every mutation path marks eagerly.
        self._dirty: set = set()
        self._delta_marker = 0

    # -- pool accounting ------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def used_pages(self) -> int:
        """DISTINCT pages currently allocated (a prefix-shared page
        counts once however many block tables alias it) — the quantity
        prefix sharing exists to shrink."""
        return self.num_pages - 1 - len(self._free_pages)

    @property
    def free_slots(self) -> List[int]:
        return [s for s in range(self.capacity) if not self.active[s]]

    def utilization(self) -> float:
        """Fraction of allocatable pages currently owned by a slot."""
        return self.used_pages / max(self.num_pages - 1, 1)

    def check_invariants(self) -> None:
        """Allocator invariants, asserted by tests after every op mix:
        refcounts match table ownership exactly, null page never owned,
        conservation (distinct owned + CoW reserves + free == pool),
        free list sorted (determinism), tables consistent with
        ownership, the prefix index only names live pages — and the
        deterministic eviction victim never holds a shared page, so
        evicting it can never free a refcount>1 page."""
        owner_count: Dict[int, int] = {}
        for slot, pages in self._slot_pages.items():
            assert self.active[slot], f"slot {slot} owns pages inactive"
            assert NULL_PAGE not in pages, "null page allocated"
            assert len(set(pages)) == len(pages), "page twice in a slot"
            assert list(self.block_tables[slot][: len(pages)]) == pages
            for p in pages:
                owner_count[p] = owner_count.get(p, 0) + 1
        reserved = set(self._cow_reserve.values())
        assert len(reserved) == len(self._cow_reserve)
        for slot, p in self._cow_reserve.items():
            assert slot in self._slot_pages, "CoW reserve w/o slot"
            assert p != NULL_PAGE and p not in owner_count
            assert int(self._refcounts[p]) == 1
        for p, n in owner_count.items():
            assert int(self._refcounts[p]) == n, f"refcount drift: {p}"
        free = set(self._free_pages)
        assert not free & set(owner_count), "free page owned"
        assert not free & reserved, "free page reserved"
        assert all(int(self._refcounts[p]) == 0 for p in free)
        assert (len(owner_count) + len(reserved) + len(free)
                == self.num_pages - 1)
        assert self._free_pages == sorted(self._free_pages)
        assert sorted(self._admit_order) == sorted(self._slot_pages)
        victim = self.choose_victim()
        if victim is not None:
            assert all(int(self._refcounts[p]) == 1
                       for p in self._slot_pages[victim]), \
                "eviction victim holds a shared page"
        for h, (pages, ntok) in self._prefix_index.items():
            assert ntok % self.page_size == 0
            assert len(pages) == ntok // self.page_size
            assert all(int(self._refcounts[p]) >= 1 for p in pages), \
                "prefix index names a freed page"

    # -- prefix index ---------------------------------------------------
    def register_prefix(self, slot: int, tokens: Sequence[int]) -> int:
        """Index ``slot``'s page-aligned prompt prefixes for future
        cross-request sharing (call after the prompt is prefilled, so
        the pages actually hold the hashed tokens).  Every fully
        page-aligned prefix is registered — all such pages sit strictly
        below the slot's write frontier (prefill writes all
        ``len(tokens)`` prompt positions; decode writes continue AT
        position ``len(tokens)``), so registered pages are immutable
        for the registrant's lifetime and only ALIASING slots — which
        carry a CoW reserve from admission — can ever need
        copy-on-write.  First registration of a chain wins; returns
        the number of NEW chain entries."""
        if slot not in self._slot_pages:
            raise KeyError(f"slot {slot} owns no pages")
        tokens = [int(t) for t in tokens]
        pages = self._slot_pages[slot]
        added, h = 0, ""
        for m in range(1, len(tokens) // self.page_size + 1):
            h = _chain_hash(
                h, tokens[(m - 1) * self.page_size: m * self.page_size]
            )
            if h not in self._prefix_index:
                self._prefix_index[h] = (
                    tuple(pages[:m]), m * self.page_size
                )
                added += 1
        return added

    def lookup_prefix(self, tokens: Sequence[int]) -> Optional[PrefixMatch]:
        """Longest indexed page-aligned prefix of ``tokens``, capped at
        ``len(tokens) - 1`` so the tail prefill always has at least one
        token (a fully-matched prompt aliases ALL its pages but starts
        one position short and copy-on-writes the final page).  Chains
        are prefix-closed (``register_prefix`` adds every prefix), so
        the scan stops at the first missing link."""
        tokens = [int(t) for t in tokens]
        if len(tokens) < 2 or not self._prefix_index:
            return None
        best, h = None, ""
        for m in range(1, len(tokens) // self.page_size + 1):
            h = _chain_hash(
                h, tokens[(m - 1) * self.page_size: m * self.page_size]
            )
            hit = self._prefix_index.get(h)
            if hit is None:
                break
            best = hit
        if best is None:
            return None
        pages, ntok = best
        shared_len = min(ntok, len(tokens) - 1)
        return PrefixMatch(tuple(pages), shared_len,
                           shared_len % self.page_size != 0)

    def _drop_index_entries(self, freed: Sequence[int]) -> None:
        gone = set(freed)
        if not gone:
            return
        self._prefix_index = {
            h: e for h, e in self._prefix_index.items()
            if not gone & set(e[0])
        }

    # -- admission ------------------------------------------------------
    def can_admit(self, total_tokens: int,
                  prefix: Optional[PrefixMatch] = None) -> bool:
        need = pages_needed(total_tokens, self.page_size)
        if need > self.pages_per_slot:
            return False
        if prefix is not None:
            need = need - len(prefix.pages) + (1 if prefix.cow else 0)
        return bool(self.free_slots) and need <= len(self._free_pages)

    def admit(self, total_tokens: int,
              prefix: Optional[PrefixMatch] = None,
              slot: Optional[int] = None) -> int:
        """Reserve a slot and its pages; returns the slot id.  The
        lowest free slot and the lowest free pages are taken (sorted
        free list), so admission is a pure function of allocator
        state.  With ``prefix`` (a :meth:`lookup_prefix` hit), the
        slot's table ALIASES the matched pages (refcount++), only the
        tail is freshly allocated, and ``lengths`` starts at the
        shared length — the caller prefills just the remainder.  A
        capped match additionally earmarks one copy-on-write page.
        An explicit ``slot`` (must be free) overrides the lowest-free
        choice — the speculative batcher uses it to mirror a
        warm-started target's slot layout onto its draft cache."""
        need = pages_needed(total_tokens, self.page_size)
        if need > self.pages_per_slot:
            raise CacheAdmissionError(
                f"request needs {need} pages > pages_per_slot="
                f"{self.pages_per_slot} (total_tokens={total_tokens})"
            )
        free = self.free_slots
        if not free:
            raise CacheAdmissionError("no free decode slot")
        if slot is not None:
            if slot not in free:
                raise CacheAdmissionError(f"slot {slot} is not free")
            free = [int(slot)]
        shared: List[int] = []
        shared_len = 0
        reserve: Optional[int] = None
        if prefix is not None:
            shared = list(prefix.pages)
            shared_len = int(prefix.shared_len)
            if shared_len >= total_tokens or len(shared) > need:
                raise CacheAdmissionError(
                    f"prefix ({len(shared)} pages / {shared_len} "
                    f"tokens) does not fit total_tokens={total_tokens}"
                )
            if any(int(self._refcounts[p]) < 1 for p in shared):
                raise CacheAdmissionError(
                    "stale prefix: an aliased page was freed"
                )
        n_fresh = need - len(shared)
        n_take = n_fresh + (1 if prefix is not None and prefix.cow else 0)
        if n_take > len(self._free_pages):
            raise CacheAdmissionError(
                f"need {n_take} pages, {len(self._free_pages)} free"
            )
        slot = free[0]
        fresh = self._free_pages[:n_fresh]
        if prefix is not None and prefix.cow:
            reserve = self._free_pages[n_fresh]
        self._free_pages = self._free_pages[n_take:]
        pages = shared + fresh
        for p in shared:
            self._refcounts[p] += 1
        for p in fresh:
            self._refcounts[p] = 1
        if reserve is not None:
            self._cow_reserve[slot] = reserve
            self._refcounts[reserve] = 1
        # fresh pages (and the CoW reserve) will be written by the
        # admitting request's prefill/decode — dirty from admission;
        # aliased prefix pages stay clean (their content predates this
        # admit and is never written through this slot un-copied)
        self._dirty.update(fresh)
        if reserve is not None:
            self._dirty.add(reserve)
        self._slot_pages[slot] = pages
        self.block_tables[slot, :] = NULL_PAGE
        self.block_tables[slot, : len(pages)] = pages
        self.lengths[slot] = shared_len
        self.active[slot] = True
        self._admit_order.append(slot)
        return slot

    def release(self, slot: int) -> None:
        """Decrement the slot's pages; return refcount-0 pages (and the
        slot's unspent CoW reserve) to the pool.  Prefix-index entries
        naming a freed page are dropped — the content is gone."""
        if not self.active[slot]:
            raise KeyError(f"slot {slot} is not active")
        pages = self._slot_pages.pop(slot)
        freed: List[int] = []
        for p in pages:
            self._refcounts[p] -= 1
            if int(self._refcounts[p]) == 0:
                freed.append(p)
        reserve = self._cow_reserve.pop(slot, None)
        if reserve is not None:
            self._refcounts[reserve] = 0
            freed.append(reserve)
        self._free_pages = sorted(self._free_pages + freed)
        self._drop_index_entries(freed)
        self.block_tables[slot, :] = NULL_PAGE
        self.lengths[slot] = 0
        self.active[slot] = False
        self._admit_order.remove(slot)

    def choose_victim(self) -> Optional[int]:
        """Deterministic eviction victim: the most recently admitted
        active slot whose pages are ALL unshared (refcount 1) — LIFO
        over unshared slots only, so eviction never disturbs a page
        another live request reads.  ``None`` when every active slot
        holds a shared page (the batcher queues instead)."""
        for slot in reversed(self._admit_order):
            if all(int(self._refcounts[p]) == 1
                   for p in self._slot_pages[slot]):
                return slot
        return None

    def evict(self, slot: int) -> None:
        """Same pool effect as :meth:`release`; named separately so the
        batcher's logs distinguish retire from preempt."""
        self.release(slot)

    def cow_for_write(self, slot: int, n: int = 1) -> bool:
        """Copy-on-write hook: call BEFORE a compiled step writes ``n``
        cache positions at ``lengths[slot]``.  If any written position
        lands in a refcount>1 page, that page is copied into the
        reserve earmarked at admission, the slot's table entry swaps to
        the copy, and the original's refcount drops — other aliasing
        slots keep reading the original untouched.  Returns True if a
        copy happened.  Only the capped final page of an aliased run
        can ever be shared at write time (fresh tail pages are private
        by construction), so one reserve per slot suffices."""
        if not self.active[slot]:
            raise KeyError(f"slot {slot} is not active")
        pages = self._slot_pages[slot]
        start = int(self.lengths[slot])
        first_pg = start // self.page_size
        last_pg = min((start + int(n) - 1) // self.page_size,
                      len(pages) - 1)
        copied = False
        for i in range(first_pg, last_pg + 1):
            p = pages[i]
            if int(self._refcounts[p]) <= 1:
                continue
            q = self._cow_reserve.pop(slot, None)
            if q is None:
                raise CacheAdmissionError(
                    f"slot {slot} must write shared page {p} but holds "
                    "no CoW reserve"
                )
            self.k_pages = self.k_pages.at[:, q].set(self.k_pages[:, p])
            self.v_pages = self.v_pages.at[:, q].set(self.v_pages[:, p])
            pages[i] = q
            self.block_tables[slot, i] = q
            self._refcounts[p] -= 1
            self._dirty.add(q)
            copied = True
        return copied

    def advance(self, slot: int, n: int = 1) -> None:
        """Account ``n`` more cache positions written for ``slot``.
        Tripwire: the written range must not cover a still-shared page
        (the engine calls :meth:`cow_for_write` before the step)."""
        if not self.active[slot]:
            raise KeyError(f"slot {slot} is not active")
        old = int(self.lengths[slot])
        new = old + n
        pages = self._slot_pages[slot]
        if new > len(pages) * self.page_size:
            raise CacheAdmissionError(
                f"slot {slot} advanced past its {len(pages)}"
                f"-page reservation ({new} tokens)"
            )
        for i in range(old // self.page_size,
                       (max(new - 1, old)) // self.page_size + 1):
            if int(self._refcounts[pages[i]]) > 1:
                raise CacheAdmissionError(
                    f"slot {slot} wrote into shared page {pages[i]} "
                    "without copy-on-write"
                )
            # the advanced-over range was just written by the step
            self._dirty.add(int(pages[i]))
        self.lengths[slot] = new

    def rollback(self, slot: int, length: int) -> None:
        """Rewind ``lengths[slot]`` to ``length`` (< current) —
        speculative decode discards rejected draft positions.  Pages
        are NOT freed (the reservation is untouched; stale positions
        are simply overwritten by the next write, exactly as the padded
        decode program already overwrites junk past ``lengths``)."""
        if not self.active[slot]:
            raise KeyError(f"slot {slot} is not active")
        length = int(length)
        if length < 0 or length > int(self.lengths[slot]):
            raise ValueError(
                f"rollback to {length} outside [0, {int(self.lengths[slot])}]"
            )
        self.lengths[slot] = length

    # -- prefill/decode handoff (disaggregated serving) ----------------
    def export_kv(self, slot: int) -> KVExport:
        """Gather ``slot``'s pages — through the block table, prefix-
        shared pages included by value — into a dense contiguous
        :class:`KVExport` handoff buffer.  The slot itself is untouched
        (still active, still owning its pages): export is a read, so a
        prefill replica can publish the handoff and only then release.

        The prefix chain rides along so the importer can re-register
        page-aligned sharing (:meth:`import_kv`): for each page-aligned
        prefix depth of the slot's valid positions, the chain hash this
        cache's index maps to exactly that page run.  The scan stops at
        the first unindexed depth — chains must stay prefix-closed or
        ``lookup_prefix``'s first-missing-link scan would never reach
        the deeper entries."""
        if not self.active[slot]:
            raise KeyError(f"slot {slot} is not active")
        pages = self._slot_pages[slot]
        length = int(self.lengths[slot])
        idx = np.asarray(pages, np.int64)
        k = np.asarray(self.k_pages[:, idx])
        v = np.asarray(self.v_pages[:, idx])
        by_entry = {e: h for h, e in self._prefix_index.items()}
        chain: List[str] = []
        for m in range(1, length // self.page_size + 1):
            h = by_entry.get((tuple(pages[:m]), m * self.page_size))
            if h is None:
                break
            chain.append(h)
        return KVExport(
            k=k, v=v, length=length, page_size=self.page_size,
            dtype=jnp.dtype(self.dtype).name,
            prefix_chain=tuple(chain),
        )

    def import_kv(self, kv: KVExport, total_tokens: int,
                  slot: Optional[int] = None) -> int:
        """Admit a handoff into THIS cache: a fresh reservation for
        ``total_tokens`` (the request's prompt + max_new budget, same
        number the exporter admitted with), the buffer's pages copied
        in by value, ``lengths`` set to the exported valid positions —
        bit-identical to having prefilled locally.  The exported prefix
        chain re-registers against the NEW pages (first registration
        wins, exactly like :meth:`register_prefix`), so later requests
        admitted here alias the imported pages without re-prefilling.
        Returns the slot id; raises :class:`CacheAdmissionError` via
        :meth:`admit` when the pool cannot take it (callers gate on
        :meth:`can_admit`)."""
        if int(kv.page_size) != self.page_size:
            raise ValueError(
                f"handoff page_size {kv.page_size} != this cache's "
                f"{self.page_size} (role pools must share page geometry)"
            )
        if jnp.dtype(kv.dtype) != jnp.dtype(self.dtype):
            raise ValueError(
                f"handoff dtype {kv.dtype} != cache dtype "
                f"{jnp.dtype(self.dtype).name}"
            )
        want = (self.n_layers, self.page_size, self.n_heads, self.d_head)
        got = tuple(np.shape(kv.k))
        if len(got) != 5 or (got[0], got[2], got[3], got[4]) != want:
            raise ValueError(
                f"handoff buffer shape {got} does not match cache "
                f"geometry (n_layers, *, page_size, n_heads, d_head)="
                f"{want}"
            )
        length = int(kv.length)
        if length > int(total_tokens):
            raise ValueError(
                f"handoff holds {length} positions > total_tokens="
                f"{total_tokens}"
            )
        if pages_needed(length, self.page_size) > got[1]:
            raise ValueError(
                f"handoff claims {length} positions but ships only "
                f"{got[1]} pages"
            )
        slot = self.admit(int(total_tokens), slot=slot)
        pages = self._slot_pages[slot]
        n_copy = min(len(pages), got[1])
        self._dirty.update(int(p) for p in pages[:n_copy])
        idx = np.asarray(pages[:n_copy], np.int64)
        self.k_pages = self.k_pages.at[:, idx].set(
            jnp.asarray(kv.k[:, :n_copy], self.dtype)
        )
        self.v_pages = self.v_pages.at[:, idx].set(
            jnp.asarray(kv.v[:, :n_copy], self.dtype)
        )
        # Wait for the copies to land.  Dispatch is asynchronous, and a
        # decode step dispatched right behind an import has attended
        # over pages the import had not yet written (CPU backend: the
        # first token after a handoff wrong in 40 of 480 serves of four
        # requests on two slots, in none of 720 with this wait, in none
        # of 120 under synchronous dispatch).  What lets the donated
        # step pass the copies is not understood; an import is a
        # host-side copy from a file, so the wait costs nothing a
        # caller could hide.
        jax.block_until_ready((self.k_pages, self.v_pages))
        self.lengths[slot] = length
        for m, h in enumerate(kv.prefix_chain, start=1):
            if m > n_copy or m * self.page_size > length:
                break
            if h not in self._prefix_index:
                self._prefix_index[h] = (
                    tuple(pages[:m]), m * self.page_size
                )
        return slot

    # -- arrays for the compiled step ----------------------------------
    # Copies, not views: the CPU backend may alias a host buffer it is
    # handed (zero-copy when the allocation happens to be aligned), and
    # the step that reads these is dispatched asynchronously while
    # ``advance`` / ``admit`` / ``release`` go on to mutate the host
    # arrays in place.
    def tables_array(self) -> jnp.ndarray:
        return jnp.array(self.block_tables)

    def lengths_array(self) -> jnp.ndarray:
        return jnp.array(self.lengths)

    def set_pages(self, k_pages, v_pages) -> None:
        """Install the decode step's updated page arrays (functional
        update — the step returns fresh arrays)."""
        self.k_pages, self.v_pages = k_pages, v_pages

    # -- checkpoint round-trip -----------------------------------------
    def state_dict(self) -> dict:
        """Flat array dict the checkpoint layer snapshots as-is.  Slot
        page counts make the table rows reconstructible (a table row is
        padded with the null page, which a real reservation never
        contains)."""
        counts = np.array(
            [len(self._slot_pages.get(s, ())) for s in range(self.capacity)],
            np.int32,
        )
        order = np.array(self._admit_order, np.int32)
        # fixed (capacity, 2) shape — NEVER zero-size (a 0-row array
        # fails the orbax backend, silently degrading the checkpoint
        # to the npz fallback, which cannot round-trip bfloat16 pages)
        reserve = np.full((self.capacity, 2), -1, np.int32)
        for i, (s, p) in enumerate(sorted(self._cow_reserve.items())):
            reserve[i] = (s, p)
        return {
            "k_pages": self.k_pages,
            "v_pages": self.v_pages,
            "block_tables": self.block_tables.copy(),
            "lengths": self.lengths.copy(),
            "active": self.active.astype(np.int8),
            "slot_page_counts": counts,
            "admit_order": order,
            # prefix sharing: refcounts are derivable from table
            # multiplicity + reserves, but saved anyway so warm start
            # cross-checks the snapshot (and readers can inspect
            # sharing without replaying the allocator)
            "page_refcounts": self._refcounts.copy(),
            "cow_reserve": reserve,
        }

    def load_state_dict(self, state: dict) -> None:
        """Rebuild pool + allocator from a snapshot (warm start)."""
        k = state["k_pages"]
        # validate against the CURRENT pool arrays, not the configured
        # paged geometry — the dense-oracle engine replaces the pool
        # with its contiguous per-slot layout, and its own snapshot
        # must round-trip too
        want = tuple(np.shape(self.k_pages))
        if tuple(np.shape(k)) != want:
            raise ValueError(
                f"cache shape mismatch: snapshot {tuple(np.shape(k))} "
                f"vs this cache's {want}"
            )
        self.k_pages = jnp.asarray(k, self.dtype)
        self.v_pages = jnp.asarray(state["v_pages"], self.dtype)
        self._load_host_accounting(state)
        self.check_invariants()

    def _load_host_accounting(self, state: dict) -> None:
        """Rebuild the allocator's host state (tables, free list, slot
        ownership, refcounts with cross-check) from a snapshot's
        accounting arrays — shared by the full and delta restore
        paths, so the two cannot drift apart."""
        self.block_tables = np.asarray(
            state["block_tables"], np.int32
        ).reshape(self.capacity, self.pages_per_slot).copy()
        self.lengths = np.asarray(
            state["lengths"], np.int32).reshape(self.capacity).copy()
        self.active = np.asarray(
            state["active"]).reshape(self.capacity).astype(bool)
        counts = np.asarray(state["slot_page_counts"], np.int32)
        self._slot_pages = {
            s: [int(p) for p in self.block_tables[s, : int(counts[s])]]
            for s in range(self.capacity) if self.active[s]
        }
        reserve = np.asarray(
            state.get("cow_reserve", np.zeros((0, 2))), np.int32
        ).reshape(-1, 2)
        self._cow_reserve = {int(s): int(p) for s, p in reserve
                             if int(s) >= 0}
        # refcounts are DERIVED from table multiplicity + reserves (the
        # tables are the ground truth a legacy snapshot also carries);
        # a snapshot that saved them is cross-checked below
        self._refcounts = np.zeros((self.num_pages,), np.int32)
        for pages in self._slot_pages.values():
            for p in pages:
                self._refcounts[p] += 1
        for p in self._cow_reserve.values():
            self._refcounts[p] = 1
        if "page_refcounts" in state:
            saved = np.asarray(
                state["page_refcounts"], np.int32
            ).reshape(self.num_pages)
            if not np.array_equal(saved, self._refcounts):
                raise ValueError(
                    "snapshot page_refcounts disagree with block tables"
                )
        used = {p for pages in self._slot_pages.values() for p in pages}
        used |= set(self._cow_reserve.values())
        self._free_pages = sorted(
            set(range(1, self.num_pages)) - used
        )
        self._admit_order = [
            int(s) for s in np.asarray(state["admit_order"], np.int32)
        ]
        # the prefix index is NOT snapshotted: entries are an optimistic
        # lookup structure over live pages, and a warm-started replica
        # rebuilds them as adopted requests re-register (replica layer)
        self._prefix_index = {}

    # -- delta snapshots -----------------------------------------------
    _DELTA_ACCOUNTING = ("block_tables", "lengths", "active",
                         "slot_page_counts", "admit_order",
                         "page_refcounts", "cow_reserve")

    def delta_base_mark(self, value: Optional[int] = None) -> int:
        """Establish a delta base: the point deltas ship FROM.  With no
        ``value``, advance this cache's marker and clear the dirty set
        (call right after taking/holding a full snapshot); with one,
        adopt the sender's marker (call right after installing that
        full snapshot on a replica) — both sides then agree on what
        "since the last marker" means.  Returns the marker."""
        if value is None:
            self._delta_marker += 1
        else:
            self._delta_marker = int(value)
        self._dirty.clear()
        return self._delta_marker

    def _delta_digest(self, delta: dict) -> str:
        """sha256 over the delta's exact content in a fixed key order —
        the integrity check :meth:`apply_delta` verifies, mirroring the
        snapshot tier's per-file digests."""
        h = hashlib.sha256()
        h.update(f"base={int(delta['base_marker'])}"
                 f":marker={int(delta['marker'])}".encode())
        for name in ("page_ids", "k_delta", "v_delta",
                     *self._DELTA_ACCOUNTING):
            arr = np.ascontiguousarray(np.asarray(delta[name]))
            h.update(f":{name}:{arr.shape}:{arr.dtype.str}:".encode())
            h.update(arr.tobytes())
        return h.hexdigest()

    def delta_state_dict(self) -> dict:
        """Incremental snapshot: ONLY the pages dirtied since the last
        marker (content), plus the complete host accounting (tables,
        lengths, refcounts, CoW reserves — tiny next to page bytes) and
        a sha256 digest over the exact shipped content.  Advances the
        marker: the next delta ships on top of this one, and a replica
        applies deltas in marker order (:meth:`apply_delta` rejects a
        base mismatch loudly)."""
        ids = np.asarray(sorted(int(p) for p in self._dirty), np.int64)
        full = self.state_dict()
        delta = {
            "base_marker": int(self._delta_marker),
            "marker": int(self._delta_marker) + 1,
            "page_ids": ids,
            "k_delta": np.asarray(self.k_pages)[:, ids],
            "v_delta": np.asarray(self.v_pages)[:, ids],
            **{name: full[name] for name in self._DELTA_ACCOUNTING},
        }
        delta["digest"] = self._delta_digest(delta)
        self._delta_marker += 1
        self._dirty.clear()
        return delta

    def apply_delta(self, delta: dict) -> None:
        """Install a :meth:`delta_state_dict` onto this cache.  The
        digest is verified first (a tampered or torn delta raises
        ``ValueError`` before any state mutates), then the base marker
        must equal this cache's marker (deltas apply in order on top of
        the snapshot they were cut from), then the shipped pages land
        at their ids, the host accounting is rebuilt exactly as a full
        restore would, and the invariants are re-checked.  The result
        is bit-identical to loading the sender's full ``state_dict``
        (pinned by test)."""
        if self._delta_digest(delta) != delta.get("digest"):
            raise ValueError(
                "delta digest mismatch: snapshot delta is torn or "
                "tampered"
            )
        if int(delta["base_marker"]) != int(self._delta_marker):
            raise ValueError(
                f"delta base marker {int(delta['base_marker'])} does "
                f"not match this cache's marker {self._delta_marker}: "
                "deltas apply in order on top of their base snapshot"
            )
        ids = np.asarray(delta["page_ids"], np.int64)
        if ids.size:
            self.k_pages = self.k_pages.at[:, ids].set(
                jnp.asarray(delta["k_delta"], self.dtype)
            )
            self.v_pages = self.v_pages.at[:, ids].set(
                jnp.asarray(delta["v_delta"], self.dtype)
            )
        self._load_host_accounting(delta)
        self._delta_marker = int(delta["marker"])
        self._dirty.clear()
        self.check_invariants()


def reshard_kv_state(states: Sequence[dict], new_world: int) -> List[dict]:
    """Re-split an N-shard paged cache (heads axis) onto M shards.

    ``states``: one :meth:`PagedKVCache.state_dict` per old TP rank, in
    rank order (each holding ``H/N`` heads of the same pool).  The host
    allocator state (tables, lengths, free list) is replicated across
    TP ranks by construction, so rank 0's is kept.  The result is
    bit-identical to splitting the concatenated global cache fresh —
    pages are re-cut on the heads dimension only, block tables never
    move (pinned by test)."""
    if not states:
        raise ValueError("reshard_kv_state needs at least one shard")
    new_world = int(new_world)
    k_full = np.concatenate(
        [np.asarray(s["k_pages"]) for s in states], axis=3
    )
    v_full = np.concatenate(
        [np.asarray(s["v_pages"]) for s in states], axis=3
    )
    heads = k_full.shape[3]
    if heads % new_world:
        raise ValueError(
            f"{heads} global heads do not split over {new_world} shards"
        )
    out = []
    for r in range(new_world):
        sl = slice(r * heads // new_world, (r + 1) * heads // new_world)
        shard = dict(states[0])
        shard["k_pages"] = k_full[:, :, :, sl]
        shard["v_pages"] = v_full[:, :, :, sl]
        out.append(shard)
    return out
