"""Paged slot memory: block-table KV/state pools, copy-on-write prefix
sharing, and opt-in int8 pages.

The contiguous slot table reserves a max-bucket-sized cache per lane, so
residency is bounded by worst-case length. This layer turns the cache into a
POOL of fixed-size pages (one row per cache token, flat
``(n_pages * page_size, *row)`` arrays per paged state leaf) plus per-request
page lists (:class:`repro.serve.slots.SlotPages`), so HBM is charged for the
tokens a request has actually produced — "admit on pages available now, not
worst case". The engine keeps its jitted decode working set in the lanes
(bit-identical math — which is what makes paged vs. contiguous decode
token-for-token provable); this store is the RESIDENCY layer under it:

* prefill completion scatters the donor's paged leaves into its pages
  (``cache_page_write``) — fixed-size recurrent/cross "tail" leaves (the
  ``state_page_axes`` ``None`` entries) are snapshotted whole;
* a completed request with no free lane PARKS (it stays resident in pages,
  counted by ``resident_requests``) and ACTIVATES later by gathering its
  pages back into a donor (``cache_page_read``) and grafting it into a lane
  — this is what lets residency exceed the lane count;
* identical prompt prefixes are prefilled ONCE: full pages of the prefix are
  content-addressed in the :class:`PrefixStore` (keyed the way
  ``core/cache.py`` keys artifacts: a sha256 digest over everything that
  determines page content) and shared read-only with refcounts. Writes into
  a shared page go through copy-on-write (:meth:`PagedKVStore._cow`), so a
  sharer can never mutate another request's prefix;
* ``int8=True`` stores pages in the absmax-int8 wire format from
  ``repro.dist.compression`` (per-row scale alongside an int8 pool) —
  activation dequantizes on gather. Opt-in because it changes numerics.

FUSED mode (``PagedConfig.fused``, the default when the family has paged
leaves) removes the activation gather from the hot path entirely: pools keep
the leaf's own layout with the token axis split in-place into
``(n_pages, page)`` (e.g. a ``(L, 1, KH, S, hd)`` KV leaf becomes a
``(L, 1, KH, n_pages, page, hd)`` pool, whose rows pack and pad tokens to
whole 128-lane tiles (``kernels/paged/rows.py``: ``(L, 1, KH, n_pages,
page // f, w)``), so the whole stack is directly the pool operand of the
``attention_decode_paged`` / ``attention_verify_paged`` UPD primitives,
read at each layer's index. KV-family slots then decode and
verify straight off the pool through per-step block tables (a dedicated
SCRATCH page absorbs table entries beyond a slot's coverage); lane
activation survives only for recurrent tails and as an explicit fallback,
and int8 pages dequantize per touched page inside the kernel instead of at
park/activate boundaries.

HOST SPILL adds an LRU tier under the pool: when the allocator runs dry and
no prefix entry is evictable, cold pages — unpinned (parked) requests'
exclusive, unshared data pages — are copied to host arrays and their device
pages released; they rehydrate into fresh pages when the request is touched
(pinned/activated) again. ``pages_free`` counts spillable pages as
reclaimable, so admission defers less under a cold-heavy pool, and the
spill/rehydrate counters land in ``report["paged"]``.

Page size is UPD data: the ``serve:`` block on ``cache_page_read`` declares
the candidates, bench selection picks the winner per hardware key, and
:func:`selected_page_size` probes the generated library for the choice (the
winning definition's page size IS the shape it returns). Gather/scatter run
through the generated primitives whenever the pool granularity matches the
selected definition, and through the same ``repro.kernels.paged`` bodies
directly when a caller overrides the page size.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.compression import (dequantize_absmax_int8,
                                    quantize_absmax_int8)
from repro.kernels.paged import ref as _pref
from repro.kernels.paged import rows as _prows

from .slots import PageAllocator, PagesExhausted, SlotPages


def serve_page_write(pool, rows, pids, ax: int):
    """Write ``rows`` (``len(pids) * page`` rows) into pages ``pids`` of a
    fused-layout pool whose token axis ``ax`` is split into (n_pages, page),
    in the pool's row format (``kernels/paged/rows.py``)."""
    n = pids.shape[0]
    blocks = jnp.moveaxis(rows.astype(pool.dtype).reshape(
        (n, rows.shape[0] // n) + rows.shape[1:]), (0, 1), (ax, ax + 1))
    idx = (slice(None),) * ax + (pids,)
    return pool.at[idx].set(_prows.pack(blocks, pool.shape[-1]))


# the page-store write as one named program (``jit_serve_page_write``)
_page_write = jax.jit(serve_page_write, static_argnums=(3,))

def selected_page_size() -> int:
    """Page size of the generated library's SELECTED cache_page_read
    definition (bench winner per hardware key, or the flag heuristic's
    first candidate). Probed, not parsed: the definition's page size is
    exactly the number of rows it gathers per table entry, so the library
    itself is the source of truth. A library without the primitive, or
    one whose definition fails, raises: the pool geometry is never guessed."""
    from repro.tsl_api import ops

    out = ops.cache_page_read(jnp.zeros((1024, 1), jnp.float32),
                              jnp.zeros((1,), jnp.int32))
    return int(out.shape[0])


def prefix_key(*, arch: str, page_size: int, int8: bool, seed: int,
               prefix_rows: int, tokens, embeds=None) -> str:
    """Content address of a shareable prefix, CacheKey-style (core/cache.py):
    a sha256 digest over everything that determines the page content — the
    arch + param seed, the page geometry and precision, the media prefix,
    and the prefix token ids (plus the raw media bytes when present)."""
    h = hashlib.sha256()
    h.update(repr((arch, page_size, bool(int8), int(seed),
                   int(prefix_rows))).encode())
    h.update(np.ascontiguousarray(np.asarray(tokens, np.int64)).tobytes())
    if embeds is not None:
        h.update(np.ascontiguousarray(np.asarray(embeds,
                                                 np.float32)).tobytes())
    return h.hexdigest()[:16]


@dataclass(frozen=True)
class PagedConfig:
    """Engine-facing switch for paged slot memory.

    ``hbm_budget_bytes`` sizes the page pool (None: room for 2x the lane
    count at worst-case length — paged strictly dominates contiguous).
    ``page_size`` None probes the bench-selected definition.
    ``int8`` stores pages quantized (parked/shared requests reactivate
    through dequantization; active lanes always run full precision).
    ``max_inflight_prefills`` caps concurrent chunk schedules (None: 2x
    lanes).
    ``fused`` decodes/verifies KV-family slots directly against the block
    table via the ``attention_decode_paged``/``attention_verify_paged``
    primitives — no page->lane gather on the steady-state decode path.
    ``False`` forces the PR 8 activate-into-a-lane fallback (bit-identical
    to contiguous decode); families with no paged leaves (rwkv) fall back
    automatically either way."""

    hbm_budget_bytes: int | None = None
    page_size: int | None = None
    int8: bool = False
    prefix_sharing: bool = True
    max_inflight_prefills: int | None = None
    fused: bool = True


@dataclass
class PrefixEntry:
    pages: list[int]
    n_rows: int                       # cache rows the pages cover
    tail: dict | None                 # host snapshot of tail leaves at n_rows
    stamp: int                        # LRU tick


class PrefixStore:
    """Content-addressed store of shared, read-only prefix pages.

    ``publish`` retains the pages (the store holds one reference);
    ``lookup`` retains them again for the new sharer. Entries whose pages
    have no sharer left (refcount 1, held only by the store) are evictable
    LRU when the allocator runs dry."""

    def __init__(self, allocator: PageAllocator):
        self._alloc = allocator
        self.entries: dict[str, PrefixEntry] = {}
        self.hits = 0
        self.misses = 0
        self._tick = 0

    def lookup(self, key: str) -> PrefixEntry | None:
        entry = self.entries.get(key)
        if entry is None:
            self.misses += 1
            return None
        self._tick += 1
        entry.stamp = self._tick
        for p in entry.pages:
            self._alloc.retain(p)
        self.hits += 1
        return entry

    def publish(self, key: str, pages: list[int], n_rows: int,
                tail: dict | None) -> bool:
        """Retain ``pages`` under ``key``; no-op if already present (the
        prefill-once guarantee: the engine publishes only on a miss)."""
        if key in self.entries:
            return False
        for p in pages:
            self._alloc.retain(p)
        self._tick += 1
        self.entries[key] = PrefixEntry(list(pages), n_rows, tail, self._tick)
        return True

    def evictable(self) -> list[str]:
        return [k for k, e in self.entries.items()
                if all(self._alloc.refcount(p) == 1 for p in e.pages)]

    def evictable_pages(self) -> int:
        return sum(len(self.entries[k].pages) for k in self.evictable())

    def evict_one(self) -> bool:
        """Drop the LRU entry with no active sharers. Returns False when
        nothing is evictable (every shared prefix is in live use)."""
        cands = self.evictable()
        if not cands:
            return False
        key = min(cands, key=lambda k: self.entries[k].stamp)
        for p in self.entries.pop(key).pages:
            self._alloc.release(p)
        return True


class PagedKVStore:
    """Device page pools + per-request page lists + the prefix store.

    Built from a DONOR's shape tree (slot axis of size 1) and the family's
    ``state_page_axes`` declaration. Leaves with a token axis get a flat
    row pool ``(n_pages * page_size, *row)`` (row = leaf shape with the
    token axis moved to the front and dropped); ``None`` leaves are TAIL
    state, stored as whole host snapshots per request and charged to the
    same page budget as a ceil(tail_bytes / page_bytes) reservation, so
    ``hbm_bytes_resident`` accounts every resident request uniformly —
    including pure-recurrent rwkv, whose "page" is its tail."""

    def __init__(self, donor_shapes: dict, page_axes: dict, *,
                 page_size: int, hbm_budget_bytes: int | None = None,
                 n_pages: int | None = None, int8: bool = False,
                 fused: bool = False):
        if not isinstance(donor_shapes, dict) or not isinstance(page_axes,
                                                                dict):
            raise TypeError("paged serving requires dict-shaped states "
                            "(all four decode families use flat dicts)")
        self.page = int(page_size)
        self.int8 = bool(int8)
        # leaf metadata from the donor shape tree
        self.paged: dict[str, tuple[int, tuple, object]] = {}
        self.tail_leaves: dict[str, tuple[tuple, object]] = {}
        tail_bytes = 0
        page_bytes = 0
        fp_row_bytes = 0
        for name, sd in donor_shapes.items():
            ax = page_axes.get(name)
            if ax is None:
                self.tail_leaves[name] = (tuple(sd.shape), sd.dtype)
                tail_bytes += int(np.prod(sd.shape)) * sd.dtype.itemsize
                continue
            row_shape = tuple(np.delete(np.asarray(sd.shape, int), ax))
            self.paged[name] = (int(ax), row_shape, sd.dtype)
            n_elem = int(np.prod(row_shape))
            fp_row_bytes += n_elem * sd.dtype.itemsize
            lead = int(np.prod(row_shape[:-1]))
            # elements a page stores: a fused pool's packed rows fill
            # whole 128-lane tiles
            stored = n_elem * self.page
            if fused and ax == len(row_shape) - 1:
                f, w = _prows.row_format(self.page, row_shape[-1])
                stored = lead * (self.page // f) * w
            if self.int8:
                # int8 payload + one f32 scale per last-axis row
                page_bytes += stored + 4 * lead * self.page
            else:
                page_bytes += stored * sd.dtype.itemsize
        self.fp_row_bytes = fp_row_bytes
        self.tail_bytes = tail_bytes
        self.page_bytes = page_bytes or max(tail_bytes, 1)
        if n_pages is None:
            if hbm_budget_bytes is None:
                raise ValueError("pass hbm_budget_bytes or n_pages")
            n_pages = max(int(hbm_budget_bytes) // self.page_bytes, 1)
        self.n_pages = int(n_pages)
        self.allocator = PageAllocator(self.n_pages)
        self.prefix_store = PrefixStore(self.allocator)
        # tail reservation: pages charged per request for its tail bytes
        self.tail_pages = -(-tail_bytes // self.page_bytes) if tail_bytes \
            else 0
        self.fused = bool(fused) and bool(self.paged)
        cap = self.n_pages * self.page
        self.pools: dict[str, jnp.ndarray] = {}
        self.scale_pools: dict[str, jnp.ndarray] = {}
        for name, (ax, row_shape, dt) in self.paged.items():
            if self.fused:
                # keep the leaf's own layout, token axis split in-place into
                # (n_pages, page): directly the fused primitives' pool
                # operand; a token axis just before the last one takes the
                # packed row format, so the pool fills whole lanes
                shape = row_shape[:ax] + (self.n_pages, self.page) \
                    + row_shape[ax:]
                sshape = shape[:-1] + (1,)
                if ax == len(row_shape) - 1:
                    f, w = _prows.row_format(self.page, row_shape[-1])
                    shape = shape[:-2] + (self.page // f, w)
            else:
                shape = (cap,) + row_shape
                sshape = (cap,) + row_shape[:-1] + (1,)
            if self.int8:
                self.pools[name] = jnp.zeros(shape, jnp.int8)
                self.scale_pools[name] = jnp.ones(sshape, jnp.float32)
            else:
                self.pools[name] = jnp.zeros(shape, dt)
        self.requests: dict[str, SlotPages] = {}
        self.tails: dict[str, dict | None] = {}
        self._tail_res: dict[str, list[int]] = {}
        # route through the generated UPD primitives when the pool
        # granularity matches the library's selected definition
        self._ops_page: int | None = None
        self.resident_peak = 0
        self.pages_used_peak = 0
        self.cow_copies = 0
        # host-spill tier: rid -> page index -> {leaf: (page, *row) host rows}
        self._spilled: dict[str, dict[int, dict[str, np.ndarray]]] = {}
        self._pinned: set[str] = set()
        self._lru: dict[str, int] = {}          # unpin stamps (cold order)
        self._lru_tick = 0
        self.spills = 0
        self.rehydrates = 0
        # fused decode needs every table entry to be a VALID page id even
        # past a slot's coverage: one scratch page absorbs them (and the
        # row writes of inactive slots)
        self.scratch_page: int | None = None
        if self.fused:
            self.scratch_page = self.allocator.alloc()

    # -- gather/scatter through the UPD primitives ---------------------------

    def _use_ops(self) -> bool:
        if self._ops_page is None:
            self._ops_page = selected_page_size()
        return self._ops_page == self.page

    def _offsets(self, pages) -> jnp.ndarray:
        return jnp.asarray([p * self.page for p in pages], jnp.int32)

    def _gather(self, pool, off):
        if self._use_ops():
            from repro.tsl_api import ops
            return ops.cache_page_read(pool, off)
        return _pref.page_read(pool, off, page=self.page)

    def _scatter(self, pool, rows, off):
        if self._use_ops():
            from repro.tsl_api import ops
            return ops.cache_page_write(pool, rows, off)
        return _pref.page_write(pool, rows, off, page=self.page)

    def _pool_gather(self, name, pids, *, scale: bool = False):
        """(len(pids)*page, *row) rows of leaf ``name``'s pool (its scale
        pool with ``scale``) for page ids ``pids``, either layout."""
        ax, row_shape, _ = self.paged[name]
        pool = (self.scale_pools if scale else self.pools)[name]
        if self.fused:
            idx = (slice(None),) * ax + (jnp.asarray(pids, jnp.int32),)
            g = _prows.unpack(pool[idx], 1 if scale else row_shape[-1])
            g = jnp.moveaxis(g, (ax, ax + 1), (0, 1))
            return g.reshape((len(pids) * self.page,) + g.shape[2:])
        return self._gather(pool, self._offsets(pids))

    def _pool_scatter(self, name, rows, pids, *, scale: bool = False):
        """Write ``rows`` into pages ``pids`` of leaf ``name``'s pool (its
        scale pool with ``scale``)."""
        pools = self.scale_pools if scale else self.pools
        if self.fused:
            pools[name] = _page_write(pools[name], rows,
                                      np.asarray(pids, np.int32),
                                      self.paged[name][0])
        else:
            pools[name] = self._scatter(pools[name], rows,
                                        self._offsets(pids))

    # -- accounting (the admission/"budget" interface) -----------------------

    def pages_for_rows(self, rows: int) -> int:
        """Pages one request needs for ``rows`` committed cache rows,
        including its tail reservation — the price admission charges."""
        data = -(-int(rows) // self.page) if self.paged else 0
        return data + self.tail_pages

    def pages_free(self) -> int:
        """Pages allocatable RIGHT NOW: the free list, every prefix-store
        page no live request shares (evictable on demand), and every cold
        page the host-spill tier can reclaim — admission defers only when
        none of the three can cover the request."""
        return self.allocator.free_pages + self.prefix_store.evictable_pages() \
            + self.spillable_pages()

    def hbm_bytes_resident(self) -> int:
        used = self.allocator.used_pages - (1 if self.scratch_page is not None
                                            else 0)
        return used * self.page_bytes

    def resident_requests(self) -> int:
        return len(self.requests)

    def contiguous_bytes_per_slot(self, max_len: int) -> int:
        """What ONE contiguous slot reserves at the same precision the
        lanes run (full-precision rows x max_len + the tail), for the
        resident-requests comparison at equal budget."""
        return max_len * self.fp_row_bytes + self.tail_bytes

    def _note_usage(self):
        self.pages_used_peak = max(self.pages_used_peak,
                                   self.allocator.used_pages)
        self.resident_peak = max(self.resident_peak, len(self.requests))

    def _alloc_page(self) -> int:
        while True:
            try:
                page = self.allocator.alloc()
                self._note_usage()
                return page
            except PagesExhausted:
                if self.prefix_store.evict_one():
                    continue
                if self._spill_one():
                    continue
                raise

    # -- request lifecycle ---------------------------------------------------

    def attach(self, rid: str, *, prompt_rows: int,
               share_key: str | None = None) -> int:
        """Admit ``rid``: retain shared prefix pages on a prefix-store hit,
        allocate the remaining prompt pages and the tail reservation.
        Returns the number of shared cache rows (0 on miss / sharing off).
        Raises PagesExhausted with everything rolled back if the pool
        cannot cover the request right now."""
        if rid in self.requests:
            raise ValueError(f"request {rid!r} already attached")
        sp = SlotPages()
        tail = None
        shared_rows = 0
        if share_key is not None:
            entry = self.prefix_store.lookup(share_key)
            if entry is not None:
                sp.pages = list(entry.pages)
                sp.n_shared = len(entry.pages)
                shared_rows = entry.n_rows
                tail = entry.tail
        got_tail_res: list[int] = []
        try:
            if self.paged:
                while sp.covered_rows(self.page) < prompt_rows:
                    sp.pages.append(self._alloc_page())
            for _ in range(self.tail_pages):
                got_tail_res.append(self._alloc_page())
        except PagesExhausted:
            for p in sp.pages[sp.n_shared:]:
                self.allocator.release(p)
            for p in sp.pages[:sp.n_shared]:
                self.allocator.release(p)      # drop the lookup retains
            for p in got_tail_res:
                self.allocator.release(p)
            raise
        sp.fill = shared_rows
        self.requests[rid] = sp
        self.tails[rid] = tail
        self._tail_res[rid] = got_tail_res
        self._pinned.add(rid)      # fresh requests are hot until parked
        self._note_usage()
        return shared_rows

    def grow(self, rid: str, rows: int) -> None:
        """Extend ``rid``'s page coverage to ``rows`` committed cache rows
        (decode growth). Raises PagesExhausted — the engine preempts."""
        sp = self.requests[rid]
        if self.paged:
            while sp.covered_rows(self.page) < rows:
                sp.pages.append(self._alloc_page())
        sp.fill = max(sp.fill, int(rows))

    def free(self, rid: str) -> None:
        """Release every page reference ``rid`` holds (prefix-store copies
        of shared pages survive through the store's own reference). Spilled
        pages (-1 markers) hold no device reference — their host copies are
        simply dropped."""
        sp = self.requests.pop(rid)
        for p in sp.pages:
            if p >= 0:
                self.allocator.release(p)
        for p in self._tail_res.pop(rid, ()):
            self.allocator.release(p)
        self.tails.pop(rid, None)
        self._spilled.pop(rid, None)
        self._pinned.discard(rid)
        self._lru.pop(rid, None)

    # -- host-spill tier -----------------------------------------------------

    def pin(self, rid: str) -> None:
        """Mark ``rid`` hot (active in a lane or on the fused decode path):
        its pages cannot spill, and any already-spilled pages rehydrate
        immediately."""
        self._pinned.add(rid)
        self._lru.pop(rid, None)
        self._rehydrate(rid)

    def unpin(self, rid: str) -> None:
        """Mark ``rid`` cold (parked): its exclusive data pages become
        spill candidates, coldest-parked first."""
        if rid not in self.requests:
            return
        self._pinned.discard(rid)
        self._lru_tick += 1
        self._lru[rid] = self._lru_tick

    def _spill_candidates(self, rid: str) -> list[int]:
        sp = self.requests[rid]
        return [i for i in range(sp.n_shared, len(sp.pages))
                if sp.pages[i] >= 0
                and self.allocator.refcount(sp.pages[i]) == 1]

    def spillable_pages(self) -> int:
        """Device pages the spill tier could reclaim right now: unpinned
        requests' exclusive (refcount-1, unshared) data pages."""
        return sum(len(self._spill_candidates(rid))
                   for rid in self.requests if rid not in self._pinned)

    def spilled_pages(self) -> int:
        return sum(len(d) for d in self._spilled.values())

    def host_spill_bytes(self) -> int:
        return self.spilled_pages() * self.page_bytes

    def _spill_one(self) -> bool:
        """Copy the coldest unpinned request's last exclusive data page to
        host arrays and release its device page. Returns False when nothing
        is spillable."""
        cold = sorted((rid for rid in self.requests
                       if rid not in self._pinned and
                       self._spill_candidates(rid)),
                      key=lambda r: self._lru.get(r, 0))
        if not cold:
            return False
        rid = cold[0]
        sp = self.requests[rid]
        i = self._spill_candidates(rid)[-1]
        pid = sp.pages[i]
        host: dict[str, np.ndarray] = {}
        for name in self.pools:
            host[name] = np.asarray(self._pool_gather(name, [pid]))
            if self.int8:
                host[f"{name}__scale"] = np.asarray(
                    self._pool_gather(name, [pid], scale=True))
        self._spilled.setdefault(rid, {})[i] = host
        sp.pages[i] = -1
        self.allocator.release(pid)
        self.spills += 1
        return True

    def _rehydrate(self, rid: str) -> None:
        """Restore every spilled page of ``rid`` into fresh device pages
        (touch-on-activate). The request is pinned for the duration so the
        allocation fallback cannot spill it back out from under itself."""
        spilled = self._spilled.get(rid)
        if not spilled:
            return
        was_pinned = rid in self._pinned
        self._pinned.add(rid)
        try:
            sp = self.requests[rid]
            for i in sorted(spilled):
                host = spilled[i]
                fresh = self._alloc_page()
                for name in self.pools:
                    self._pool_scatter(name, jnp.asarray(host[name]), [fresh])
                    if self.int8:
                        self._pool_scatter(
                            name, jnp.asarray(host[f"{name}__scale"]),
                            [fresh], scale=True)
                sp.pages[i] = fresh
                self.rehydrates += 1
            del self._spilled[rid]
        finally:
            if not was_pinned:
                self._pinned.discard(rid)

    # -- data movement -------------------------------------------------------

    def _cow(self, sp: SlotPages, p0: int, p1: int) -> None:
        """Copy-on-write: any page in [p0, p1) shared with someone else
        (refcount > 1) is copied into a fresh exclusive page before the
        caller writes. A sharer can therefore never mutate a page another
        request (or the prefix store) still reads."""
        for i in range(p0, min(p1, len(sp.pages))):
            pid = sp.pages[i]
            if self.allocator.refcount(pid) <= 1:
                continue
            fresh = self._alloc_page()
            for name in self.pools:
                self._pool_scatter(name, self._pool_gather(name, [pid]),
                                   [fresh])
                if self.int8:
                    self._pool_scatter(
                        name, self._pool_gather(name, [pid], scale=True),
                        [fresh], scale=True)
            self.allocator.release(pid)
            sp.pages[i] = fresh
            sp.n_shared = min(sp.n_shared, i)
            self.cow_copies += 1

    def write_rows(self, rid: str, row0: int, row1: int,
                   rows_by_leaf: dict) -> None:
        """Write cache rows [row0, row1) for every paged leaf (rows_by_leaf:
        {leaf: (row1-row0, *row) arrays}) through copy-on-write + the
        cache_page_write primitive. row0 must be page-aligned; the final
        partial page is zero-padded (those rows are beyond the request's
        fill, never read)."""
        if not self.paged or row1 <= row0:
            return
        if row0 % self.page:
            raise ValueError(f"write start {row0} not page-aligned "
                             f"({self.page})")
        sp = self.requests[rid]
        p0, p1 = row0 // self.page, -(-row1 // self.page)
        if p1 > len(sp.pages):
            raise ValueError(f"write [{row0},{row1}) beyond {rid!r}'s "
                             f"{len(sp.pages)} pages")
        self._cow(sp, p0, p1)
        pids = sp.pages[p0:p1]
        need = (p1 - p0) * self.page
        for name in self.pools:
            rows = rows_by_leaf[name]
            if rows.shape[0] < need:
                pad = jnp.zeros((need - rows.shape[0],) + rows.shape[1:],
                                rows.dtype)
                rows = jnp.concatenate([rows, pad], axis=0)
            if self.int8:
                q, scale = quantize_absmax_int8(rows)
                self._pool_scatter(name, q, pids)
                self._pool_scatter(name, scale, pids, scale=True)
            else:
                self._pool_scatter(name, rows, pids)

    def snapshot_tail(self, donor: dict) -> dict:
        """Host copies of the tail leaves (donation-safe: the donor buffer
        may be donated to a jitted insert right after)."""
        return {name: np.asarray(donor[name]) for name in self.tail_leaves}

    def store_donor(self, rid: str, donor: dict, *, fill: int,
                    tail: dict | None = None) -> None:
        """Scatter a completed prefill's paged rows [shared_end, fill) into
        the request's pages and stash its tail snapshot. Shared prefix rows
        are already resident — exactly the prefill-once contract."""
        sp = self.requests[rid]
        self.grow(rid, fill)
        row0 = sp.n_shared * self.page
        if self.paged and fill > row0:
            slabs = {}
            for name, (ax, _, _) in self.paged.items():
                rows = jnp.moveaxis(donor[name], ax, 0)
                slabs[name] = rows[row0:min(fill, rows.shape[0])]
            self.write_rows(rid, row0, fill, slabs)
        sp.fill = int(fill)
        if tail is not None:
            self.tails[rid] = tail
        elif self.tail_leaves:
            self.tails[rid] = self.snapshot_tail(donor)

    def load_donor(self, rid: str, donor: dict) -> dict:
        """Gather the request's pages (and tail snapshot) back into a
        freshly zeroed donor — the parked-request activation path (and the
        fused engine's explicit lane fallback). Full precision pages
        round-trip bit-exactly; int8 pages dequantize. Donor templates
        without the paged leaves (a fused engine restoring tails only)
        skip the gather entirely."""
        self._rehydrate(rid)
        sp = self.requests[rid]
        out = dict(donor)
        want = [n for n in self.paged if n in out]
        if want and sp.pages and sp.fill:
            for name in want:
                ax, _, dt = self.paged[name]
                if self.int8:
                    q = self._pool_gather(name, sp.pages)
                    s = self._pool_gather(name, sp.pages, scale=True)
                    rows = dequantize_absmax_int8(q, s, dtype=dt)
                else:
                    rows = self._pool_gather(name, sp.pages)
                n_rows = out[name].shape[ax]
                rows = rows[:min(sp.fill, n_rows)]
                full = jnp.zeros((n_rows,) + rows.shape[1:], dt)
                full = full.at[:rows.shape[0]].set(rows)
                out[name] = jnp.moveaxis(full, 0, ax)
        tail = self.tails.get(rid)
        if tail:
            for name, arr in tail.items():
                _, dt = self.tail_leaves[name]
                out[name] = jnp.asarray(arr, dt)
        return out

    # -- fused-decode interface ----------------------------------------------

    def device_pools(self) -> dict:
        """The device-resident pool arrays, keyed by leaf name (int8 scale
        pools ride along as ``{leaf}__scale``) — the engine threads these
        through its donated jitted step calls."""
        out = dict(self.pools)
        out.update({f"{n}__scale": s for n, s in self.scale_pools.items()})
        return out

    def set_device_pools(self, pools: dict) -> None:
        """Adopt the pool arrays a jitted step returned (the donated,
        in-place-updated successors of :meth:`device_pools`)."""
        for n in self.pools:
            self.pools[n] = pools[n]
        for n in self.scale_pools:
            self.scale_pools[n] = pools[f"{n}__scale"]

    def table_row(self, rid: str, width: int) -> np.ndarray:
        """``rid``'s block-table row, padded to ``width`` entries with the
        scratch page (every entry must be a valid page id — the fused
        kernels' index maps fetch unconditionally). The request must be
        pinned/rehydrated: spilled pages have no device identity."""
        sp = self.requests[rid]
        pages = sp.pages[:width]
        if any(p < 0 for p in pages):
            raise ValueError(f"request {rid!r} has spilled pages — pin() "
                             "before building its table row")
        row = np.full((width,), self.scratch_page, np.int32)
        row[:len(pages)] = pages
        return row

    def publish_prefix(self, rid: str, key: str, *, n_rows: int,
                       tail: dict | None) -> bool:
        """Publish ``rid``'s leading full pages covering [0, n_rows) under
        ``key``. No-op when the key is already present."""
        sp = self.requests[rid]
        if self.paged:
            if n_rows % self.page:
                raise ValueError(f"publish boundary {n_rows} not "
                                 f"page-aligned ({self.page})")
            pages = sp.pages[:n_rows // self.page]
        else:
            pages = []
        return self.prefix_store.publish(key, pages, n_rows, tail)
