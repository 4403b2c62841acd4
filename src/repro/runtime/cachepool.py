"""The serving cache: paged blocks with vLLM-style block tables.

A slot-contiguous pool would reserve ``max_len`` cache rows per slot
for the slot's whole lifetime — a 5-token lookup would hold the same KV
memory as a 250-token generation.  The pool here pages instead:

* **pages** — every position-indexed cache tree (attention k/v/pos,
  MLA ckv/krope/pos) is stored as stacked ``(n_periods, n_pages,
  page_size, ...)`` leaves.  Page 0 is the reserved ZERO page (pristine
  fill: payload 0, position sentinel -1) that unallocated block-table
  entries point at, so a gathered view of an empty slot is exactly a
  freshly initialized cache (``init_caches``).
* **block tables** — a host-side ``(n_slots, blocks_per_slot)`` int32
  table per page GROUP (caches sharing a length ``L`` share one
  free-list allocator and one table; sliding-window layers form their
  own small group of ``window // page_size`` blocks).  The device
  mirror is an ordinary jit argument: table CONTENT changes never
  retrace.
* **gather/scatter adapters** — ``device_view`` gathers pages into the
  exact logical ``(n_periods, B, L, ...)`` layout ``decode_rows`` /
  ``segment_step`` already consume (bit-identical values), and
  ``commit_rows`` scatters back ONLY the rows a step adds (decode: the
  1 row a lane ``decode_rows`` returns; speculative verify: k+1 rows
  cut from its view, whose rejected entries carry the rolled-back
  ``before`` bits — page-granular restore stays bit-exact).
  Cumulative SSM state is O(1) per slot and stays slot-contiguous
  inside the same state tree.
* **prefix sharing** — full pages are keyed by a SHA-256 chain over
  the token prefix (page ``i`` hashes tokens ``[0, (i+1)*page_size)``
  through its predecessor's digest); matching requests attach the
  cached pages by reference (refcounted, copy-on-write) and prefill
  only their tail.  Restricted to models whose caches are ALL
  full-context position-indexed: a sliding-window buffer's content at a
  boundary depends on when prefill passed it, and SSM state is
  cumulative — neither is a pure function of the token prefix, so
  neither can be shared by content hash.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import cache_layout, init_caches
from repro.models.config import ModelConfig

__all__ = [
    "PageAllocator",
    "PrefixCache",
    "token_hash_chain",
    "PagedCachePool",
]


# ---------------------------------------------------------------------------
# page allocator (pure host state)
# ---------------------------------------------------------------------------


class PageAllocator:
    """Free-list page allocator with reference counts.

    Page 0 is the reserved zero page: never allocated, refcount pinned.
    Shared pages (prefix reuse) carry refcount > 1; writes to them must
    go through copy-on-write (``PagedCachePool._ensure_exclusive``).
    Invariants (property-tested in tests/test_cachepool.py):

    * conservation: ``n_free + len(live) + 1 == n_pages`` always;
    * no double allocation: ``alloc`` never returns a live page;
    * refcounts never go negative (``decref`` on a free page raises);
    * full churn drains clean: freeing everything restores ``n_free``
      to ``n_pages - 1``.
    """

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError("need at least 2 pages (zero page + 1 usable)")
        self.n_pages = n_pages
        # pop() from the tail -> pages hand out in ascending order
        self._free: List[int] = list(range(n_pages - 1, 0, -1))
        self.refcount = np.zeros((n_pages,), np.int64)
        self.refcount[0] = 1  # the zero page is permanently pinned
        self.high_water = 0   # max live pages ever (capacity reporting)

    @property
    def n_free(self) -> int:
        return len(self._free)

    def live(self) -> List[int]:
        return [p for p in range(1, self.n_pages) if self.refcount[p] > 0]

    def alloc(self) -> int:
        if not self._free:
            raise MemoryError(f"page pool exhausted ({self.n_pages} pages)")
        pid = self._free.pop()
        assert self.refcount[pid] == 0, f"double allocation of page {pid}"
        self.refcount[pid] = 1
        self.high_water = max(self.high_water, self.n_pages - 1 - len(self._free))
        return pid

    def incref(self, pid: int) -> None:
        if pid == 0:
            return  # the zero page is shared by construction
        if self.refcount[pid] <= 0:
            raise ValueError(f"incref on free page {pid}")
        self.refcount[pid] += 1

    def decref(self, pid: int) -> bool:
        """Drop one reference; returns True when the page was freed."""
        if pid == 0:
            return False
        if self.refcount[pid] <= 0:
            raise ValueError(f"decref on free page {pid} (refcount underflow)")
        self.refcount[pid] -= 1
        if self.refcount[pid] == 0:
            self._free.append(pid)
            return True
        return False


# ---------------------------------------------------------------------------
# prefix hashing + cache
# ---------------------------------------------------------------------------


def token_hash_chain(tokens: Sequence[int], page_size: int) -> List[bytes]:
    """The prefix-sharing hash contract: digest ``i`` commits to the
    ENTIRE token prefix ``tokens[0:(i+1)*page_size]`` — each full
    page's tokens are hashed together with the previous page's digest
    (SHA-256, collision-safe: a match is treated as content identity).
    Only FULL pages enter the chain; a partial tail page is never
    shared."""
    chain: List[bytes] = []
    h = b""
    for i in range(len(tokens) // page_size):
        page = np.asarray(
            tokens[i * page_size : (i + 1) * page_size], np.int64
        ).tobytes()
        h = hashlib.sha256(h + page).digest()
        chain.append(h)
    return chain


class PrefixCache:
    """Chain-digest -> page-run map with LRU eviction.

    Entry ``i`` (keyed by the chain's ``i``-th digest) holds the page
    ids of blocks ``[0, i+1)``; the cache holds its OWN reference on
    every page of every entry, so a page stays resident while any entry
    (or any slot) still points at it.  ``evict_lru`` releases one
    entry's references — pages whose refcount drops to zero return to
    the allocator's free list."""

    def __init__(self, allocator: PageAllocator):
        self._alloc = allocator
        self._entries: "OrderedDict[bytes, Tuple[int, ...]]" = OrderedDict()

    def __len__(self) -> int:
        return len(self._entries)

    def match(self, chain: Sequence[bytes]) -> Tuple[int, Tuple[int, ...]]:
        """Longest cached prefix: returns ``(n_pages, page_ids)`` with
        ``n_pages`` full pages matched (0 = miss)."""
        for i in range(len(chain), 0, -1):
            pages = self._entries.get(chain[i - 1])
            if pages is not None:
                self._entries.move_to_end(chain[i - 1])
                return i, pages
        return 0, ()

    def insert(self, key: bytes, pages: Sequence[int]) -> bool:
        """Record a page run under its chain digest (takes a reference
        on every page).  Returns False if the key was already present
        (just refreshed its LRU position)."""
        if key in self._entries:
            self._entries.move_to_end(key)
            return False
        for p in pages:
            self._alloc.incref(p)
        self._entries[key] = tuple(pages)
        return True

    def evict_lru(self) -> int:
        """Release the least-recently-used entry; returns the number of
        pages actually FREED (refcount reached zero)."""
        if not self._entries:
            return 0
        _, pages = self._entries.popitem(last=False)
        return sum(1 for p in pages if self._alloc.decref(p))

    def drop_all(self) -> int:
        freed = 0
        while self._entries:
            freed += self.evict_lru()
        return freed


# ---------------------------------------------------------------------------
# the paged pool
# ---------------------------------------------------------------------------


class PagedCachePool:
    """Fixed-size pages + free-list block tables (see module docstring).

    Device state tree (returned by :meth:`alloc`):

    * ``state["pages"][key][leaf]`` — ``(n_periods, n_pages, page_size,
      ...)`` for every position-indexed cache ``key``;
    * ``state["slot"][key][leaf]`` — the cumulative SSM leaves,
      ``(n_periods, n_slots, ...)``, one row per slot.

    Jit-safe adapters (device tables passed as arguments so table
    edits never retrace): :meth:`device_view`, :meth:`commit_rows`,
    :meth:`slot_view`, :meth:`slot_commit`.  Host lifecycle:
    :meth:`prepare_admission`, :meth:`ensure_rows`, :meth:`free_slot`,
    :meth:`finish_admission`.
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int,
                 page_size: int, dtype=jnp.float32, *,
                 n_pages: Optional[int] = None, prefix_sharing: bool = False,
                 registry=None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.page_size = page_size
        self.dtype = dtype
        layout = cache_layout(cfg, max_len)
        self.slot_keys = [k for k, _, L in layout if L is None]

        # group position-indexed caches by length L: one allocator + one
        # block table per group (same L -> same block arithmetic, so all
        # the group's leaves can share page ids)
        by_len: Dict[int, List[str]] = {}
        for key, _, L in layout:
            if L is not None:
                by_len.setdefault(L, []).append(key)
        for L in by_len:
            if L % page_size:
                raise ValueError(
                    f"page_size {page_size} must divide every cache length; "
                    f"got L={L} (sliding window shorter than a page? use a "
                    f"page_size that divides the smallest window)"
                )

        self.shareable = bool(by_len) and not self.slot_keys and set(by_len) == {max_len}
        if prefix_sharing and not self.shareable:
            raise ValueError(
                "prefix_sharing requires a model whose caches are all "
                "full-context position-indexed (no sliding windows, no SSM "
                f"state); {cfg.name} has layout {[(k, L) for k, _, L in layout]}"
            )
        self.prefix_sharing = prefix_sharing

        self.groups: Dict[str, dict] = {}
        for L, keys in sorted(by_len.items()):
            nb = L // page_size
            if L == max_len and n_pages is not None:
                npg = n_pages
            else:
                npg = n_slots * nb + 1  # exact contiguous footprint + zero page
                if L == max_len and prefix_sharing:
                    npg += n_slots * nb  # headroom for resident prefix entries
            self.groups[f"L{L}"] = {
                "L": L,
                "nb": nb,
                "keys": list(keys),
                "alloc": PageAllocator(npg),
                "table": np.zeros((n_slots, nb), np.int32),
            }
        self._tables_dev = None  # device mirror, rebuilt when dirty
        self._dirty = True
        self.prefix: Optional[PrefixCache] = None
        if prefix_sharing:
            self.prefix = PrefixCache(self.groups[f"L{max_len}"]["alloc"])

        # pool telemetry: shares the server's registry when given, keeps
        # a private one otherwise (counting is always on — see
        # repro.runtime.telemetry's overhead contract).  Gauges are
        # refreshed on demand by :meth:`scrape_gauges`, not per alloc.
        if registry is None:
            from repro.runtime.telemetry import MetricsRegistry
            registry = MetricsRegistry()
        self._registry = registry
        self._m_allocs = registry.counter(
            "page_allocs_total", "pages handed out by the free-list allocator",
            labelnames=("group",))
        self._m_cow = registry.counter(
            "cow_copies_total", "copy-on-write page duplications")
        self._m_prefix_evictions = registry.counter(
            "prefix_evictions_total",
            "prefix-cache LRU entries released under page pressure")
        self._m_pages_free = registry.gauge(
            "pages_free", "free pages per group", labelnames=("group",))
        self._m_pages_live = registry.gauge(
            "pages_live", "resident (refcounted) pages per group",
            labelnames=("group",))
        self._m_pages_hw = registry.gauge(
            "pages_high_water", "max pages ever live per group",
            labelnames=("group",))
        self._m_prefix_entries = registry.gauge(
            "prefix_entries", "prefix-cache entries resident")

        # leaf templates from the model's cache initializer: the pool
        # stores EXACTLY the same leaves, page-major
        single = init_caches(cfg, 1, max_len, dtype=dtype)
        self._templates = {
            key: {name: (leaf.shape, leaf.dtype) for name, leaf in single[key].items()}
            for key in single
        }
        self._build_jits()

    # -- device state -------------------------------------------------------

    def _fill(self, name):
        return -1 if name == "pos" else 0

    def alloc(self):
        pages = {}
        for g in self.groups.values():
            npg = g["alloc"].n_pages
            for key in g["keys"]:
                pages[key] = {}
                for name, (shape, dt) in self._templates[key].items():
                    tail = shape[3:]  # (P, 1, L, *tail)
                    P = shape[0]
                    pages[key][name] = jnp.full(
                        (P, npg, self.page_size) + tail, self._fill(name), dt
                    )
        slot = {}
        for key in self.slot_keys:
            slot[key] = {
                name: jnp.zeros((shape[0], self.n_slots) + shape[2:], dt)
                for name, (shape, dt) in self._templates[key].items()
            }
        return {"pages": pages, "slot": slot}

    def device_tables(self):
        """Device mirror of the block tables (a jit ARGUMENT — content
        changes never retrace).  A copy, never a view: the host edits
        its tables in place while dispatched steps may not yet have
        read the mirror, and on the CPU ``jnp.asarray`` of an aligned
        array shares its memory."""
        if self._dirty or self._tables_dev is None:
            self._tables_dev = {
                gk: jnp.array(g["table"]) for gk, g in self.groups.items()
            }
            self._dirty = False
        return self._tables_dev

    def slot_tables(self, slot: int):
        """One slot's table rows (device, a copy as in
        :meth:`device_tables`), for the B=1 admission path."""
        return {gk: jnp.array(g["table"][slot]) for gk, g in self.groups.items()}

    def scatter_ids(self, slot: int):
        """Per-group scatter targets for a whole-slot commit: the
        slot's page id per block, with non-writable blocks (the zero
        page, and any SHARED page) remapped out of range so a
        ``mode="drop"`` scatter skips them.  Shared pages are read-only
        by contract — a writer must copy-on-write first."""
        out = {}
        for gk, g in self.groups.items():
            row = g["table"][slot].copy()
            rc = g["alloc"].refcount
            drop = (row == 0) | (rc[row] > 1)
            row[drop] = g["alloc"].n_pages  # out of range -> dropped
            out[gk] = jnp.asarray(row)
        return out

    # -- jit-safe gather/scatter adapters -----------------------------------

    def _build_jits(self):
        ps = self.page_size

        def zero_pages(state, gk, pids):
            """Restore pages ``pids`` (padded with out-of-range ids) of
            one group to the pristine fill — freshly allocated pages
            must not expose a previous occupant's rows."""
            pages = dict(state["pages"])
            for key in self.groups[gk]["keys"]:
                leaves = {}
                for name, arr in pages[key].items():
                    fill = jnp.full(
                        (arr.shape[0], pids.shape[0]) + arr.shape[2:],
                        self._fill(name), arr.dtype,
                    )
                    leaves[name] = arr.at[:, pids].set(fill, mode="drop")
                pages[key] = leaves
            return {"pages": pages, "slot": state["slot"]}

        def copy_page(state, gk, src, dst):
            """Copy-on-write body: duplicate one page of one group."""
            pages = dict(state["pages"])
            for key in self.groups[gk]["keys"]:
                pages[key] = {
                    name: arr.at[:, dst].set(arr[:, src])
                    for name, arr in pages[key].items()
                }
            return {"pages": pages, "slot": state["slot"]}

        self._zero_pages = {
            gk: jax.jit(lambda state, pids, gk=gk: zero_pages(state, gk, pids),
                        donate_argnums=(0,))
            for gk in self.groups
        }
        self._copy_page = {
            gk: jax.jit(lambda state, src, dst, gk=gk: copy_page(state, gk, src, dst),
                        donate_argnums=(0,))
            for gk in self.groups
        }

    def device_view(self, state, tables):
        """Gather the logical ``(n_periods, B, L, ...)`` cache tree the
        model steps consume — bit-identical values to an
        ``init_caches`` tree holding the same logical content
        (unallocated blocks show the zero page's pristine rows)."""
        view = {}
        for gk, g in self.groups.items():
            t = tables[gk]  # (B, nb)
            for key in g["keys"]:
                view[key] = {}
                for name, arr in state["pages"][key].items():
                    gathered = arr[:, t]  # (P, B, nb, ps, *tail)
                    P, B = gathered.shape[0], gathered.shape[1]
                    view[key][name] = gathered.reshape(
                        (P, B, g["nb"] * self.page_size) + gathered.shape[4:]
                    )
        for key in self.slot_keys:
            view[key] = state["slot"][key]
        return view

    def commit_rows(self, state, tables, rows, pos, mask):
        """Scatter one row per lane into the pages (masked lanes write
        nothing), and fold the cumulative SSM leaves under the same
        mask.  ``rows`` is a decode step's rows
        (:func:`repro.models.decode_rows`): leaves ``(n_periods, B,
        ...)``, lane ``b``'s row landing at logical position ``pos[b]``
        (mod L for rolling windows); for the SSM keys, the new state."""
        ps = self.page_size
        pages = {k: dict(v) for k, v in state["pages"].items()}
        for gk, g in self.groups.items():
            L, NP = g["L"], g["alloc"].n_pages
            idx = pos % L                           # (B,) logical row
            block = idx // ps
            pid = jnp.take_along_axis(tables[gk], block[:, None], axis=1)[:, 0]
            pid = jnp.where(mask, pid, NP)          # masked -> dropped
            off = idx % ps
            for key in g["keys"]:
                for name, arr in pages[key].items():
                    pages[key][name] = arr.at[:, pid, off].set(
                        rows[key][name].astype(arr.dtype), mode="drop"
                    )
        slot = {}
        for key in self.slot_keys:
            slot[key] = {}
            for name, arr in state["slot"][key].items():
                m = mask.reshape((1, -1) + (1,) * (arr.ndim - 2))
                slot[key][name] = jnp.where(
                    m, rows[key][name].astype(arr.dtype), arr
                )
        return {"pages": pages, "slot": slot}

    def view_rows(self, view, pos):
        """Cut the rows at logical position ``pos`` (B,) (mod L) out of a
        gathered view, in :meth:`commit_rows`' form (the SSM leaves
        whole).  Speculative verify commits its k+1 segment rows this
        way: the view's rejected rows already carry the rolled-back
        ``before`` bits, so the scatter IS the page-granular restore."""
        rows = {}
        for g in self.groups.values():
            for key in g["keys"]:
                rows[key] = {}
                for name, v in view[key].items():   # (P, B, L, *tail)
                    ir = (pos % g["L"]).reshape((1, -1, 1) + (1,) * (v.ndim - 3))
                    rows[key][name] = jnp.take_along_axis(v, ir, axis=2)[:, :, 0]
        for key in self.slot_keys:
            rows[key] = view[key]
        return rows

    def slot_view(self, state, slot_tables, slot):
        """One slot's logical cache as a ``(n_periods, 1, ...)`` tree
        (the chunked-prefill admission view)."""
        view = {}
        for gk, g in self.groups.items():
            t = slot_tables[gk]  # (nb,)
            for key in g["keys"]:
                view[key] = {}
                for name, arr in state["pages"][key].items():
                    gathered = arr[:, t]  # (P, nb, ps, *tail)
                    view[key][name] = gathered.reshape(
                        (gathered.shape[0], 1, g["nb"] * self.page_size)
                        + gathered.shape[3:]
                    )
        for key in self.slot_keys:
            view[key] = {
                name: jax.lax.dynamic_slice_in_dim(arr, slot, 1, axis=1)
                for name, arr in state["slot"][key].items()
            }
        return view

    def slot_commit(self, state, scatter_ids, slot, view):
        """Scatter a whole single-slot view back: every WRITABLE block
        (allocated and exclusive — see :meth:`scatter_ids`) receives
        its page worth of rows; shared/zero blocks are dropped (their
        view rows are bit-identical to the page content by
        construction: prefix pages are read-only and padded segment
        writes were rolled back before commit)."""
        pages = {k: dict(v) for k, v in state["pages"].items()}
        for gk, g in self.groups.items():
            sp = scatter_ids[gk]  # (nb,) page ids, non-writable -> out of range
            for key in g["keys"]:
                for name, arr in pages[key].items():
                    v = view[key][name]  # (P, 1, L, *tail)
                    blocks = v.reshape(
                        (v.shape[0], g["nb"], self.page_size) + v.shape[3:]
                    )
                    pages[key][name] = arr.at[:, sp].set(
                        blocks.astype(arr.dtype), mode="drop"
                    )
        slot_leaves = {}
        for key in self.slot_keys:
            slot_leaves[key] = {
                name: jax.lax.dynamic_update_slice_in_dim(
                    arr, view[key][name].astype(arr.dtype), slot, axis=1
                )
                for name, arr in state["slot"][key].items()
            }
        return {"pages": pages, "slot": slot_leaves}

    # -- host lifecycle ------------------------------------------------------

    def _evict_prefix(self) -> int:
        """Release one LRU prefix entry (counted); returns pages freed."""
        had = len(self.prefix)
        freed = self.prefix.evict_lru()
        if len(self.prefix) < had:
            self._m_prefix_evictions.inc()
        return freed

    def _alloc_page(self, gk: str) -> int:
        """Allocate one page, evicting LRU prefix entries under
        pressure; raises MemoryError when the pool is truly full."""
        g = self.groups[gk]
        while True:
            try:
                pid = g["alloc"].alloc()
                self._m_allocs.inc(group=gk)
                return pid
            except MemoryError:
                if self.prefix is None or not self._evict_prefix():
                    raise MemoryError(
                        f"page pool {gk} exhausted "
                        f"({g['alloc'].n_pages} pages, none evictable); "
                        "raise ServingConfig.n_pages"
                    ) from None

    def _attach_fresh(self, state, slot: int, gk: str, blocks: Sequence[int]):
        """Allocate + pristine-zero pages for ``blocks`` of ``slot``."""
        g = self.groups[gk]
        fresh = []
        for b in blocks:
            pid = self._alloc_page(gk)
            g["table"][slot, b] = pid
            fresh.append(pid)
        if fresh:
            pids = np.full((g["nb"],), g["alloc"].n_pages, np.int32)
            pids[: len(fresh)] = fresh
            state = self._zero_pages[gk](state, jnp.asarray(pids))
            self._dirty = True
        return state

    def _ensure_exclusive(self, state, slot: int, gk: str, block: int):
        """Copy-on-write: make ``block`` of ``slot`` privately owned
        before a write can land on it."""
        g = self.groups[gk]
        pid = int(g["table"][slot, block])
        if pid != 0 and g["alloc"].refcount[pid] == 1:
            return state
        dst = self._alloc_page(gk)
        if pid == 0:
            # fresh block: pristine-fill instead of copying the zero page
            pids = np.full((g["nb"],), g["alloc"].n_pages, np.int32)
            pids[0] = dst
            state = self._zero_pages[gk](state, jnp.asarray(pids))
        else:
            state = self._copy_page[gk](state, jnp.int32(pid), jnp.int32(dst))
            g["alloc"].decref(pid)
            self._m_cow.inc()
        g["table"][slot, block] = dst
        self._dirty = True
        return state

    def ensure_rows(self, state, slot: int, lo: int, hi: int):
        """Make positions ``[lo, hi]`` of ``slot`` writable in every
        group: allocate missing blocks (pristine), copy-on-write shared
        ones.  The per-decode-step host check (cheap: almost always a
        no-op integer compare)."""
        ps = self.page_size
        for gk, g in self.groups.items():
            L = g["L"]
            blocks = sorted({((p % L) // ps) for p in range(lo, hi + 1)})
            missing = [b for b in blocks if g["table"][slot, b] == 0]
            if missing:
                state = self._attach_fresh(state, slot, gk, missing)
            for b in blocks:
                pid = int(g["table"][slot, b])
                if g["alloc"].refcount[pid] > 1:
                    state = self._ensure_exclusive(state, slot, gk, b)
        return state

    def prepare_admission(self, state, slot: int, prompt: Sequence[int]):
        """Admission setup for one request: prefix match + attach, then
        allocate the rest of the prompt's blocks (plus the first decode
        block) fresh.  Sliding-window groups allocate their whole
        (small) window — chunked prefill wraps through it.  Returns
        ``(state, matched_tokens, chain)``."""
        plen = len(prompt)
        for g in self.groups.values():
            assert (g["table"][slot] == 0).all(), (
                f"slot {slot} still holds pages — free_slot before re-admission"
            )
        matched = 0
        chain: List[bytes] = []
        if self.prefix is not None:
            chain = token_hash_chain(prompt, self.page_size)
            # a full-page-aligned prompt must keep its LAST page partial
            # from the matcher's perspective: position plen (the first
            # decode write) lands in block plen // ps, which must be
            # writable, so never attach it shared
            n_match, pages = self.prefix.match(chain[: max(0, (plen - 1) // self.page_size)])
            if n_match:
                gk = f"L{self.max_len}"
                g = self.groups[gk]
                for b in range(n_match):
                    g["alloc"].incref(pages[b])
                    g["table"][slot, b] = pages[b]
                self._dirty = True
                matched = n_match * self.page_size
        ps = self.page_size
        for gk, g in self.groups.items():
            if g["L"] < self.max_len:
                blocks = list(range(g["nb"]))  # the whole rolling window
            else:
                blocks = list(range(matched // ps, plen // ps + 1))
            missing = [b for b in blocks if g["table"][slot, b] == 0]
            state = self._attach_fresh(state, slot, gk, missing)
        return state, matched, chain

    def finish_admission(self, slot: int, chain: Sequence[bytes], matched: int) -> int:
        """After the tail prefill: publish this slot's full-page runs
        into the prefix cache (boundaries the match didn't already
        cover).  Returns the number of NEW entries inserted."""
        if self.prefix is None or not chain:
            return 0
        g = self.groups[f"L{self.max_len}"]
        inserted = 0
        for i in range(matched // self.page_size + 1, len(chain) + 1):
            if self.prefix.insert(chain[i - 1], g["table"][slot, :i].tolist()):
                inserted += 1
        return inserted

    def free_slot(self, slot: int) -> None:
        """Eviction: release every table reference of the slot (freed
        pages keep their stale bits — allocation pristine-fills)."""
        for g in self.groups.values():
            row = g["table"][slot]
            for b in range(g["nb"]):
                if row[b]:
                    g["alloc"].decref(int(row[b]))
            row[:] = 0
        self._dirty = True

    def can_admit(self, prompt: Sequence[int]) -> bool:
        """Capacity predicate for scheduler admission: enough free (or
        LRU-evictable) pages for the prompt's worst-case block span in
        every group (prefix-match savings are NOT assumed)."""
        plen = len(prompt)
        for g in self.groups.values():
            if g["L"] < self.max_len:
                need = g["nb"]
            else:
                need = plen // self.page_size + 1
            free = g["alloc"].n_free
            if free < need and self.prefix is not None:
                while free < need and self._evict_prefix() >= 0 and len(self.prefix):
                    free = g["alloc"].n_free
                free = g["alloc"].n_free
            if free < need:
                return False
        return True

    # -- whole-slot host paths (eager jnp) -----------------------------------

    def write(self, state, single, slot):
        """Scatter a fully-populated single-request tree (leaves
        ``(n_periods, 1, ...)``) into ``slot``, allocating the slot's
        whole block span."""
        for gk, g in self.groups.items():
            missing = [b for b in range(g["nb"]) if g["table"][slot, b] == 0]
            state = self._attach_fresh(state, slot, gk, missing)
        for gk in self.groups:
            for b in range(self.groups[gk]["nb"]):
                state = self._ensure_exclusive(state, slot, gk, b)
        return self.slot_commit(
            state, self.scatter_ids(slot), jnp.int32(slot), single
        )

    def read(self, state, slot):
        """``slot``'s logical cache as a single-request tree."""
        return self.slot_view(state, self.slot_tables(slot), jnp.int32(slot))

    def reset(self, state, slot):
        """Evict ``slot``: release its pages and zero its SSM lanes."""
        self.free_slot(slot)
        slot_leaves = {}
        for key in self.slot_keys:
            slot_leaves[key] = {
                name: arr.at[:, slot].set(0)
                for name, arr in state["slot"][key].items()
            }
        return {"pages": state["pages"], "slot": slot_leaves}

    def snapshot(self, state, slot):
        return jax.tree.map(lambda l: l.copy(), self.read(state, slot))

    def restore(self, state, snap, slot):
        return self.write(state, snap, slot)

    # -- reporting -----------------------------------------------------------

    def scrape_gauges(self) -> None:
        """Refresh the occupancy gauges (``pages_free`` / ``pages_live``
        / ``pages_high_water`` per group, ``prefix_entries``) from the
        allocators.  Called at snapshot/export time rather than per
        alloc — gauges are point-in-time reads, not event counts."""
        for gk, g in self.groups.items():
            a = g["alloc"]
            self._m_pages_free.set(a.n_free, group=gk)
            self._m_pages_live.set(a.n_pages - 1 - a.n_free, group=gk)
            self._m_pages_hw.set(a.high_water, group=gk)
        if self.prefix is not None:
            self._m_prefix_entries.set(len(self.prefix))

    def report(self) -> dict:
        """Capacity numbers for the serving benchmark: pages resident /
        high-water per group, plus the pages a slot-contiguous pool
        (every slot's full span) would have reserved."""
        out = {"page_size": self.page_size, "groups": {}}
        for gk, g in self.groups.items():
            a = g["alloc"]
            out["groups"][gk] = {
                "n_pages": a.n_pages,
                "live": len(a.live()),
                "high_water": a.high_water,
                "contiguous_pages_equiv": self.n_slots * g["nb"],
            }
        if self.prefix is not None:
            out["prefix_entries"] = len(self.prefix)
        return out
