"""Paged cache pool: allocator invariants (property-based), prefix-hash
contract, gather/scatter round trips, and copy-on-write semantics.

The serving-level contracts (batch serving == alone serving, chunked
prefill, sharing on == off) live in tests/test_paged_serving.py;
this module pins the host-side machinery underneath them.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _pbt import given, settings, strategies as st
from repro.configs import smoke
from repro.models import init_caches
from repro.runtime.cachepool import (
    PageAllocator,
    PagedCachePool,
    PrefixCache,
    token_hash_chain,
)


# ---------------------------------------------------------------------------
# PageAllocator: free-list + refcount invariants
# ---------------------------------------------------------------------------


def _check_conservation(alloc):
    live = alloc.live()
    assert alloc.n_free + len(live) + 1 == alloc.n_pages
    assert 0 not in live  # the zero page is never handed out
    assert alloc.refcount[0] == 1


@settings(max_examples=40)
@given(st.integers(min_value=2, max_value=24), st.integers(min_value=0, max_value=2**31 - 1))
def test_allocator_invariants_under_churn(n_pages, seed):
    """Free-list conservation, no double allocation, refcounts never
    negative, and full churn drains the pool — under a random
    alloc/incref/decref schedule."""
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(n_pages)
    held = []  # one entry per reference we hold
    for _ in range(200):
        op = rng.integers(0, 3)
        if op == 0 and alloc.n_free:
            pid = alloc.alloc()
            assert pid != 0
            assert held.count(pid) == 0 or alloc.refcount[pid] > 1
            held.append(pid)
        elif op == 1 and held:
            pid = held[rng.integers(len(held))]
            alloc.incref(pid)
            held.append(pid)
        elif op == 2 and held:
            pid = held.pop(rng.integers(len(held)))
            freed = alloc.decref(pid)
            assert freed == (pid not in held)
        assert (alloc.refcount >= 0).all()
        _check_conservation(alloc)
    # full churn: release every reference -> pool completely free again
    while held:
        alloc.decref(held.pop())
    assert alloc.n_free == n_pages - 1
    assert alloc.live() == []


def test_allocator_no_double_allocation_exhaustive():
    alloc = PageAllocator(6)
    pids = [alloc.alloc() for _ in range(5)]
    assert sorted(pids) == [1, 2, 3, 4, 5]  # every page exactly once
    with pytest.raises(MemoryError):
        alloc.alloc()


def test_allocator_refcount_underflow_raises():
    alloc = PageAllocator(4)
    pid = alloc.alloc()
    alloc.decref(pid)
    with pytest.raises(ValueError):
        alloc.decref(pid)
    with pytest.raises(ValueError):
        alloc.incref(pid)  # incref on a FREE page is also a bug


def test_allocator_zero_page_pinned():
    alloc = PageAllocator(4)
    assert alloc.decref(0) is False
    alloc.incref(0)  # no-op by contract
    assert alloc.refcount[0] == 1


# ---------------------------------------------------------------------------
# the prefix-hash contract
# ---------------------------------------------------------------------------


@settings(max_examples=30)
@given(st.integers(min_value=1, max_value=8), st.integers(min_value=0, max_value=2**31 - 1))
def test_hash_chain_prefix_property(page_size, seed):
    """Digest i is a pure function of tokens[0:(i+1)*page_size]: two
    sequences agree on digest i iff they agree on that whole prefix."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1000, size=4 * page_size + rng.integers(0, page_size)).tolist()
    b = list(a)
    flip = rng.integers(0, len(b))
    b[flip] = int(b[flip]) + 1
    ca, cb = token_hash_chain(a, page_size), token_hash_chain(b, page_size)
    assert len(ca) == len(a) // page_size
    assert ca == token_hash_chain(list(a), page_size)  # deterministic
    flip_page = flip // page_size
    for i in range(len(cb)):
        if i < flip_page:
            assert ca[i] == cb[i]
        else:
            assert ca[i] != cb[i]  # divergence propagates through the chain


def test_hash_chain_ignores_partial_tail():
    ps = 4
    assert token_hash_chain([1, 2, 3], ps) == []
    full = token_hash_chain([1, 2, 3, 4], ps)
    assert token_hash_chain([1, 2, 3, 4, 9, 9], ps) == full


# ---------------------------------------------------------------------------
# PrefixCache: longest-match, LRU, refcount ownership
# ---------------------------------------------------------------------------


def test_prefix_cache_longest_match_and_lru():
    alloc = PageAllocator(16)
    cache = PrefixCache(alloc)
    toks = list(range(12))
    chain = token_hash_chain(toks, 4)  # 3 full pages
    pages = [alloc.alloc() for _ in range(3)]
    for i in range(1, 4):
        cache.insert(chain[i - 1], pages[:i])
    # cache holds 1+2+3 = 6 references on top of ours
    assert alloc.refcount[pages[0]] == 1 + 3
    assert alloc.refcount[pages[2]] == 1 + 1

    n, got = cache.match(chain)
    assert (n, list(got)) == (3, pages)
    n, got = cache.match(chain[:2])
    assert (n, list(got)) == (2, pages[:2])
    assert cache.match(token_hash_chain([9] * 8, 4)) == (0, ())

    # our references released: pages stay resident via the cache alone
    for p in pages:
        alloc.decref(p)
    assert alloc.live() != []
    while len(cache):
        cache.evict_lru()
    assert alloc.live() == []  # cache eviction returned everything


@settings(max_examples=25)
@given(st.integers(min_value=0, max_value=2**31 - 1))
def test_prefix_cache_refcounts_never_negative(seed):
    rng = np.random.default_rng(seed)
    alloc = PageAllocator(12)
    cache = PrefixCache(alloc)
    runs = []
    for _ in range(60):
        # insert contract: the caller extends a run that is still
        # RESIDENT (its pages live, held by the cache), like admission
        # extending a matched prefix
        resident = [r for r in runs if r[0] in cache._entries]
        op = rng.integers(0, 3)
        if op == 0 and alloc.n_free:
            pid = alloc.alloc()
            key = bytes(rng.integers(0, 256, 8, dtype=np.uint8))
            base = (list(resident[rng.integers(len(resident))][1])
                    if resident and rng.integers(2) else [])
            pages = base + [pid]
            cache.insert(key, pages)
            alloc.decref(pid)  # cache now the sole owner of the new page
            runs.append((key, pages))
        elif op == 1:
            cache.evict_lru()
        elif op == 2 and resident:
            key, pages = resident[rng.integers(len(resident))]
            cache.insert(key, pages)  # duplicate insert must not double-count
        assert (alloc.refcount >= 0).all()
        _check_conservation(alloc)
    cache.drop_all()
    assert alloc.live() == []


# ---------------------------------------------------------------------------
# PagedCachePool: gather/scatter + copy-on-write
# ---------------------------------------------------------------------------


def _mk_pool(arch="deepseek-7b", **kw):
    cfg = smoke(arch)
    pool = PagedCachePool(cfg, n_slots=2, max_len=32, page_size=8, **kw)
    return cfg, pool, pool.alloc()


def _tree_equal(a, b):
    ok = True
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        ok &= bool((np.asarray(la) == np.asarray(lb)).all())
    return ok


def test_paged_empty_view_is_pristine():
    """An unallocated slot's gathered view == a freshly initialized
    cache (payload 0, pos sentinel -1) — the zero-page
    contract the model steps rely on."""
    cfg, pool, state = _mk_pool()
    view = pool.device_view(state, pool.device_tables())
    ref = init_caches(cfg, 2, 32, dtype=jnp.float32)
    assert _tree_equal(view, ref)


def test_paged_write_read_roundtrip_and_free():
    cfg, pool, state = _mk_pool()
    key = jax.random.PRNGKey(1)
    single = jax.tree.map(
        lambda l: jax.random.normal(key, l.shape).astype(l.dtype),
        init_caches(cfg, 1, 32, dtype=jnp.float32),
    )
    state = pool.write(state, single, 0)
    assert _tree_equal(pool.read(state, 0), single)
    # the OTHER slot still reads pristine
    assert _tree_equal(pool.read(state, 1),
                       init_caches(cfg, 1, 32, dtype=jnp.float32))
    # reset releases every page; a re-allocated slot reads pristine
    # again even though freed page payloads keep their stale bits
    state = pool.reset(state, 0)
    g = pool.groups["L32"]
    assert g["alloc"].live() == []
    assert (g["table"] == 0).all()
    assert _tree_equal(pool.read(state, 0),
                       init_caches(cfg, 1, 32, dtype=jnp.float32))


def test_device_tables_are_snapshots_of_the_host_tables():
    """A device table handed to a dispatched step keeps the content it
    had when it was taken.  The host edits its tables in place at the
    next admission or eviction, often before an asynchronously
    dispatched step has read them; on the CPU ``jnp.asarray`` of an
    aligned NumPy array shares its memory, so an alias would let those
    edits reach the step.  Whether an array is aligned depends on where
    it was allocated, hence tables of many sizes."""
    cfg = smoke("deepseek-7b")
    for n_slots in range(1, 25):
        pool = PagedCachePool(cfg, n_slots=n_slots, max_len=32, page_size=8)
        state = pool.alloc()
        tables, row = pool.device_tables(), pool.slot_tables(0)
        state = pool.ensure_rows(state, 0, 0, 31)   # attaches every block
        assert pool.groups["L32"]["table"][0].all()
        assert not np.asarray(tables["L32"]).any(), n_slots
        assert not np.asarray(row["L32"]).any(), n_slots


def test_paged_commit_rows_masked_lane_untouched():
    cfg, pool, state = _mk_pool()
    state = pool.ensure_rows(state, 0, 0, 0)
    state = pool.ensure_rows(state, 1, 0, 0)
    tables = pool.device_tables()
    view = pool.device_view(state, tables)
    poked = jax.tree.map(lambda l: l + 7 if l.dtype != jnp.int32 else l + 1,
                         view)
    pos = jnp.zeros((2,), jnp.int32)
    state2 = pool.commit_rows(state, tables, pool.view_rows(poked, pos),
                              pos, jnp.asarray([True, False]))
    v2 = pool.device_view(state2, tables)
    for keyname, node in v2.items():
        for name, leaf in node.items():
            a, b = np.asarray(leaf), np.asarray(view[keyname][name])
            # lane 1 bit-identical; lane 0 row 0 changed
            assert (a[:, 1] == b[:, 1]).all(), (keyname, name)
            assert (a[:, 0, 0] != b[:, 0, 0]).all(), (keyname, name)


@pytest.mark.parametrize("arch", ["deepseek-7b", "gemma2-2b"])
def test_paged_commit_rows_lands_at_pos_mod_L(arch):
    """A decode step's rows: an unmasked lane's row lands at ``pos % L``
    of each page group (gemma2: its 8-row window wraps, its full
    layers do not) and nothing else of the slot moves; a masked lane's
    rows are dropped."""
    cfg, pool, state = _mk_pool(arch)
    pos = jnp.asarray([13, 29], jnp.int32)
    for slot in (0, 1):
        state = pool.ensure_rows(state, slot, 0, 31)
    tables = pool.device_tables()
    view = pool.device_view(state, tables)
    rng = np.random.default_rng(5)
    rows = jax.tree.map(  # distinct values, the shape of a row per lane
        lambda l: jnp.asarray(rng.integers(1, 100, l.shape[:2] + l.shape[3:]), l.dtype),
        view)
    state2 = pool.commit_rows(state, tables, rows, pos, jnp.asarray([True, False]))
    v2 = pool.device_view(state2, tables)
    for keyname, node in v2.items():
        for name, leaf in node.items():
            a, b = np.array(leaf), np.asarray(view[keyname][name])
            slot = 13 % a.shape[2]
            assert (a[:, 0, slot] == np.asarray(rows[keyname][name])[:, 0]).all()
            a[:, 0, slot] = b[:, 0, slot]
            assert (a == b).all(), (keyname, name)   # nothing else moved


def test_paged_copy_on_write():
    """A shared page is never written through: the writer gets a
    private copy, the other holder keeps the original bits, refcounts
    stay exact."""
    cfg, pool, state = _mk_pool()
    g = pool.groups["L32"]
    # slot 0 owns block 0; share that page into slot 1's table
    state = pool.ensure_rows(state, 0, 0, 7)
    pid = int(g["table"][0, 0])
    g["alloc"].incref(pid)
    g["table"][1, 0] = pid
    pool._dirty = True
    assert g["alloc"].refcount[pid] == 2

    before = np.asarray(pool.read(state, 0)["pos0"]["k"])

    # slot 1 wants to write rows 0..7 -> CoW must trigger
    state = pool.ensure_rows(state, 1, 0, 7)
    new_pid = int(g["table"][1, 0])
    assert new_pid != pid
    assert g["alloc"].refcount[pid] == 1
    assert g["alloc"].refcount[new_pid] == 1
    # the copy carries the shared bits; the original is untouched
    assert (np.asarray(pool.read(state, 1)["pos0"]["k"][:, :, :8])
            == np.asarray(pool.read(state, 0)["pos0"]["k"][:, :, :8])).all()
    assert (np.asarray(pool.read(state, 0)["pos0"]["k"]) == before).all()

    # exclusive pages do NOT re-copy
    state = pool.ensure_rows(state, 1, 0, 7)
    assert int(g["table"][1, 0]) == new_pid


def test_paged_prepare_admission_with_sharing():
    cfg, pool, state = _mk_pool(prefix_sharing=True)
    prompt = list(range(20))  # 2 full pages of 8 + partial tail
    state, matched, chain = pool.prepare_admission(state, 0, prompt)
    assert matched == 0 and len(chain) == 2
    assert pool.finish_admission(0, chain, matched) == 2

    # same prefix, different tail -> 2 pages reused
    state, matched2, chain2 = pool.prepare_admission(
        state, 1, list(range(16)) + [99, 98, 97, 96]
    )
    assert matched2 == 16
    g = pool.groups["L32"]
    assert g["table"][1, 0] == g["table"][0, 0]
    assert g["table"][1, 1] == g["table"][0, 1]
    # shared blocks are refcounted per holder: block 0's page is held
    # by both slots AND both cache entries (each entry refs every page
    # of its run); block 1's only by the i=2 entry
    assert g["alloc"].refcount[g["table"][0, 0]] == 4
    assert g["alloc"].refcount[g["table"][0, 1]] == 3

    # a full-page-aligned prompt never attaches its LAST page shared
    # (the first decode write must land on a private block)
    pool.free_slot(0)
    state, matched3, _ = pool.prepare_admission(state, 0, list(range(16)))
    assert matched3 == 8

    # full churn: free both slots + drop the prefix cache -> pool empty
    pool.free_slot(0)
    pool.free_slot(1)
    pool.prefix.drop_all()
    assert g["alloc"].live() == []


def test_paged_can_admit_pressure_and_eviction():
    cfg = smoke("deepseek-7b")
    # 5 pages: the zero page + one slot's worth of 4 blocks — tight on
    # purpose so admission pressure is reachable
    pool = PagedCachePool(cfg, n_slots=2, max_len=32, page_size=8,
                          n_pages=5, prefix_sharing=True)
    state = pool.alloc()
    g = pool.groups["L32"]

    state, m, chain = pool.prepare_admission(state, 0, list(range(20)))
    pool.finish_admission(0, chain, m)  # 3 pages live, 2 prefix entries
    pool.free_slot(0)
    # the prefix cache alone keeps its 2 full pages resident
    assert len(pool.prefix) == 2 and len(g["alloc"].live()) == 2
    # a disjoint 20-token prompt needs 3 pages but only 2 are free:
    # can_admit must evict LRU prefix entries to make room
    assert pool.can_admit(list(range(100, 120)))
    assert g["alloc"].n_free >= 3

    # an ACTIVE slot pins its pages — eviction cannot free them, so an
    # over-capacity ask stays rejected (admission waits for a finish)
    state, m, chain = pool.prepare_admission(state, 0, list(range(200, 220)))
    pool.finish_admission(0, chain, m)  # 3 live again
    assert not pool.can_admit(list(range(300, 320)))


def test_paged_rejects_sharing_on_windowed_or_ssm_models():
    for arch in ("gemma2-2b", "jamba-v0.1-52b"):
        with pytest.raises(ValueError, match="prefix_sharing"):
            PagedCachePool(smoke(arch), n_slots=2, max_len=32, page_size=4,
                           prefix_sharing=True)


def test_paged_page_size_must_divide_windows():
    with pytest.raises(ValueError, match="divide"):
        # gemma2 smoke window is 8; page_size 32 cannot tile it
        PagedCachePool(smoke("gemma2-2b"), n_slots=2, max_len=64, page_size=32)
