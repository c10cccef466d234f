"""Host-side bookkeeping of the paged serving cache (engine/paged.py) and
batch bucket sizing (engine/generate.py) — pure logic, no compiles.

These invariants are what make continuous batching safe: the free-list
can never hand out the scratch page or double-allocate, admission is
all-or-nothing, and the serving batch shape is the smallest compiled
bucket that fits the live rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine.paged import (
    PageAllocator,
    PagedKVCache,
    PrefixCache,
    pages_needed,
)
from tensorlink_tpu.models import ModelConfig

TINY = ModelConfig(
    family="llama", vocab_size=64, d_model=16, n_layers=2, n_heads=2,
    n_kv_heads=2, head_dim=8, d_ff=32, max_seq_len=32,
    dtype=jnp.float32, tie_embeddings=False,
)


# ---------------------------------------------------------------------------
# PageAllocator
# ---------------------------------------------------------------------------
def test_allocator_excludes_scratch_page():
    a = PageAllocator(9)
    assert a.n_free == 8  # ids 1..8; page 0 reserved
    got = set()
    while a.n_free:
        got.update(a.alloc(1))
    assert got == set(range(1, 9))  # never page 0


def test_allocator_all_or_nothing():
    a = PageAllocator(5)  # 4 usable
    assert a.alloc(5) is None
    assert a.n_free == 4  # a refused alloc takes nothing
    pages = a.alloc(4)
    assert len(pages) == 4 and a.n_free == 0
    assert a.alloc(1) is None


def test_allocator_free_and_lifo_reuse():
    a = PageAllocator(6)
    first = a.alloc(3)
    a.free(first)
    assert a.n_free == 5
    # freed pages come back most-recent-first (locality)
    assert a.alloc(1) == [first[-1]]


def test_allocator_never_double_allocates():
    a = PageAllocator(10)
    one = a.alloc(4)
    two = a.alloc(4)
    assert not set(one) & set(two)
    a.free(one)
    three = a.alloc(5)
    assert not set(three) & set(two)


def test_allocator_free_ignores_scratch_id():
    a = PageAllocator(4)
    a.free([0, 0])  # page 0 must never enter the free list
    assert a.n_free == 3
    while a.n_free:
        assert a.alloc(1) != [0]


# ---------------------------------------------------------------------------
# pages_needed
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "total,page,want",
    [(1, 16, 1), (16, 16, 1), (17, 16, 2), (32, 16, 2), (33, 16, 3),
     (7, 8, 1), (64, 8, 8)],
)
def test_pages_needed(total, page, want):
    assert pages_needed(total, page) == want


# ---------------------------------------------------------------------------
# PagedKVCache layout
# ---------------------------------------------------------------------------
def test_paged_cache_shapes_and_properties():
    c = PagedKVCache.init(TINY, max_slots=3, page_size=8, max_len=32)
    n_pp = 32 // 8
    P = 1 + 3 * n_pp  # + the scratch page
    assert c.k.shape == (2, P, 2, 8, 8)  # [L, P, n_kv, page, hd]
    assert c.v.shape == c.k.shape
    assert c.block_tables.shape == (3, n_pp)
    assert c.lengths.shape == (3,)
    assert (c.page_size, c.max_slots, c.pages_per_slot, c.n_pages) == \
        (8, 3, n_pp, P)


def test_paged_cache_starts_free():
    c = PagedKVCache.init(TINY, max_slots=2, page_size=8, max_len=32)
    # every slot starts detached: zeroed table rows (→ scratch) + length 0
    assert int(np.asarray(c.block_tables).sum()) == 0
    assert int(np.asarray(c.lengths).sum()) == 0


def test_paged_cache_ragged_max_len_rounds_up():
    c = PagedKVCache.init(TINY, max_slots=1, page_size=8, max_len=20)
    assert c.pages_per_slot == 3  # ceil(20 / 8)
    assert c.pages_per_slot * c.page_size >= 20


# ---------------------------------------------------------------------------
# PrefixCache (host-side trie over full KV pages: refcounts, COW, LRU)
# ---------------------------------------------------------------------------
def _insert_chain(pc: PrefixCache, tokens, pages):
    """Insert consecutive full blocks of ``tokens`` mapped to ``pages``."""
    node = None
    p = pc.page_size
    for i, pid in enumerate(pages):
        node, adopted = pc.insert(node, tuple(tokens[i * p : (i + 1) * p]), pid)
        assert adopted
    return node


def test_prefix_match_walks_longest_chain():
    pc = PrefixCache(4)
    toks = list(range(100, 112))  # 3 full blocks
    _insert_chain(pc, toks, [5, 7, 9])
    # full prompt (plus a divergent tail) matches the whole chain...
    nodes = pc.match(toks + [1, 2], limit=14)
    assert [n.page for n in nodes] == [5, 7, 9]
    # ...a limit mid-chain caps the walk to FULL blocks below it
    assert [n.page for n in pc.match(toks, limit=11)] == [5, 7]
    # ...and divergence in an early block stops the walk there
    div = toks[:4] + [0] + toks[5:]
    assert [n.page for n in pc.match(div, limit=12)] == [5]
    # chain keys are position-anchored: the same block at a different
    # depth is NOT a hit (rope-offset invariance by construction)
    assert pc.match(toks[4:], limit=8) == []


def _parents_blocks(tokens, limit, p):
    """``PrefixCache._blocks`` as it stood until PR 58: an ``int()`` an id."""
    for i in range(0, (limit // p) * p, p):
        yield tuple(int(t) for t in tokens[i : i + p])


@pytest.mark.parametrize("as_given", [
    list,
    lambda ids: [np.int32(t) for t in ids],
    lambda ids: np.asarray(ids, np.int32),
    lambda ids: np.asarray(ids, np.int64),
    tuple,
], ids=["ints", "int32-scalars", "int32-array", "int64-array", "tuple"])
def test_prefix_keys_from_a_slice_find_what_the_parents_keys_built(as_given):
    """A page's key is the slice itself: it hashes and compares equal to
    the key the per-element ``int()`` built, NumPy integers included, so
    ``match`` finds every node of a trie built before and ``insert`` under
    such a key adopts nothing twice."""
    p = 16
    ids = [int(t) for t in np.random.default_rng(2).integers(0, 150_000, 20 * p + 5)]
    pc = PrefixCache(p)
    node = None
    for i, block in enumerate(_parents_blocks(ids, len(ids), p)):
        node, adopted = pc.insert(node, block, 100 + i)
        assert adopted
    tokens = as_given(ids)
    keys = list(pc._blocks(tokens, len(ids)))
    want = list(_parents_blocks(ids, len(ids), p))
    assert keys == want and [hash(k) for k in keys] == [hash(k) for k in want]
    assert [n.page for n in pc.match(tokens, len(ids))] == list(range(100, 120))
    assert [n.page for n in pc.match(tokens, 7 * p + 3)] == list(range(100, 107))
    # a re-insert under the slice's key is the resident node, not a second one
    again, adopted = pc.insert(None, keys[0], 999)
    assert not adopted and again.page == 100
    # a new chain inserted under slice keys is found by the parent's keys too
    fresh = as_given([t + 1 for t in ids])
    node = None
    for i, block in enumerate(pc._blocks(fresh, 4 * p)):
        node, adopted = pc.insert(node, block, 200 + i)
        assert adopted
    assert node.key_hash == list(_chain(pc, [t + 1 for t in ids], 4 * p))[-1]
    assert [n.page for n in pc.match([t + 1 for t in ids], 4 * p)] == [
        200, 201, 202, 203]


def _chain(pc, ids, limit):
    from tensorlink_tpu.engine.paged import chain_hash

    h = ""
    for block in _parents_blocks(ids, limit, pc.page_size):
        h = chain_hash(h, block)
        yield h


def test_prefix_partial_match_picks_longest_cow_candidate():
    pc = PrefixCache(4)
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    last = _insert_chain(pc, toks, [5, 7])
    pc.insert(last, (9, 9, 2, 2), 11)
    pc.insert(last, (9, 9, 9, 2), 12)
    nodes = pc.match(toks + [9, 9, 9, 5], limit=12)
    assert [n.page for n in nodes] == [5, 7]
    got = pc.partial_match(nodes, toks + [9, 9, 9, 5], limit=12)
    assert got is not None
    node, n = got
    assert node.page == 12 and n == 3  # the 3-token prefix beats 2
    # no shared first token -> no COW candidate
    assert pc.partial_match(nodes, toks + [4, 4, 4, 4], limit=12) is None


def test_prefix_refcounts_block_eviction():
    pc = PrefixCache(4)
    toks = list(range(8))
    _insert_chain(pc, toks, [3, 4])
    nodes = pc.match(toks + [99], limit=9)
    pc.acquire(nodes)
    assert pc.evict_one() is None  # both referenced
    pc.release(nodes)
    # now evictable — leaf first (page 4 is the chain's leaf)
    assert pc.evict_one() == 4
    assert pc.evict_one() == 3  # parent became a leaf
    assert pc.evict_one() is None
    assert pc.n_resident == 0


def test_prefix_eviction_is_lru_among_leaves():
    pc = PrefixCache(2)
    a = pc.insert(None, (1, 2), 3)[0]
    pc.insert(None, (5, 6), 4)
    pc.insert(None, (7, 8), 5)
    # touching a's chain via a match refreshes its recency
    pc.match([1, 2, 0], limit=3)
    assert pc.evict_one() == 4  # oldest untouched leaf goes first
    assert pc.evict_one() == 5
    assert pc.evict_one() == a.page


def test_prefix_insert_dedups_identical_chains():
    pc = PrefixCache(4)
    toks = [9, 8, 7, 6]
    _insert_chain(pc, toks, [2])
    node, adopted = pc.insert(None, tuple(toks), 6)
    assert not adopted and node.page == 2  # caller keeps page 6
    assert pc.n_resident == 1
    assert pc.stats["inserts"] == 1


def test_prefix_interior_nodes_never_evict():
    pc = PrefixCache(2)
    last = _insert_chain(pc, [1, 2, 3, 4, 5, 6], [7, 8, 9])
    pc.acquire([last])  # pin only the LEAF
    # 9 is referenced; 7 and 8 are interior — nothing may evict
    assert pc.evict_one() is None
    pc.release([last])
    assert pc.drop_all() == [9, 8, 7]  # leaf-first cascade


def test_prefix_n_evictable_excludes_pinned_subtrees():
    """n_evictable counts exactly what a cascading evict can reach: a
    referenced node blocks itself and every ancestor, but an unreferenced
    leaf below a pinned interior node is still fair game."""
    pc = PrefixCache(2)
    last = _insert_chain(pc, [1, 2, 3, 4, 5, 6], [5, 6, 7])
    pc.insert(None, (9, 9), 8)  # independent leaf
    assert pc.n_evictable() == 4
    pc.acquire([last])  # pin the leaf: the whole chain is stuck
    assert pc.n_evictable() == 1
    pc.release([last])
    pc.acquire([last.parent])  # pin mid-chain: the leaf BELOW it still
    assert pc.n_evictable() == 2  # evicts (7 + the independent 8)
    pc.release([last.parent])
    assert pc.n_evictable() == 4
    assert len(pc.evict(4)) == 4  # and evict() reaches all of them


def test_prefix_batch_evict_is_lru_with_cascade():
    """evict(k) frees the k LRU unreferenced leaves in one pass, with a
    parent becoming eligible the moment its last child goes — identical
    order to k sequential evict_one calls, without k resident scans."""
    pc = PrefixCache(2)
    last = _insert_chain(pc, [1, 2, 3, 4], [5, 6])  # chain 5 -> 6
    pc.insert(None, (9, 9), 7)  # independent leaf, most recent
    pc.match([1, 2, 0], limit=3)  # refresh the chain root's recency
    # oldest leaf 6 goes first; its parent 5 cascades into the pool but
    # the match refreshed it, so leaf 7 (older tick) evicts before 5
    assert pc.evict(3) == [6, 7, 5]
    assert pc.n_resident == 0
    # a pinned leaf caps the batch below k
    last = _insert_chain(pc, [1, 2, 3, 4], [5, 6])
    pc.acquire([last])
    assert pc.evict(4) == []  # leaf pinned, parent interior
    pc.release([last])
    assert pc.evict(1) == [6]  # partial batch: only what's evictable
    assert pc.evict(4) == [5]


# ---------------------------------------------------------------------------
# page conservation with the IN-TRANSIT term (live slot migration)
# ---------------------------------------------------------------------------
@pytest.fixture()
def mig_engine():
    """A ContinuousEngine whose accounting we drive BY HAND — engine
    construction allocates device zeros but compiles nothing, keeping
    this module's no-compiles contract."""
    from tensorlink_tpu.engine.continuous import ContinuousEngine
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import init_params

    eng = GenerationEngine(
        TINY, init_params(TINY, jax.random.PRNGKey(0)),
        seq_buckets=(8,), batch_buckets=(1,), max_seq_len=32,
    )
    ce = ContinuousEngine(eng, max_slots=2, page_size=8, chunk_steps=2)
    yield ce
    ce._migrations.clear()  # hand-built tickets; close() would free them


def test_conservation_counts_staged_migrations_in_transit(mig_engine):
    ce = mig_engine
    ce.check_page_conservation()
    pages = ce.alloc.alloc(2)
    # allocated-but-unowned pages are a leak...
    with pytest.raises(AssertionError, match="leak"):
        ce.check_page_conservation()
    # ...until a staged migration ticket claims them as in-transit
    ce._migrations["m1"] = {"pages": pages, "nodes": [], "t": 0.0}
    ce.check_page_conservation()
    assert ce.page_accounting()["in_transit"] == pages
    assert ce.serving_snapshot()["pages_in_transit"] == 2
    # releasing the ticket returns the pages to the free-list
    ce.drop_staged_migration("m1")
    ce.check_page_conservation()
    assert ce.serving_snapshot()["pages_in_transit"] == 0


def test_conservation_rejects_double_ownership_across_transit(mig_engine):
    from tensorlink_tpu.engine.continuous import ContinuousRequest
    from tensorlink_tpu.engine.sampling import SamplingParams

    ce = mig_engine
    pages = ce.alloc.alloc(2)
    ce._migrations["m1"] = {"pages": pages, "nodes": [], "t": 0.0}
    # the same page claimed by a slot AND a ticket must be caught
    req = ContinuousRequest(
        rid=1, prompt=[1], budget=1, sampling=SamplingParams.make(),
        eos=frozenset(), seed=0,
    )
    req.pages = [pages[0]]
    ce._slots[0] = req
    with pytest.raises(AssertionError, match="in-transit"):
        ce.check_page_conservation()
    ce._slots[0] = None
    ce.check_page_conservation()


def test_frozen_slot_pages_count_in_transit_not_owned(mig_engine):
    from tensorlink_tpu.engine.continuous import ContinuousRequest
    from tensorlink_tpu.engine.sampling import SamplingParams

    ce = mig_engine
    pages = ce.alloc.alloc(3)
    req = ContinuousRequest(
        rid=1, prompt=[1], budget=1, sampling=SamplingParams.make(),
        eos=frozenset(), seed=0,
    )
    req.pages = list(pages)
    ce._slots[1] = req
    acc = ce.page_accounting()
    assert acc["slots"] == pages and acc["in_transit"] == []
    ce._frozen.add(1)  # freeze-for-export reclassifies, conserves
    acc = ce.page_accounting()
    assert acc["slots"] == [] and acc["in_transit"] == pages
    ce.check_page_conservation()
    ce._frozen.discard(1)
    ce._slots[1] = None
    ce.alloc.free(pages)
    ce.check_page_conservation()


def test_staged_migration_ttl_gc_frees_abandoned_pages(mig_engine):
    ce = mig_engine
    ce.migration_ttl_s = 0.0  # everything staged is immediately stale
    pages = ce.alloc.alloc(2)
    ce._migrations["m1"] = {"pages": pages, "nodes": [], "t": 0.0}
    free_before = ce.alloc.n_free
    ce._gc_staged_migrations()
    assert "m1" not in ce._migrations
    assert ce.alloc.n_free == free_before + 2
    ce.check_page_conservation()


# ---------------------------------------------------------------------------
# batch bucket sizing (the serving batch-shape contract)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def bucket_engine():
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.models import init_params

    return GenerationEngine(
        TINY, init_params(TINY, jax.random.PRNGKey(0)),
        seq_buckets=(8,), batch_buckets=(1, 2, 4, 8), max_seq_len=32,
    )


@pytest.mark.parametrize(
    "n,want", [(1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (6, 8), (7, 8), (8, 8)]
)
def test_batch_bucket_smallest_fit(bucket_engine, n, want):
    assert bucket_engine.batch_bucket(n) == want


def test_batch_bucket_overflow_raises(bucket_engine):
    with pytest.raises(ValueError):
        bucket_engine.batch_bucket(9)


# ---------------------------------------------------------------------------
# shared multi-tenant page pool (SharedPagePool / PoolTenant) — quota
# accounting + per-tenant conservation, driven BY HAND (no compiles)
# ---------------------------------------------------------------------------
def _pool_engines(n_pages=20, quotas=(8, 8), kv_quant="none"):
    from tensorlink_tpu.engine.continuous import ContinuousEngine
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.engine.paged import SharedPagePool
    from tensorlink_tpu.models import init_params

    eng = GenerationEngine(
        TINY, init_params(TINY, jax.random.PRNGKey(0)),
        seq_buckets=(8,), batch_buckets=(1,), max_seq_len=32,
    )
    pool = SharedPagePool(TINY, n_pages, page_size=8, kv_quant=kv_quant)
    ces = [
        ContinuousEngine(
            eng, max_slots=2, page_size=8, chunk_steps=2,
            kv_quant=kv_quant, pool=pool, model_id=f"m{i}", page_quota=q,
        )
        for i, q in enumerate(quotas)
    ]
    return pool, ces


def test_pool_tenant_quota_bounds_allocation():
    pool, (a, b) = _pool_engines(n_pages=20, quotas=(3, 0))
    assert a.alloc.n_free == 3  # min(pool free, quota room)
    assert b.alloc.n_free == 20  # uncapped: bounded by the pool alone
    got = a.alloc.alloc(3)
    assert got is not None and a.alloc.used == 3
    assert a.alloc.alloc(1) is None  # quota dry, pool is not
    assert pool.alloc.n_free == 17
    a.alloc.free(got)
    assert a.alloc.used == 0 and pool.alloc.n_free == 20


def test_pool_attach_refuses_geometry_mismatch():
    from tensorlink_tpu.engine.continuous import ContinuousEngine
    from tensorlink_tpu.engine.generate import GenerationEngine
    from tensorlink_tpu.engine.paged import SharedPagePool
    from tensorlink_tpu.models import init_params

    pool = SharedPagePool(TINY, 16, page_size=8, kv_quant="int8")
    eng = GenerationEngine(
        TINY, init_params(TINY, jax.random.PRNGKey(0)),
        seq_buckets=(8,), batch_buckets=(1,), max_seq_len=32,
    )
    # kv_quant mismatch: an int4 tenant cannot draw on an int8 pool
    with pytest.raises(ValueError, match="geometry"):
        ContinuousEngine(
            eng, max_slots=2, page_size=8, chunk_steps=2, kv_quant="int4",
            pool=pool, model_id="bad",
        )
    # page-size mismatch refuses too
    with pytest.raises(ValueError, match="geometry"):
        ContinuousEngine(
            eng, max_slots=2, page_size=16, chunk_steps=2, kv_quant="int8",
            pool=pool, model_id="bad2",
        )
    # duplicate tenant ids refuse (a rebuilt engine must detach first)
    ContinuousEngine(
        eng, max_slots=2, page_size=8, chunk_steps=2, kv_quant="int8",
        pool=pool, model_id="ok",
    )
    with pytest.raises(ValueError, match="already attached"):
        ContinuousEngine(
            eng, max_slots=2, page_size=8, chunk_steps=2, kv_quant="int8",
            pool=pool, model_id="ok",
        )


def test_pool_conservation_sums_across_tenants():
    from tensorlink_tpu.engine.continuous import ContinuousRequest
    from tensorlink_tpu.engine.sampling import SamplingParams

    pool, (a, b) = _pool_engines(n_pages=20, quotas=(10, 10))
    pool.check_page_conservation()
    pa = a.alloc.alloc(3)
    pb = b.alloc.alloc(2)
    # allocated-but-unowned pages are a leak until an owner claims them
    with pytest.raises(AssertionError, match="leak"):
        pool.check_page_conservation()
    ra = ContinuousRequest(
        rid=1, prompt=[1], budget=1, sampling=SamplingParams.make(),
        eos=frozenset(), seed=0,
    )
    ra.pages = list(pa)
    a._slots[0] = ra
    b._migrations["m1"] = {"pages": pb, "nodes": [], "t": 0.0}
    pool.check_page_conservation()  # slots(a) + in_transit(b) + free == total
    # a page held by BOTH tenants is caught with both names in the report
    rb = ContinuousRequest(
        rid=2, prompt=[2], budget=1, sampling=SamplingParams.make(),
        eos=frozenset(), seed=0,
    )
    rb.pages = [pa[0]]
    b._slots[0] = rb
    with pytest.raises(AssertionError, match="held by both"):
        pool.check_page_conservation()
    b._slots[0] = None
    # quota counter drift (pages held != tenant.used) is caught per-tenant
    a.alloc.used += 1
    with pytest.raises(AssertionError, match="quota accounting"):
        pool.check_page_conservation()
    a.alloc.used -= 1
    # cleanup restores the invariant
    a._slots[0] = None
    b._migrations.clear()
    a.alloc.free(pa)
    b.alloc.free(pb)
    pool.check_page_conservation()
    assert pool.alloc.n_free == 20


def test_pool_cache_reclaim_takes_cold_neighbors_only():
    pool, (a, b) = _pool_engines(n_pages=6, quotas=(6, 6))
    # tenant b parks 4 cold pages in its prefix cache
    pages = b.alloc.alloc(4)
    node = None
    for i, p in enumerate(pages):
        node, adopted = b.prefix.insert(node, tuple(range(8 * i, 8 * i + 8)), p)
        assert adopted
    pool.check_page_conservation()
    assert pool.alloc.n_free == 2
    # a needs 5: its own trie is empty, b's cold pages reclaim to the pool
    got = a._alloc_pages(5)
    assert got is not None and len(got) == 5
    assert pool.cache_reclaims >= 3 and b.alloc.used <= 1
    a.alloc.free(got)
    pool.check_page_conservation()


def test_pool_snapshot_rides_serving_snapshot():
    pool, (a, b) = _pool_engines(n_pages=20, quotas=(12, 6))
    snap = a.serving_snapshot()
    assert snap["pool_pages_total"] == 20
    assert snap["pool_quota"] == 12 and snap["pool_pages_used"] == 0
    assert snap["pool_tenants"] == 2
    assert snap["pool_used"]["m1"]["quota"] == 6
    # per-tenant gauges render under the registry (the /metrics view)
    text = a.metrics.render({"model": "m0"})
    assert 'tlink_engine_pool_quota{model="m0"} 12' in text
