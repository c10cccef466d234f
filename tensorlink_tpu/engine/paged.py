"""Block-paged KV cache + slot-batched decode step (continuous batching).

The dense :class:`~tensorlink_tpu.models.base.KVCache` is ``[L, B, S_max,
n_kv, hd]`` — one contiguous span per batch row, so a batched decode is
welded to one (B, S_max) shape and a finished row's span stays allocated
until the whole batch drains. Here KV lives in fixed-size **pages**
``[L, P, n_kv, page, hd]`` (kv-head-major, so the Pallas kernel's
per-(page, head) blocks carry TPU-native ``(page, hd)`` trailing tiles)
with a per-slot **block table**: sequences of
ragged lengths share ONE compiled decode program (the block table and
lengths are data, not shape), a finished slot's pages return to the
free-list immediately, and a queued prompt is admitted by writing a new
block-table row — no recompile, no cache realloc.

Page 0 is a reserved scratch page: free slots ride the fixed slot-batch
shape with an all-zero block-table row and length 0, so their (masked,
invisible) per-step KV writes land on scratch instead of a page another
slot owns — that invariant is what makes eviction safe with zero
cross-slot contamination.

Attention routes through ops/attention.py: the Pallas
:func:`~tensorlink_tpu.ops.attention.ragged_paged_attention` kernel on
TPU (whole mixed prefill+decode block, KV copied page-by-page out of the
layer-stacked pool via a scalar-prefetched block table and layer index)
with
:func:`~tensorlink_tpu.ops.attention.ragged_paged_attention_ref` on CPU
and in parity tests; the decode continuation inside the step runs the
:func:`~tensorlink_tpu.ops.attention.paged_attention` kernel per token.

**Quantized pages** (``MLConfig.kv_quant="int8"`` / ``"int4"``): the page
pool stores KV int8 — or PACKED int4, two values per byte over a
split-half nibble layout (models/quant.py::quantize_kv4) — with
per-(page, position, head) symmetric f32 scales carried page-granular
alongside the payload. Quantization happens at THE one page-write path
(``_ragged_write_indices`` feeds every program), one position at a time —
a position's (quantized bytes, scale) pair depends only on its own KV
row, so the bitwise cache contract survives by construction: a quantized
page + its scale rows IS the cache value, and COW ``copy_page``, trie
promotion, LRU eviction, crash-recovery re-prefill and preemption resume
all move it byte-exactly. The kernels dequantize at the page fetch
(nibble unpack + scale multiply fused into the HBM read), so KV bytes
halve (int8) or quarter (int4) while the MXU math stays in the model
dtype — ~2×/~4× serving slots and prefix-cache residency at fixed HBM.

**Multi-tenant pool** (:class:`SharedPagePool`): co-hosted models with
matching page geometry share ONE physical pool under per-tenant quotas —
the reclaimed HBM spent on scenario diversity instead of headroom.
"""

from __future__ import annotations

import hashlib
import heapq
from dataclasses import dataclass, replace
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..models.base import ModelConfig
from ..models.latent import Pattern
from ..models.transformer import (
    _embed_tokens,
    _logits,
    _mlp,
    _norm,
    _rms_head_norm,
    _tp_gather,
    apply_rope,
    _rope_dim,
    rope_tables,
    tp_partition_specs,
    tp_shardable,
)
from ..models.quant import matmul as _mm
from ..models.quant import quantize_kv as _quant_kv
from ..models.quant import quantize_kv4 as _quant_kv4
from ..ops.attention import (
    paged_attention,
    paged_attention_ref,
    ragged_paged_attention,
    ragged_paged_attention_ref,
)
from .latent import (
    LatentPagedCache,
    attention_only,
    cache_pools,
    decode_layers,
    layer_loop,
    ragged_layers,
    with_pools,
)


@jax.tree_util.register_dataclass
@dataclass
class PagedKVCache:
    """Paged decode cache: ``k``/``v`` are ``[L, P, n_kv, page, hd]``,
    ``block_tables`` maps each serving slot to its pages ``[S, n_pp]``
    (0 = the reserved scratch page), ``lengths`` counts valid positions
    per slot ``[S]``. Stacked over layers like the dense cache, and
    never taken apart: the step's layer loops CARRY the stacks
    (:func:`_scan_layers`), rows are written at ``(layer, page, head,
    offset)`` and the attention kernel is handed the stack and a layer
    index, so no step slices a layer's pool out, stacks one back or
    copies a pool. Donated into the step, so XLA updates pages in place.

    **int8 mode** (``quantized=True``): ``k``/``v`` hold int8 and
    ``k_scale``/``v_scale`` ``[L, P, n_kv, page]`` carry the
    per-(page, position, head) symmetric f32 scales — page-granular
    storage, so every page operation (COW, promotion, eviction, clear)
    moves payload and scales together byte-exactly."""

    k: jax.Array
    v: jax.Array
    block_tables: jax.Array  # int32 [S, pages_per_slot]
    lengths: jax.Array  # int32 [S]
    k_scale: jax.Array | None = None  # f32 [L, P, n_kv, page] — int8 mode
    v_scale: jax.Array | None = None

    @classmethod
    def init(
        cls,
        cfg: ModelConfig,
        max_slots: int,
        *,
        page_size: int = 16,
        max_len: int | None = None,
        dtype=None,
        quantized: bool = False,
        kv_quant: str | None = None,
        n_pages: int | None = None,
    ) -> "PagedKVCache":
        """``kv_quant`` ("none"/"int8"/"int4") supersedes the legacy
        ``quantized`` bool (kept as an "int8" alias). ``n_pages``
        overrides the slots×capacity pool sizing — how a shared
        multi-tenant pool decouples its page budget from any one
        tenant's slot count (:class:`SharedPagePool`)."""
        mode = kv_quant or ("int8" if quantized else "none")
        S_max = max_len or cfg.max_seq_len
        n_pp = -(-S_max // page_size)  # pages per slot (ceil)
        # page 0 = scratch, never allocated
        P = n_pages if n_pages is not None else 1 + max_slots * n_pp
        hd = cfg.head_dim
        if mode == "int4":
            if hd % 2:
                raise ValueError(
                    f"kv_quant='int4' packs two values per byte — "
                    f"head_dim {hd} must be even"
                )
            hd //= 2  # packed: two int4 values per stored byte
        shape = (cfg.n_layers, P, cfg.n_kv_heads, page_size, hd)
        if mode in ("int8", "int4"):
            return cls(
                k=jnp.zeros(shape, jnp.int8),
                v=jnp.zeros(shape, jnp.int8),
                block_tables=jnp.zeros((max_slots, n_pp), jnp.int32),
                lengths=jnp.zeros((max_slots,), jnp.int32),
                k_scale=jnp.zeros(shape[:-1], jnp.float32),
                v_scale=jnp.zeros(shape[:-1], jnp.float32),
            )
        if mode != "none":
            raise ValueError(f"unknown kv_quant mode {mode!r}")
        dt = dtype or cfg.dtype
        return cls(
            k=jnp.zeros(shape, dt),
            v=jnp.zeros(shape, dt),
            block_tables=jnp.zeros((max_slots, n_pp), jnp.int32),
            lengths=jnp.zeros((max_slots,), jnp.int32),
        )

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def page_size(self) -> int:
        return self.k.shape[3]

    @property
    def n_pages(self) -> int:
        return self.k.shape[1]

    @property
    def max_slots(self) -> int:
        return self.block_tables.shape[0]

    @property
    def pages_per_slot(self) -> int:
        return self.block_tables.shape[1]


class PageAllocator:
    """Host-side free-list over physical page ids 1..P-1 (0 is scratch).

    Pure bookkeeping — allocation order is irrelevant to correctness (the
    block table names pages explicitly), so a freed page is reused LIFO
    for locality. ``alloc`` is all-or-nothing: admission either gets every
    page a request could need or stays queued."""

    def __init__(self, n_pages: int):
        self._free = list(range(n_pages - 1, 0, -1))  # pop() yields 1 first

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        if n > len(self._free):
            return None
        return [self._free.pop() for _ in range(n)]

    def free(self, pages: list[int]) -> None:
        for p in pages:
            if p > 0:
                self._free.append(p)


# ---------------------------------------------------------------------------
# Shared multi-tenant page pool (co-hosted models, docs/SERVING.md
# "Co-hosting multiple models")
# ---------------------------------------------------------------------------


class PoolTenant:
    """One co-hosted model's quota-bounded allocator façade over a
    :class:`SharedPagePool` — the ``PageAllocator`` interface a
    ``ContinuousEngine`` consumes (``n_free``/``alloc``/``free``), with
    two extra constraints: an allocation must fit BOTH the shared pool's
    free list and this tenant's page quota, and every page this tenant
    holds (slot-owned, prefix-cache-resident, or in transit) counts
    against ``used`` until it returns through :meth:`free` — which is
    what makes the per-tenant conservation term checkable."""

    def __init__(self, pool: "SharedPagePool", model_id: str, quota: int):
        self.pool = pool
        self.model_id = str(model_id)
        # 0 = uncapped (bounded by the pool alone)
        self.quota = int(quota) if quota else pool.n_pages - 1
        self.used = 0
        self.engine = None  # bound by SharedPagePool.attach

    @property
    def n_free(self) -> int:
        return min(self.pool.alloc.n_free, self.quota - self.used)

    @property
    def _free(self):
        # page_accounting compatibility: the authoritative free list is
        # the shared pool's
        return self.pool.alloc._free

    def alloc(self, n: int) -> list[int] | None:
        if self.used + n > self.quota:
            return None  # quota dry — the tenant's own eviction/preemption
            # ladder must reclaim ITS pages; other tenants are unaffected
        pages = self.pool.alloc.alloc(n)
        if pages is not None:
            self.used += len(pages)
        return pages

    def free(self, pages: list[int]) -> None:
        n = sum(1 for p in pages if p > 0)
        self.pool.alloc.free(pages)
        self.used -= n
        assert self.used >= 0, (
            f"tenant {self.model_id!r} freed more pages than it held"
        )


class SharedPagePool:
    """ONE physical KV page pool shared by several co-hosted tenant
    engines — the multi-tenant density play: the HBM a quantized page
    pool reclaims is spent on MORE MODELS resident per chip instead of
    idle headroom. Tenants must share page geometry (layers, kv heads,
    head_dim, page size, storage mode) — the many-small-fine-tunes
    shape, where N adapters of one base model serve from one worker;
    each keeps its OWN block tables, slots, scheduler, and prefix cache
    (cache keys are per-model by construction — tries never mix), while
    the physical pages and the free list are shared under per-tenant
    quotas.

    Threading contract: the pool extends the engines' single-driver
    discipline ACROSS tenants — every attached engine must be stepped
    from the same driver thread (the worker's run loop already is), so
    cross-tenant reclaim and preemption can walk another tenant's
    host-side state without racing its driver.

    Cross-tenant policy (the PR 4 scheduler's rank rules, extended):
    when a tenant's allocation fails on the SHARED free list (not its
    quota), the admission ladder may (1) evict other tenants'
    refcount-0 prefix-cache pages LRU-first (:meth:`reclaim_cache`),
    then (2) preempt another tenant's strictly-lower-ranked running
    slot (:meth:`cross_model_victim`) through that engine's normal
    preemption path — so an interactive request of model A outranks a
    best_effort slot of model B, but can never touch B's equal-or-
    better-ranked work."""

    def __init__(
        self,
        cfg: ModelConfig,
        n_pages: int,
        *,
        page_size: int = 16,
        dtype=None,
        kv_quant: str = "none",
    ):
        self.page_size = int(page_size)
        self.kv_quant = str(kv_quant or "none")
        proto = PagedKVCache.init(
            cfg, 0, page_size=self.page_size, max_len=self.page_size,
            dtype=dtype, kv_quant=self.kv_quant, n_pages=1 + int(n_pages),
        )
        # the canonical layer-stacked page arrays: tenant engines read
        # them through their cache property and write them back after
        # every donated step — one physical pool, N block-table views
        self.kv: tuple = _cache_kv(proto)
        self.alloc = PageAllocator(1 + int(n_pages))
        self.tenants: dict[str, PoolTenant] = {}
        self.geometry = (
            cfg.n_layers, cfg.n_kv_heads, cfg.head_dim, self.page_size,
            self.kv_quant, str(proto.k.dtype),
        )
        self.cross_preemptions = 0
        self.cache_reclaims = 0

    @property
    def n_pages(self) -> int:
        return self.kv[0].shape[1]

    @property
    def n_free(self) -> int:
        return self.alloc.n_free

    def attach(self, model_id: str, engine, *, quota: int = 0) -> PoolTenant:
        """Register a tenant engine. Geometry must match the pool's —
        a mismatched model cannot share physical pages and must get its
        own pool (loud, never a silent corruption)."""
        t_dtype = (
            "int8" if engine.kv_quant in ("int8", "int4")
            else str(jnp.dtype(engine.engine.cache_dtype))
        )
        geo = (
            engine.cfg.n_layers, engine.cfg.n_kv_heads,
            engine.cfg.head_dim, engine.page_size, engine.kv_quant,
            t_dtype,
        )
        if geo != self.geometry:
            raise ValueError(
                f"tenant {model_id!r} page geometry {geo} does not match "
                f"the shared pool's {self.geometry} — co-hosted models "
                "must share (layers, kv_heads, head_dim, page_size, "
                "kv_quant, dtype)"
            )
        if model_id in self.tenants:
            raise ValueError(f"tenant {model_id!r} already attached")
        t = PoolTenant(self, model_id, quota)
        t.engine = engine
        self.tenants[model_id] = t
        return t

    def detach(self, model_id: str) -> None:
        t = self.tenants.pop(model_id, None)
        assert t is None or t.used == 0, (
            f"tenant {model_id!r} detached holding {t.used} pages"
        )

    # -- cross-tenant reclaim / preemption (single driver thread) --------
    def reclaim_cache(self, n: int, exclude) -> int:
        """Evict up to ``n`` refcount-0 prefix-cache pages from OTHER
        tenants (LRU within each trie) back to the shared free list.
        Returns how many pages came back. The first rung of the
        cross-tenant ladder: cold resident prefixes are the cheapest
        HBM to take — no stream is disturbed."""
        freed = 0
        for t in self.tenants.values():
            if t.engine is exclude or t.engine.prefix is None:
                continue
            need = n - freed
            if need <= 0:
                break
            pages = t.engine.prefix.evict(need)
            if pages:
                t.engine.alloc.free(pages)
                freed += len(pages)
        self.cache_reclaims += freed
        return freed

    def cross_model_victim(self, cand_rank: int, exclude):
        """The running request another tenant should preempt for a
        candidate of effective rank ``cand_rank``, or None — the PR 4
        victim rules applied across models: only slots whose
        ADMISSION-TIME rank is strictly worse are eligible, worst rank
        first (ties broken toward the tenant holding the most pages, so
        one teardown frees the most HBM). Returns ``(engine, request)``;
        the caller preempts through that engine's normal path, so the
        victim's resume contract (promotion, requeue, bit-identical
        stream) is untouched."""
        best = None
        for t in self.tenants.values():
            eng = t.engine
            if eng is exclude:
                continue
            with eng._lock:
                v = eng.sched.victim_for_rank(eng._preemptable(), cand_rank)
            if v is None:
                continue
            key = (v.admit_rank, t.used)
            if best is None or key > best[0]:
                best = (key, eng, v)
        if best is None:
            return None
        self.cross_preemptions += 1
        return best[1], best[2]

    # -- conservation ----------------------------------------------------
    def check_page_conservation(self) -> None:
        """The multi-tenant free-list invariant: shared free + Σ per
        tenant (slot-owned + cache-resident + in-transit) == total
        usable pages, every set pairwise disjoint ACROSS tenants, each
        tenant's ``used`` counter equal to what its engine actually
        holds, scratch page 0 nowhere. Raises AssertionError on
        violation — the per-tenant terms are what keep a quota
        meaningful: a tenant can neither hide pages from its quota nor
        leak them into a neighbor's."""
        problems: list[str] = []
        free = set(self.alloc._free)
        if len(free) != len(self.alloc._free):
            problems.append("shared free-list holds a duplicate page")
        seen: dict[int, str] = {p: "free" for p in free}
        total_held = 0
        for mid, t in self.tenants.items():
            acc = t.engine.page_accounting()
            slots, cached = list(acc["slots"]), set(acc["cached"])
            transit = list(acc["in_transit"])
            if len(slots) != len(set(slots)):
                problems.append(f"[{mid}] a page is owned by two slots")
            if len(transit) != len(set(transit)):
                problems.append(f"[{mid}] a page is in transit twice")
            held = set(slots) | cached | set(transit)
            if len(held) != len(slots) + len(cached) + len(transit):
                problems.append(f"[{mid}] page in two ownership classes")
            for p in held:
                prev = seen.get(p)
                if prev is not None:
                    problems.append(
                        f"page {p} held by both {prev} and {mid}"
                    )
                seen[p] = mid
            n_held = len(slots) + len(cached) + len(transit)
            total_held += n_held
            if n_held != t.used:
                problems.append(
                    f"[{mid}] quota accounting drifted: engine holds "
                    f"{n_held} pages, tenant.used={t.used}"
                )
            if t.used > t.quota:
                problems.append(
                    f"[{mid}] over quota: used={t.used} > {t.quota}"
                )
        if 0 in seen:
            problems.append("scratch page 0 entered an ownership set")
        total = self.n_pages - 1
        if len(free) + total_held != total:
            problems.append(
                f"leak: free={len(free)} + held={total_held} != "
                f"total={total}"
            )
        if problems:
            raise AssertionError(
                "pool page conservation violated: " + "; ".join(problems)
            )

    def snapshot(self) -> dict:
        """Pool-level telemetry (each tenant's engine merges this into
        its serving_snapshot; /metrics reads the same numbers through
        per-engine callback gauges)."""
        return {
            "pool_pages_total": self.n_pages - 1,
            "pool_pages_free": self.alloc.n_free,
            "pool_tenants": len(self.tenants),
            "pool_cross_preemptions": self.cross_preemptions,
            "pool_cache_reclaims": self.cache_reclaims,
            "pool_used": {
                mid: {"used": t.used, "quota": t.quota}
                for mid, t in self.tenants.items()
            },
        }


# ---------------------------------------------------------------------------
# Automatic prefix cache (host-side index over physical pages)
# ---------------------------------------------------------------------------


def chain_hash(parent_hash: str, block) -> str:
    """16-hex-char rolling hash of a trie chain: the previous prefix's
    hash folded with one page-size token block. Structural trie equality
    stays the CACHE key (no collision can ever map a wrong page); these
    hashes exist only so a chain can be NAMED compactly off-box — the
    fleet router scores a replica's cache affinity against a digest of
    them (docs/SERVING.md "Fleet serving") without shipping the trie. A
    collision merely misguides placement by one request, never
    correctness."""
    h = hashlib.blake2b(digest_size=8)
    h.update(parent_hash.encode("ascii"))
    h.update(",".join(str(int(t)) for t in block).encode("ascii"))
    return h.hexdigest()


def prompt_chain_hashes(tokens, page_size: int, max_pages: int) -> list[str]:
    """The rolling chain hashes of ``tokens``' leading full page blocks
    (up to ``max_pages``) — what the router matches against a replica's
    :meth:`PrefixCache.digest`. Index i covers ``(i + 1) * page_size``
    tokens. Host-only, no trie required."""
    out: list[str] = []
    prev = ""
    p = int(page_size)
    limit = min((len(tokens) // p), int(max_pages))
    for i in range(limit):
        prev = chain_hash(prev, tokens[i * p : (i + 1) * p])
        out.append(prev)
    return out


class _TrieNode:
    """One cached FULL page: the KV of ``block`` (page_size token ids) at
    the absolute positions its chain depth implies."""

    __slots__ = (
        "block", "page", "parent", "children", "refs", "tick",
        "depth", "key_hash", "weights_version", "snap",
    )

    def __init__(self, block: tuple, page: int, parent: "_TrieNode | None"):
        self.block = block
        self.page = page
        self.parent = parent
        self.children: dict[tuple, _TrieNode] = {}
        self.refs = 0  # slots currently mapping this page
        self.tick = 0  # LRU recency (monotonic engine counter)
        # the model weights version this page's KV was computed under
        # (PrefixCache.insert stamps it): the match fence for live weight
        # publishes — see ContinuousEngine.publish_weights
        self.weights_version = 1
        # a model with recurrent layers: the engine's snapshot of the
        # slot's state after this page's last position (its place in the
        # snapshot pool), or None. Dropped with the node (``on_drop``)
        self.snap: int | None = None
        # chain identity for the fleet digest: pages-from-root count and
        # the rolling chain hash (root carries depth 0 / hash "")
        if parent is None:
            self.depth = 0
            self.key_hash = ""
        else:
            self.depth = parent.depth + 1
            self.key_hash = chain_hash(parent.key_hash, block)


class PrefixCache:
    """Host-side automatic-prefix-cache index over ``PagedKVCache`` pages.

    A trie over page-size token blocks: a node's path from the root IS the
    cache key — the exact token chain from position 0 — so two prompts
    share a cached page only when every earlier token matches, which makes
    the key rope-offset-invariant by construction (same tokens at the same
    absolute positions ⇒ bitwise the same KV). The cache is per engine,
    hence per (model, dtype): no model id needs to ride the key.

    Only FULL pages are cached. ``refs`` counts slots whose block tables
    currently name the page; refcount-0 pages stay resident and are
    evicted leaf-first in LRU order when the allocator runs dry (evicting
    an interior node would orphan descendants whose positions assume it).
    Structural equality (no hashing) means no collision can ever map a
    wrong page — the "hash map" is Python's dict over the block tuples.
    """

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        self.root = _TrieNode((), 0, None)
        self._by_page: dict[int, _TrieNode] = {}
        self._tick = 0
        # bumped on every membership change (insert/evict) so the engine
        # can skip rebuilding the fleet digest when nothing moved
        self.version = 0
        # the CURRENT model weights version (the engine bumps it on every
        # live weight publish, docs/TRAINING.md): inserts stamp it onto
        # their nodes, and match() refuses chains stamped with any other
        # version — cached KV from older weights can never become a hit,
        # which is what keeps the bitwise cache contract true across a
        # hot-swap. Stale refcount-0 chains are evicted at publish time;
        # still-referenced ones free as their slots do.
        self.weights_version = 1
        # the demote seam (docs/SERVING.md "Tiered prefix cache"): when
        # set, evict() hands each victim node to this callable BEFORE the
        # page id returns to the free-list — the engine wires it to the
        # host-RAM tier so the bytes survive the eviction. Best-effort by
        # contract: the spill contains its own failures (a page that
        # fails to demote is simply destroyed, the pre-tier behavior),
        # so eviction itself can never be blocked by the tier below.
        self.spill = None
        # called with every node that leaves the trie, after ``spill``:
        # the engine frees what it keeps by node (a state snapshot)
        self.on_drop = None
        self.stats = {
            "lookups": 0,
            "hits": 0,
            "hit_tokens": 0,
            "cow_copies": 0,
            "evictions": 0,
            "inserts": 0,
        }

    # -- introspection ---------------------------------------------------
    @property
    def resident_pages(self) -> set[int]:
        return set(self._by_page)

    @property
    def n_resident(self) -> int:
        return len(self._by_page)

    def digest(self, max_chains: int = 32) -> dict:
        """Compact export of the resident chains for off-box cache-
        affinity scoring (docs/SERVING.md "Fleet serving"): the
        ``max_chains`` most-recently-used nodes as ``{chain_hash:
        covered_tokens}``. Interior prefixes of a hot chain are touched
        by every hit, so recency order naturally exports them too — a
        prompt matching only part of a resident chain still scores.
        Bounded bytes by construction (~26 B/entry serialized), JSON-
        safe, and NEVER authoritative: admission re-walks the real trie,
        so a stale or colliding digest can only misplace a request, not
        corrupt a stream."""
        nodes = sorted(
            (
                n for n in self._by_page.values()
                if n.weights_version == self.weights_version
            ),
            key=lambda n: n.tick, reverse=True,
        )[: max(int(max_chains), 0)]
        return {
            "page_size": self.page_size,
            "chains": {
                n.key_hash: n.depth * self.page_size for n in nodes
            },
        }

    def _touch(self, node: _TrieNode) -> None:
        self._tick += 1
        node.tick = self._tick

    # -- lookup ----------------------------------------------------------
    def _blocks(self, tokens, limit: int):
        # a page's key is the slice itself: ``submit`` made the prompt a
        # list of ints once, and NumPy integers hash and compare as the
        # ints they hold, so a key built either way finds the same node
        p = self.page_size
        for i in range(0, (limit // p) * p, p):
            yield tuple(tokens[i : i + p])

    def match(self, tokens, limit: int) -> list[_TrieNode]:
        """Longest chain of cached full pages covering ``tokens[:limit]``.
        Returns the matched nodes in position order (refs NOT yet taken —
        callers acquire() before anything can evict, single-driver).
        lookup/hit telemetry is counted at successful ADMISSION, not
        here: a head-of-line request waiting for pages re-matches every
        chunk and must not inflate the operator-facing hit rate."""
        node = self.root
        out: list[_TrieNode] = []
        for block in self._blocks(tokens, limit):
            child = node.children.get(block)
            if child is None or child.weights_version != self.weights_version:
                # a version mismatch fences the WHOLE chain below: its KV
                # was computed under different weights (publish_weights)
                break
            out.append(child)
            self._touch(child)  # a hit IS a use: refresh LRU recency
            node = child
        return out

    def partial_match(
        self, nodes: list[_TrieNode], tokens, limit: int
    ) -> tuple[_TrieNode, int] | None:
        """Best divergent child for copy-on-write: among the children of
        the last matched node, the page whose block shares the LONGEST
        non-empty token prefix with what the request still needs (capped
        at ``limit`` tokens past the full-page hit). The caller copies
        that page and owns the copy — the cached original is never
        written."""
        parent = nodes[-1] if nodes else self.root
        done = len(nodes) * self.page_size
        want = [int(t) for t in tokens[done : done + min(self.page_size, limit - done)]]
        if not want:
            return None
        best: tuple[_TrieNode, int] | None = None
        for block, child in parent.children.items():
            if child.weights_version != self.weights_version:
                # stale-version KV (live weight publish) must not seed a
                # COW copy any more than it may full-page match
                continue
            n = 0
            for a, b in zip(want, block):
                if a != b:
                    break
                n += 1
            if n > 0 and (best is None or n > best[1]):
                best = (child, n)
        return best

    # -- refcounts -------------------------------------------------------
    def acquire(self, nodes: list[_TrieNode]) -> None:
        for n in nodes:
            n.refs += 1
            self._touch(n)

    def release(self, nodes: list[_TrieNode]) -> None:
        for n in nodes:
            assert n.refs > 0, "prefix-cache refcount underflow"
            n.refs -= 1
            self._touch(n)

    # -- insert / evict --------------------------------------------------
    def insert(
        self, parent: "_TrieNode | None", block: tuple, page: int,
        freed: "list[int] | None" = None,
    ) -> tuple[_TrieNode, bool]:
        """Adopt ``page`` as the cached KV of ``block`` under ``parent``
        (None = root). Returns ``(node, adopted)`` — ``adopted=False``
        means an identical chain is already resident: the caller keeps
        ownership of ``page`` (frees it) and continues the walk from the
        existing node.

        A STALE-version unreferenced leaf shadowing this block (its KV
        predates a weight publish, so it can never match again) is
        evicted in place and the fresh page adopted — its page id lands
        in ``freed`` for the caller's allocator. A stale node that still
        has refs or children stays (its readers are mid-stream); the
        fresh page is declined and the chain re-caches once they drain."""
        parent = parent or self.root
        existing = parent.children.get(block)
        if (
            existing is not None
            and existing.weights_version != self.weights_version
            and existing.refs == 0
            and not existing.children
        ):
            del parent.children[block]
            del self._by_page[existing.page]
            if self.on_drop is not None:
                self.on_drop(existing)
            self.stats["evictions"] += 1
            self.version += 1
            if freed is not None:
                freed.append(existing.page)
            existing = None
        if existing is not None:
            self._touch(existing)
            return existing, False
        node = _TrieNode(block, int(page), parent)
        node.weights_version = self.weights_version
        parent.children[block] = node
        self._by_page[int(page)] = node
        self._touch(node)
        self.stats["inserts"] += 1
        self.version += 1
        return node, True

    def n_evictable(self) -> int:
        """Pages a (cascading) evict could free in the limit: nodes whose
        WHOLE subtree is unreferenced — a referenced descendant pins its
        ancestors because eviction is leaf-first. Lets the allocator skip
        a destructive cache wipe when eviction can never satisfy the
        allocation anyway."""
        def walk(node: _TrieNode) -> tuple[int, bool]:
            total, clear = 0, node.refs == 0
            for child in node.children.values():
                c_total, c_clear = walk(child)
                total += c_total
                clear = clear and c_clear
            return total + (1 if clear else 0), clear
        return sum(walk(c)[0] for c in self.root.children.values())

    def evict(self, k: int) -> list[int]:
        """Free up to ``k`` least-recently-used unreferenced LEAF pages
        in one pass (a parent whose last child evicts becomes a leaf and
        is eligible within the same call); returns the freed page ids.
        One resident scan amortized over the whole batch — the allocator
        asks for the full deficit at once instead of one page per retry."""
        heap = [
            (n.tick, n.page, n)
            for n in self._by_page.values()
            if n.refs == 0 and not n.children
        ]
        heapq.heapify(heap)
        freed: list[int] = []
        while heap and len(freed) < k:
            _, _, victim = heapq.heappop(heap)
            if self.spill is not None:
                # tiered demotion: the victim's bytes are still intact in
                # HBM (its page id hasn't been reused yet) — offer them
                # to the tier below before the trie forgets the chain
                self.spill(victim)
            if self.on_drop is not None:
                self.on_drop(victim)
            del victim.parent.children[victim.block]
            del self._by_page[victim.page]
            self.stats["evictions"] += 1
            self.version += 1
            freed.append(victim.page)
            parent = victim.parent
            if (
                parent is not self.root
                and parent.refs == 0
                and not parent.children
            ):
                heapq.heappush(heap, (parent.tick, parent.page, parent))
        return freed

    def evict_one(self) -> int | None:
        """Free the least-recently-used unreferenced LEAF page; returns
        its physical page id (for the allocator's free-list) or None when
        nothing is evictable."""
        freed = self.evict(1)
        return freed[0] if freed else None

    def drop_all(self) -> list[int]:
        """Evict everything evictable (teardown): returns the freed page
        ids. Referenced pages stay — their slots still map them."""
        return self.evict(len(self._by_page))


# the step program's device phases, in execution order: each is one
# top-level loop of ``paged_ragged_step`` under a ``jax.named_scope`` of
# this name (inside them: attn, kv_write, mlp, lm_head, sample)
RAGGED_PASS = "tlink.ragged_pass"
VERIFY_EMIT = "tlink.verify_emit"
DECODE_CONT = "tlink.decode_cont"
STEP_PHASES = (RAGGED_PASS, VERIFY_EMIT, DECODE_CONT)


def _paged_qkv(h, lp, cfg: ModelConfig, cos, sin):
    """Shared projection prologue of the paged blocks — q/k/v with
    biases, both qk-norm variants, and (partial-dim) rope. IDENTICAL math
    to transformer.py::_block's opening (the parity tests' anchor),
    generic over the ``[B, T, d]`` input so the decode step (S slots × 1
    token) and the prefill chunk (1 slot × C tokens) maintain ONE copy.
    A new model-family flag added to the dense block must land here once,
    not once per paged path."""
    B, T = h.shape[:2]
    ap = lp["attn"]
    q = _mm(h, ap["wq"])
    k = _mm(h, ap["wk"])
    v = _mm(h, ap["wv"])
    if "bq" in ap:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    if cfg.qk_norm_full:
        q = _rms_head_norm(q, ap["q_norm"], cfg.norm_eps)
        k = _rms_head_norm(k, ap["k_norm"], cfg.norm_eps)
    # -1 head counts: under tensor parallelism the projections hold a
    # head-major-contiguous LOCAL slice, so the head axis is n/tp there
    # and the full n on the single-device path — same reshape either way
    q = q.reshape(B, T, -1, cfg.head_dim)
    k = k.reshape(B, T, -1, cfg.head_dim)
    v = v.reshape(B, T, -1, cfg.head_dim)
    if cfg.qk_norm:
        q = _rms_head_norm(q, ap["q_norm"], cfg.norm_eps)
        k = _rms_head_norm(k, ap["k_norm"], cfg.norm_eps)
    if cos is not None:
        rd = cos.shape[-1]
        if rd == cfg.head_dim:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        else:
            q = jnp.concatenate(
                [apply_rope(q[..., :rd], cos, sin), q[..., rd:]], axis=-1
            )
            k = jnp.concatenate(
                [apply_rope(k[..., :rd], cos, sin), k[..., rd:]], axis=-1
            )
    return q, k, v


def _paged_residual(
    x, attn_raw, lp, cfg: ModelConfig,
    tp_axis: str | None = None, tp_quant: bool = False,
):
    """Shared epilogue: output projection (+bias) and the norm-position /
    parallel-residual wiring, identical to transformer.py::_block's
    closing. ``attn_raw`` is the attention output ``[B, T, Hq, hd]``.

    Under tensor parallelism ``attn_raw`` holds the LOCAL heads; the
    flattened head outputs gather to the full ``q_dim`` (head-major
    contiguous slices, so the flattened-axis concat IS the head-axis
    concat), wo produces LOCAL d_model columns (+ its local bias slice)
    and gathers back — the residual stream ``x`` is always FULL, so
    norms and residual adds are untouched by sharding."""
    B, T = attn_raw.shape[:2]
    ap = lp["attn"]
    with jax.named_scope("attn"):
        attn_flat = _tp_gather(attn_raw.reshape(B, T, -1), tp_axis, tp_quant)
        attn_out = _mm(attn_flat, ap["wo"])
        if "bo" in ap:
            attn_out = attn_out + ap["bo"]
        attn_out = _tp_gather(attn_out, tp_axis, tp_quant)

    def mlp(h):
        with jax.named_scope("mlp"):
            return _mlp(h, lp["mlp"], cfg, tp_axis, tp_quant)

    if cfg.norm_position == "post":
        x = x + _norm(attn_out, lp["ln1"], cfg)
        x = x + _norm(mlp(x), lp["ln2"], cfg)
    elif cfg.parallel_residual:
        x = x + attn_out + mlp(_norm(x, lp["ln2"], cfg))
    else:
        x = x + attn_out
        x = x + mlp(_norm(x, lp["ln2"], cfg))
    return x


def _attn_scale(cfg: ModelConfig) -> float:
    return cfg.attn_scale if cfg.attn_scale is not None else cfg.head_dim**-0.5


def _ragged_write_indices(block_tables, starts, n_valid, page, n_pp, C):
    """Physical ``(page, offset)`` write targets for a ragged ``[S, C]``
    token block: position ``j`` of slot ``s`` lands at absolute position
    ``starts[s] + j`` when ``j < n_valid[s]``; every other (padding row,
    idle slot) write lands on the scratch page, unreachable from any
    block table. THE one page-write path: prefill-written and
    decode-written KV route through this same computation — a decode
    token is just the ``C = 1`` / ``n_valid = 1`` case (the clamp is
    belt-and-braces; the host evicts a slot before it reaches capacity).
    Also returns the uncapped absolute positions (the rope offsets) and
    the validity mask. :func:`_page_write_plan` names the same targets
    page by page, for the blocks whose payload is written that way."""
    idx = jnp.arange(C)[None, :]
    pos = starts[:, None] + idx  # [S, C]
    valid = idx < n_valid[:, None]
    cpos = jnp.minimum(pos, n_pp * page - 1)
    pg = jnp.take_along_axis(block_tables, cpos // page, axis=1)
    write_pg = jnp.where(valid, pg, 0)
    write_off = jnp.where(valid, cpos % page, 0)
    return write_pg, write_off, pos, valid


def _page_write_plan(block_tables, starts, n_valid, page, n_pp, C):
    """The same writes as :func:`_ragged_write_indices` names row by row,
    as WHOLE PAGES: a slot's block of ``C`` consecutive positions lies in
    at most ``n_pg = ceil((C + page - 1) / page)`` consecutive pages of
    its table, from page ``starts // page`` on. Returns ``(target [S,
    n_pg], shift [S], written [S, n_pg, 1, page, 1])``: the physical page
    each of those holds (the scratch page where no valid position falls
    into it), the offset of the block's first position in the first
    page, and which positions of the ``n_pg`` pages the block writes (a
    mask over ``[S, n_pg, Hkv, page, hd]``)."""
    n_pg = -(-(C + page - 1) // page)
    shift = starts % page
    r = jnp.arange(n_pg * page)[None, :]
    lp = (starts // page)[:, None] + jnp.arange(n_pg)[None, :]  # [S, n_pg]
    rows = (
        (r >= shift[:, None]) & (r < (shift + n_valid)[:, None])
        & jnp.repeat(lp < n_pp, page, axis=1)
    )
    pg = jnp.take_along_axis(
        block_tables, jnp.minimum(lp, n_pp - 1), axis=1
    )
    written = rows.reshape(-1, n_pg, page)
    target = jnp.where(written.any(-1), pg, 0)
    return target, shift, written[:, :, None, :, None]


def _merge_pages(pool, layer, plan, rows):
    """``pool`` ``[L, P, Hkv, page, hd]`` with the block's ``rows`` ``[S,
    C, Hkv, hd]`` written into layer ``layer`` page by page: each page
    the block touches is read, its written positions replaced, and put
    back whole — ``S·n_pg`` contiguous updates where a row scatter makes
    ``S·C·Hkv``."""
    target, shift, written = plan
    S, C, Hkv, hd = rows.shape
    n_pg, page = target.shape[1], pool.shape[3]
    R = n_pg * page
    # row j of the block to position shift + j of the page run
    padded = jnp.pad(rows, ((0, 0), (page - 1, R - C), (0, 0), (0, 0)))
    run = jax.vmap(
        lambda x, o: jax.lax.dynamic_slice_in_dim(x, page - 1 - o, R, 0)
    )(padded, shift)
    new = run.reshape(S, n_pg, page, Hkv, hd).transpose(0, 1, 3, 2, 4)
    old = pool[layer, target]  # [S, n_pg, Hkv, page, hd]
    return pool.at[layer, target].set(jnp.where(written, new, old))


def _cache_kv(cache: PagedKVCache) -> tuple:
    """The layer-stacked KV tuple the layer loops carry — ``(k, v)``
    plain, ``(k, v, k_scale, v_scale)`` in int8 mode; the blocks branch
    on the tuple arity (a trace-time constant). A patterned model's
    cache carries its pools per kind (engine/latent.py)."""
    if isinstance(cache, LatentPagedCache):
        return cache_pools(cache)
    if cache.k_scale is None:
        return (cache.k, cache.v)
    return (cache.k, cache.v, cache.k_scale, cache.v_scale)


def _with_kv(cache: PagedKVCache, kv: tuple, **kw) -> PagedKVCache:
    """Rebuild the cache from the KV tuple a layer loop carried (inverse
    of :func:`_cache_kv`)."""
    if isinstance(cache, LatentPagedCache):
        return with_pools(cache, kv, **kw)
    if len(kv) == 4:
        return replace(
            cache, k=kv[0], v=kv[1], k_scale=kv[2], v_scale=kv[3], **kw
        )
    return replace(cache, k=kv[0], v=kv[1], **kw)


# tlint: hot-path
def _scatter_kv(cache_kv: tuple, layer, write_pg, write_off, k, v,
                plan=None) -> tuple:
    """THE one page-write path's scatter: land this block's KV rows at
    their ``(layer, page, offset)`` targets in the layer-stacked pools
    ``[L, P, Hkv, page, hd]``, across every program. In quantized
    mode this is the single quantize site — each position's row quantizes
    independently (per-(position, head) scale over ``head_dim``,
    models/quant.py::quantize_kv — or ``quantize_kv4`` when the pages are
    PACKED int4, detected by the page dim being half the row's), which is
    exactly what keeps chunk framing, COW and promotion byte-exact under
    quantization. ``k``/``v`` are ``[..., Hkv, hd]`` with leading dims
    matching ``write_pg``.

    The pools are the ones the layer loop carries, written in place: no
    layer's pool is cut out of the stack or put back. A block of single
    positions (the decode step: ``S·Hkv`` rows) is a row scatter. A block
    of ``C`` positions a slot comes with its ``plan``
    (:func:`_page_write_plan`) and is written page by page
    (:func:`_merge_pages`): the ``S·C·Hkv`` = 8,192 row updates of 128
    bytes into HBM took 40 ms of a 91-ms ragged pass on a v5e; as the 72
    pages they lie in, the pass takes 52 ms (PERF.md section 6, PR 29)."""
    # the layer and every kv head are named in the index, so each
    # scattered update is one contiguous ``[hd]`` row of the kernel's
    # kv-head-major page layout. Slicing the head axis instead
    # (``.at[pg, :, off]``) makes the TPU compiler re-lay the whole page
    # pool position-major for the scatter and back for the Pallas call —
    # a second copy of the pool per step.
    idx = (
        write_pg[..., None], jnp.arange(cache_kv[0].shape[2]),
        write_off[..., None],
    )

    def payload(pool, rows):
        if plan is None:
            return pool.at[(layer,) + idx].set(rows)
        return _merge_pages(pool, layer, plan, rows)

    if len(cache_kv) == 4:
        ck, cv, cks, cvs = cache_kv
        quant = _quant_kv4 if ck.shape[-1] != k.shape[-1] else _quant_kv
        k8, ks = quant(k)
        v8, vs = quant(v)

        # a block's scales go through the layer's own plane (1 MB where
        # a pool is 34): scattered straight into the stacked planes, the
        # TPU compiler moved all L layers of them to fast memory and back
        # a layer-call of the ragged pass (cross-compile, PERF.md, PR 29).
        # The plane is scattered FLAT, one index a scale: by (page, head,
        # offset) the scatter's own layout reached the loop's carry, and
        # at a block 16 rows wide with one kv head a chip the compiler
        # carried the stack layer-minor, every layer's plane cut out of
        # and put back into every tile of it (tests/test_chip_compile.py,
        # PERF.md section 6, PR 35)
        def planes(pool, s):
            if plan is None:
                return pool.at[(layer,) + idx].set(s)
            plane = jax.lax.dynamic_index_in_dim(pool, layer, 0, False)
            _, n_kv, page = plane.shape
            flat = (idx[0] * n_kv + idx[1]) * page + idx[2]  # [S, C, Hkv]
            new = plane.reshape(-1).at[flat.reshape(-1)].set(s.reshape(-1))
            return jax.lax.dynamic_update_index_in_dim(
                pool, new.reshape(plane.shape), layer, 0
            )

        return (
            payload(ck, k8), payload(cv, v8), planes(cks, ks), planes(cvs, vs)
        )
    ck, cv = cache_kv
    return payload(ck, k.astype(ck.dtype)), payload(cv, v.astype(cv.dtype))


def _paged_attend(kernel_fn, ref_fn, kernel: bool, q, kv: tuple, layer,
                  *ctl, scale: float):
    """Attention of ``q`` over layer ``layer`` of the carried pools
    ``kv``, through ``kernel_fn`` or the reference ``ref_fn`` (``ctl``:
    the block tables and what places the queries). The kernel takes the
    stack and the layer's index and copies the live pages out of it
    itself; the reference gets the layer's pools indexed out, as does
    the kernel for plain pages stored in another dtype than the queries'
    (the cast would otherwise be of every layer)."""
    scales = {}
    if len(kv) == 4:
        scales = {"k_scale": kv[2], "v_scale": kv[3]}
    if kernel and (scales or kv[0].dtype == q.dtype):
        return kernel_fn(
            q, kv[0], kv[1], *ctl, scale=scale, layer=layer, **scales
        )
    k, v = kv[0][layer], kv[1][layer]
    if scales:
        scales = {n: a[layer] for n, a in scales.items()}
    else:
        k, v = k.astype(q.dtype), v.astype(q.dtype)
    return (kernel_fn if kernel else ref_fn)(
        q, k, v, *ctl, scale=scale, **scales
    )


def _paged_block(x, lp, layer, cfg: ModelConfig, cos, sin, cache_kv,
                 write_pg, write_off, att_len, block_tables, kernel: bool,
                 tp_axis: str | None = None, tp_quant: bool = False):
    """One transformer block over a slot batch of single tokens (T=1),
    reading/writing KV through pages. Mirrors transformer.py::_block's
    projection/norm/residual structure exactly (via the shared
    prologue/epilogue above) — the parity tests pin the two paths
    token-for-token — but swaps the contiguous-cache dynamic_update_slice
    for a flat page scatter and the masked einsum for paged attention."""
    with jax.named_scope("attn"):
        h = x if cfg.norm_position == "post" else _norm(x, lp["ln1"], cfg)
        q, k, v = _paged_qkv(h, lp, cfg, cos, sin)  # [S, 1, H, hd]

    # per-slot scatter of the new token's KV through THE one write path
    # (quantizes in int8 mode); cache_kv is every layer's pages, written
    # and read at ``layer``
    with jax.named_scope("kv_write"):
        kv = _scatter_kv(
            cache_kv, layer, write_pg, write_off, k[:, 0], v[:, 0]
        )
    with jax.named_scope("attn"):
        attn_raw = _paged_attend(
            paged_attention, paged_attention_ref, kernel, q[:, 0], kv,
            layer, block_tables, att_len, scale=_attn_scale(cfg),
        )[:, None]  # [S, 1, Hq, hd]
    return _paged_residual(x, attn_raw, lp, cfg, tp_axis, tp_quant), kv


def _scan_layers(params, x, cache: PagedKVCache, block):
    """The layer loop of both passes of a dense GQA model: ``block(x, lp,
    layer, kv) -> (x, kv)`` over the stacked layer parameters, the page
    pools CARRIED whole beside the activations. The one-kind case of the
    loop every model runs (``engine/latent.py::layer_loop``: no lead
    layers, a period of one layer, no tail). Returns ``(x, kv)``."""
    return layer_loop(
        (), (params["layers"],), (),
        Pattern((), ("gqa",), cache.k.shape[0], ()), x, _cache_kv(cache),
        lambda x, lp, _kind, layer, kv: block(x, lp, layer, kv),
    )


def _final_norm(x, params, cfg: ModelConfig):
    """The final norm and, where the model has one, the divisor of what
    goes to the head (MiniCPM: ``hidden_size / dim_model_base``)."""
    x = _norm(x, params["final_norm"], cfg)
    if cfg.logit_div != 1.0:
        x = x / jnp.asarray(cfg.logit_div, x.dtype)
    return x


# tlint: hot-path
def _decode_step_impl(
    params,
    tok: jax.Array,
    cache: PagedKVCache,
    active: jax.Array,
    cfg: ModelConfig,
    kernel: bool,
    tp_axis: str | None = None,
    tp_quant: bool = False,
):
    """Unjitted body of :func:`paged_decode_step` — also traced inside
    the tensor-parallel shard_map (:func:`make_tp_ragged_step`), where
    ``tp_axis`` names the mesh axis the weights/KV-heads are split over
    and the blocks gather activations back to full width."""
    S = tok.shape[0]
    lengths = cache.lengths
    page = cache.page_size
    n_pp = cache.pages_per_slot
    # physical write position for each slot's new token via the shared
    # ragged write path (C=1, n_valid=active); free slots have a zeroed
    # block-table row and length 0 → scratch page 0
    write_pg, write_off, _, _ = _ragged_write_indices(
        cache.block_tables, lengths, active.astype(jnp.int32), page, n_pp, 1
    )
    write_pg = write_pg[:, 0]
    write_off = write_off[:, 0]
    att_len = jnp.where(active, lengths + 1, 0)

    x = _embed_tokens(params, tok[:, None], cfg)  # [S, 1, d]
    positions = lengths[:, None]
    if cfg.pos == "learned":
        x = x + params["embed"]["pos"][positions].astype(cfg.dtype)
    cos = sin = None
    if cfg.pos == "rope":
        cos, sin = rope_tables(positions, _rope_dim(cfg), cfg.rope_theta)

    if cfg.patterned:
        x, kv_new = decode_layers(
            params, x, cache, cfg, kernel, positions=positions,
            active=active, write_pg=write_pg, write_off=write_off,
            att_len=att_len,
        )
    else:
        x, kv_new = _scan_layers(
            params, x, cache,
            lambda x, lp, layer, kv: _paged_block(
                x, lp, layer, cfg, cos, sin, kv, write_pg, write_off,
                att_len, cache.block_tables, kernel, tp_axis, tp_quant,
            ),
        )
    x = _final_norm(x, params, cfg)
    logits = _logits(params, x, cfg, tp_axis, tp_quant)[:, 0]
    new_cache = _with_kv(
        cache, kv_new, lengths=jnp.where(active, lengths + 1, lengths)
    )
    return logits, new_cache


# tlint: hot-path  # tlint: one-program
@partial(
    jax.jit, static_argnames=("cfg", "kernel"), donate_argnames=("cache",)
)
def paged_decode_step(
    params,
    tok: jax.Array,  # int32 [S] — each slot's last token
    cache: PagedKVCache,
    active: jax.Array,  # bool [S] — slots holding a live request
    cfg: ModelConfig,
    kernel: bool = False,
):
    """ONE fixed-shape decode step over every serving slot. Returns
    ``(logits [S, V], cache)`` with each active slot's new KV written to
    its pages and its length advanced by one.

    This is the continuous-batching engine's only decode program: its
    shape depends on (max_slots, model) alone — never on the request mix —
    so the compiled set stays at exactly one entry per engine (asserted by
    tests/test_continuous.py). Free slots write their masked token to the
    scratch page and attend over nothing (length 0 → zero row)."""
    return _decode_step_impl(params, tok, cache, active, cfg, kernel)


def _decode_loop_body(params, seeds, temp, top_k, top_p, pres, freq, eos,
                      cfg: ModelConfig, kernel: bool,
                      tp_axis: str | None = None, tp_quant: bool = False):
    """The decode-continuation while_loop body of ``paged_ragged_step``
    (one fixed-shape slot decode step + in-program sampling per
    iteration). A slot that finishes mid-chunk (EOS / budget) freezes:
    its length stops advancing, it re-feeds its own token, and its
    per-slot key index stops — so the emitted stream is BIT-IDENTICAL to
    stepping one token at a time, which is what keeps the
    solo/co-batched/recovery parity contract intact. Tokens land at each
    slot's OWN column cursor (``col``): a speculating slot's verify pass
    may have emitted several tokens in the ragged block, so the
    continuation appends after them instead of at a shared step index
    (frozen slots re-write their token at a column the host never reads —
    delivery stops at the per-slot token count)."""
    from .continuous import _row_keys, _sample_rows

    S = seeds.shape[0]
    rows = jnp.arange(S)

    def body(st):
        i, tok, cache, done, steps, counts, remaining, col, tokens = st
        if tp_axis is None:
            logits, cache = paged_decode_step(
                params, tok, cache, ~done, cfg, kernel
            )
        else:  # already inside the TP shard_map — trace the body inline
            logits, cache = _decode_step_impl(
                params, tok, cache, ~done, cfg, kernel, tp_axis, tp_quant
            )
        keys = _row_keys(seeds, steps)
        nxt = _sample_rows(
            logits, keys, temp, top_k, top_p, pres, freq, counts
        )
        nxt = jnp.where(done, tok, nxt)  # frozen slots re-feed their token
        live = (~done).astype(jnp.int32)
        counts = counts.at[rows, nxt].add(live)
        steps = steps + live
        remaining = remaining - live
        done = done | (nxt[:, None] == eos).any(-1) | (remaining <= 0)
        tokens = tokens.at[
            rows, jnp.minimum(col, tokens.shape[1] - 1)
        ].set(nxt)
        return (
            i + 1, nxt, cache, done, steps, counts, remaining,
            col + live, tokens,
        )

    return body


# tlint: hot-path
def _verify_emit(blk, logits_v, base, n_spec, emit, seeds, steps, temp,
                 top_k, top_p, pres, freq, counts, remaining, eos):
    """The unified step's sampling epilogue, generalized to speculative
    verification — the in-program acceptance walk over each slot's
    gathered verification rows ``[S, W]``.

    Row ``j`` of a speculating slot holds the logits of block row
    ``base + j`` (absolute position ``start + base + j``) — the model's
    view AFTER the draft tokens up to that row were scattered — so the
    draw at row ``j`` with key ``fold_in(seed, steps + j)`` is EXACTLY
    the draw sequential decode would make at that step, provided every
    earlier draft matched its draw. The walk therefore accepts the
    longest prefix of drafts whose tokens equal their own-row draws and
    emits ONE extra token (the correction on a reject, the bonus draw
    when every draft matched), updating the penalty histogram, key index
    and budget per accepted token so the RNG/penalty state after the
    pass equals the sequential state bit-for-bit. A non-speculating slot
    (``n_spec == 0``) walks exactly one row — its last valid row — which
    reduces to the plain single-draw epilogue, token for token.

    The walk is as long as the longest draft of the block, as data:
    ``max(n_spec over emitting slots) + 1`` rows (none when nothing
    emits), a ``while_loop`` with a traced bound. Every row past that
    bound finds every slot ``stopped``: it would emit 0 and leave the
    carry as it was, so leaving it out changes no output. A block of
    plain decodes walks one row whatever ``W`` is. The traced bound also
    keeps the phase a loop of the lowered program at any ``W``
    (``STEP_PHASES``: a trace tells the phases apart by loop order).

    Returns ``(tokens [S, W], last, m, ended, counts, steps, remaining)``
    where ``m`` is each slot's emitted count this pass (the verify-pass
    amortization the kill switch measures) and ``ended`` marks slots
    that hit EOS or their budget INSIDE the pass."""
    S, W, _V = logits_v.shape
    rows = jnp.arange(S)
    # the draft token draw j must match to be accepted: the NEXT packed
    # block row's token (clamped gather; masked by j < n_spec)
    j_idx = jnp.arange(W)[None, :]
    nxt_rows = jnp.clip(base[:, None] + j_idx + 1, 0, blk.shape[1] - 1)
    draft_next = jnp.take_along_axis(blk, nxt_rows, axis=1)  # [S, W]
    has_draft = j_idx < n_spec[:, None]  # [S, W]
    n_walk = jnp.minimum(jnp.max(jnp.where(emit, n_spec + 1, 0)), W)

    from .continuous import _row_keys, _sample_rows

    def vstep(st):
        j, counts, steps, remaining, stopped, ended, last, m, toks = st
        lg, dnext, hd = (
            jax.lax.dynamic_index_in_dim(a, j, 1, keepdims=False)
            for a in (logits_v, draft_next, has_draft)
        )
        keys = _row_keys(seeds, steps)
        t = _sample_rows(lg, keys, temp, top_k, top_p, pres, freq, counts)
        live = emit & ~stopped
        liv32 = live.astype(jnp.int32)
        t = jnp.where(live, t, 0)
        counts = counts.at[rows, t].add(liv32)
        steps = steps + liv32
        remaining = remaining - liv32
        end_now = live & ((t[:, None] == eos).any(-1) | (remaining <= 0))
        # accept: this row's draw reproduced the next draft token, so the
        # already-scattered KV at that position is the TRUE token's KV
        # and the walk may trust the next row's logits
        accept = live & hd & (dnext == t) & ~end_now
        last = jnp.where(live, t, last)
        m = m + liv32
        ended = ended | end_now
        stopped = stopped | (live & ~accept)
        toks = jax.lax.dynamic_update_index_in_dim(toks, t, j, 1)
        return (
            j + 1, counts, steps, remaining, stopped, ended, last, m, toks,
        )

    init = (
        jnp.int32(0), counts, steps, remaining, ~emit,
        jnp.zeros_like(emit), jnp.zeros(S, jnp.int32),
        jnp.zeros(S, jnp.int32), jnp.zeros((S, W), jnp.int32),
    )
    with jax.named_scope(VERIFY_EMIT):
        _j, counts, steps, remaining, _stopped, ended, last, m, toks = (
            jax.lax.while_loop(lambda st: st[0] < n_walk, vstep, init)
        )
    return toks, last, m, ended, counts, steps, remaining


FLAT_ROW_TILE = 128  # the flat rung's row count is a multiple of this


def flat_rung_rows(slots: int, chunk: int, spec_width: int = 1) -> int:
    """Rows of the flat rung of a ``[slots, chunk]`` geometry, from the
    shapes alone: the smallest multiple of ``FLAT_ROW_TILE`` that is at
    least a quarter of the block and holds one full grant beside every
    slot's decode row and drafts. 0: no such rung (it would compute as
    many rows as the block holds)."""
    need = max(-(-slots * chunk // 4), chunk + slots * spec_width)
    rows = -(-need // FLAT_ROW_TILE) * FLAT_ROW_TILE
    return rows if rows < slots * chunk else 0


ROW_TILE = 512  # rows of one trip of the tiled pass, at most


def row_tile(slots: int, chunk: int) -> int:
    """Rows of one trip of the tiled pass over a ``[slots, chunk]``
    geometry, from the shapes alone: a quarter of the block in whole
    eights (a block with every row live streams a layer's position-wise
    weights four times), at most ``ROW_TILE`` (where a trip's dots hide
    the next stream of the weights on a v5e: PERF.md section 6, PR 53)."""
    return min(ROW_TILE, max(slots * chunk // 32 * 8, 8))


def tiled_rows(slots: int, chunk: int) -> int:
    """Rows of the tiled pass's row list: what holds the whole block, in
    whole tiles of :func:`row_tile`."""
    tile = row_tile(slots, chunk)
    return -(-slots * chunk // tile) * tile


class FlatRows(NamedTuple):
    """The ragged pass's live rows as one token-major list of ``R`` rows:
    slot 0's grant (or its decode row and drafts), then slot 1's, back to
    back; rows past the chunk's ``n_valid.sum()`` carry nothing. Computed
    in the program from ``n_valid`` (a cumulative sum), so the control
    buffer holds the ``[S, C]`` block as ever. Position-wise work runs
    over ``[1, R, ...]``; the two seams that need a slot (the attention
    call, the page write) take ``expand``'s ``[S, C, ...]`` and hand
    their result to ``collect``. With a ``tile`` the list holds the whole
    block and the row count is data: position-wise work goes through
    :meth:`by_tile`, ``trips`` tiles of it."""

    to_flat: jax.Array  # int32 [S, C]: block row -> flat row; R: no token
    slot: jax.Array  # int32 [R]: flat row -> its slot ...
    col: jax.Array  # int32 [R]: ... and its column in the block
    live: jax.Array  # bool [R]: the flat row carries a token
    trips: jax.Array | None = None  # int32: tiles that hold a live row
    tile: int = 0  # rows of a tile (0: the list is computed whole)

    @classmethod
    def of(cls, n_valid: jax.Array, C: int, R: int,
           tile: int = 0) -> "FlatRows":
        S = n_valid.shape[0]
        end = jnp.cumsum(n_valid)
        first = end - n_valid
        j = jnp.arange(C)[None, :]
        to_flat = jnp.where(j < n_valid[:, None], first[:, None] + j, R)
        r = jnp.arange(R)
        slot = jnp.minimum((r[:, None] >= end[None, :]).sum(-1), S - 1)
        col = jnp.clip(r - first[slot], 0, C - 1)
        trips = (end[-1] + tile - 1) // tile if tile else None
        return cls(to_flat, slot, col, r < end[-1], trips, tile)

    def by_tile(self, fn, *xs, axis: int = 1):
        """``fn(*xs)`` for a position-wise ``fn`` (row ``r`` of every
        result reads row ``r`` of each of ``xs`` and nothing else of them;
        rows lie along ``axis``: ``[1, R, ...]``; a tree of row lists
        back): the list whole without a ``tile``, else tile by tile over
        the ``trips`` tiles that hold a live row, a loop whose trip count
        is data. The rows past them are not computed (zeros) and nothing
        reads them."""
        if not self.tile:
            return fn(*xs)
        T, R = self.tile, self.live.shape[0]
        cut = partial(jax.lax.dynamic_slice_in_dim, slice_size=T, axis=axis)
        rows = lambda a, n: a.shape[:axis] + (n,) + a.shape[axis + 1:]
        like = jax.eval_shape(
            fn, *(jax.ShapeDtypeStruct(rows(a, T), a.dtype) for a in xs))

        def trip(i, out):
            return jax.tree.map(
                lambda o, y: jax.lax.dynamic_update_slice_in_dim(
                    o, y, i * T, axis),
                out, fn(*(cut(a, i * T) for a in xs)))

        out = jax.tree.map(lambda s: jnp.zeros(rows(s, R), s.dtype), like)
        return jax.lax.fori_loop(0, self.trips, trip, out)

    def take(self, a: jax.Array, idx: jax.Array) -> jax.Array:
        """Rows ``idx`` of the flat ``a`` ``[1, R, ...]``; zeros where
        ``idx`` names no row (a block row without a token)."""
        return a[0].at[idx].get(mode="fill", fill_value=0)

    def expand(self, a: jax.Array) -> jax.Array:
        """``[1, R, ...]`` -> ``[S, C, ...]``, one gather."""
        return self.take(a, self.to_flat)

    def collect(self, a: jax.Array) -> jax.Array:
        """``[S, C, ...]`` -> ``[1, R, ...]``, one gather (a dead flat
        row reads a live one's value; nothing reads it back)."""
        return a[self.slot, self.col][None]


def _ragged_block(x, lp, layer, cfg: ModelConfig, cos, sin, cache_kv, plan,
                  write_pg, write_off, block_tables, starts, n_valid,
                  kernel: bool, tp_axis: str | None = None,
                  tp_quant: bool = False, rows: FlatRows | None = None):
    """One transformer block over the ragged ``[S, C]`` token block,
    reading/writing KV through every slot's pages at once. Shares
    ``_paged_block``'s prologue/epilogue (scatter-then-attend order
    preserved) but carries the whole mixed prefill+decode block: a
    decode slot's single token and a mid-prefill slot's chunk go through
    the SAME projection, the SAME page scatter and the SAME ragged
    attention — the kernel-level erasure of the prefill/decode split.
    On the flat rung (``rows``) ``x`` is the live rows ``[1, R, d]``:
    q/k/v are projected from them and go to ``[S, C, ...]`` for the page
    write and the attention call, whose output comes back flat for
    ``wo`` and the MLP."""
    with jax.named_scope("attn"):
        h = x if cfg.norm_position == "post" else _norm(x, lp["ln1"], cfg)
        q, k, v = _paged_qkv(h, lp, cfg, cos, sin)  # [S, C, H, hd]
        if rows is not None:
            q, k, v = rows.expand(q), rows.expand(k), rows.expand(v)

    # block scatter through the one write path (quantizes in int8 mode):
    # position (s, j) lands at (write_pg[s, j], write_off[s, j]); padding
    # rows and idle slots land on scratch page 0, unreachable from any
    # block table
    with jax.named_scope("kv_write"):
        kv = _scatter_kv(cache_kv, layer, write_pg, write_off, k, v, plan)
    with jax.named_scope("attn"):
        attn_raw = _paged_attend(
            ragged_paged_attention, ragged_paged_attention_ref, kernel, q,
            kv, layer, block_tables, starts, n_valid,
            scale=_attn_scale(cfg),
        )  # [S, C, Hq, hd]
        if rows is not None:
            attn_raw = rows.collect(attn_raw)  # [1, R, Hq, hd]
    return _paged_residual(x, attn_raw, lp, cfg, tp_axis, tp_quant), kv


# tlint: hot-path
def _ragged_pass(
    params, blk, cache, starts, n_valid, n_spec,
    cfg: ModelConfig, spec_width: int, kernel: bool,
    tp_axis: str | None = None, tp_quant: bool = False,
    flat_rows: int = 0,
):
    """The step's first phase: every layer over the packed ``[S, C]``
    block through the pages, then the vocabulary head over each slot's
    verification rows. Returns ``(logits_v [S, W, V], base, kv_new)``;
    the caller advances the lengths.

    ``flat_rows`` = ``R`` > 0 (the flat rung): the residual stream is the
    chunk's live rows ``[1, R, d]`` (:class:`FlatRows`; the chunk holds no
    more, which the host saw to), so embedding, norms, projections, rope,
    MLP and experts cost ``R`` rows whatever ``S x C`` is; only the page
    write and the attention call see ``[S, C, ...]``. 0: rows are
    computed where they lie in the block."""
    S, C = blk.shape
    page = cache.page_size
    n_pp = cache.pages_per_slot
    bt = cache.block_tables
    with jax.named_scope(RAGGED_PASS):
        write_pg, write_off, pos, valid = _ragged_write_indices(
            bt, starts, n_valid, page, n_pp, C
        )
        plan = _page_write_plan(bt, starts, n_valid, page, n_pp, C)

        rows, tok, positions = None, blk, pos
        if flat_rows:
            # a patterned model's list that holds the whole block is
            # computed tile by tile, as far as the chunk's live rows reach
            tile = row_tile(S, C) if (
                cfg.patterned and flat_rows >= S * C) else 0
            rows = FlatRows.of(n_valid, C, int(flat_rows), tile)
            tok = jnp.where(rows.live, blk[rows.slot, rows.col], 0)[None]
            positions = (starts[rows.slot] + rows.col)[None]  # [1, R]
        tiled = rows is not None and rows.tile > 0
        # (the tiled pass embeds its whole list too, a gather: a loop over
        # the tokens alone is invariant of ``ragged_layers``' one loop, the
        # compiler moves it in front of it, and a trace tells the phases
        # apart by the order of the top-level loops)
        x = _embed_tokens(params, tok, cfg)  # [S, C, d], or [1, R, d]
        if cfg.pos == "learned":
            x = x + params["embed"]["pos"][positions].astype(cfg.dtype)
        cos = sin = None
        if cfg.pos == "rope":
            cos, sin = rope_tables(positions, _rope_dim(cfg), cfg.rope_theta)

        if cfg.patterned:
            x, kv_new = ragged_layers(
                params, x, cache, cfg, kernel, positions=pos, valid=valid,
                plan=plan, n_valid=n_valid, rows=rows,
                rope_positions=None if rows is None else positions,
            )
        else:
            x, kv_new = _scan_layers(
                params, x, cache,
                lambda x, lp, layer, kv: _ragged_block(
                    x, lp, layer, cfg, cos, sin, kv, plan, write_pg,
                    write_off, bt, starts, n_valid, kernel, tp_axis,
                    tp_quant, rows,
                ),
            )
        if not tiled:  # (the tiled pass: over the rows the head reads)
            x = _final_norm(x, params, cfg)
    # verification rows: the last spec_width rows of each slot's valid
    # span — base = n_valid - 1 - n_spec, so a non-speculating slot
    # (n_spec 0: plain decode, completing prefill, idle) gathers exactly
    # its last valid row at walk index 0 and the epilogue reduces to the
    # plain single draw. The vocab head runs over [S, W] rows only —
    # never the whole [S, C] block (idle slots read row 0: garbage,
    # masked out of sampling by `emit`).
    W = int(spec_width)
    base = jnp.maximum(n_valid - 1 - n_spec, 0)
    gather = jnp.minimum(
        base[:, None] + jnp.arange(W)[None, :],
        jnp.maximum(n_valid - 1, 0)[:, None],
    )  # [S, W]
    with jax.named_scope(RAGGED_PASS):
        if rows is None:
            h_v = x[jnp.arange(S)[:, None], gather]  # [S, W, d]
        else:  # the same block rows, where the flat list holds them
            h_v = rows.take(x, jnp.take_along_axis(rows.to_flat, gather, 1))
        if tiled:  # position-wise: the norm of a row taken is the row's
            h_v = _final_norm(h_v, params, cfg)
        logits_v = _logits(params, h_v, cfg, tp_axis, tp_quant)  # [S, W, V]
    return logits_v, base, kv_new


# A chunk crosses the host-device boundary as ONE array each way
# (docs/SERVING.md "The anatomy of a chunk"). In: ``int32 [S, C +
# CTL_COLS + n_pp]``, the packed token block's ``C`` columns, then one
# column for each of ``CTL_INTS`` (``emit``, ``bind`` and ``reset`` as 0 /
# 1), the ``EOS_WIDTH`` EOS ids, the bits of the four float32 knobs
# ``CTL_FLOATS`` and last the ``n_pp`` columns of the table rows that
# ``bind`` marks (``pages_per_slot`` of the cache the program is called
# with; none where a caller packs no rows). Out: ``int32 [S, n_steps +
# spec_width - 1 + 3 (+ the step's own counts)]``, see
# :func:`pack_results`. :class:`Control` names the rows in the order both
# packers take them.
CTL_INTS = (
    "starts", "n_valid", "n_spec", "emit", "seeds", "steps", "top_k",
    "remaining", "bind", "bind_len", "reset",
)
CTL_FLOATS = ("temp", "top_p", "pres", "freq")
EOS_WIDTH = 8  # EOS ids a slot carries INTO the program (pad with -1)
CTL_COLS = len(CTL_INTS) + EOS_WIDTH + len(CTL_FLOATS)
_CTL_FLAGS = ("emit", "bind", "reset")  # bool in the program, 0 / 1 packed


class Control(NamedTuple):
    """A chunk's control rows, as the step's phases read them."""

    blk: Any  # int32 [S, C]: packed ragged token block (0-padded)
    starts: Any  # int32 [S]: absolute position of blk[s, 0]
    n_valid: Any  # int32 [S]: valid tokens per slot (0 = idle)
    n_spec: Any  # int32 [S]: draft tokens per slot (rows 1..n_spec)
    emit: Any  # bool [S]: slot samples from its last valid row
    seeds: Any  # int32 [S]: per-slot RNG seeds
    steps: Any  # int32 [S]: per-slot next draw index
    temp: Any  # f32 [S] sampling knobs ...
    top_k: Any  # int32 [S]
    top_p: Any  # f32 [S]
    pres: Any  # f32 [S]
    freq: Any  # f32 [S]
    remaining: Any  # int32 [S]: tokens still wanted per slot
    eos: Any  # int32 [S, <= EOS_WIDTH]: per-slot EOS ids (pad with -1)
    # what admissions and retirements since the last chunk changed, applied
    # by the program before its ragged pass (left out: nothing changed)
    bind: Any = None  # bool [S]: the slot takes bind_rows[s] and bind_len[s]
    bind_len: Any = None  # int32 [S]: the length a bound slot starts at
    reset: Any = None  # bool [S]: the slot's histogram starts at zero
    bind_rows: Any = None  # int32 [S, n_pp]: table rows (0 = scratch page)


# tlint: hot-path
def pack_control(blk, starts, n_valid, n_spec, emit, seeds, steps, temp,
                 top_k, top_p, pres, freq, remaining, eos, bind=None,
                 bind_len=None, reset=None, bind_rows=None, *,
                 pages_per_slot: int = 0) -> np.ndarray:
    """The step program's one control operand from a chunk's rows
    (:class:`Control`'s fields, in order), on the host: the float knobs
    ride as their float32 bits, so every value comes out of
    :func:`unpack_control` as it went in. A caller with no slot to bind
    leaves the last four out and names the table's width
    (``pages_per_slot``: the program splits the buffer by its cache's)."""
    S, C = np.shape(blk)
    n_pp = int(pages_per_slot) if bind_rows is None else np.shape(bind_rows)[1]
    off = np.zeros(S, np.int32)
    rows = Control(
        blk, starts, n_valid, n_spec, emit, seeds, steps, temp, top_k,
        top_p, pres, freq, remaining, eos,
        off if bind is None else bind, off if bind_len is None else bind_len,
        off if reset is None else reset, bind_rows,
    )
    n_eos = np.shape(eos)[1]
    if n_eos > EOS_WIDTH:
        raise ValueError(f"{n_eos} EOS ids a slot, over {EOS_WIDTH}")
    ctl = np.empty((S, C + CTL_COLS + n_pp), np.int32)
    ctl[:, :C] = blk
    for i, name in enumerate(CTL_INTS):
        ctl[:, C + i] = getattr(rows, name)
    col = C + len(CTL_INTS)
    ctl[:, col + n_eos : col + EOS_WIDTH] = -1
    ctl[:, col : col + n_eos] = eos
    knobs = np.empty((S, len(CTL_FLOATS)), np.float32)
    for i, name in enumerate(CTL_FLOATS):
        knobs[:, i] = getattr(rows, name)
    ctl[:, col + EOS_WIDTH : C + CTL_COLS] = knobs.view(np.int32)
    ctl[:, C + CTL_COLS :] = 0 if bind_rows is None else bind_rows
    return ctl


# tlint: hot-path
def unpack_control(ctl: jax.Array, pages_per_slot: int = 0) -> Control:
    """:func:`pack_control`'s inverse inside the program: slices and four
    bitcasts, no loop. ``pages_per_slot``: the width of the table rows at
    the buffer's end."""
    C = ctl.shape[1] - CTL_COLS - int(pages_per_slot)
    ints = {n: ctl[:, C + i] for i, n in enumerate(CTL_INTS)}
    for n in _CTL_FLAGS:
        ints[n] = ints[n] != 0
    col = C + len(CTL_INTS)
    floats = {
        n: jax.lax.bitcast_convert_type(
            ctl[:, col + EOS_WIDTH + i], jnp.float32
        )
        for i, n in enumerate(CTL_FLOATS)
    }
    return Control(
        blk=ctl[:, :C], eos=ctl[:, col : col + EOS_WIDTH],
        bind_rows=ctl[:, C + CTL_COLS :], **ints, **floats
    )


# tlint: hot-path
def pack_results(tokens, n_tok, spec_m, n_exec, stats=None) -> jax.Array:
    """The step program's one result besides its resident state:
    ``int32 [S, T + 3 (+ len(stats))]`` of ``tokens [S, T]``, ``n_tok``,
    ``spec_m``, ``n_exec`` broadcast down a column and, for a patterned
    model, the step's own counts (``cache.stats``) broadcast down one
    column each."""
    S = tokens.shape[0]
    cols = [tokens, n_tok[:, None], spec_m[:, None],
            jnp.broadcast_to(n_exec, (S, 1))]
    if stats is not None:
        cols.append(jnp.broadcast_to(stats[None, :], (S, stats.shape[0])))
    return jnp.concatenate(cols, axis=1)


# tlint: hot-path
def unpack_results(out: np.ndarray, n_steps: int, spec_width: int):
    """:func:`pack_results`' inverse on the host's copy: ``(tokens,
    n_tok, spec_m, n_exec, stats)``; ``stats`` is empty where the
    program packed none."""
    T = int(n_steps) + int(spec_width) - 1
    return (out[:, :T], out[:, T], out[:, T + 1], int(out[0, T + 2]),
            out[0, T + 3 :])


# tlint: hot-path
def _ragged_step_impl(
    params, ctl, cache, counts,
    cfg: ModelConfig, n_steps: int, spec_width: int, kernel: bool,
    tp_axis: str | None = None, tp_quant: bool = False,
    flat_rows: int = 0,
):
    """Unjitted body of :func:`paged_ragged_step` — also traced inside
    the tensor-parallel shard_map (:func:`make_tp_ragged_step`). There
    ``params`` holds head-major column slices, the per-layer KV pages
    hold the LOCAL kv heads (axis 2 of ``[L, P, n_kv, page, hd]``), and
    every control-state array (block tables, the packed control rows,
    histograms) is replicated — so the sampling epilogue sees gathered
    full-width logits and draws the SAME token on every shard."""
    (blk, starts, n_valid, n_spec, emit, seeds, steps, temp, top_k, top_p,
     pres, freq, remaining, eos, bind, bind_len, reset,
     bind_rows) = unpack_control(ctl, cache.pages_per_slot)
    S = blk.shape[0]
    W = int(spec_width)
    # admissions and retirements since the last chunk, first: a bound
    # slot's table row and start length, a retired slot's row to the
    # scratch page and its length to 0, a histogram that starts at zero
    # (the host holds all three; engine/continuous.py ``_bind_slot``)
    cache = replace(
        cache,
        block_tables=jnp.where(bind[:, None], bind_rows, cache.block_tables),
        lengths=jnp.where(bind, bind_len, cache.lengths),
    )
    counts = jnp.where(reset[:, None], 0, counts)
    # the program's three phases carry names of their own (STEP_PHASES):
    # each is one top-level loop, in this order, which is how a profiler
    # trace whose events keep no scope still tells them apart
    # (tests/test_step_scopes.py pins names and order)
    logits_v, base, kv_new = _ragged_pass(
        params, blk, cache, starts, n_valid, n_spec, cfg, W, kernel,
        tp_axis, tp_quant, flat_rows,
    )

    toks0, nxt, spec_m, ended, counts, steps, remaining = _verify_emit(
        blk, logits_v, base, n_spec, emit, seeds, steps, temp, top_k,
        top_p, pres, freq, counts, remaining, eos,
    )
    done = ~emit | ended
    # KV unwind at the write seam: a speculating slot's length advances
    # only past its ACCEPTED tokens (spec_m includes the final
    # bonus/correction draw, which — like a plain decode's draw — is not
    # yet written); everything else keeps the full-block advance
    adv = jnp.where((n_spec > 0) & emit, spec_m, n_valid)
    cache = _with_kv(
        cache, kv_new,
        lengths=jnp.where(n_valid > 0, starts + adv, cache.lengths),
    )
    tokens = (
        jnp.zeros((S, n_steps + W - 1), jnp.int32).at[:, :W].set(toks0)
    )

    # decode continuation, starting past the ragged block's step, each
    # slot appending at its own column cursor (the verify pass emitted
    # spec_m tokens there)
    body = _decode_loop_body(
        params, seeds, temp, top_k, top_p, pres, freq, eos, cfg, kernel,
        tp_axis, tp_quant,
    )

    def cond(st):
        return (st[0] < n_steps) & ~st[3].all()

    init = (
        jnp.int32(1), nxt, cache, done, steps, counts, remaining,
        spec_m, tokens,
    )
    with jax.named_scope(DECODE_CONT):
        n_exec, _tok, cache, _done, _steps, counts, _rem, n_tok, tokens = (
            jax.lax.while_loop(cond, body, init)
        )
    out = pack_results(
        tokens, n_tok, spec_m, n_exec,
        cache.stats if cfg.patterned else None,
    )
    return out, cache, counts


# tlint: hot-path  # tlint: one-program
@partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "spec_width", "kernel", "flat_rows"),
    donate_argnames=("cache", "counts"),
)
def paged_ragged_step(
    params,
    ctl: jax.Array,  # int32 [S, C + CTL_COLS + n_pp]: pack_control's
    cache: PagedKVCache,
    counts: jax.Array,  # int32 [S, V] context histograms (penalties)
    cfg: ModelConfig,
    n_steps: int,
    spec_width: int = 1,
    kernel: bool = False,
    flat_rows: int = 0,
):
    """THE serving hot loop's single step function, compiled once a
    rung of the packed block's ladder: one ragged prefill+decode forward over
    the packed ``[S, C]`` token block, then up to ``n_steps - 1`` decode
    continuation steps in the same on-device while_loop — one host
    round trip per chunk, zero scheduling seams between prefilling and
    decoding slots. ``C`` is one of the engine's ``block_widths`` (the
    narrow one when no slot's grant is longer, else ``prefill_chunk``)
    and ``flat_rows`` what the ragged pass computes position-wise: 0, the
    ``S x C`` rows where they lie, or ``R`` (the flat rung of the
    ``prefill_chunk``-wide geometry, :func:`flat_rung_rows`): the chunk's
    live rows as one token-major list ``[1, R, d]`` through embedding,
    norms, projections, rope, MLP, experts and the head's gather, with
    ``[S, C, ...]`` only at the two seams that need a slot, the page
    write and the attention call (:class:`FlatRows`; same kernels, same
    write plan, same pages). One program a rung of the ladder, at most
    three (narrow, flat, full), fixed at construction; the host picks the
    rung from the chunk's ``n_valid`` alone, and no argument's VALUE
    picks a program (what ``# tlint: one-program`` holds callers to).

    The packed block (assembled by the host-side
    ``engine/continuous.py::pack_prefill_budgets`` packing) carries every
    slot's role as DATA: a decode slot contributes its 1 current token at
    ``starts = length``, a mid-prefill slot its next prompt piece, an
    idle slot 0 tokens. Slots with ``emit`` set (decode slots, and
    prefills whose prompt completes in this block) sample their next
    token from their last valid row's logits with the request's own key
    chain and continue through the decode loop; mid-prefill slots
    that didn't finish stay frozen for the rest of the chunk and get
    their next grant at the next step boundary. One compiled program a
    width serves every (prefill/decode mix, prompt length, offset,
    budget split) — asserted in tests/test_continuous.py and
    tests/test_block_width.py. With a quantized
    cache the same program stores int8 pages: the scatter quantizes,
    the kernels dequantize at the fetch.

    **Speculative slots** (``spec_width > 1``, docs/SERVING.md
    "Speculative decoding"): a decoding slot may pack up to
    ``spec_width - 1`` host-drafted tokens as EXTRA valid rows after its
    current token (``n_spec[s]`` of them, DATA like everything else —
    spec/non-spec mixes never recompile). The ragged forward then
    verifies all rows in-program (draft row ``j`` attends ``<= start +
    j`` — the kernel's existing causal ``q_pos`` masking, pinned bitwise
    against sequential decode in tests/test_ops.py), and the
    :func:`_verify_emit` walk accepts the longest draft prefix matching
    the slot's own fold_in draw chain plus one bonus/correction token —
    so speculative streams are bit-identical to plain decode. Rejected
    draft positions hold garbage KV that the length truncation below
    unwinds: ``lengths`` advances only past ACCEPTED tokens (write-then-
    truncate at the one ``_scatter_kv`` write seam — the int8
    payload+scales pairing and page conservation hold mid-rejection
    because the slot already owns every page it wrote), and the next
    pass overwrites the garbage before any mask can reach it.

    The chunk's control rows arrive as ONE operand, ``ctl``
    (:func:`pack_control` on the host, :func:`unpack_control` here). With
    them ride the slots bound and cleared since the last chunk: where
    ``bind`` is set the slot's table row and length are replaced before
    the ragged pass, where ``reset`` is set its histogram starts at zero
    (an admission and a retirement call no program of their own). What
    the host reads of a chunk leaves as ONE result: returns ``(out,
    cache, counts)`` with ``out`` = :func:`pack_results` of ``tokens [S,
    n_steps + spec_width - 1]``, ``n_tok [S]``, ``spec_m [S]`` and
    ``n_exec`` (:func:`unpack_results` on the host's copy): per-slot
    token counts ``n_tok`` replace the old shared column convention
    (column 0..n_tok[s]-1 hold slot ``s``'s draws), and ``spec_m`` is
    the ragged pass's emitted count (the tokens-per-verify-pass signal
    the engine's kill switch consumes)."""
    return _ragged_step_impl(
        params, ctl, cache, counts, cfg, n_steps, spec_width, kernel,
        flat_rows=flat_rows,
    )


def tp_cache_specs(quantized: bool, axis: str = "tp") -> "PagedKVCache":
    """PartitionSpec pytree for a tensor-parallel :class:`PagedKVCache`:
    pages shard by kv head (axis 2 of ``[L, P, n_kv, page, hd]`` — the
    per-row int8 scales ``[L, P, n_kv, page]`` shard with them), while
    block tables and lengths REPLICATE. That replication is the
    control-state invariant (docs/SHARDING.md): the host-side scheduler,
    allocator, spec decode, and the export/stage/migrate path all read
    and write page indices and lengths exactly as on one device."""
    kv = P(None, None, axis)
    rep = P()
    return PagedKVCache(
        k=kv, v=kv, block_tables=rep, lengths=rep,
        k_scale=kv if quantized else None,
        v_scale=kv if quantized else None,
    )


def tp_gather_costs(cfg: ModelConfig, tp: int, quant: bool = False):
    """What the tensor-parallel step's activation gathers move, from the
    shapes alone: ``(bytes a chip receives per block row through all the
    layers, bytes per row of the vocabulary head, all-gathers a pass)``.
    A pass (the ragged pass, or one continuation step) gathers four
    activations a layer (:func:`_paged_residual`: the heads' outputs and
    the ``wo`` columns; ``_mlp``: the hidden and the ``w_down`` columns) and, for
    an untied head, the logits (``_logits``); a chip receives the other
    ``tp - 1`` shards of each. ``quant`` is the int8 gather: one byte an
    element and an f32 scale a row and shard, in two collectives."""
    tp = int(tp)
    if tp <= 1:
        return 0.0, 0.0, 0
    item = 1 if quant else jnp.dtype(cfg.dtype).itemsize
    scales = 4 * tp if quant else 0

    def recv(width: int) -> float:
        return (width * item + scales) * (tp - 1) / tp

    layers = cfg.n_layers * sum(
        recv(w) for w in (cfg.q_dim, cfg.d_model, cfg.d_ff, cfg.d_model)
    )
    head = 0.0 if cfg.tie_embeddings else recv(cfg.vocab_size)
    calls = (4 * cfg.n_layers + (0 if cfg.tie_embeddings else 1)) * (
        2 if quant else 1
    )
    return layers, head, calls


# Compiled tensor-parallel ragged-step programs, keyed by every static
# that shapes the trace. Engines sharing (mesh, model, chunk geometry)
# share ONE step a rung (compiled once a width of the packed block, at
# most twice; the flat rung is an entry of its own) — churn in slots/requests/spec mixes never adds entries, which
# is what the per-shard-degree jit-cache guard in tests/test_tp.py pins.
# tlint: disable=TL006(append-only compiled-program registry, the TP analogue of a @jax.jit function's cache, bounded by hosted configs)
_TP_RAGGED_CACHE: dict = {}


def make_tp_ragged_step(
    mesh,
    cfg: ModelConfig,
    *,
    n_steps: int,
    spec_width: int = 1,
    kernel: bool = False,
    tp_quant: bool = False,
    axis: str = "tp",
    flat_rows: int = 0,
):
    """Build (or fetch) THE tensor-parallel serving program: the ragged
    step body shard_mapped over ``mesh[axis]`` and jitted with the same
    donation discipline as :func:`paged_ragged_step`.

    Weights enter as head-major column slices (tp_partition_specs), KV
    pages as kv-head slices (:func:`tp_cache_specs`), everything else
    replicated; outputs mirror that layout, so the donated cache keeps
    its sharding across chunks. Call with the SAME positional operands as
    ``paged_ragged_step`` minus the trailing statics (closed over
    here). ``tp_quant`` routes the per-chunk activation gathers through
    the int8 quantized collective (bounded divergence, opt-in via
    ModelConfig.collective_quant). ``flat_rows``: the rung, as
    :func:`paged_ragged_step` takes it (the flat rows are replicated
    control like the block; a rung is a program of its own here too)."""
    key = (mesh, cfg, int(n_steps), int(spec_width), bool(kernel),
           bool(tp_quant), axis, int(flat_rows))
    hit = _TP_RAGGED_CACHE.get(key)
    if hit is not None:
        return hit
    pspecs = tp_partition_specs(cfg, axis=axis)
    rep = P()

    # the name is the compiled module's (``jit_tp_ragged_step``): what a
    # profiler trace calls this program's executions
    def tp_ragged_step(params, ctl, cache, counts):
        return _ragged_step_impl(
            params, ctl, cache, counts, cfg, n_steps, spec_width, kernel,
            axis, tp_quant, flat_rows,
        )

    def specs_for(quantized: bool):
        cspecs = tp_cache_specs(quantized, axis)
        # replicated results spelled rank-expanded: the jit's
        # out_shardings are made of these (see _canon)
        rep1, rep2 = P(None), P(None, None)
        out_cache = replace(cspecs, block_tables=rep2, lengths=rep1)
        return (pspecs, rep, cspecs, rep), (rep2, out_cache, rep2)

    def build(quantized: bool):
        in_specs, out_specs = specs_for(quantized)
        out_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), out_specs,
            is_leaf=lambda s: isinstance(s, P),
        )
        return jax.jit(
            # check_vma off: a pallas_call's out_shape carries no
            # varying-axis type, and all_gather's result is typed varying
            # though every shard holds the same bytes — the replicated
            # out_specs hold by construction (fixed-order gathers), which
            # the bit-identity tests pin, not the type checker
            jax.shard_map(
                tp_ragged_step, mesh=mesh, in_specs=in_specs,
                out_specs=out_specs, check_vma=False,
            ),
            donate_argnums=(2, 3),  # cache, counts — as the 1-dev step
            out_shardings=out_shardings,
        )

    # int8-cache engines carry scale planes (a different cache pytree),
    # so the spec tree is chosen at first call by the cache's own arity
    plain, quant = build(False), build(True)

    def _canon(x):
        # Replicated control arrays reach the dispatcher with two
        # spellings of the same placement — P() from host-side
        # device_puts and rank-expanded P(None, ...) from jit/shard_map
        # outputs — and the jit cache keys on the spelling, not the
        # placement. Pin ONE canonical form (the rank-expanded one, which
        # the step's own outputs are given above whatever their rank, so
        # steady-state decode chunks pass through untouched: left to
        # itself the rank-1 ``lengths`` came back as ``P()`` and was
        # placed again every chunk) to keep the hot loop at one program
        # a width.
        want = NamedSharding(mesh, P(*([None] * x.ndim)))
        sh = getattr(x, "sharding", None)
        if isinstance(sh, NamedSharding) and sh == want:
            return x
        if isinstance(x, jax.ShapeDtypeStruct):  # lowered from shapes
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=want)
        return jax.device_put(x, want)

    def placed(params, ctl, cache, counts):
        """The program for this cache's arity and its operands as it is
        called with them: ``ctl`` as the host made it (the call places it
        on every chip of the mesh, once)."""
        fn = plain if cache.k_scale is None else quant
        bt = _canon(cache.block_tables)
        ln = _canon(cache.lengths)
        if bt is not cache.block_tables or ln is not cache.lengths:
            cache = replace(cache, block_tables=bt, lengths=ln)
        # counts is donated, like the cache
        return fn, (params, ctl, cache, _canon(counts))

    def step(*ops):
        fn, ops = placed(*ops)
        return fn(*ops)

    def lower(*ops):
        # the jitted program itself, lowered without running, at the
        # placements a call gives it: what it compiles to is the program
        # the call then finds (chip_smoke.py reads the kernel and the
        # collectives off it; ContinuousEngine.build_steps builds it)
        fn, ops = placed(*ops)
        return fn.lower(*ops)

    step._cache_size = lambda: (  # compile-count guard hook, summed
        plain._cache_size() + quant._cache_size()
    )
    step.lower = lower
    _TP_RAGGED_CACHE[key] = step
    return step


def make_logits_probe(mesh, cfg: ModelConfig, *, kernel: bool = False,
                      tp_quant: bool = False, axis: str = "tp"):
    """The serving step's two passes as programs of their own that return
    logits where the step samples: ``(ragged, decode)``, over ``mesh``'s
    ``axis`` exactly as :func:`make_tp_ragged_step` shards them. What
    compares the served path with a reference forward logit by logit
    (tests/test_tp_load.py, ``chip_smoke.py``); nothing serves through it.

    ``ragged(params, blk, cache, starts, n_valid)`` runs the ragged pass
    over the packed block and returns each slot's logits at its last valid
    row ``[S, V]`` and the cache with those rows written;
    ``decode(params, tok, cache, active)`` is one continuation step
    (:func:`_decode_step_impl`)."""
    def ragged(params, blk, cache, starts, n_valid):
        logits_v, _base, kv_new = _ragged_pass(
            params, blk, cache, starts, n_valid, jnp.zeros_like(n_valid),
            cfg, 1, kernel, axis, tp_quant,
        )
        lengths = jnp.where(n_valid > 0, starts + n_valid, cache.lengths)
        return logits_v[:, 0], _with_kv(cache, kv_new, lengths=lengths)

    def decode(params, tok, cache, active):
        return _decode_step_impl(
            params, tok, cache, active, cfg, kernel, axis, tp_quant
        )

    pspecs = tp_partition_specs(cfg, axis=axis)
    rep = P()

    def sharded(fn, n_ctl: int):
        def build(quantized: bool):
            cspecs = tp_cache_specs(quantized, axis)
            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=(pspecs, rep, cspecs) + (rep,) * n_ctl,
                out_specs=(rep, cspecs), check_vma=False,
            ))

        plain, quant = build(False), build(True)
        return lambda params, x, cache, *ctl: (
            plain if cache.k_scale is None else quant
        )(params, x, cache, *ctl)

    return sharded(ragged, 2), sharded(decode, 1)


def make_layer_probe(cfg: ModelConfig, kind: str, *, kernel: bool = False):
    """ONE layer's attention of a patterned model through the pages, on
    hidden states given from outside: ``(ragged, decode)``, placed exactly
    as :func:`_ragged_pass` and :func:`_decode_step_impl` place the step's
    rows. What compares a layer's cached rows and its attention with a
    reference ON THE SAME INPUT, so that nothing an earlier layer's
    discrete choices did (which positions, which experts) is in the
    difference (``benchmarks/reference/dots3_note.py``); nothing serves
    through it.

    ``ragged(lp, x [S, C, d], cache, li, starts, n_valid)`` and
    ``decode(lp, x [S, 1, d], cache, li, active)`` return ``(what the
    attention adds to x, cache)`` with the rows written to layer ``li`` of
    ``kind``'s pools and the lengths advanced; ``lp`` holds the layer's
    ``ln1`` and ``attn``. The addition and not the sum: rounded into a
    bf16 residual stream ten times its size, the sum loses most of what
    the comparison is after."""

    def ragged(lp, x, cache, li, starts, n_valid):
        page, n_pp = cache.page_size, cache.pages_per_slot
        bt = cache.block_tables
        _, _, pos, valid = _ragged_write_indices(
            bt, starts, n_valid, page, n_pp, x.shape[1]
        )
        added, pools = attention_only(
            lp, x, cache, cfg, kernel, kind, li, positions=pos, valid=valid,
            plan=_page_write_plan(bt, starts, n_valid, page, n_pp, x.shape[1]),
            n_valid=n_valid,
        )
        lengths = jnp.where(n_valid > 0, starts + n_valid, cache.lengths)
        return added, _with_kv(cache, pools, lengths=lengths)

    def decode(lp, x, cache, li, active):
        lengths = cache.lengths
        write_pg, write_off, _, _ = _ragged_write_indices(
            cache.block_tables, lengths, active.astype(jnp.int32),
            cache.page_size, cache.pages_per_slot, 1,
        )
        added, pools = attention_only(
            lp, x, cache, cfg, kernel, kind, li, positions=lengths[:, None],
            active=active, write_pg=write_pg[:, 0], write_off=write_off[:, 0],
            att_len=jnp.where(active, lengths + 1, 0),
        )
        return added, _with_kv(
            cache, pools, lengths=jnp.where(active, lengths + 1, lengths)
        )

    return (jax.jit(ragged, donate_argnums=(2,)),
            jax.jit(decode, donate_argnums=(2,)))


def _page_pools(cache) -> dict:
    """A cache's page pools by field name, page axis 1: what a page
    operation moves. ``k`` / ``v`` and, quantized, their scale planes; a
    patterned model's pools per kind (engine/latent.py)."""
    if isinstance(cache, LatentPagedCache):
        return cache.pools()
    names = ("k", "v") if cache.k_scale is None else (
        "k", "v", "k_scale", "v_scale")
    return {n: getattr(cache, n) for n in names}


# tlint: hot-path  # tlint: one-program
@partial(jax.jit, donate_argnames=("cache",))
def copy_page(
    cache: PagedKVCache, src: jax.Array, dst: jax.Array
) -> PagedKVCache:
    """Copy-on-write: duplicate a cached page's KV (every layer) into a
    page the admitting slot owns, so the slot can overwrite its tail
    without touching the shared original. In int8 mode the scale rows
    move with the payload — the copy is byte-exact, so a COW'd quantized
    page dequantizes to exactly what the original does."""
    return replace(cache, **{
        n: a.at[:, dst].set(a[:, src]) for n, a in _page_pools(cache).items()
    })


# tlint: hot-path  # tlint: one-program
@jax.jit
def gather_page(cache: PagedKVCache, page: jax.Array) -> tuple:
    """Read one physical page's KV across every layer — the migration
    EXPORT device path. Returns ``(k, v)`` (``[L, n_kv, page, hd]``) or
    ``(k, v, k_scale, v_scale)`` in int8 mode. The bytes are the cache
    value itself (no dequantize, no cast), which is what makes a shipped
    page byte-exact on the destination: an adopted quantized page
    dequantizes to exactly what the source's kernels read."""
    return tuple(a[:, page] for a in _page_pools(cache).values())


# tlint: hot-path  # tlint: one-program
@partial(jax.jit, donate_argnames=("cache",))
def scatter_page(
    cache: PagedKVCache,
    page: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> PagedKVCache:
    """Write one shipped page's KV into a destination-owned physical page —
    the migration IMPORT device path (inverse of :func:`gather_page`,
    byte-exact; page shape is fixed, so any migration compiles this ONCE
    per engine mode regardless of how many pages move). The arrays given
    are the cache's pools in :func:`gather_page`'s order."""
    given = [x for x in (k, v, k_scale, v_scale) if x is not None]
    return replace(cache, **{
        n: a.at[:, page].set(x)
        for (n, a), x in zip(_page_pools(cache).items(), given)
    })


# tlint: hot-path  # tlint: one-program
@partial(jax.jit, donate_argnames=("counts",))
def set_counts_row(
    counts: jax.Array, slot: jax.Array, row: jax.Array
) -> jax.Array:
    """A slot's context histogram, whole: what a request with a presence
    or frequency penalty brings at admission. A slot without one starts
    at zero through the step's own ``reset`` column (:class:`Control`),
    as a slot's table row and length ride ``bind``: nothing else binds or
    clears a slot on the device."""
    return counts.at[slot].set(row)


def pages_needed(total_len: int, page_size: int) -> int:
    """Pages a request of ``total_len`` positions (prompt + budget, capped
    at the engine's max_seq_len) occupies."""
    return -(-int(total_len) // int(page_size))


__all__ = [
    "LatentPagedCache",
    "PagedKVCache",
    "PageAllocator",
    "PoolTenant",
    "PrefixCache",
    "SharedPagePool",
    "paged_decode_step",
    "paged_ragged_step",
    "make_tp_ragged_step",
    "FlatRows",
    "flat_rung_rows",
    "row_tile",
    "tiled_rows",
    "pack_control",
    "unpack_control",
    "unpack_results",
    "make_logits_probe",
    "make_layer_probe",
    "tp_cache_specs",
    "tp_gather_costs",
    "copy_page",
    "gather_page",
    "scatter_page",
    "set_counts_row",
    "pages_needed",
]
