"""Fleet-wide prefix digest map (docs/SERVING.md "Tiered prefix cache").

The router already scores PLACEMENT by each replica's compact trie
digest (fleet/router.py::cache_affinity). This module answers the
complementary question after placement: a request LANDED somewhere and
missed locally — which sibling replica holds the prefix, in either
tier, so admission can PULL the pages over the MIGRATE wire instead of
re-prefilling?

:class:`FleetPrefixMap` consumes the same rid → view dicts the router's
refresh sweep already maintains (``views()``), reading the per-tier
digests each engine piggybacks on its router snapshot (``prefix_digest``
for HBM residency, ``host_tier_digest`` for the host-RAM tier — both
refreshed by the engine driver between chunks and shipped on the /stats
heartbeat for remote replicas). Digests are ADVISORY: they name chains
by rolling hash and can be seconds stale, so :meth:`locate` only ranks
candidates — the pull itself re-verifies the structural chain on the
source (export walks the real trie) and the sha256 content digest on
the destination (stage_prefix). A stale map misguides one RPC, never
bytes.

:func:`make_fleet_fetcher` closes the loop for in-process fleets (the
tests): it builds the
``engine.fetch_prefix`` callback from a view provider plus per-replica
pull functions, implementing the fallback ladder's third rung — best
candidate first, next on refusal, None (→ re-prefill) when the map has
nothing. Cross-process fleets wire the same shape through the MIGRATE
``pull`` op instead (ml/worker.py::_migrate_in).
"""

from __future__ import annotations

from typing import Callable

from tensorlink_tpu.core.logging import get_logger
from tensorlink_tpu.engine.paged import prompt_chain_hashes

# Hashing more leading pages than this per locate() is wasted host work:
# a pull that deep already amortizes; same bound as router affinity.
MAX_LOCATE_PAGES = 64


class FleetPrefixMap:
    """Rank sibling replicas by how much of a prompt's leading chain
    their published digests cover — the lookup behind the fleet-pull
    rung of admission's ladder.

    Stateless over the view dict it is handed: callers pass the
    router's current ``views()`` (or any rid → view mapping of the same
    shape), so the map never runs its own refresh sweep or holds a
    second copy of fleet state that could drift."""

    def __init__(self, page_size: int):
        self.page_size = int(page_size)

    def coverage(self, view: dict, hashes: list[str]) -> tuple[int, int]:
        """(covered_tokens, hbm_tokens) this view's digests predict for
        a prompt whose leading page hashes are ``hashes``. hbm_tokens
        counts only trie-resident coverage — a pull from HBM skips the
        source's own promote, so ties break toward it."""
        covered = hbm = 0
        for tier_key in ("prefix_digest", "host_tier_digest"):
            dig = view.get(tier_key) or {}
            if int(dig.get("page_size") or 0) != self.page_size:
                continue
            chains = dig.get("chains") or {}
            if not chains:
                continue
            deep = 0
            for i, h in enumerate(hashes):
                if h in chains:
                    deep = (i + 1) * self.page_size
            covered = max(covered, deep)
            if tier_key == "prefix_digest":
                hbm = deep
        return covered, hbm

    def locate(
        self,
        views: dict[str, dict],
        prompt_ids,
        *,
        exclude: tuple | frozenset = (),
        min_tokens: int = 0,
    ) -> list[tuple[str, int]]:
        """Candidate source replicas for a fleet pull, best first:
        ``[(rid, predicted_covered_tokens), ...]`` over every healthy,
        non-excluded view whose digests cover more than ``min_tokens``
        of the prompt's leading chain (pass the puller's own local
        coverage so a pull is only attempted when a sibling beats it).
        Deeper coverage wins; HBM residency breaks ties."""
        hashes = prompt_chain_hashes(
            prompt_ids, self.page_size, MAX_LOCATE_PAGES
        )
        if not hashes:
            return []
        ranked = []
        for rid, view in views.items():
            if rid in exclude or not view.get("ok", True):
                continue
            covered, hbm = self.coverage(view, hashes)
            if covered > max(int(min_tokens), 0):
                ranked.append((covered, hbm, rid))
        ranked.sort(key=lambda t: (-t[0], -t[1], t[2]))
        return [(rid, covered) for covered, _hbm, rid in ranked]


def make_fleet_fetcher(
    rid: str,
    page_size: int,
    views_fn: Callable[[], dict[str, dict]],
    pull_fns: dict[str, Callable],
    max_candidates: int = 2,
):
    """Build an ``engine.fetch_prefix`` callback — the fleet-pull rung —
    from a view provider (the router's ``views``) and per-replica pull
    functions (``(chain, limit, n_skip) -> blob | None``; in-process
    that is the sibling batcher's ``pull_prefix``, cross-process the
    MIGRATE ``pull`` RPC).

    ``rid`` is the PULLING replica (excluded from candidates — a
    replica must never pull from itself). The fetcher tries at most
    ``max_candidates`` sources best-coverage-first and returns the
    first blob, or None when every candidate refused / had nothing —
    the engine then falls through to re-prefill. Candidate errors are
    swallowed into the degrade (logged at debug): a sibling dying
    mid-pull must cost this request a re-prefill, not an exception."""
    fleet_map = FleetPrefixMap(page_size)
    log = get_logger("fleet.prefixmap")

    def fetch(chain, limit, n_local_pages):
        views = views_fn()
        candidates = fleet_map.locate(
            views, chain,
            exclude=(rid,),
            min_tokens=int(n_local_pages) * int(page_size),
        )
        for src, _covered in candidates[: max(int(max_candidates), 1)]:
            pull = pull_fns.get(src)
            if pull is None:
                continue
            try:
                blob = pull(chain, int(limit), int(n_local_pages))
            except Exception as e:
                log.debug("fleet pull %s -> %s failed: %s", src, rid, e)
                continue
            if blob:
                return blob
        return None

    return fetch


__all__ = ["FleetPrefixMap", "make_fleet_fetcher", "MAX_LOCATE_PAGES"]
