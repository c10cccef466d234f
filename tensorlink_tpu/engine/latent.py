"""The page cache and the layer loop of a patterned model (layers of more
than one kind, models/latent.py) under the one serving step
(engine/paged.py::paged_ragged_step).

**Pools per kind, one page table.** A slot's block-table row names the
same physical pages in every pool. A full layer caches a latent row and,
where it has a selector, a selector key a position, a sliding layer a
(wider) latent row; a pool exists only for what the model has:

    full   [Lf, P, 1, page, pool_dim(full)]      latent | rotated key | pad
    index  [Lf, P, 1, page, index_dim]           the selector's keys
    slide  [Ls, P, 1, page, pool_dim(sliding)]

in the dense cache's own layout (one "kv head"), so the page operations,
the page-by-page block write (``_merge_pages``) and the paged kernel take
them as they are. ``stats`` rides along: what the step counts of its own
routing and selection (``STEP_STATS``), zeroed by each step's first phase
and read by the host with the chunk's one sync.

**The layer loop** (:func:`run_layers`) carries the pools whole, like
``_scan_layers``: lead layers unrolled, then a scan over the periods (one
traced period whatever the depth), then the tail. A layer of kind ``k`` at
place ``j`` of period ``i`` is layer ``base_k + i * per_k + off_j`` of its
kind's pool.

**Attention by pass and kind.**

* ragged pass, sliding layer: the slot's window span (its block's first
  query minus the window, to its last query) is gathered once and keys and
  values are materialised from it for all the block's queries;
* continuation step, sliding layer: absorbed, through the paged kernel with
  a window start and the latent row as key and value both
  (``latent_window_attention``);
* full layer without a selector, either pass: absorbed, over the slot's
  LIVE span through the paged kernel with the latent row as key and value
  both (``latent_full_attention``): a continuation step and every slot's
  first row of the ragged pass as one query position a slot, a slot's
  block of more rows (a prefill, a verify) slot by slot as a ragged walk
  with each row's causal limit. Nothing is gathered and no score array
  spans a context;
* full layer with a selector, either pass: the selector scores the live
  span, the ``index_topk`` best rows are gathered and attended absorbed
  (each query has rows of its own). The ragged pass does this for every
  slot's first row in one batch and, slot by slot, for the whole block
  of a slot that holds more than one valid row (a prefill, a verify) —
  so a decode row in the block costs a decode row. A context that holds no more than
  ``index_topk`` positions is attended whole and nothing is scored.

The latent sliding layers (``slide``) keep every page of a slot and read
the window's span only (ROADMAP R2).

**Grouped-query kinds** (``gqa_full`` / ``gqa_window``,
models/base.py::GqaAttn). A ``gqa_full`` layer's keys and values are page
pools under the slot's table (``k`` / ``v``), the trie's like any page. A
``gqa_window`` layer's live in pools of their own (``wk`` / ``wv``) under
a second table that no array holds: slot ``s`` owns ring pages ``1 + s R
.. s R + R`` (page 0 is the scratch page) and its logical page ``j`` is
ring page ``j mod R`` (:func:`ring_table`), ``R`` = :func:`ring_len`
pages: the window, one block of the ragged pass and a page of slack, so
that the page-by-page write and the page walk take the ring as they take
any table and a block's write never lands on a page its first query still
reads. Those pages are never the trie's: what a prefix hit reuses of them
is a *snapshot* of the window at a page edge (the ``window - 1`` positions
before it, :func:`take_window` / :func:`restore_window`, the engine's
snapshot pool: engine/continuous.py), under the rule of the recurrent
states. Both kinds, both passes, go through the page walk
(``gqa_full_attention`` / ``gqa_window_attention``). A ``gqa_full`` kind
whose heads are half a lane row wide (``head_dim`` 64) keeps a position's
keys BESIDE its values in one row of 128 (:func:`kv_beside`: the pool
``k`` holds both, there is no ``v``): the walk then reads the row as key
and value both, as it reads a latent row, against queries padded with
zeros, and its output's second half is the attention. A pool 64 wide
would be padded to whole lane rows a call, a copy that follows the pool's
capacity (ops/attention.py::_lane_pad).

**Short convolutions** (kind ``conv``, models/base.py::ShortConv) stand
beside ``gqa_full`` layers. What a slot holds of them is the *tail*: the
last ``kernel - 1`` positions of the gated stream ``z``, a layer and slot
(``state`` ``[Lc, S, kernel - 1, width]``, in the activations' dtype). The
ragged pass convolves a slot's rows behind its carried tail (zeros where
the slot starts at position 0) and rewrites the tail at the slot's last
live row; a continuation step is ``kernel`` taps. No page describes the
tail: a prefix hit restores a snapshot of it (``engine/sala.py``'s
``take_snapshot`` / ``restore_snapshot``, generic over a state's trailing
dims) under the rule of the recurrent states.

**Gated delta-rule layers** (kind ``gated_delta``,
models/base.py::GatedDelta) stand beside ``gqa_full`` layers too. A slot
holds TWO arrays of them, a layer: the float32 state (``state`` ``[Lg, S,
dk, H dv]``: ops/gated_delta.py has the recurrence and why the heads lie
along the lanes) and the tail of the convolution in front of q, k and v
(``tail`` ``[Lg, S, kernel - 1, conv_width]``, in the activations' dtype).
Both passes update both in place: a continuation step is one position
(``gated_delta_step``), the ragged pass the chunkwise form over a slot's
live rows (``gated_delta_chunk``), state and tail written at the slot's
last live row, a slot without rows keeps both, a slot whose block starts
at position 0 starts from zeros whatever the arrays hold (so an admission
without a prefix makes no device call). A snapshot is both arrays, taken
and restored together (``ModelConfig.slot_arrays``, engine/sala.py).

**The norm's place** (``ModelConfig.norm_position``): ``"post"`` norms what
a branch ADDS (``x + norm(op(x))``, the OLMo family's block) in the
``gqa_full`` and ``gated_delta`` kinds, where ``"pre"`` norms its input.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models.base import (
    CONV_KIND, GATED_DELTA, GQA_KINDS, SALA_KINDS, GqaAttn, LatentAttn,
    ModelConfig,
)
from ..models.latent import (
    _rms,
    EXPERT_STACKS,
    GATED_DELTA_SCOPE,
    INDEX_SELECT,
    LATENT_ATTN,
    MOE,
    N_MOE_STATS,
    SHORT_CONV,
    NEG_INF,
    STEP_STATS,
    WINDOW_ATTN,
    absorbed_output,
    absorbed_query,
    attend_absorbed,
    attend_materialised,
    gated_delta_in,
    gated_delta_out,
    gated_delta_qkv,
    gated_mlp,
    gqa_qkv,
    index_scores,
    kind_counts,
    latent_qkv,
    moe_mlp,
    Pattern,
    pattern_of,
    rope_by_kind,
    short_conv_in,
    short_conv_taps,
    top_k_positions,
)
from ..models.quant import matmul as _mm
from ..models.sala import is_sala, step_stats
from ..ops.gated_delta import (
    gated_delta_chunk,
    gated_delta_chunk_ref,
    gated_delta_step,
    gated_delta_step_ref,
)
from ..ops.attention import (
    paged_attention,
    paged_attention_ref,
    ragged_paged_attention,
    ragged_paged_attention_ref,
)

WINDOW_KERNEL = "latent_window_attention"  # the pallas_call's name
FULL_KERNEL = "latent_full_attention"  # ... the full layers' walk, both passes
# the full layers' walk (ops/attention.py::_paged_walk, ``latent``): key
# positions a KV block spans, query rows a row block holds, query rows a
# grid step holds. One row serves every head, so a 128 x 128 tile is on
# the chip's ridge with nothing to spare for the loop around it; sized on
# the chip (PERF.md section 6, PR 34)
FULL_KV_TILE, FULL_ROWS, FULL_GROUP_ROWS = 512, 512, 2048
# a grouped-query kind's walk (both passes) is the pallas_call
# ``<kind>_attention`` under the scope ``tlink.<kind>``


def kv_beside(ga: GqaAttn) -> bool:
    """A ``gqa_full`` kind keeps keys beside values in one lane row (module
    docstring): heads of half a lane row, no window."""
    return ga.window is None and 2 * ga.head_dim == 128


def ring_len(window: int, chunk: int, page: int) -> int:
    """Pages of a slot's ring: the window and one block of the ragged pass
    in whole pages, and one page more (a block that starts inside a page
    ends inside another)."""
    return -(-(window + chunk) // page) + 1


def snapshot_pages(window: int, page: int) -> int:
    """Pages a window snapshot holds: the ``window - 1`` positions before
    a page edge."""
    return -(-(window - 1) // page)


def ring_table(max_slots: int, n_pp: int, R: int) -> jax.Array:
    """The ring layers' block table ``[S, n_pp]``: logical page ``j`` of
    slot ``s`` is ring page ``1 + s R + j mod R`` (a constant: no array of
    the cache holds it)."""
    return (1 + jnp.arange(max_slots, dtype=jnp.int32)[:, None] * R
            + jnp.arange(n_pp, dtype=jnp.int32)[None, :] % R)


@jax.tree_util.register_dataclass
@dataclass
class LatentPagedCache:
    """Paged cache of a patterned model (module docstring). Same control
    state as :class:`~tensorlink_tpu.engine.paged.PagedKVCache`
    (``block_tables``, ``lengths``), pools per kind in its layout."""

    full: jax.Array | None  # None: the model has no such layers
    index: jax.Array | None  # ... no selector
    slide: jax.Array | None
    block_tables: jax.Array  # int32 [S, pages_per_slot]
    lengths: jax.Array  # int32 [S]
    stats: jax.Array  # int32 [len(step_stats(cfg))]: this step's counts
    # block-sparse GQA layers (engine/sala.py): keys, values, and one
    # float32 sum of keys a page; lightning layers: a state a slot, which
    # no page describes
    k: jax.Array | None = None
    v: jax.Array | None = None
    ksum: jax.Array | None = None
    state: jax.Array | None = None  # float32 [Ll, S, H, hd, hd]
    # ... or, of a model with ``conv`` layers, the slots' tails in the
    # activations' dtype ``[Lc, S, kernel - 1, width]``
    # ``gqa_window`` layers: keys and values in rings, ``[Lw, 1 + S R,
    # Hkv, page, hd]`` (module docstring); not page pools of the trie
    wk: jax.Array | None = None
    wv: jax.Array | None = None
    # ``gated_delta`` layers: the convolution's tails ``[Lg, S, kernel - 1,
    # conv_width]`` beside their float32 states in ``state`` ``[Lg, S, dk,
    # H dv]``
    tail: jax.Array | None = None

    POOLS = ("full", "index", "slide", "k", "v", "ksum")  # page axis 1

    @classmethod
    def init(cls, cfg: ModelConfig, max_slots: int, *, page_size: int = 16,
             max_len: int | None = None, dtype=None,
             n_pages: int | None = None,
             prefill_chunk: int = 128) -> "LatentPagedCache":
        S_max = max_len or cfg.max_seq_len
        n_pp = -(-S_max // page_size)
        P = n_pages if n_pages is not None else 1 + max_slots * n_pp
        dt = dtype or cfg.dtype
        n = kind_counts(cfg)
        sizes = dict(cfg.latent)
        full, slide = sizes.get("full"), sizes.get("sliding")
        extra = {}
        if is_sala(cfg):
            from .sala import init_pools

            extra = init_pools(cfg, max_slots, P, page_size, dt)
        for kind, names in (("gqa_full", ("k", "v")),
                            ("gqa_window", ("wk", "wv"))):
            if not n.get(kind):
                continue
            ga = sizes[kind]
            # pages of the slot's table, or the scratch page and a ring a slot
            pages = P if ga.window is None else 1 + max_slots * ring_len(
                ga.window, min(prefill_chunk, S_max), page_size)
            width = ga.head_dim
            if kv_beside(ga):  # keys beside values: one pool, no ``v``
                names, width = names[:1], 2 * width
            shape = (n[kind], pages, ga.n_kv_heads, page_size, width)
            extra |= {name: jnp.zeros(shape, dt) for name in names}
        if n.get(CONV_KIND):
            sc = sizes[CONV_KIND]
            extra["state"] = jnp.zeros(
                (n[CONV_KIND], max_slots, sc.tail, sc.width), dt)
        if n.get(GATED_DELTA):
            gd = sizes[GATED_DELTA]
            extra["state"] = jnp.zeros(
                (n[GATED_DELTA], max_slots, gd.key_dim,
                 gd.n_heads * gd.value_dim), jnp.float32)
            extra["tail"] = jnp.zeros(
                (n[GATED_DELTA], max_slots, gd.tail, gd.conv_width), dt)

        def pool(kind, width):
            # no pool for a kind, or a selector, the model has not: a
            # placeholder one value wide is padded to whole lanes on the
            # chip (0.4 GB of nothing at 6 layers x 16 slots x 16,384)
            if not n.get(kind) or not width:
                return None
            return jnp.zeros((n[kind], P, 1, page_size, width), dt)

        return cls(
            full=pool("full", full and full.pool_dim),
            index=pool("full", full and full.index_heads and full.index_dim),
            slide=pool("sliding", slide and slide.pool_dim),
            block_tables=jnp.zeros((max_slots, n_pp), jnp.int32),
            lengths=jnp.zeros((max_slots,), jnp.int32),
            stats=jnp.zeros((len(step_stats(cfg)),), jnp.int32),
            **extra,
        )

    def pools(self) -> dict:
        """The pools there are, by field name."""
        return {n: getattr(self, n) for n in self.POOLS
                if getattr(self, n) is not None}

    @property
    def rows(self) -> jax.Array:
        """A page pool (any: the control state is shared)."""
        return next(iter(self.pools().values()))

    @property
    def quantized(self) -> bool:
        return False

    @property
    def page_size(self) -> int:
        return self.rows.shape[3]

    @property
    def state_bytes(self) -> int:
        """The lightning or gated-delta layers' states (the conv layers'
        tails) of every slot."""
        return 0 if self.state is None else (
            self.state.size * self.state.dtype.itemsize)

    @property
    def tail_bytes(self) -> int:
        """The gated-delta layers' tails of every slot."""
        return 0 if self.tail is None else (
            self.tail.size * self.tail.dtype.itemsize)

    @property
    def ring_pages(self) -> int:
        """Pages of one slot's ring (0: no ring layers)."""
        return 0 if self.wk is None else (
            (self.wk.shape[1] - 1) // self.max_slots)

    @property
    def ring_bytes(self) -> int:
        """The ring layers' keys and values of every slot."""
        return 0 if self.wk is None else 2 * (
            self.wk.size * self.wk.dtype.itemsize)

    @property
    def n_pages(self) -> int:
        return self.rows.shape[1]

    @property
    def max_slots(self) -> int:
        return self.block_tables.shape[0]

    @property
    def pages_per_slot(self) -> int:
        return self.block_tables.shape[1]

    @property
    def pool_bytes(self) -> int:
        return sum(a.size * a.dtype.itemsize for a in self.pools().values())


# -- a slot's window and its snapshots (engine/continuous.py) ---------------
# A snapshot is the ring layers' keys and values of the ``window - 1``
# positions before a page edge ``pos``: ``[2, Lw, n, Hkv, page, hd]``, the
# ``n`` = :func:`snapshot_pages` logical pages ``pos / page - n ..`` of a
# slot's ring. A logical page lies at the same place of every slot's ring,
# so a restore writes them where the taking slot read them.


def window_snapshot_pool(cache: LatentPagedCache, n: int, window: int):
    """``n`` empty places for ``cache``'s ring layers."""
    Lw, _, Hkv, page, hd = cache.wk.shape
    return jnp.zeros(
        (n, 2, Lw, snapshot_pages(window, page), Hkv, page, hd),
        cache.wk.dtype)


def _snapshot_ring_pages(cache, n: int, slot, pos):
    R, page = cache.ring_pages, cache.page_size
    return 1 + slot * R + (pos // page - n + jnp.arange(n)) % R


# tlint: one-program
@partial(jax.jit, donate_argnames=("snaps",))
def take_window(snaps, cache, slot, idx, pos):
    """``slot``'s window before the page edge ``pos`` into place ``idx``
    of the snapshot pool."""
    pages = _snapshot_ring_pages(cache, snaps.shape[3], slot, pos)
    return snaps.at[idx].set(
        jnp.stack([cache.wk[:, pages], cache.wv[:, pages]]))


# tlint: one-program
@partial(jax.jit, donate_argnames=("cache",))
def restore_window(cache, snaps, slot, idx, pos):
    """Place ``idx`` of the snapshot pool, taken at ``pos``, as ``slot``'s
    window there."""
    pages = _snapshot_ring_pages(cache, snaps.shape[3], slot, pos)
    return replace(cache, wk=cache.wk.at[:, pages].set(snaps[idx, 0]),
                   wv=cache.wv.at[:, pages].set(snaps[idx, 1]))


def unsupported(cfg: ModelConfig) -> str | None:
    """Why the slot engine cannot serve a patterned config; None when it
    can: layers of the two kinds this module implements, either or both,
    each kind in use with its sizes."""
    kinds = set(cfg.layer_kinds)
    sizes = dict(cfg.latent)
    beside = {CONV_KIND, GATED_DELTA}  # kinds that stand beside gqa_full
    served = {"full", "sliding"} | set(SALA_KINDS) | set(GQA_KINDS) | beside
    if not kinds <= served or not kinds <= set(sizes):
        return (f"layer kinds {sorted(kinds)} with sizes for "
                f"{sorted(sizes)} (served: full, sliding, sparse, "
                "lightning, gated_delta, gqa_full, gqa_window, conv)")
    if kinds & (set(GQA_KINDS) | beside):
        if kinds - set(GQA_KINDS) - beside:
            return ("grouped-query, short-convolution or gated-delta "
                    "layers beside latent or sparse layers")
        if "gqa_full" not in kinds:
            return ("window, short-convolution or gated-delta layers "
                    "without a full layer (no page pool)")
        if CONV_KIND in kinds and "gqa_window" in kinds:
            return ("short-convolution layers beside window layers (a "
                    "slot's snapshot holds a ring or a tail, not both)")
        if GATED_DELTA in kinds and kinds & {CONV_KIND, "gqa_window"}:
            return ("gated-delta layers beside short-convolution or window "
                    "layers (a slot's snapshot holds a ring, a tail, or a "
                    "state and its tail: one of them)")
        if GATED_DELTA in kinds and sizes[GATED_DELTA].n_heads % 2:
            return ("an odd number of gated-delta heads (the kernels take "
                    "the heads two at a time)")
        if sizes["gqa_full"].window is not None:
            return "a window on the gqa_full layers"
        if "gqa_window" in kinds and sizes["gqa_window"].window is None:
            return "a gqa_window layer without a window"
    if kinds & set(SALA_KINDS):
        if kinds - set(SALA_KINDS):
            return "sparse / lightning layers beside latent layers"
        if cfg.n_experts:
            return "routed experts beside sparse / lightning layers"
        return None
    if "sliding" in kinds:
        if sizes["sliding"].window is None:
            return "a sliding layer without a window"
        if sizes["sliding"].index_heads:
            return "a selector on the sliding layers"
    if "full" in kinds and sizes["full"].window is not None:
        return "a window on the full layers"
    if cfg.moe_n_group and (
        cfg.n_experts % cfg.moe_n_group
        or not 0 < cfg.moe_topk_group <= cfg.moe_n_group
    ):
        return (f"{cfg.n_experts} experts in {cfg.moe_n_group} groups, "
                f"{cfg.moe_topk_group} a token")
    return None


# ---------------------------------------------------------------------------
# One layer
# ---------------------------------------------------------------------------


@dataclass
class _Ctx:
    """What places a pass's queries: shared by every layer of the pass."""

    cfg: ModelConfig
    kernel: bool
    block_tables: jax.Array
    positions: jax.Array  # [S, T] absolute position of each query
    row_ok: jax.Array  # [S, T] the query carries a token
    rope: dict  # kind -> (cos, sin)
    # ragged pass: the page-by-page write plan; decode: the row's target
    plan: tuple | None = None
    write_pg: jax.Array | None = None
    write_off: jax.Array | None = None
    n_valid: jax.Array | None = None  # ragged pass [S]
    att_len: jax.Array | None = None  # decode [S]: positions attended
    # ring layers: their table, and the write's plan / the row's page in it
    ring_bt: jax.Array | None = None
    ring_plan: tuple | None = None
    ring_pg: jax.Array | None = None
    # the ragged pass's flat rung (paged.FlatRows): ``x`` is the live rows
    # ``[1, R, d]``; ``positions`` / ``row_ok`` stay the block's ``[S, C]``
    # for the seams that need a slot (the page write, the attention call)
    rows: tuple | None = None

    @property
    def x_ok(self) -> jax.Array:
        """``row_ok`` in ``x``'s own layout."""
        return self.row_ok if self.rows is None else self.rows.live[None]


def _expand(a, ctx: _Ctx):
    """``a`` in ``x``'s layout to the block's ``[S, C, ...]`` (a gather
    on the flat rung, nothing else)."""
    return a if ctx.rows is None else ctx.rows.expand(a)


def _collect(a, ctx: _Ctx):
    """The block's ``[S, C, ...]`` back to ``x``'s layout."""
    return a if ctx.rows is None else ctx.rows.collect(a)


def _by_tile(fn, ctx: _Ctx, *xs):
    """Position-wise ``fn`` over ``xs`` in ``x``'s layout: at once, or,
    where the ragged pass bounds its row list by the chunk's live rows,
    tile by tile as far as they reach (``paged.FlatRows.by_tile``)."""
    return fn(*xs) if ctx.rows is None else ctx.rows.by_tile(fn, *xs)


def _attn_out(o, gate, ap, dtype, ctx: _Ctx):
    """What a layer's attention adds: the heads' outputs ``o`` in ``x``'s
    layout, gated where the kind has a gate, through ``wo``."""

    def out(o, *gate):
        if gate:
            o = (o.astype(jnp.float32) * gate[0][..., None]).astype(dtype)
        return _mm(o.reshape(o.shape[:2] + (-1,)), ap["wo"])

    return _by_tile(out, ctx, o, *(() if gate is None else (gate,)))


def _write(pool, li, rows, ctx: _Ctx):
    """``rows`` ``[S, T, W]`` into layer ``li`` of ``pool`` through the one
    write path's targets: a block page by page, a single row scattered."""
    from .paged import _merge_pages

    if ctx.plan is not None:
        return _merge_pages(pool, li, ctx.plan, rows[:, :, None].astype(
            pool.dtype))
    return pool.at[li, ctx.write_pg, 0, ctx.write_off].set(
        rows[:, 0].astype(pool.dtype)
    )


def _slot_rows(pool, li, bt_rows):
    """The pages ``bt_rows`` ``[.., n]`` of layer ``li`` as contiguous
    positions ``[.., n * page, W]``."""
    x = pool[li, bt_rows, 0]
    return x.reshape(x.shape[:-3] + (x.shape[-3] * x.shape[-2], x.shape[-1]))


def _window_span(ctx: _Ctx, la: LatentAttn, page: int, T: int):
    """The pages a block's queries can reach through the window:
    ``(physical pages [S, n], key positions [S, n * page])`` from the page
    of the first query's oldest key on."""
    n_pp = ctx.block_tables.shape[1]
    first = jnp.maximum(ctx.positions[:, 0] - (la.window - 1), 0) // page
    n = min(-(-(page - 1 + la.window - 1 + T) // page), n_pp)
    logical = first[:, None] + jnp.arange(n)[None, :]
    phys = jnp.take_along_axis(
        ctx.block_tables, jnp.minimum(logical, n_pp - 1), axis=1
    )
    k_pos = (first * page)[:, None] + jnp.arange(n * page)[None, :]
    return phys, k_pos


def _sliding_attend(q, pool, li, ap, la: LatentAttn, ctx: _Ctx):
    """A sliding layer's attention over its pool; ``[S, T, H, v]``."""
    S, T = ctx.positions.shape
    scale = la.qk_dim**-0.5
    if ctx.plan is None:  # a continuation step: absorbed, through pages
        qa = absorbed_query(q["q_n"][:, 0], q["q_r"][:, 0], ap, la)
        if ctx.kernel and pool.dtype == qa.dtype:
            out = paged_attention(
                qa, pool, None, ctx.block_tables, ctx.att_len, scale=scale,
                layer=li, window=la.window, name=WINDOW_KERNEL,
            )
        else:
            rows = pool[li].astype(qa.dtype)
            out = paged_attention_ref(
                qa, rows, rows, ctx.block_tables, ctx.att_len, scale=scale,
                window=la.window,
            )
        return absorbed_output(out, ap, la)[:, None]
    phys, k_pos = _window_span(ctx, la, pool.shape[3], T)
    rows = _slot_rows(pool, li, phys)  # [S, K, W]
    q_pos = ctx.positions[:, :, None]
    mask = (
        (k_pos[:, None, :] <= q_pos) & (k_pos[:, None, :] > q_pos - la.window)
        & ctx.row_ok[:, :, None]
    )
    return attend_materialised(
        _expand(q["q_n"], ctx), _expand(q["q_r"], ctx), rows, mask, ap, la)


def _select_attend(q_n, q_r, qi, wi, q_pos, row_ok, bt_row, full, index, li,
                   ap, la: LatentAttn):
    """One slot's queries (``R`` of them, at ``q_pos`` ``[R]``) through a
    full layer: score the slot's cached positions, keep the ``index_topk``
    best of each query's causal span, attend those rows absorbed. Returns
    ``(o [R, H, v], kept, scored)``: positions attended and positions the
    span held, summed over the valid queries."""
    lat = _slot_rows(full, li, bt_row)  # [Kc, W]
    Kc = lat.shape[0]
    causal = jnp.arange(Kc)[None, :] <= q_pos[:, None]  # [R, Kc]
    span = jnp.where(row_ok, q_pos + 1, 0).sum()
    if Kc <= la.index_topk:
        # nothing to drop: every live position is attended
        mask = causal & row_ok[:, None]
        rows = jnp.broadcast_to(lat[None], (q_pos.shape[0],) + lat.shape)
        o = attend_absorbed(q_n, q_r, rows, mask, ap, la)
        return o, span, span
    with jax.named_scope(INDEX_SELECT):
        sc = index_scores(qi, wi, _slot_rows(index, li, bt_row))
        sc = jnp.where(causal, sc, NEG_INF)
        idx = top_k_positions(sc, la.index_topk)  # [R, K]
    # a query with fewer than index_topk causal positions picks the rest
    # from behind the mask: those are not attended
    mask = (idx <= q_pos[:, None]) & row_ok[:, None]
    with jax.named_scope(LATENT_ATTN):
        o = attend_absorbed(q_n, q_r, lat[idx], mask, ap, la)
    return o, mask.sum(), span


def _walk_attend(q, pool, li, ap, la: LatentAttn, ctx: _Ctx):
    """A full layer's attention with nothing selected: absorbed, over each
    slot's live span through the page walk, the latent row as key and
    value both; ``[S, T, H, v]``."""
    T = ctx.positions.shape[1]
    bt, scale = ctx.block_tables, la.softmax_scale
    # the absorbed query is position-wise: over x's rows, flat or not
    qa = _by_tile(  # [S, T, H, W]
        lambda q_n, q_r: absorbed_query(q_n, q_r, ap, la), ctx,
        q["q_n"], q["q_r"])
    if ctx.rows is None:
        first_q, per_slot, rows_of = (lambda: qa[:, 0]), qa, (lambda a: a)
    else:  # a slot's rows are gathered where its block is walked
        to_flat = per_slot = ctx.rows.to_flat
        first_q = lambda: ctx.rows.take(qa, to_flat[:, 0])
        rows_of = partial(ctx.rows.take, qa)
    shape = (la.kv_rank, FULL_KV_TILE, FULL_ROWS, FULL_GROUP_ROWS)
    kernel = ctx.kernel and pool.dtype == qa.dtype

    def walk(fn, ref, qs, *place):  # either entry point, or its reference
        if kernel:
            return fn(qs, pool, None, *place, scale=scale, layer=li,
                      name=FULL_KERNEL, latent=shape)
        rows = pool[li].astype(qs.dtype)
        return ref(qs, rows, rows, *place, scale=scale)[..., :la.kv_rank]

    def first_rows(lengths):  # one query position a slot, at lengths - 1
        out = walk(
            paged_attention, paged_attention_ref, first_q(), bt, lengths)
        return absorbed_output(out, ap, la)[:, None]  # [S, 1, H, v]

    if ctx.plan is None:  # a continuation step
        return first_rows(ctx.att_len)
    # every slot's first row in one call: all there is of a decode slot
    o1 = first_rows(jnp.where(ctx.n_valid > 0, ctx.positions[:, 0] + 1, 0))
    if T == 1:
        return o1
    many = ctx.n_valid > 1  # a prefill's or a verify's block, slot by slot

    def block(a):
        ok, qs, bt_row, start, nv = a
        return lax.cond(
            ok,
            lambda: absorbed_output(walk(
                ragged_paged_attention, ragged_paged_attention_ref,
                rows_of(qs)[None], bt_row[None], start[None], nv[None])[0],
                ap, la),
            lambda: jnp.zeros((T, la.n_heads, la.v_dim), o1.dtype),
        )

    oT = lax.map(
        block, (many, per_slot, bt, ctx.positions[:, 0], ctx.n_valid))
    first = jnp.pad(o1, ((0, 0), (0, T - 1), (0, 0), (0, 0)))
    return jnp.where(many[:, None, None, None], oT, first)


def _full_attend(q, full, index, li, ap, la: LatentAttn, ctx: _Ctx):
    """A full layer's attention; ``([S, T, H, v], kept, scored)``:
    positions attended and positions the causal spans held, summed over
    the valid queries."""
    S, T = ctx.positions.shape
    if not la.index_heads:
        with jax.named_scope(LATENT_ATTN):
            o = _walk_attend(q, full, li, ap, la, ctx)
        span = jnp.where(ctx.row_ok, ctx.positions + 1, 0).sum()
        return o, span, span

    def slot(args, rows=slice(None)):
        q_n, q_r, qi, wi, pos, ok, bt_row = args
        return _select_attend(
            q_n[rows], q_r[rows], qi[rows], wi[rows], pos[rows], ok[rows],
            bt_row, full, index, li, ap, la,
        )

    args = tuple(_expand(q[n], ctx) for n in ("q_n", "q_r", "qi", "wi")) + (
        ctx.positions, ctx.row_ok, ctx.block_tables,
    )
    # every slot's first row in one batch: all there is of a decode slot
    o1, kept1, span1 = jax.vmap(lambda a: slot(a, slice(0, 1)))(args)
    if T == 1:
        return o1, kept1.sum(), span1.sum()
    many = ctx.n_valid > 1  # a prefill's or a verify's block, slot by slot
    H, v = la.n_heads, la.v_dim

    def block(a):
        return lax.cond(
            a[0], lambda: slot(a[1:]),
            lambda: (jnp.zeros((T, H, v), o1.dtype), jnp.int32(0),
                     jnp.int32(0)),
        )

    oT, keptT, spanT = lax.map(block, (many,) + args)
    first = jnp.pad(o1, ((0, 0), (0, T - 1), (0, 0), (0, 0)))
    o = jnp.where(many[:, None, None, None], oT, first)
    return (
        o, jnp.where(many, keptT, kept1).sum(),
        jnp.where(many, spanT, span1).sum(),
    )


def _gqa_write(pool, li, rows, plan, pg, off):
    """``rows`` ``[S, T, Hkv, hd]`` into layer ``li`` of a key or value
    pool: a block page by page under ``plan``, a single row at ``(pg,
    off)``."""
    from .paged import _merge_pages

    if plan is not None:
        return _merge_pages(pool, li, plan, rows.astype(pool.dtype))
    at = (li, pg[:, None], jnp.arange(rows.shape[2])[None, :], off[:, None])
    return pool.at[at].set(rows[:, 0].astype(pool.dtype))


def _gqa_attend(q, kp, vp, li, bt, ga: GqaAttn, ctx: _Ctx, name: str):
    """Queries ``q`` ``[S, T, H, hd]`` over layer ``li`` of ``kp`` / ``vp``
    under the table ``bt`` through the page walk, from the window's first
    key on where the kind has one: a continuation step as one query a
    slot, the ragged pass as every slot's block in one call (a decode row
    in it walks the short row block: one position); ``[S, T, H, hd]``."""
    kw = dict(scale=ga.softmax_scale, window=ga.window)
    kernel = ctx.kernel and kp.dtype == q.dtype

    def rows():  # ``vp`` None: the key rows are the values too
        kr = kp[li].astype(q.dtype)
        return kr, (kr if vp is None else vp[li].astype(q.dtype))

    if ctx.plan is None:  # a continuation step
        if kernel:
            o = paged_attention(q[:, 0], kp, vp, bt, ctx.att_len, layer=li,
                                name=name, **kw)
        else:
            o = paged_attention_ref(q[:, 0], *rows(), bt, ctx.att_len, **kw)
        return o[:, None]
    starts = ctx.positions[:, 0]
    if kernel:
        return ragged_paged_attention(q, kp, vp, bt, starts, ctx.n_valid,
                                      layer=li, name=name, **kw)
    return ragged_paged_attention_ref(
        q, *rows(), bt, starts, ctx.n_valid, **kw)


def _gqa_attention(x, lp, kind: str, li, pools: tuple, ctx: _Ctx):
    """:func:`_attention` for the grouped-query kinds: a ``gqa_full``
    layer's rows go to the pages of the slot's table, a ``gqa_window``
    layer's to the slot's ring."""
    cfg = ctx.cfg
    ga = cfg.latent_of(kind)
    ap = lp["attn"]

    def project(x, *rope):  # a kind without positions has no table
        return gqa_qkv(_pre(x, lp, cfg), ap, ga, *(rope or (None, None)),
                       eps=cfg.norm_eps)

    with jax.named_scope("attn"):
        q = _by_tile(project, ctx, x, *ctx.rope.get(kind, ()))
        qs, k, v = (_expand(q[n], ctx) for n in ("q", "k", "v"))
    if kind == "gqa_window":
        names, bt = ("wk", "wv"), ctx.ring_bt
        place = (ctx.ring_plan, ctx.ring_pg, ctx.write_off)
    else:
        names, bt = ("k", "v"), ctx.block_tables
        place = (ctx.plan, ctx.write_pg, ctx.write_off)
    beside = kind == "gqa_full" and kv_beside(ga)
    if beside:  # one row a position: the key, then the value
        k, v = jnp.concatenate([k, v], axis=-1), None
        qs = jnp.pad(qs, ((0, 0),) * 3 + ((0, ga.head_dim),))
    with jax.named_scope("kv_write"):
        kp = _gqa_write(getattr(pools, names[0]), li, k, *place)
        vp = None if beside else _gqa_write(
            getattr(pools, names[1]), li, v, *place)
    with jax.named_scope(f"tlink.{kind}"):
        o = _gqa_attend(qs, kp, vp, li, bt, ga, ctx, f"{kind}_attention")
        if beside:
            o = o[..., ga.head_dim:]
    with jax.named_scope("attn"):
        added = _post(
            _attn_out(_collect(o, ctx), q.get("gate"), ap, x.dtype, ctx),
            lp, ctx)
    return added, pools._replace(**{names[0]: kp, names[1]: vp})


def _pre(x, lp, cfg: ModelConfig):
    """A branch's input: normed, unless the model norms what the branch
    adds (module docstring)."""
    if cfg.norm_position == "post":
        return x
    return _rms(x, lp["ln1"]["scale"], cfg.norm_eps)


def _post(added, lp, ctx: _Ctx):
    """What a branch adds, normed where the model norms that."""
    cfg = ctx.cfg
    if cfg.norm_position != "post":
        return added
    return _by_tile(
        lambda a: _rms(a, lp["ln1"]["scale"], cfg.norm_eps), ctx, added)


def _begins(ctx: _Ctx):
    """The slots whose block is their sequence's first ``[S]``: no position
    lies before it."""
    return (ctx.positions[:, 0] == 0) & (ctx.n_valid > 0)


def _conv_pass(z, tail, taps, ctx: _Ctx):
    """The depthwise causal convolution of either pass over ``z`` (``x``'s
    layout) behind the slots' carried ``tail`` ``[S, kernel - 1, W]``:
    ``(c, tail)``, ``c`` float32 in ``x``'s layout and the tails as the
    pass leaves them."""
    if ctx.plan is None:  # a continuation step: ``kernel`` taps
        zc = jnp.concatenate([tail, z.astype(tail.dtype)], axis=1)
        c = short_conv_taps(zc, taps, 1)
        return c, jnp.where(ctx.row_ok[:, :, None], zc[:, 1:], tail)
    # a slot's rows behind its carried tail; no position lies before a
    # sequence's first
    tail = jnp.where(_begins(ctx)[:, None, None], 0, tail)
    zb = _expand(z, ctx).astype(tail.dtype)  # [S, C, W]
    zc = jnp.concatenate([tail, zb], axis=1)
    c = _collect(short_conv_taps(zc, taps, zb.shape[1]), ctx)
    # the tail behind the slot's last live row (a slot without rows keeps
    # its own)
    at = ctx.n_valid[:, None] + jnp.arange(tail.shape[1])[None, :]
    return c, jnp.take_along_axis(zc, at[:, :, None], axis=1)


def _short_conv(x, lp, li, pools: tuple, ctx: _Ctx):
    """:func:`_attention` for a ``conv`` layer (module docstring): what the
    gated short convolution adds to ``x``, and the slots' tails as the
    pass leaves them (layer ``li`` of ``pools.state``)."""
    cfg = ctx.cfg
    ap = lp["attn"]
    tail = pools.state[li]  # [S, kernel - 1, width]
    with jax.named_scope(SHORT_CONV):
        z, gate = _by_tile(lambda x: short_conv_in(
            _rms(x, lp["ln1"]["scale"], cfg.norm_eps), ap), ctx, x)
        c, new = _conv_pass(z, tail, ap["taps"], ctx)
        added = _by_tile(lambda gate, c: _mm(
            (gate.astype(jnp.float32) * c).astype(x.dtype), ap["w_out"]),
            ctx, gate, c)
    return added, pools._replace(state=pools.state.at[li].set(new))


def _gated_delta(x, lp, li, pools: tuple, ctx: _Ctx):
    """:func:`_attention` for a ``gated_delta`` layer (module docstring):
    what the layer adds to ``x``, and the slots' states and tails as the
    pass leaves them (layer ``li`` of ``pools.state`` / ``pools.tail``)."""
    cfg = ctx.cfg
    gd = cfg.latent_of(GATED_DELTA)
    ap = lp["attn"]
    state = pools.state
    with jax.named_scope(SHORT_CONV):
        z, gate, g, beta = _by_tile(
            lambda x: gated_delta_in(_pre(x, lp, cfg), ap, gd), ctx, x)
        c, tail = _conv_pass(z, pools.tail[li], ap["taps"], ctx)
    with jax.named_scope(GATED_DELTA_SCOPE):
        q, k, v = _by_tile(lambda c: gated_delta_qkv(c, gd), ctx, c)
        if ctx.plan is None:  # a continuation step
            args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
            active = ctx.row_ok[:, 0]
            if ctx.kernel:
                o, state = gated_delta_step(*args, state, active, li)
            else:
                o, new = gated_delta_step_ref(*args, state[li], active)
                state = state.at[li].set(new)
            o = o[:, None]  # [S, 1, H, dv]
        else:
            args = tuple(_expand(a, ctx) for a in (q, k, v, g, beta))
            if ctx.kernel:
                o, state = gated_delta_chunk(
                    *args, state, ctx.n_valid, _begins(ctx), li)
            else:
                o, new = gated_delta_chunk_ref(
                    *args, state[li], ctx.n_valid, _begins(ctx))
                state = state.at[li].set(new)
            o = _collect(o, ctx)
    with jax.named_scope("attn"):
        added = _post(_by_tile(
            lambda o, gate: gated_delta_out(
                o, gate, ap, cfg.norm_eps, x.dtype), ctx, o, gate), lp, ctx)
    return added, pools._replace(
        state=state, tail=pools.tail.at[li].set(tail))


def _attention(x, lp, kind: str, li, pools: tuple, ctx: _Ctx):
    """What the attention of one layer of ``kind`` (layer ``li`` of its
    kind's pools) adds to ``x`` ``[S, T, d]``, its rows written to the
    pools first; returns ``(added, pools)`` with ``pools`` = ``(full,
    index, slide, stats)``."""
    cfg = ctx.cfg
    if kind == CONV_KIND:
        return _short_conv(x, lp, li, pools, ctx)
    if kind == GATED_DELTA:
        return _gated_delta(x, lp, li, pools, ctx)
    if kind in SALA_KINDS:
        from . import sala

        return sala.attention(x, lp, kind, li, pools, ctx)
    if kind in GQA_KINDS:
        return _gqa_attention(x, lp, kind, li, pools, ctx)
    la = cfg.latent_of(kind)
    full, index, slide, stats = pools[:4]
    ap = lp["attn"]

    def project(x, cos, sin):
        h = _rms(x, lp["ln1"]["scale"], cfg.norm_eps)
        return latent_qkv(h, ap, la, cfg.norm_eps, cos, sin)

    with jax.named_scope("attn"):
        q = _by_tile(project, ctx, x, *ctx.rope[kind])
    with jax.named_scope("kv_write"):
        if kind == "sliding":
            slide = _write(slide, li, _expand(q["row"], ctx), ctx)
        else:
            full = _write(full, li, _expand(q["row"], ctx), ctx)
            if la.index_heads:
                index = _write(index, li, _expand(q["ki"], ctx), ctx)
    if kind == "sliding":
        with jax.named_scope(WINDOW_ATTN):
            o = _sliding_attend(q, slide, li, ap, la, ctx)
    else:
        o, kept, scored = _full_attend(q, full, index, li, ap, la, ctx)
        stats = stats.at[N_MOE_STATS:].add(
            jnp.stack([kept, scored]).astype(jnp.int32))
    with jax.named_scope("attn"):
        added = _attn_out(_collect(o, ctx), q.get("gate"), ap, x.dtype, ctx)
    return added, pools._replace(
        full=full, index=index, slide=slide, stats=stats)


def _layer(x, lp, kind: str, li, pools: tuple, ctx: _Ctx):
    """One layer: :func:`_attention`, then its MLP or its experts."""
    cfg = ctx.cfg
    S, T, d = x.shape
    added, pools = _attention(x, lp, kind, li, pools, ctx)

    post = cfg.norm_position == "post"  # norm what the branch adds

    def normed(x, added):
        with jax.named_scope("attn"):
            x = x + added
        return x, (x if post else _rms(x, lp["ln2"]["scale"], cfg.norm_eps))

    def dense(x, added):
        x, h = normed(x, added)
        with jax.named_scope("mlp"):
            y = gated_mlp(h, lp["mlp"])
            if post:
                y = _rms(y, lp["ln2"]["scale"], cfg.norm_eps)
            return x + y

    if "mlp" in lp:
        return _by_tile(dense, ctx, x, added), pools
    x, h = _by_tile(normed, ctx, x, added)
    with jax.named_scope(MOE):
        # the router and the shared expert by tile; the routed experts'
        # tiles follow the experts' rows, whatever the list holds
        y, ms = moe_mlp(
            h.reshape(S * T, d), lp["moe"], cfg, ctx.x_ok.reshape(-1),
            None if ctx.rows is None else partial(ctx.rows.by_tile, axis=0),
        )
        # each adds up over layers and steps
        pools = pools._replace(stats=pools.stats.at[:N_MOE_STATS].add(ms))
    return _by_tile(jnp.add, ctx, x, y.reshape(S, T, d)), pools


# ---------------------------------------------------------------------------
# The layer loop
# ---------------------------------------------------------------------------


class Pools(NamedTuple):
    """What the layer loop carries of a patterned model's cache: the page
    pools there are, the step's counts and, for lightning layers, the
    slots' states. A field the model has not is None (no leaf)."""

    full: jax.Array | None
    index: jax.Array | None
    slide: jax.Array | None
    stats: jax.Array
    k: jax.Array | None = None
    v: jax.Array | None = None
    ksum: jax.Array | None = None
    state: jax.Array | None = None
    wk: jax.Array | None = None
    wv: jax.Array | None = None
    tail: jax.Array | None = None


def cache_pools(cache: LatentPagedCache) -> Pools:
    return Pools(*(getattr(cache, n) for n in Pools._fields))


def with_pools(cache: LatentPagedCache, pools: Pools, **kw):
    return replace(cache, **pools._asdict(), **kw)


def layer_loop(lead, periods, tail, pat: Pattern, x, carry, layer,
               whole=None):
    """THE layer loop of the serving step, for every model: ``layer(x, lp,
    kind, li, carry) -> (x, carry)`` over the lead layers (unrolled), a
    scan over the periods (one traced period whatever the depth) and the
    tail, ``li`` the layer's index among the layers of its kind. ``carry``
    (the page pools) is carried whole beside the activations: it is not
    the scan's ``xs`` / ``ys``, so a layer's pool is never sliced out of
    its stack, the updated one never stacked back, and the loop's result
    is the buffer it was given (an enclosing loop, the decode
    continuation, carries it without a copy). A dense GQA model is the
    one-kind case: no lead, a period of one layer, ``li`` the scan's own
    index (``paged._scan_layers``). ``whole``: for each place of a
    period, the leaves of ``lp["moe"]`` that the scan must NOT slice a
    period at a time (None: none): they reach ``layer`` stacked over the
    periods with ``"stacked"``, the period's index, beside them. The
    scan's slice of an ``xs`` leaf is a copy where its reader cannot fuse
    it, and the expert loop reads one expert of a layer's stack by a
    dynamic index: five periods of 20 experts copied 0.94 GB of expert
    weights a layer and step (PERF.md section 6, PR 34). Returns ``(x,
    carry)``."""
    seen = dict.fromkeys(pat.lead + pat.period + pat.tail, 0)

    def single(x, carry, lp, kind):
        x, carry = layer(x, lp, kind, jnp.int32(seen[kind]), carry)
        seen[kind] += 1
        return x, carry

    for lp, kind in zip(lead, pat.lead):
        x, carry = single(x, carry, lp, kind)
    if pat.n_periods:
        index_of = {k: pat.kind_index(k) for k in set(pat.period)}

        def period(c, xs):
            x, carry = c
            lps, i = xs
            for j, (lp, kind) in enumerate(zip(lps, pat.period)):
                if whole is not None and whole[j] is not None:
                    lp = {**lp, "moe": {**lp["moe"], **whole[j], "stacked": i}}
                base, per, offs = index_of[kind]
                li = i if (base, per, offs[j]) == (0, 1, 0) else (
                    base + i * per + offs[j])
                x, carry = layer(x, lp, kind, li, carry)
            return (x, carry), None

        (x, carry), _ = lax.scan(
            period, (x, carry), (periods, jnp.arange(pat.n_periods))
        )
        for kind in set(pat.period):
            seen[kind] += pat.n_periods * pat.period.count(kind)
    for lp, kind in zip(tail, pat.tail):
        x, carry = single(x, carry, lp, kind)
    return x, carry


def run_layers(params, x, cache: LatentPagedCache, ctx: _Ctx):
    """Every layer of a patterned model over ``x``: :func:`layer_loop`
    with the pools per kind as its carry and the periods' expert stacks
    kept whole; a model of sparse / lightning layers, whose order has no
    period, loops over runs of one kind (engine/sala.py). Returns ``(x,
    pools)``."""
    if is_sala(ctx.cfg):
        from . import sala

        return sala.run_layers(params, x, cache_pools(cache), ctx)
    sliced, whole = [], []
    for lp in params["periods"]:
        moe = lp.get("moe", {})
        whole.append({k: moe[k] for k in EXPERT_STACKS if k in moe} or None)
        sliced.append(lp if whole[-1] is None else {**lp, "moe": {
            k: v for k, v in moe.items() if k not in EXPERT_STACKS}})
    return layer_loop(
        params["lead"], tuple(sliced), params["tail"],
        pattern_of(ctx.cfg), x, cache_pools(cache),
        lambda x, lp, kind, li, pools: _layer(x, lp, kind, li, pools, ctx),
        whole=tuple(whole),
    )


def _ring_place(cache, positions, n_valid=None, active=None) -> dict:
    """Where a pass writes and reads the ring layers (nothing for a model
    without them): their table, and the ragged pass's write plan or the
    continuation step's page in it."""
    if cache.wk is None:
        return {}
    from .paged import _page_write_plan

    page, n_pp = cache.page_size, cache.pages_per_slot
    bt = ring_table(cache.max_slots, n_pp, cache.ring_pages)
    start = positions[:, 0]
    if n_valid is not None:
        return dict(ring_bt=bt, ring_plan=_page_write_plan(
            bt, start, n_valid, page, n_pp, positions.shape[1]))
    pg = jnp.take_along_axis(
        bt, jnp.minimum(start // page, n_pp - 1)[:, None], axis=1)[:, 0]
    return dict(ring_bt=bt, ring_pg=jnp.where(active, pg, 0))


def _ragged_ctx(cache, cfg: ModelConfig, kernel: bool, *, positions, valid,
                plan, n_valid, rows=None, rope_positions=None) -> _Ctx:
    """``rows`` / ``rope_positions``: the flat rung's row map and its
    rows' positions ``[1, R]`` (rope is applied where the rows are
    projected: over ``x``'s layout)."""
    return _Ctx(
        cfg=cfg, kernel=kernel, block_tables=cache.block_tables,
        positions=positions, row_ok=valid, rope=rope_by_kind(
            cfg, positions if rope_positions is None else rope_positions),
        plan=plan, n_valid=n_valid, rows=rows,
        **_ring_place(cache, positions, n_valid),
    )


def _decode_ctx(cache, cfg: ModelConfig, kernel: bool, *, positions, active,
                write_pg, write_off, att_len) -> _Ctx:
    return _Ctx(
        cfg=cfg, kernel=kernel, block_tables=cache.block_tables,
        positions=positions, row_ok=active[:, None],
        rope=rope_by_kind(cfg, positions), write_pg=write_pg,
        write_off=write_off, att_len=att_len,
        **_ring_place(cache, positions, active=active),
    )


def ragged_layers(params, x, cache, cfg: ModelConfig, kernel: bool, **place):
    """The ragged pass's layers over the packed block ``x`` ``[S, C, d]``
    (the step's first phase: the step's counts start here); ``place``:
    ``positions``, ``valid``, ``plan``, ``n_valid`` and, on the flat rung
    (``x`` ``[1, R, d]``), ``rows`` and ``rope_positions``."""
    cache = replace(cache, stats=jnp.zeros_like(cache.stats))
    ctx = _ragged_ctx(cache, cfg, kernel, **place)
    # the pass stays ONE top-level loop of the step program, as the dense
    # model's layer scan is: a trace tells the step's phases apart by the
    # order of its top-level loops (paged.STEP_PHASES), and this pass
    # holds loops of its own (the expert tiles, the slots' blocks) beside
    # unrolled layers. So the layers run as the single trip of a loop
    # whose bound is data, which no pass can inline (the verify walk's
    # trick, ``_verify_emit``).
    once = jnp.minimum(jnp.sum(place["n_valid"] >= 0), 1)

    def trip(c):
        x, pools = run_layers(params, c[1], with_pools(cache, c[2]), ctx)
        return c[0] + 1, x, pools

    _, x, pools = lax.while_loop(
        lambda c: c[0] < once, trip, (jnp.int32(0), x, cache_pools(cache))
    )
    return x, pools


def decode_layers(params, x, cache, cfg: ModelConfig, kernel: bool, **place):
    """One continuation step's layers over ``x`` ``[S, 1, d]``; ``place``:
    ``positions``, ``active``, ``write_pg``, ``write_off``, ``att_len``."""
    return run_layers(
        params, x, cache, _decode_ctx(cache, cfg, kernel, **place)
    )


def attention_only(lp, x, cache, cfg: ModelConfig, kernel: bool, kind: str,
                   li, **place):
    """What the attention of ONE layer adds (``lp``: its ``ln1`` and
    ``attn``; layer ``li`` of ``kind``'s pools) to hidden states given from
    outside, through the pages as either pass places them (``place`` as
    :func:`ragged_layers` or :func:`decode_layers` take it). Nothing serves
    through it: ``paged.make_layer_probe`` compares a layer's cached rows
    and attention with a reference on the same input."""
    make = _ragged_ctx if "plan" in place else _decode_ctx
    return _attention(
        x, lp, kind, li, cache_pools(cache), make(cache, cfg, kernel, **place)
    )


__all__ = [
    "FULL_KERNEL", "LatentPagedCache", "Pools",
    "WINDOW_KERNEL", "attention_only",
    "cache_pools",
    "decode_layers", "kv_beside", "layer_loop", "restore_window",
    "ragged_layers",
    "ring_len", "ring_table", "run_layers", "snapshot_pages", "take_window",
    "unsupported", "window_snapshot_pool", "with_pools",
]
