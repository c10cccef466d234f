"""The page cache and the layer loop of a patterned model (layers of more
than one kind, models/latent.py) under the one serving step
(engine/paged.py::paged_ragged_step).

**Pools per kind, one page table.** A slot's block-table row names the
same physical pages in every pool. A full layer caches a latent row and a
selector key a position, a sliding layer a (wider) latent row:

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
* full layer, either pass: the selector scores the live span, the
  ``index_topk`` best rows are gathered and attended absorbed (each query
  has rows of its own). The ragged pass does this for every slot's first
  row in one batch and, slot by slot, for the whole block of a slot that
  holds more than one valid row (a prefill, a verify) — so a decode row in
  the block costs a decode row. A context that holds no more than
  ``index_topk`` positions is attended whole and nothing is scored.

First support keeps every page of a slot for the sliding layers and reads
the window's span only; freeing pages behind the window is ROADMAP R2.
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax import lax

from ..models.base import LatentAttn, ModelConfig
from ..models.latent import (
    _rms,
    INDEX_SELECT,
    LATENT_ATTN,
    MOE,
    NEG_INF,
    STEP_STATS,
    WINDOW_ATTN,
    absorbed_output,
    absorbed_query,
    attend_absorbed,
    attend_materialised,
    gated_mlp,
    index_scores,
    kind_counts,
    latent_qkv,
    moe_mlp,
    Pattern,
    pattern_of,
    rope_by_kind,
    top_k_positions,
)
from ..models.quant import matmul as _mm
from ..ops.attention import paged_attention, paged_attention_ref

WINDOW_KERNEL = "latent_window_attention"  # the pallas_call's name


@jax.tree_util.register_dataclass
@dataclass
class LatentPagedCache:
    """Paged cache of a patterned model (module docstring). Same control
    state as :class:`~tensorlink_tpu.engine.paged.PagedKVCache`
    (``block_tables``, ``lengths``), pools per kind in its layout."""

    full: jax.Array
    index: jax.Array
    slide: jax.Array
    block_tables: jax.Array  # int32 [S, pages_per_slot]
    lengths: jax.Array  # int32 [S]
    stats: jax.Array  # int32 [len(STEP_STATS)]: this step's counts

    POOLS = ("full", "index", "slide")  # pages in the model dtype

    @classmethod
    def init(cls, cfg: ModelConfig, max_slots: int, *, page_size: int = 16,
             max_len: int | None = None, dtype=None,
             n_pages: int | None = None) -> "LatentPagedCache":
        S_max = max_len or cfg.max_seq_len
        n_pp = -(-S_max // page_size)
        P = n_pages if n_pages is not None else 1 + max_slots * n_pp
        dt = dtype or cfg.dtype
        n = kind_counts(cfg)
        full, slide = cfg.latent_of("full"), cfg.latent_of("sliding")

        def pool(layers, width):
            return jnp.zeros((layers, P, 1, page_size, width), dt)

        return cls(
            full=pool(n["full"], full.pool_dim),
            index=pool(n["full"], max(full.index_dim, 1)),
            slide=pool(n["sliding"], slide.pool_dim),
            block_tables=jnp.zeros((max_slots, n_pp), jnp.int32),
            lengths=jnp.zeros((max_slots,), jnp.int32),
            stats=jnp.zeros((len(STEP_STATS),), jnp.int32),
        )

    @property
    def quantized(self) -> bool:
        return False

    @property
    def page_size(self) -> int:
        return self.full.shape[3]

    @property
    def n_pages(self) -> int:
        return self.full.shape[1]

    @property
    def max_slots(self) -> int:
        return self.block_tables.shape[0]

    @property
    def pages_per_slot(self) -> int:
        return self.block_tables.shape[1]

    @property
    def pool_bytes(self) -> int:
        return sum(
            getattr(self, n).size * getattr(self, n).dtype.itemsize
            for n in self.POOLS
        )


def unsupported(cfg: ModelConfig) -> str | None:
    """Why the slot engine cannot serve a patterned config; None when it
    can: the two kinds this module implements, each with sizes."""
    kinds = set(cfg.layer_kinds)
    have = {k for k, _ in cfg.latent}
    if not kinds <= {"full", "sliding"} or have != {"full", "sliding"}:
        return f"layer kinds {sorted(kinds)} (served: full, sliding)"
    if cfg.latent_of("sliding").window is None:
        return "a sliding layer without a window"
    if cfg.latent_of("full").window is not None:
        return "a window on the full layers"
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
    return attend_materialised(q["q_n"], q["q_r"], rows, mask, ap, la)


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
    if not la.index_heads or Kc <= la.index_topk:
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


def _full_attend(q, full, index, li, ap, la: LatentAttn, ctx: _Ctx):
    """A full layer's attention; ``([S, T, H, v], kept, scored)``."""
    S, T = ctx.positions.shape

    def slot(args, rows=slice(None)):
        q_n, q_r, qi, wi, pos, ok, bt_row = args
        return _select_attend(
            q_n[rows], q_r[rows], qi[rows], wi[rows], pos[rows], ok[rows],
            bt_row, full, index, li, ap, la,
        )

    zi = jnp.zeros((S, T, 1, 1), q["q_n"].dtype)
    args = (
        q["q_n"], q["q_r"], q.get("qi", zi), q.get("wi", zi[..., 0]),
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


def _attention(x, lp, kind: str, li, pools: tuple, ctx: _Ctx):
    """What the attention of one layer of ``kind`` (layer ``li`` of its
    kind's pools) adds to ``x`` ``[S, T, d]``, its rows written to the
    pools first; returns ``(added, pools)`` with ``pools`` = ``(full,
    index, slide, stats)``."""
    cfg = ctx.cfg
    la = cfg.latent_of(kind)
    full, index, slide, stats = pools
    S, T, d = x.shape
    ap = lp["attn"]
    cos, sin = ctx.rope[kind]
    with jax.named_scope("attn"):
        h = _rms(x, lp["ln1"]["scale"], cfg.norm_eps)
        q = latent_qkv(h, ap, la, cfg.norm_eps, cos, sin)
    with jax.named_scope("kv_write"):
        if kind == "sliding":
            slide = _write(slide, li, q["row"], ctx)
        else:
            full = _write(full, li, q["row"], ctx)
            if la.index_heads:
                index = _write(index, li, q["ki"], ctx)
    if kind == "sliding":
        with jax.named_scope(WINDOW_ATTN):
            o = _sliding_attend(q, slide, li, ap, la, ctx)
    else:
        o, kept, scored = _full_attend(q, full, index, li, ap, la, ctx)
        stats = stats.at[5:7].add(jnp.stack([kept, scored]).astype(jnp.int32))
    with jax.named_scope("attn"):
        o = (o.astype(jnp.float32) * q["gate"][..., None]).astype(x.dtype)
        added = _mm(o.reshape(S, T, -1), ap["wo"])
    return added, (full, index, slide, stats)


def _layer(x, lp, kind: str, li, pools: tuple, ctx: _Ctx):
    """One layer: :func:`_attention`, then its MLP or its experts."""
    cfg = ctx.cfg
    S, T, d = x.shape
    added, (full, index, slide, stats) = _attention(
        x, lp, kind, li, pools, ctx
    )
    with jax.named_scope("attn"):
        x = x + added
    h = _rms(x, lp["ln2"]["scale"], cfg.norm_eps)
    if "mlp" in lp:
        with jax.named_scope("mlp"):
            return x + gated_mlp(h, lp["mlp"]), (full, index, slide, stats)
    with jax.named_scope(MOE):
        y, ms = moe_mlp(
            h.reshape(S * T, d), lp["moe"], cfg, ctx.row_ok.reshape(-1)
        )
        stats = stats.at[:5].add(ms)  # each adds up over layers and steps
    return x + y.reshape(S, T, d), (full, index, slide, stats)


# ---------------------------------------------------------------------------
# The layer loop
# ---------------------------------------------------------------------------


def cache_pools(cache: LatentPagedCache) -> tuple:
    return (cache.full, cache.index, cache.slide, cache.stats)


def with_pools(cache: LatentPagedCache, pools: tuple, **kw):
    full, index, slide, stats = pools
    return replace(
        cache, full=full, index=index, slide=slide, stats=stats, **kw
    )


def layer_loop(lead, periods, tail, pat: Pattern, x, carry, layer):
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
    index (``paged._scan_layers``). Returns ``(x, carry)``."""
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
    with the pools per kind as its carry. Returns ``(x, pools)``."""
    return layer_loop(
        params["lead"], params["periods"], params["tail"],
        pattern_of(ctx.cfg), x, cache_pools(cache),
        lambda x, lp, kind, li, pools: _layer(x, lp, kind, li, pools, ctx),
    )


def _ragged_ctx(cache, cfg: ModelConfig, kernel: bool, *, positions, valid,
                plan, n_valid) -> _Ctx:
    return _Ctx(
        cfg=cfg, kernel=kernel, block_tables=cache.block_tables,
        positions=positions, row_ok=valid, rope=rope_by_kind(cfg, positions),
        plan=plan, n_valid=n_valid,
    )


def _decode_ctx(cache, cfg: ModelConfig, kernel: bool, *, positions, active,
                write_pg, write_off, att_len) -> _Ctx:
    return _Ctx(
        cfg=cfg, kernel=kernel, block_tables=cache.block_tables,
        positions=positions, row_ok=active[:, None],
        rope=rope_by_kind(cfg, positions), write_pg=write_pg,
        write_off=write_off, att_len=att_len,
    )


def ragged_layers(params, x, cache, cfg: ModelConfig, kernel: bool, **place):
    """The ragged pass's layers over the packed block ``x`` ``[S, C, d]``
    (the step's first phase: the step's counts start here); ``place``:
    ``positions``, ``valid``, ``plan``, ``n_valid``."""
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
    "LatentPagedCache", "WINDOW_KERNEL", "attention_only", "cache_pools",
    "decode_layers", "layer_loop", "ragged_layers", "run_layers",
    "unsupported", "with_pools",
]
