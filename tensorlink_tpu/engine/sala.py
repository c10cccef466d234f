"""The cache and the layers of block-sparse GQA and lightning
(linear-attention) layers (models/sala.py) under the one serving step:
engine/latent.py's ``_attention`` / ``_layer`` / ``run_layers`` hand these
kinds over to this module, and ``LatentPagedCache`` carries their state.

**Two kinds of state under one slot table.**

    k, v    [Ls, P, Hkv, page, hd]    the sparse layers' keys and values
    ksum    [Ls, P, Hkv, hd] float32  one sum of keys a page: pooled key j
                                      is pages j and j + 1 of a slot's table
    state   [Ll, S, H, hd, hd] float32  a lightning layer's state a slot

``k`` / ``v`` / ``ksum`` are page pools (page axis 1): the page operations,
copy-on-write and the prefix trie move them with the page. ``state``
belongs to the slot; what the trie shares of it is a *snapshot*
(``[Ll, H, hd, hd]``, the engine's snapshot pool: engine/continuous.py),
taken where a chunk of the ragged pass ended.

**Attention by pass.** Sparse layer, a slot's first row of either pass
(all there is of a decoding slot): one selection a slot and kv head, the
kept blocks' pages laid out as a table, and the page walk over that table
(``block_sparse_attention``). A slot's block of more rows (a prefill): a
selection a ROW, applied as a mask on a dense walk over the slot's live
span in tiles of keys (XLA; a walk over a table a row is what this PR
leaves open). Lightning layer: the chunk form in the ragged pass (rows
past ``n_valid`` leave the state alone), one step in a continuation step.

**The layer loop** runs over runs of one kind (the order has no period):
one loop a run, the layer's parameters read out of the kind's whole stack
by the loop's index, the pools and the state carried whole.
"""

from __future__ import annotations

from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..models.base import LinearAttn, SparseAttn
from ..models.latent import _rms, NEG_INF, STEP_STATS, gated_mlp
from ..models.quant import matmul as _mm
from ..models.sala import (
    BLOCK_SELECT,
    LIGHTNING,
    SALA_STATS,
    SPARSE_ATTN,
    block_scores,
    kept_table,
    lightning_chunk_ref,
    lightning_step_ref,
    pooled_keys,
    qkv,
    runs_of,
    select_blocks,
)
from ..models.transformer import apply_rope
from ..ops.attention import block_sparse_attention, block_sparse_attention_ref
from ..ops.lightning import lightning_attention_chunk, lightning_attention_step
from .latent import _collect, _expand

SPARSE_KERNEL = "block_sparse_attention"
KEY_TILE = 2048  # key positions a trip of the masked dense walk attends
_N_BASE = len(STEP_STATS)  # where SALA_STATS start in the stats vector


def init_pools(cfg, max_slots: int, n_pages: int, page_size: int, dt) -> dict:
    """The arrays of module docstring for ``cfg``'s kinds, by cache field;
    None for a kind the model has not."""
    sizes = dict(cfg.latent)
    out = dict(k=None, v=None, ksum=None, state=None)
    n = cfg.layer_kinds.count("sparse")
    if n:
        sa = sizes["sparse"]
        shape = (n, n_pages, sa.n_kv_heads, page_size, sa.head_dim)
        out |= dict(
            k=jnp.zeros(shape, dt), v=jnp.zeros(shape, dt),
            ksum=jnp.zeros(shape[:3] + (sa.head_dim,), jnp.float32),
        )
    n = cfg.layer_kinds.count("lightning")
    if n:
        la = sizes["lightning"]
        out["state"] = jnp.zeros(
            (n, max_slots, la.n_heads, la.head_dim, la.head_dim), jnp.float32)
    return out


# -- what a slot holds whole, and its snapshots (engine/continuous.py) -----
# A slot of a model with recurrent layers holds, beside its pages, the
# arrays ``ModelConfig.slot_arrays`` names (a lightning layer's states; a
# conv layer's tails; a gated-delta layer's states AND tails), each ``[L,
# S, ...]``. A snapshot is one slot's part of every one of them, taken and
# restored TOGETHER; the engine's pool is a dict of ``[N, L, ...]`` arrays
# under the same names. These are the entry points for all of them; where
# a model holds ONE array, the array itself stands for ``{"state": array}``
# (in :func:`held`'s result and as the pool).


def _named(cache) -> dict:
    return {n: getattr(cache, n) for n in ("state", "tail")
            if getattr(cache, n, None) is not None}


def held(cache):
    """The arrays ``cache`` holds a slot at a time, by field name (the
    array, where it is one)."""
    named = _named(cache)
    return named["state"] if set(named) == {"state"} else named


def snapshot_pool(cache, n: int):
    """``n`` empty places for a snapshot of everything :func:`held`."""
    return jax.tree.map(
        lambda a: jnp.zeros((n,) + a.shape[:1] + a.shape[2:], a.dtype),
        held(cache))


def tree_bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


# tlint: one-program
@partial(jax.jit, donate_argnames=("snaps",))
def take_snapshot(snaps, state, slot, idx):
    """``slot``'s part of ``state`` (an array, or :func:`held`'s dict) into
    place ``idx`` of the snapshot pool ``snaps`` (of the same form)."""
    return jax.tree.map(lambda sn, st: sn.at[idx].set(st[:, slot]),
                        snaps, state)


def _set_slot(cache, slot, value):
    """``value(name, array)`` as ``slot``'s part of every array held."""
    return replace(cache, **{
        name: a.at[:, slot].set(value(name, a))
        for name, a in _named(cache).items()})


# tlint: one-program
@partial(jax.jit, donate_argnames=("cache",))
def restore_snapshot(cache, snaps, slot, idx):
    """Place ``idx`` of the snapshot pool as what ``slot`` holds."""
    if not isinstance(snaps, dict):
        snaps = {"state": snaps}
    return _set_slot(cache, slot, lambda name, _: snaps[name][idx])


# tlint: one-program
@partial(jax.jit, donate_argnames=("cache",))
def zero_state(cache, slot):
    """``slot`` starts a sequence: no position has been seen."""
    return _set_slot(cache, slot, lambda _, a: jnp.zeros((), a.dtype))


# ---------------------------------------------------------------------------
# The sparse layer
# ---------------------------------------------------------------------------


def _write_kv(pools, li, k, v, ctx):
    """The block's keys and values into layer ``li``'s pages, then the
    key sum of every page the block touched, from the page as it now
    stands (positions at or past the slot's new length masked: a copied
    page's tail, a former owner's rows)."""
    from .paged import _merge_pages

    page, Hkv = pools.k.shape[3], k.shape[2]
    first = ctx.positions[:, 0] // page
    if ctx.plan is not None:
        kp = _merge_pages(pools.k, li, ctx.plan, k.astype(pools.k.dtype))
        vp = _merge_pages(pools.v, li, ctx.plan, v.astype(pools.v.dtype))
        target = ctx.plan[0]  # [S, n_pg]
        new_len = ctx.positions[:, 0] + ctx.n_valid
    else:
        at = (li, ctx.write_pg[:, None], jnp.arange(Hkv)[None, :],
              ctx.write_off[:, None])
        kp = pools.k.at[at].set(k[:, 0].astype(pools.k.dtype))
        vp = pools.v.at[at].set(v[:, 0].astype(pools.v.dtype))
        target = ctx.write_pg[:, None]
        new_len = ctx.att_len
    n_pg = target.shape[1]
    pos = ((first[:, None] + jnp.arange(n_pg)[None, :]) * page)[..., None] \
        + jnp.arange(page)  # [S, n_pg, page]
    live = (pos < new_len[:, None, None])[:, :, None, :, None]
    rows = kp[li, target].astype(jnp.float32)  # [S, n_pg, Hkv, page, hd]
    sums = jnp.where(live, rows, 0.0).sum(3)
    ksum = pools.ksum.at[li, target].set(sums)
    return pools._replace(k=kp, v=vp, ksum=ksum)


def _select(q, pos, sums, sa: SparseAttn):
    """The blocks kept for one slot's queries ``q`` ``[R, H, d]`` at
    ``pos`` ``[R]``, from its pages' key sums ``[n_pp, Hkv, d]``:
    ``[R, Hkv, NB]`` bool."""
    with jax.named_scope(BLOCK_SELECT):
        sc = block_scores(q, pooled_keys(sums, sa), pos, sa)
        return select_blocks(sc, pos, sa)


def _first_rows(q1, t, live, pools, li, sa: SparseAttn, ctx):
    """One query position a slot (``q1`` ``[S, H, d]`` at ``t`` ``[S]``,
    ``live`` ``[S]``) through the walk over its kept blocks; ``(o [S, H,
    d], kept)`` with ``kept`` ``[S, Hkv, NB]``."""
    bt = ctx.block_tables
    page = pools.k.shape[3]
    sums = pools.ksum[li, bt]  # [S, n_pp, Hkv, d]
    kept = jax.vmap(
        lambda q, p, s: _select(q[None], p[None], s, sa)[0])(q1, t, sums)
    NB = kept.shape[-1]
    table, count = kept_table(kept, min(sa.max_kept, NB))
    ppb = sa.block // page
    pages = (table[..., None] * ppb + jnp.arange(ppb)).reshape(
        table.shape[:2] + (-1,))
    pages = jnp.minimum(pages, bt.shape[1] - 1)
    vt = jnp.take_along_axis(
        jnp.broadcast_to(bt[:, None, :], pages.shape[:2] + bt.shape[1:]),
        pages, axis=-1)
    # every kept block but the query's own lies whole before the query
    length = jnp.where(
        live[:, None], (count - 1) * sa.block + (t % sa.block + 1)[:, None], 0)
    with jax.named_scope(SPARSE_ATTN):
        if ctx.kernel:
            o = block_sparse_attention(
                q1, pools.k, pools.v, vt, length, scale=sa.softmax_scale,
                layer=li, name=SPARSE_KERNEL)
        else:
            o = block_sparse_attention_ref(
                q1, pools.k[li], pools.v[li], vt, length,
                scale=sa.softmax_scale)
    return o, kept


def _block_rows(q, pos, ok, bt_row, pools, li, sa: SparseAttn):
    """One slot's block of queries (``q`` ``[T, H, d]`` at ``pos`` ``[T]``,
    ``ok`` ``[T]``): a selection a row, as a mask on a dense walk over the
    slot's live span, ``KEY_TILE`` keys a trip with a running softmax.
    ``(o [T, H, d], kept [T, Hkv, NB])``."""
    T, H, d = q.shape
    page = pools.k.shape[3]
    Hkv = sa.n_kv_heads
    G = H // Hkv
    kept = _select(q, pos, pools.ksum[li, bt_row], sa)
    NB = kept.shape[-1]
    tile = min(KEY_TILE, NB * sa.block)
    n_tiles = -(-NB * sa.block // tile)
    bpt, ppt = tile // sa.block, tile // page
    keptp = jnp.pad(kept, ((0, 0), (0, 0), (0, n_tiles * bpt - NB)))
    btp = jnp.pad(bt_row, (0, n_tiles * ppt - bt_row.shape[0]))
    last = jnp.max(jnp.where(ok, pos, -1))
    qg = q.reshape(T, Hkv, G, d).transpose(1, 0, 2, 3).reshape(Hkv, T * G, d)
    rows_pos = jnp.repeat(pos, G)  # [T * G]
    rows_ok = jnp.repeat(ok, G)

    def trip(i, c):
        m, l, acc = c
        pages = lax.dynamic_slice_in_dim(btp, i * ppt, ppt)
        k = pools.k[li, pages].transpose(1, 0, 2, 3).reshape(Hkv, tile, d)
        v = pools.v[li, pages].transpose(1, 0, 2, 3).reshape(Hkv, tile, d)
        kb = lax.dynamic_slice_in_dim(keptp, i * bpt, bpt, axis=2)
        kb = jnp.repeat(kb.transpose(1, 0, 2), G, axis=1)  # [Hkv, T*G, bpt]
        k_pos = i * tile + jnp.arange(tile)
        mask = (
            jnp.repeat(kb, sa.block, axis=2)
            & (k_pos[None, None, :] <= rows_pos[None, :, None])
            & rows_ok[None, :, None]
        )
        sc = jnp.einsum("grd,gkd->grk", qg, k,
                        preferred_element_type=jnp.float32) * sa.softmax_scale
        sc = jnp.where(mask, sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1, keepdims=True))
        alpha = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - m_new))
        p = jnp.where(mask, jnp.exp(sc - m_new), 0.0)
        l = l * alpha + p.sum(-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "grk,gkd->grd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (
        jnp.full((Hkv, T * G, 1), NEG_INF, jnp.float32),
        jnp.zeros((Hkv, T * G, 1), jnp.float32),
        jnp.zeros((Hkv, T * G, d), jnp.float32),
    )
    with jax.named_scope(SPARSE_ATTN):
        _, l, acc = lax.fori_loop(0, (last + tile) // tile, trip, init)
        o = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    o = o.reshape(Hkv, T, G, d).transpose(1, 0, 2, 3).reshape(T, H, d)
    return o, kept


def _count_blocks(kept, pos, ok, sa: SparseAttn):
    """``[blocks kept, blocks visible, rows under dense_len]`` of one
    slot's queries ``ok``: ``kept`` ``[R, Hkv, NB]``, ``pos`` / ``ok``
    ``[R]``."""
    n_kept = jnp.where(ok[:, None], kept.sum(-1), 0).sum()
    visible = jnp.where(ok, pos // sa.block + 1, 0).sum() * kept.shape[1]
    dense = (ok & (pos < sa.dense_len)).sum()
    return jnp.stack([n_kept, visible, dense]).astype(jnp.int32)


def _sparse(h, ap, li, pools, sa: SparseAttn, ctx):
    """A sparse layer's gated attention output ``[S, T, H hd]`` over the
    normed input ``h``, its rows written first; ``(o, pools)``."""
    cfg = ctx.cfg
    T = ctx.positions.shape[1]
    H, hd = sa.n_heads, sa.head_dim
    with jax.named_scope("attn"):
        q, k, v, gate = qkv(h, ap, H, sa.n_kv_heads, hd, cfg.norm_eps, _mm)
        q, k, v = (_expand(a, ctx) for a in (q, k, v))
    with jax.named_scope("kv_write"):
        pools = _write_kv(pools, li, k, v, ctx)
    t0 = ctx.positions[:, 0]
    live = ctx.att_len > 0 if ctx.plan is None else ctx.n_valid > 0
    o1, kept1 = _first_rows(q[:, 0], t0, live, pools, li, sa, ctx)
    count = jax.vmap(lambda kp, p, okk: _count_blocks(kp, p, okk, sa))
    counts = count(kept1[:, None], t0[:, None], live[:, None])  # [S, 3]
    o = o1[:, None]
    if T > 1:
        many = ctx.n_valid > 1  # a prefill's block, slot by slot
        NB = kept1.shape[-1]

        def block(a):
            return lax.cond(
                a[0], lambda: _block_rows(*a[1:], pools, li, sa),
                lambda: (jnp.zeros((T, H, hd), q.dtype),
                         jnp.zeros((T, sa.n_kv_heads, NB), bool)),
            )

        oT, keptT = lax.map(
            block, (many, q, ctx.positions, ctx.row_ok, ctx.block_tables))
        first = jnp.pad(o, ((0, 0), (0, T - 1), (0, 0), (0, 0)))
        o = jnp.where(many[:, None, None, None], oT, first)
        counts = jnp.where(
            many[:, None], count(keptT, ctx.positions, ctx.row_ok), counts)
    stats = pools.stats.at[_N_BASE:_N_BASE + 3].add(counts.sum(0))
    with jax.named_scope("attn"):
        o = _collect(o, ctx)
        o = (o.reshape(gate.shape).astype(jnp.float32) * gate).astype(h.dtype)
    return o, pools._replace(stats=stats)


# ---------------------------------------------------------------------------
# The lightning layer
# ---------------------------------------------------------------------------


def _lightning(h, ap, li, pools, la: LinearAttn, ctx):
    """A lightning layer's normed, gated output ``[S, T, H hd]`` over the
    normed input ``h``, the slots' states advanced; ``(o, pools)``."""
    cfg = ctx.cfg
    H, hd = la.n_heads, la.head_dim
    with jax.named_scope("attn"):
        q, k, v, gate = qkv(h, ap, H, H, hd, cfg.norm_eps, _mm)
        cos, sin = ctx.rope["lightning"]
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        q, k, v = (_expand(a, ctx) for a in (q, k, v))
    slopes = jnp.asarray(la.slopes(), jnp.float32)
    state = pools.state
    with jax.named_scope(LIGHTNING):
        if ctx.plan is None:  # a continuation step
            active = ctx.row_ok[:, 0]
            if ctx.kernel:
                o, state = lightning_attention_step(
                    q[:, 0], k[:, 0], v[:, 0], state, slopes, active, li)
            else:
                o, new = lightning_step_ref(
                    q[:, 0], k[:, 0], v[:, 0], state[li], slopes, active)
                state = state.at[li].set(new)
            o = o[:, None]  # [S, 1, H, hd]
            n_rows = active.sum()
        else:
            qT, kT, vT = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
            if ctx.kernel:
                o, state = lightning_attention_chunk(
                    qT, kT, vT, state, slopes, ctx.n_valid, li)
            else:
                o, new = lightning_chunk_ref(
                    qT, kT, vT, state[li], slopes, ctx.n_valid)
                state = state.at[li].set(new)
            o = o.transpose(0, 2, 1, 3)
            n_rows = ctx.n_valid.sum()
    stats = pools.stats.at[_N_BASE + 3].add(n_rows.astype(jnp.int32))
    with jax.named_scope("attn"):
        o = _collect(o, ctx)
        o = _rms(o.reshape(gate.shape), ap["o_norm"], cfg.norm_eps)
        o = (o * gate).astype(h.dtype)
    return o, pools._replace(state=state, stats=stats)


# ---------------------------------------------------------------------------
# One layer, and the loop over runs
# ---------------------------------------------------------------------------


def attention(x, lp, kind: str, li, pools, ctx):
    """What the attention of one layer of ``kind`` adds to ``x`` (before
    the residual scale); ``(added, pools)``."""
    cfg = ctx.cfg
    sz = cfg.latent_of(kind)
    with jax.named_scope("attn"):
        h = _rms(x, lp["ln1"]["scale"], cfg.norm_eps)
    mixer = _sparse if kind == "sparse" else _lightning
    o, pools = mixer(h, lp["attn"], li, pools, sz, ctx)
    with jax.named_scope("attn"):
        added = _mm(o, lp["attn"]["wo"])
    return added, pools


def _add(x, y, r: float):
    """``x + r y`` summed in float32."""
    return (x.astype(jnp.float32) + r * y.astype(jnp.float32)).astype(x.dtype)


def layer(x, lp, kind: str, li, pools, ctx):
    cfg = ctx.cfg
    added, pools = attention(x, lp, kind, li, pools, ctx)
    with jax.named_scope("attn"):
        x = _add(x, added, cfg.residual_mult)
    with jax.named_scope("mlp"):
        h = _rms(x, lp["ln2"]["scale"], cfg.norm_eps)
        x = _add(x, gated_mlp(h, lp["mlp"]), cfg.residual_mult)
    return x, pools


def run_layers(params, x, pools, ctx):
    """Every layer over ``x``, a loop a run of one kind: the run's layers
    are read out of the kind's stack by the loop's own index, so one body
    is traced a run whatever its length and no stack is cut."""
    for kind, base, n in runs_of(ctx.cfg.layer_kinds):
        stack = params[kind]

        def body(i, c, kind=kind, stack=stack):
            lp = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                stack)
            return layer(c[0], lp, kind, i, c[1], ctx)

        x, pools = lax.fori_loop(base, base + n, body, (x, pools))
    return x, pools


__all__ = [
    "KEY_TILE", "SALA_STATS", "SPARSE_KERNEL", "attention", "init_pools",
    "layer", "restore_snapshot", "run_layers", "take_snapshot", "zero_state",
]
