"""Attention kernels (Pallas/TPU): flash prefill + paged decode.

The einsum attention in models/transformer.py materializes the full
``[B, H, T, S]`` score tensor in HBM — fine for decode (T=1) and short
prefills, quadratic HBM traffic for long ones. The flash kernel computes
attention blockwise with an online softmax so scores never leave VMEM:
grid ``(batch·kv_head·group, q_blocks, k_blocks)`` with the k loop
innermost, carrying running max/denominator/accumulator in VMEM scratch
(the standard FlashAttention recurrence).

:func:`ragged_paged_attention` is the continuous-batching engine's
unified prefill+decode kernel (engine/paged.py::paged_ragged_step): one
fixed-shape ``[slots, chunk]`` query block where per-slot ``(start,
n_valid)`` are data — a decode-only slot carries 1 valid query, a
mid-prefill slot up to a chunk, padding slots 0. It and
:func:`paged_attention` (the decode continuation's one query a slot) are
ONE kernel body, the live-span page walk (:func:`_paged_walk_kernel`):
grid ``(slot, kv-head block)`` — one head block where a slot's kv heads
fit a grid step's VMEM, as qwen3-4b's 8 do — the page pool left in HBM,
and inside a slot a loop over the KV blocks its live span reaches —
whole pages (every kv head of a page is one contiguous copy) gathered
through the scalar-prefetched block table, several pages a block so
that a score tile spans 128 key positions, the next block's copies in
flight under this block's arithmetic. Query rows are walked the same way: row blocks up to
``n_valid``, each against KV up to its own causal limit. Nothing is
walked, fetched or computed past ``start + n_valid``: cost follows what
is live, not the slot's page capacity. The ``*_ref`` functions are the
pure-jax.numpy references the CPU path and the parity tests run — the
ragged reference is pinned bitwise against the composition of the
one-slot pair (:func:`paged_prefill_attention_ref` and
:func:`paged_attention_ref`).

Scope: **forward-only, causal, offset-0 prefill** — exactly the serving
engine's fresh-cache prefill (engine/generate.py::_prefill). Training and
decode keep the einsum path (training needs the vjp; decode is T=1).
Right-padded prompt buckets are safe under pure causal masking: a padded
key column can only be attended by a padded query row, whose logits are
never read (the engine takes the last *real* row per prompt).

GQA without KV repetition: queries reshape to ``[B·Hkv·G, T, hd]`` and the
kernel's batch axis runs over (B, Hkv, G) while the k/v block specs index
``b // G`` — repeated KV heads are never materialized, matching the einsum
path's memory behavior.

Quantized paged KV (``MLConfig.kv_quant="int8"`` / ``"int4"``): every paged
entry point accepts optional ``k_scale``/``v_scale`` arrays ``[P, Hkv,
page]`` marking the pages quantized — the kernels fetch the quantized KV
bytes per page (half for int8; a page whose trailing dim is ``hd // 2``
is PACKED int4, two values per byte) and fuse the per-(position, head)
dequant multiply (plus the int4 nibble unpack) into the VMEM read (the
models/quant.py weight pattern), so the MXU arithmetic is unchanged.
The walk's manual copies need operands whose minor dim is whole lane
rows: int8 and bf16 pages of 128-wide heads go in as they are stored,
scale planes as one lane row a page (:func:`_scale_rows`), packed int4
pages lane-padded (:func:`_lane_pad`: a quarter of the bytes in HBM,
half on the way into the kernel). The ``_ref`` twins dequantize at the
same gather, pinned against the kernels in tests/test_ops.py.

A latent cache (engine/latent.py) is walked by the same body: one "kv
head" whose row is key and value both (``v_pages=None``), read by every
query head of the model. ``window=`` starts the walk at a window's first
block; ``latent=`` is the walk of 128 heads over a whole context, where
a slot's prefill block is 16,384 query rows: larger tiles, bf16 operands,
groups of query rows over a third grid axis (:func:`_paged_walk`).

Every ``pl.pallas_call`` names its kernel (``name=``) after its entry
point (a caller of a latent cache passes its own): a profiler trace's reduction finds the kernels by these names, so
renaming a Python function must not rename them
(tests/test_step_scopes.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, bq, hd]
    k_ref,  # [1, bk, hd]
    v_ref,  # [1, bk, hd]
    o_ref,  # [1, bq, hd]
    m_ref,  # [bq, 1] running max (VMEM scratch)
    l_ref,  # [bq, 1] running denominator
    acc_ref,  # [bq, hd] f32 accumulator
    *,
    scale: float,
    block_q: int,
    block_k: int,
    n_k_blocks: int,
    window: int | None,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: k blocks fully right of this q block's diagonal contribute
    # nothing — skip their compute entirely. A sliding window also skips
    # blocks fully left of the earliest visible position
    # (k_pos > q_pos - window required).
    in_reach = ki * block_k <= qi * block_q + block_q - 1
    if window is not None:
        in_reach &= ki * block_k + block_k - 1 > qi * block_q - window

    @pl.when(in_reach)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [bq, bk]

        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        causal = k_pos <= q_pos
        if window is not None:  # Mistral sliding window (models/base.py)
            causal &= k_pos > q_pos - window
        s = jnp.where(causal, s, NEG_INF)

        m_prev = m_ref[:]  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # rows with no attendable key yet keep m == NEG_INF; exp(0) there
        # must not pollute the denominator
        alpha = jnp.where(m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(causal, jnp.exp(s - m_new), 0.0)  # [bq, bk]

        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(ki == n_k_blocks - 1)
    def _finalize():
        # under offset-0 causal masking every q row attends at least its
        # own key, so l > 0; the floor only guards degenerate inputs
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(
            o_ref.dtype
        )


# tlint: hot-path
@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_k", "interpret", "window"),
)
def flash_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k: jax.Array,  # [B, T, Hkv, hd]
    v: jax.Array,  # [B, T, Hkv, hd]
    *,
    scale: float,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Causal offset-0 attention; returns ``[B, T, Hq, hd]``.

    ``window`` applies Mistral-style sliding-window masking (position j
    visible from i iff ``i - window < j <= i``); out-of-window k blocks
    skip compute entirely. ``interpret=True`` runs the kernel in Pallas
    interpret mode (CPU) — how the parity tests pin it without TPU
    hardware.
    """
    B, T, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    if T % block_q or T % block_k:
        raise ValueError(
            f"seq len {T} must divide block sizes ({block_q}, {block_k}) — "
            "the engine's bucketed prefill shapes guarantee this"
        )

    # [B, T, Hq, hd] -> [(B Hkv G), T, hd]; kv -> [(B Hkv), T, hd]
    qg = (
        q.reshape(B, T, Hkv, G, hd)
        .transpose(0, 2, 3, 1, 4)
        .reshape(B * Hkv * G, T, hd)
    )
    kg = k.transpose(0, 2, 1, 3).reshape(B * Hkv, T, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * Hkv, T, hd)

    n_q = T // block_q
    n_k = T // block_k
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        n_k_blocks=n_k,
        window=int(window) if window is not None else None,
    )
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B * Hkv * G, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j, G=G: (b // G, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j, G=G: (b // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv * G, T, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qg, kg, vg)

    return (
        out.reshape(B, Hkv, G, T, hd)
        .transpose(0, 3, 1, 2, 4)
        .reshape(B, T, Hq, hd)
    )


# ---------------------------------------------------------------------------
# Paged attention references (continuous batching, engine/paged.py)
# ---------------------------------------------------------------------------


def _unpack4(x):
    """In-kernel/inline int4 dequant prologue: packed nibbles ``[.., h]``
    int8 → f32 ``[.., 2h]``. Delegates to models/quant.py::unpack_int4 —
    ONE implementation of the split-half layout, so the kernels' VMEM
    unpack and the write-side packing can never drift (the bit-ops are
    plain jnp and trace fine inside pallas)."""
    from ..models.quant import unpack_int4

    return unpack_int4(x).astype(jnp.float32)


def _gather_pages(pages, scales, block_tables, shape):
    """Contiguous f32 per-slot KV view over a (possibly quantized) page
    pool: gathers each block table's pages, dequantizing with the
    per-(page, position, head) scales when present — the scale multiply
    rides the gather read, exactly the models/quant.py weight pattern.
    Packed int4 pages (two values per byte: the page's trailing dim is
    half the target head_dim) unpack before the scale multiply."""
    x = pages[block_tables]
    if scales is not None and x.shape[-1] * 2 == shape[-1]:
        x = _unpack4(x)  # packed int4 pages → f32 [.., hd]
    else:
        x = x.astype(jnp.float32)
    if scales is not None:
        x = x * scales[block_tables].astype(jnp.float32)[..., None]
    # [.., n_pp, Hkv, page, hd] -> [.., n_pp, page, Hkv, hd] -> [.., K, ..]
    nd = x.ndim
    perm = tuple(range(nd - 4)) + (nd - 4, nd - 2, nd - 3, nd - 1)
    return x.transpose(perm).reshape(shape)


# tlint: hot-path
def paged_attention_ref(
    q: jax.Array,  # [S, Hq, hd] — one query token per slot
    k_pages: jax.Array,  # [P, Hkv, page, hd] — cache dtype, or int8
    v_pages: jax.Array,  # [P, Hkv, page, hd]
    block_tables: jax.Array,  # int32 [S, pages_per_slot]
    lengths: jax.Array,  # int32 [S] — valid positions per slot
    *,
    scale: float,
    k_scale: jax.Array | None = None,  # f32 [P, Hkv, page] — int8 pages
    v_scale: jax.Array | None = None,
    window: int | None = None,  # keys length - window <= s < length
) -> jax.Array:
    """Pure-jnp paged attention — the CPU serving path and the ground truth
    the Pallas kernel is pinned against.

    Pages are ``[P, Hkv, page, hd]`` — kv-head-major, so the kernel's
    per-(page, head) blocks have TPU-native ``(page, hd)`` trailing tiles.
    This gathers each slot's pages into a contiguous ``[S, K, Hkv, hd]``
    view (K = pages_per_slot·page) and runs the same masked-softmax GQA
    math as models/transformer.py::attention. With ``k_scale``/``v_scale``
    the pages are int8 (quantized paged KV cache): the per-(page, position,
    head) scale multiply is fused into the gather, so arithmetic stays f32
    while the cache bytes halve. Positions at or beyond
    ``lengths`` mask to NEG_INF (exp underflows to exactly 0, matching
    the dense path's -inf bias); a slot with length 0 (free slot riding
    the fixed batch shape) outputs zeros instead of a NaN row."""
    S, Hq, hd = q.shape
    P, Hkv, page, _ = k_pages.shape
    n_pp = block_tables.shape[1]
    K = n_pp * page
    # whole-page gather: [S, n_pp, Hkv, page, hd] -> [S, K, Hkv, hd]
    k = _gather_pages(k_pages, k_scale, block_tables, (S, K, Hkv, hd))
    v = _gather_pages(v_pages, v_scale, block_tables, (S, K, Hkv, hd))
    G = Hq // Hkv
    qg = q.reshape(S, Hkv, G, hd).astype(jnp.float32)
    scores = (
        jnp.einsum(
            "skgd,sxkd->skgx", qg, k.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        * scale
    )
    valid = jnp.arange(K)[None, :] < lengths[:, None]  # [S, K]
    if window is not None:  # the query sits at lengths - 1 and counts
        valid &= jnp.arange(K)[None, :] >= lengths[:, None] - window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(lengths[:, None, None, None] > 0, w, 0.0)
    out = jnp.einsum("skgx,sxkd->skgd", w, v.astype(jnp.float32))
    return out.reshape(S, Hq, hd).astype(q.dtype)


# tlint: hot-path
def paged_prefill_attention_ref(
    q: jax.Array,  # [C, Hq, hd] — one slot's prefill-chunk queries
    k_pages: jax.Array,  # [P, Hkv, page, hd]
    v_pages: jax.Array,  # [P, Hkv, page, hd]
    bt_row: jax.Array,  # int32 [n_pp] — the slot's block-table row
    start: jax.Array,  # int32 scalar — absolute position of q[0]
    *,
    scale: float,
    k_scale: jax.Array | None = None,  # f32 [P, Hkv, page] — int8 pages
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Pure-jnp offset-carrying paged prefill attention: the one-slot
    ground truth the ragged kernel and its reference are pinned against.

    This is what lifts the offset-0-only restriction of the monolithic
    flash prefill: query ``j`` sits at absolute position ``start + j`` and
    attends every key position ``<= start + j`` through the slot's pages
    (the chunk's own keys included — the caller scatters the chunk's KV
    into the pages BEFORE attention, exactly like the decode step). Same
    masked-softmax GQA math as ``paged_attention_ref``, so a chunked
    prefill is bit-identical to the monolithic one on positions the two
    share. Positions past ``start + j`` (including any garbage beyond the
    chunk's valid span) mask to NEG_INF; every query sees at least its own
    key, so no zero-denominator guard is needed beyond the shared floor."""
    C, Hq, hd = q.shape
    P, Hkv, page, _ = k_pages.shape
    n_pp = bt_row.shape[0]
    K = n_pp * page
    k = _gather_pages(k_pages, k_scale, bt_row, (K, Hkv, hd))
    v = _gather_pages(v_pages, v_scale, bt_row, (K, Hkv, hd))
    G = Hq // Hkv
    qg = q.reshape(C, Hkv, G, hd).astype(jnp.float32)
    scores = (
        jnp.einsum(
            "ckgd,xkd->ckgx", qg, k.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [C, Hkv, G, K]
    q_pos = start + jnp.arange(C)[:, None]  # [C, 1]
    k_pos = jnp.arange(K)[None, :]  # [1, K]
    causal = k_pos <= q_pos  # [C, K]
    scores = jnp.where(causal[:, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("ckgx,xkd->ckgd", w, v.astype(jnp.float32))
    return out.reshape(C, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ragged paged attention (unified prefill+decode step, engine/continuous.py)
# ---------------------------------------------------------------------------


# tlint: hot-path
def ragged_paged_attention_ref(
    q: jax.Array,  # [S, C, Hq, hd] — per-slot query block (ragged valid span)
    k_pages: jax.Array,  # [P, Hkv, page, hd]
    v_pages: jax.Array,  # [P, Hkv, page, hd]
    block_tables: jax.Array,  # int32 [S, pages_per_slot]
    starts: jax.Array,  # int32 [S] — absolute position of q[s, 0]
    n_valid: jax.Array,  # int32 [S] — valid queries per slot (0 = padding)
    *,
    scale: float,
    k_scale: jax.Array | None = None,  # f32 [P, Hkv, page] — int8 pages
    v_scale: jax.Array | None = None,
    window: int | None = None,  # keys q_pos - window < s <= q_pos
) -> jax.Array:
    """Pure-jnp ragged paged attention — the CPU serving path of the
    unified prefill+decode step, and the ground truth the Pallas kernel is
    pinned against.

    One fixed-shape ``[S, C]`` block where per-slot ``(start, n_valid)``
    are DATA (the Ragged Paged Attention framing): a decode-only slot
    carries 1 valid query at its current length, a mid-prefill slot
    carries up to C prompt queries at its prefill offset, and a padding
    slot carries 0 and outputs zeros. Query ``j`` of slot ``s`` sits at
    absolute position ``starts[s] + j`` and attends every key position
    ``<= starts[s] + j`` through the slot's own pages (the caller
    scatters the block's KV into the pages BEFORE attention, exactly
    like the decode step and the prefill chunk). Per valid row this is
    bitwise the same masked-softmax GQA math as
    ``paged_prefill_attention_ref`` (and, for a 1-valid-token slot,
    ``paged_attention_ref`` at length ``start + 1``) — the composition
    the parity tests pin. Rows at or past ``n_valid`` zero out instead
    of carrying garbage.

    **Verify mode** (speculative decoding, engine/paged.py): a
    speculating slot is just ``k + 1`` valid query rows at its current
    ``start`` — its token plus ``k`` draft tokens — and needs NO new
    masking: the causal ``q_pos`` rule above already makes draft row
    ``j`` attend exactly ``<= start + j``, which is exactly the context
    ``k`` sequential decode steps would each see (held to the
    sequential ``paged_attention_ref`` oracle within a few ulps in
    tests/test_ops.py::test_ragged_verify_rows_match_sequential_decode:
    the two references contract einsums of different shapes, and
    tolerance, not bit equality, is the contract between them)."""
    S, C, Hq, hd = q.shape
    P, Hkv, page, _ = k_pages.shape
    n_pp = block_tables.shape[1]
    K = n_pp * page
    k = _gather_pages(k_pages, k_scale, block_tables, (S, K, Hkv, hd))
    v = _gather_pages(v_pages, v_scale, block_tables, (S, K, Hkv, hd))
    G = Hq // Hkv
    qg = q.reshape(S, C, Hkv, G, hd).astype(jnp.float32)
    scores = (
        jnp.einsum(
            "sckgd,sxkd->sckgx", qg, k.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        * scale
    )  # [S, C, Hkv, G, K]
    q_pos = starts[:, None] + jnp.arange(C)[None, :]  # [S, C]
    k_pos = jnp.arange(K)[None, None, :]  # [1, 1, K]
    causal = k_pos <= q_pos[:, :, None]  # [S, C, K]
    if window is not None:  # the query's own position counts
        causal &= k_pos > q_pos[:, :, None] - window
    scores = jnp.where(causal[:, :, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    # invalid rows (j >= n_valid, including whole padding slots) masked
    # all-NEG_INF rows would softmax to NaN upstream of the zeroing, so
    # the zero guard rides the weights like paged_attention_ref's
    row_ok = jnp.arange(C)[None, :] < n_valid[:, None]  # [S, C]
    w = jnp.where(row_ok[:, :, None, None, None], w, 0.0)
    out = jnp.einsum("sckgx,sxkd->sckgd", w, v.astype(jnp.float32))
    return out.reshape(S, C, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# The live-span page walk: the one kernel body behind paged_attention and
# ragged_paged_attention
# ---------------------------------------------------------------------------

# A score tile spans at least this many key positions (one full lane row)
# and a row block holds at most this many query rows: both block sizes
# follow from the operands' shapes, nothing is configured.
_MIN_TILE = 128

# Rows of a packed-sublane (bf16) tile: a row block that starts at a
# dynamic offset must start on a tile edge, so its height is a multiple.
_ROW_TILE = 16

# What one grid step's blocks, buffers and f32 tiles may take of VMEM by
# _heads_per_block's estimate (the scoped default is 16 MiB on a v5e).
_VMEM_BUDGET = 12 * 2**20
_LATENT_VMEM = 64 * 2**20  # the limit a latent walk asks for (_paged_walk)


def _pages_per_block(page: int, n_pp: int, tile: int = _MIN_TILE) -> int:
    """Pages a KV block of the walk holds: enough that a score tile spans
    ``tile`` key positions (8 pages of 16 at ``_MIN_TILE``), never more
    than a slot has."""
    return max(1, min(-(-tile // page), n_pp))


def _positions_per_row_block(C: int, G: int, rows: int = _MIN_TILE) -> int:
    """Chunk positions a row block holds: whole positions (``G`` query
    rows each) dividing the chunk, so that every row block is whole. The
    whole chunk if it is at most ``rows`` rows (one block, sliced
    statically: any height will do). Else the largest divisor whose
    ``cb·G`` rows are whole ``_ROW_TILE`` tiles within ``rows`` rows,
    or failing that (a group size like 9) the smallest such divisor
    above it."""
    if C * G <= rows:
        return C
    whole = [cb for cb in range(1, C + 1)
             if C % cb == 0 and cb * G % _ROW_TILE == 0]
    within = [cb for cb in whole if cb * G <= rows]
    return max(within) if within else min(whole, default=C)


def _short_positions(cb: int) -> int:
    """Chunk positions of the walk's SHORT row block, the height a slot
    with no more live positions walks at in place of its first tall block
    (``cb`` positions): ONE position, ``G`` rows, a continuation step's
    own block. It starts at row 0 of the slot's query block, a static
    slice, so no tile edge binds it ("any height will do"), and Mosaic
    compiles it for every group size the presets have (1, 4, 6, 7, 9, 64,
    128: tests/test_chip_compile.py; were one refused, the fallback
    would be the smallest whole-tile height, ``min(whole)`` of
    :func:`_positions_per_row_block`). 0 where the tall block is one
    position already (every continuation step: ``C`` = 1): such a call
    traces no second body and stays the program it was."""
    return 1 if cb > 1 else 0


def _heads_per_block(
    Hkv: int, CG: int, R: int, T: int, hd: int, q_itemsize: int,
    kv_row_bytes: int,
) -> int:
    """KV heads a grid step computes: all of them where its VMEM fits
    ``_VMEM_BUDGET``, else the largest divisor of ``Hkv`` that does. A
    head costs its double-buffered query and output blocks (``CG`` rows
    of ``hd`` values), two KV buffers (``T`` stored rows, K and V) and
    the f32 tiles of one block's arithmetic: K and V dequantized, the
    ``[R, T]`` scores, weights and mask, the accumulator and its
    update."""
    head = (
        4 * CG * hd * q_itemsize + 4 * T * kv_row_bytes
        + 4 * (2 * T * hd + 3 * R * T + 3 * R * hd)
    )
    return max(
        hb for hb in range(1, Hkv + 1)
        if Hkv % hb == 0 and (hb == 1 or hb * head <= _VMEM_BUDGET)
    )


def _lane_pad(x: jax.Array) -> jax.Array:
    """``x`` with its minor dim padded to whole lane rows: a manual copy
    cannot slice an HBM operand whose minor dim is not a multiple of 128
    (Mosaic refuses the slice). int8 and bf16 pages of 128-wide heads
    pass through untouched (and, stacked over the layers, are handed to
    the kernel whole); packed int4 pages and pages of a head_dim under
    128 pay a padded copy of the layer's pool a call, on top of the
    relayout XLA makes of such a pool for any kernel (it stores a minor
    dim under a lane row page-minor). That cost follows the pool's
    capacity, not the live span: ~0.4 ms of a 0.6 ms call at int4 and
    ~0.9 ms a call at head_dim 64 for a 2,049-page pool, where the grid
    walk took 2-6 ms (PERF.md section 6, PR 25)."""
    short = -x.shape[-1] % _MIN_TILE
    if not short:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, short)])


def _one_layer(pool: jax.Array, layer: jax.Array) -> jax.Array:
    """Layer ``layer`` of a stacked pool ``[L, P, ...]``, cut out as
    ``[P, ...]``: a copy of one layer's capacity, for the operands the
    kernel cannot address inside the stack (see :func:`_paged_walk`)."""
    return jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)


def _scale_rows(scales: jax.Array, hb: int) -> jax.Array:
    """A scale pool ``[P, Hkv, page]`` as one lane row a page and block of
    ``hb`` heads, ``[P, Hkv/hb, 1, hb·page]`` (lane-padded): a page's
    plane is under a lane row, and see :func:`_lane_pad`. This is not a
    new copy in the step program: it stores the scale pool page-minor
    and re-lays it for the kernel anyway (as it did for the BlockSpec
    kernels, into lane-padded planes eight times this size)."""
    P, Hkv, page = scales.shape
    return _lane_pad(scales.reshape(P, Hkv // hb, 1, hb * page))


def _walk_trips(start, n_valid, rb, *, cb: int, page: int, ppb: int):
    """The walk's trip counts for row block ``rb`` of a slot whose valid
    queries sit at positions ``start .. start + n_valid - 1``: ``(row
    blocks to walk, key positions row block rb may attend, live pages
    holding them, KV blocks holding those)``.

    A row block sees keys up to its own last valid query, so a later row
    block walks further than an earlier one and nothing walks past the
    live span; a slot with no valid query walks nothing. The decode
    kernel is the case ``start = max(length - 1, 0)``, ``n_valid =
    min(length, 1)``. Integer arithmetic only: the kernel calls it on
    scalars read from SMEM, the tests on Python ints."""
    n_rb = (n_valid + cb - 1) // cb
    kv_len = start + jnp.minimum((rb + 1) * cb, n_valid)
    n_pages = (kv_len + page - 1) // page
    n_kb = (n_pages + ppb - 1) // ppb
    return n_rb, kv_len, n_pages, n_kb


def _walk_start(start, rb, *, cb: int, page: int, ppb: int, window: int):
    """Where row block ``rb`` of a windowed walk begins: ``(first key
    position any of its queries may attend, the KV block holding it)``.
    A query at position ``t`` attends ``t - window < s <= t`` (the token
    itself counts), and the block's first query sits at ``start + rb·cb``:
    the blocks before that key's are never copied. Integer arithmetic
    only, like :func:`_walk_trips`."""
    lo = jnp.maximum(start + rb * cb - (window - 1), 0)
    return lo, lo // page // ppb


def _paged_walk_kernel(
    bt_ref,  # scalar-prefetch: block tables [S, n_pp]
    start_ref,  # scalar-prefetch: absolute position of each slot's row 0
    nv_ref,  # scalar-prefetch: valid query positions per slot
    layer_ref,  # scalar-prefetch [1]: the layer of the stack to walk
    q_ref,  # [1, hb, C·G, hd] (VMEM): this step's block of kv heads
    k_hbm,  # [L, P, Hkv, page, hdk] — every layer's pool, left in HBM
    v_hbm,
    *rest,  # quantized: ks_hbm, vs_hbm [P, Hkv/hb, 1, lanes]; out + scratch
    scale: float,
    page: int,
    ppb: int,
    G: int,
    cb: int,
    quantized: bool,
    packed: bool,
    window: int | None = None,
    shared_kv: bool = False,
    v_width: int | None = None,
    row_groups: bool = False,
    per_head: bool = False,
    cb_s: int = 0,
):
    """One slot and one block of ``hb`` kv heads of the walk (grid
    ``(slot, head block)``): row blocks of ``cb`` chunk positions up to
    the slot's last valid query, each against KV blocks of ``ppb`` pages
    up to its own causal limit, double-buffered.

    ``cb_s`` (:func:`_short_positions`): a row block is as tall as the
    slot's live rows need. A slot with at most ``cb_s`` valid positions (a
    decode row in a packed block) walks its one row block ``cb_s·G`` rows
    tall: the first rows of its query block and of the scratch, the same
    KV blocks, copies and mask, so that a KV block costs it the scores
    and weights of ``G`` rows and not of ``cb·G`` (64 to 144, 512 in a
    latent walk). The rows behind are zero-filled. A scalar branch on
    ``nv`` around the WHOLE walk: every other slot walks the code it
    walked before, and a row's scores, softmax and output do not depend
    on the rows beside it. (A branch around each KV block's arithmetic
    alone would trace the copies once, but it cost a full block 3-30% on
    the chip: PERF.md section 6, PR 61.) 0: one body (``C`` = 1).

    ``row_groups``: the grid has a third axis over groups of whole row
    blocks, and this step holds one group's query rows (a slot's rows do
    not fit VMEM at once where 128 heads share a row: 16,384 rows of 640).
    ``v_width`` (with ``shared_kv``; a latent cache's walk): the value is
    the key row's first ``v_width`` columns, and so wide is the output;
    the operands go to the MXU as they are stored (bf16), products summed
    in float32 and the scale applied to the scores, as the XLA fallback's
    einsums do. Else everything is float32.

    ``window``: a query at ``t`` attends ``t - window < s <= t`` and the
    walk starts at the KV block that holds its row block's first such
    key (:func:`_walk_start`). ``shared_kv``: the value of a position is
    its key row (a latent cache: one row serves both sides, the caller
    keeps the value's columns of the output), so ``v_hbm`` is not an
    operand and a page is copied once. ``per_head``: every (slot, head
    block) has a block-table row, a start and a count of its own (row
    ``slot · head blocks + head block`` of each): a table of kept blocks
    a kv head (:func:`block_sparse_attention`)."""
    if shared_kv:
        v_hbm, rest = None, (v_hbm,) + rest
        o_ref, kbuf, sem, m_ref, l_ref, acc_ref = rest
        vbuf = None
    elif quantized:
        ks_hbm, vs_hbm, o_ref, kbuf, vbuf, ksbuf, vsbuf, sem, \
            m_ref, l_ref, acc_ref = rest
    else:
        o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref = rest
    s = pl.program_id(0)
    hblk = pl.program_id(1)
    if per_head:  # the walk's row of the tables, not the query's slot
        s = s * pl.num_programs(1) + hblk
    start = start_ref[s]
    nv = nv_ref[s]
    Hkv, CG, hd = q_ref.shape[1:]  # Hkv: the heads, CG: the rows, here
    vw = hd if v_width is None else v_width
    native_dot = v_width is not None
    # the block's heads of a page: all of it where one block holds them
    # all, and contiguous in the pool either way
    heads = () if Hkv == k_hbm.shape[2] else (pl.ds(hblk * Hkv, Hkv),)
    hdk = hd // 2 if packed else hd  # bytes of a packed int4 row
    R = cb * G  # query rows a row block holds
    T = ppb * page  # key positions a KV block holds
    trips = functools.partial(_walk_trips, start, nv, cb=cb, page=page,
                              ppb=ppb)

    def page_copies(kb, buf, p, wait: bool):
        # a copy is one whole page, every kv head of the block: [Hkv,
        # page, hdk] is contiguous in the pool. A wait needs the copy's
        # shape only, so it names page 0 and never reads the block table
        pg = (0 if wait else bt_ref[s, kb * ppb + p],)
        at = (0 if wait else layer_ref[0],) + pg + heads
        copies = [
            pltpu.make_async_copy(
                k_hbm.at[at], kbuf.at[buf, :, p], sem.at[buf, 0]),
        ]
        if not shared_kv:
            copies.append(pltpu.make_async_copy(
                v_hbm.at[at], vbuf.at[buf, :, p], sem.at[buf, 1]))
        if quantized:
            copies += [
                pltpu.make_async_copy(
                    ks_hbm.at[pg + (hblk,)], ksbuf.at[buf, p],
                    sem.at[buf, 2]),
                pltpu.make_async_copy(
                    vs_hbm.at[pg + (hblk,)], vsbuf.at[buf, p],
                    sem.at[buf, 3]),
            ]
        return copies

    def live_pages(kb, buf, n_pages, wait: bool):
        # only the block's LIVE pages move: a block-table entry past the
        # slot's live span is never read as an address
        for p in range(ppb):
            @pl.when(kb * ppb + p < n_pages)
            def _():
                for c in page_copies(kb, buf, p, wait):
                    c.wait() if wait else c.start()

    def scale_rows(sbuf, buf):
        # [Hkv, T] f32, positions on the lane axis: a copied row holds
        # one page's plane as (head, position) lanes, and head h's rows
        # of the block's pages are laid side by side
        return jnp.concatenate([
            jnp.concatenate(
                [sbuf[buf, p, :, pl.ds(h * page, page)] for p in range(ppb)],
                axis=-1,
            )
            for h in range(Hkv)
        ], axis=0).astype(jnp.float32)

    q_row = jax.lax.broadcasted_iota(jnp.int32, (R, T), 0)
    k_col = jax.lax.broadcasted_iota(jnp.int32, (R, T), 1)
    k_row = jax.lax.broadcasted_iota(jnp.int32, (T, vw), 0)
    k_lane = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

    # the first row block of this step's rows among the slot's
    rb0 = pl.program_id(2) * (CG // R) if row_groups else None

    def rows_of(lb):
        # one row block is the whole query block, whatever its height; a
        # dynamic offset lands on a tile edge (_positions_per_row_block)
        if CG == R:
            return slice(None)
        return pl.ds(pl.multiple_of(lb * R, R), R)

    def row_block(lb, carry, q_row=q_row, k_col=k_col, m_ref=m_ref,
                  l_ref=l_ref, acc_ref=acc_ref, rows_of=rows_of, rb0=rb0):
        # the tall block's by default; the short one's: walk_short
        rb = lb if rb0 is None else rb0 + lb
        _, kv_len, n_pages, n_kb = trips(rb)
        rows = rows_of(lb)
        q = q_ref[0, :, rows, :]  # [Hkv, rows, hd]
        if not native_dot:
            q = q.astype(jnp.float32) * scale
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # query row r of the block is chunk position rb·cb + r // G
        c_pos = rb * cb + q_row // G
        kb0 = 0
        if window is not None:
            kb0 = _walk_start(start, rb, cb=cb, page=page, ppb=ppb,
                              window=window)[1]
        live_pages(kb0, kb0 % 2, n_pages, wait=False)

        def kv_block(kb, carry):
            buf = kb % 2

            @pl.when(kb + 1 < n_kb)
            def _():  # the next block's copies fly under this one's math
                live_pages(kb + 1, 1 - buf, n_pages, wait=False)

            live_pages(kb, buf, n_pages, wait=True)
            k = kbuf[buf, :, :, :, pl.ds(0, hdk)]  # [Hkv, ppb, page, hdk]
            v = k if shared_kv else vbuf[buf, :, :, :, pl.ds(0, hdk)]
            if packed:
                k, v = _unpack4(k), _unpack4(v)
            elif not native_dot:
                k, v = k.astype(jnp.float32), v.astype(jnp.float32)
            k = k.reshape(Hkv, T, hd)
            v = v.reshape(Hkv, T, hd)[..., :vw]
            # positions of the buffer past kv_len were not copied, or
            # lie past the span inside a copied page: whatever they hold
            # (NaN included) must not reach p @ v as 0 × NaN
            left = kv_len - kb * T  # live key positions of this block
            if native_dot:  # only a row block's last KV block has any
                v = jax.lax.cond(
                    left < T,
                    lambda v: jnp.where((k_row < left)[None], v,
                                        jnp.zeros_like(v)),
                    lambda v: v, v,
                )
            else:
                v = jnp.where((k_row < left)[None], v, 0.0)
            sc = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )  # [Hkv, R, T]
            if native_dot:
                sc = sc * scale
            if quantized:
                # the dequant rides the score columns and the softmax
                # weights: per-position scales stay on the lane axis
                ks, vs = scale_rows(ksbuf, buf), scale_rows(vsbuf, buf)
                vs = jnp.where(k_lane < left, vs, 0.0)
                sc = sc * ks[:, None, :]
            ok = (kb * T + k_col <= start + c_pos) & (c_pos < nv)
            if window is not None:
                ok &= kb * T + k_col > start + c_pos - window
            ok = ok[None]
            sc = jnp.where(ok, sc, NEG_INF)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
            alpha = jnp.where(
                m_prev == NEG_INF, 0.0, jnp.exp(m_prev - m_new)
            )
            p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(
                p, axis=2, keepdims=True
            )
            acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
                p * vs[:, None, :] if quantized
                else p.astype(v.dtype) if native_dot else p, v,
                (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            m_ref[...] = m_new
            return carry

        jax.lax.fori_loop(kb0, n_kb, kv_block, 0)
        # rows past the last valid query met no unmasked key: l == 0 and
        # the floor yields a zero row, like the references
        o_ref[0, :, rows, :] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)
        return carry

    def dead_row_block(lb, carry):
        o_ref[0, :, rows_of(lb), :] = jnp.zeros((Hkv, R, vw), o_ref.dtype)
        return carry

    def walk_tall():
        n_rb = trips(0)[0]
        if rb0 is not None:  # the slot's live row blocks in this group
            n_rb = jnp.clip(n_rb - rb0, 0, CG // R)
        jax.lax.fori_loop(0, n_rb, row_block, 0)
        jax.lax.fori_loop(n_rb, CG // R, dead_row_block, 0)

    if not cb_s:  # the query block is one position: nothing is shorter
        return walk_tall()

    def walk_short():
        # row block 0 at the short height (its trips, its window start
        # and its mask are the tall block's: none depends on the height
        # while nv <= cb_s), over a zero-filled output block
        jax.lax.fori_loop(0, CG // R, dead_row_block, 0)
        h = cb_s * G
        own = (slice(None), slice(0, h))  # the scratch's first h rows
        row_block(
            0, 0, q_row=jax.lax.broadcasted_iota(jnp.int32, (h, T), 0),
            k_col=jax.lax.broadcasted_iota(jnp.int32, (h, T), 1),
            m_ref=m_ref.at[own], l_ref=l_ref.at[own],
            acc_ref=acc_ref.at[own], rows_of=lambda lb: slice(0, h),
            rb0=None)

    short = (nv > 0) & (nv <= cb_s)
    if row_groups:  # the slot's other groups hold no live row
        short &= pl.program_id(2) == 0
    jax.lax.cond(short, walk_short, walk_tall)


def _paged_walk(
    name: str,
    qg: jax.Array,  # [S, Hkv, C·G, hd] — kv-head-major query rows
    k_pages: jax.Array,  # [L, P, Hkv, page, hdk], or one layer's [P, ...]
    v_pages: jax.Array,
    block_tables: jax.Array,  # int32 [S, n_pp]
    starts: jax.Array,  # int32 [S]
    n_valid: jax.Array,  # int32 [S]
    k_scale: jax.Array | None,  # [L, P, Hkv, page], or [P, ...]
    v_scale: jax.Array | None,
    layer: jax.Array | None,  # int32 scalar: the layer of a stack to walk
    *,
    G: int,
    scale: float,
    interpret: bool,
    window: int | None = None,
    shared_kv: bool = False,  # values are the key rows: v_pages unused
    latent: tuple | None = None,
    per_head: bool = False,  # tables, starts, counts: [S · Hkv, ...]
) -> jax.Array:
    """The ``pl.pallas_call`` of the walk, named ``name``; returns
    ``[S, Hkv, C·G, hd]``. Block sizes come from the shapes: KV blocks of
    :func:`_pages_per_block` pages, row blocks of
    :func:`_positions_per_row_block` chunk positions, grid steps of
    :func:`_heads_per_block` kv heads.

    ``latent`` = ``(v_width, kv_tile, rows, group_rows)`` is the walk of a
    latent cache that one row serves many heads of (``shared_kv``): the
    output is the softmax-weighted first ``v_width`` columns of the rows
    (``[S, 1, C·G, v_width]``), the operands reach the MXU in the stored
    dtype, a KV block spans ``kv_tile`` positions and a row block ``rows``
    query rows (at 128 x 128 a walk of 16 slots x 12,800 positions took
    1.59 ms on a v5e, at 512 x 512 1.10, a full prefill block 12.4 against
    4.3: PERF.md section 6, PR 34), and the grid gets a third axis over
    groups of ``group_rows`` query rows where a slot's rows are more.

    The pools are addressed, not loaded: the kernel copies page
    ``(layer, page)`` out of the stack ``[L, P, ...]`` itself, so a layer
    loop that carries the stack hands it over whole and nothing cuts a
    layer's pool out of it. One layer's ``[P, ...]`` pool is the stack of
    one (a free reshape). Two operands are still cut out a layer-call,
    each a copy of ONE layer's capacity: a pool :func:`_lane_pad` has to
    pad (padding the stack would cost ``L`` layers a call), and the scale
    planes, which :func:`_scale_rows` re-lays."""
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    else:
        if k_scale is not None:
            k_scale = _one_layer(k_scale, layer)
            v_scale = _one_layer(v_scale, layer)
        if k_pages.shape[-1] % _MIN_TILE:
            k_pages = _one_layer(k_pages, layer)[None]
            if not shared_kv:
                v_pages = _one_layer(v_pages, layer)[None]
            layer = 0
    S, Hkv, CG, hd = qg.shape
    page, hdk = k_pages.shape[3:]  # hdk = hd // 2 for packed int4
    n_pp = block_tables.shape[1]
    vw, kv_tile, max_rows, group_rows = latent or (
        hd, _MIN_TILE, _MIN_TILE, CG)
    ppb = _pages_per_block(page, n_pp, kv_tile)
    cb = _positions_per_row_block(CG // G, G, max_rows)
    # rows of a grid step: the slot's, or one group of whole row blocks
    QR = CG if CG <= group_rows else group_rows // (cb * G) * (cb * G)
    if CG % QR:
        raise ValueError(f"{CG} query rows in groups of {QR}")
    quantized = k_scale is not None
    cb_s = _short_positions(cb)
    kernel = functools.partial(
        _paged_walk_kernel, scale=scale, page=page, ppb=ppb, G=G, cb=cb,
        quantized=quantized, packed=quantized and hdk * 2 == hd,
        **({"cb_s": cb_s} if cb_s else {}),
        **({"window": window} if window is not None else {}),
        **({"shared_kv": True} if shared_kv else {}),
        **({"v_width": vw} if latent else {}),
        **({"row_groups": True} if QR < CG else {}),
        **({"per_head": True} if per_head else {}),
    )
    args = [qg, _lane_pad(k_pages)]
    if not shared_kv:
        args.append(_lane_pad(v_pages))
    hb = _heads_per_block(
        Hkv, QR, cb * G, ppb * page, hd, qg.dtype.itemsize,
        args[1].shape[-1] * args[1].dtype.itemsize,
    )
    if per_head:
        hb = 1  # a table a kv head: a grid step walks one head's
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    if QR < CG:
        grid = (S, Hkv // hb, CG // QR)
        q_spec = pl.BlockSpec(
            (1, hb, QR, hd), lambda s, h, g, *_: (s, h, g, 0))
        o_spec = pl.BlockSpec(
            (1, hb, QR, vw), lambda s, h, g, *_: (s, h, g, 0))
    else:
        grid = (S, Hkv // hb)
        q_spec = pl.BlockSpec((1, hb, CG, hd), lambda s, h, *_: (s, h, 0, 0))
        o_spec = q_spec if vw == hd else pl.BlockSpec(
            (1, hb, CG, vw), lambda s, h, *_: (s, h, 0, 0))
    # two buffers of one KV block each, laid out so that page p of a
    # block lands at [:, p]: the same (page, hdk) trailing tile as the
    # pool, and a leading-dim merge away from [Hkv, T, hd]
    scratch = [
        pltpu.VMEM((2, hb, ppb) + a.shape[3:], a.dtype) for a in args[1:]
    ]
    if quantized:
        args += [_scale_rows(k_scale, hb), _scale_rows(v_scale, hb)]
        scratch += [
            pltpu.VMEM((2, ppb) + a.shape[2:], a.dtype) for a in args[3:]
        ]
    scratch += [
        pltpu.SemaphoreType.DMA(
            (2, 4 if quantized else 1 if shared_kv else 2)),
        pltpu.VMEM((hb, cb * G, 1), jnp.float32),  # running max
        pltpu.VMEM((hb, cb * G, 1), jnp.float32),  # running denominator
        pltpu.VMEM((hb, cb * G, vw), jnp.float32),  # accumulator
    ]
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[q_spec] + [in_hbm] * (len(args) - 1),
            out_specs=o_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape[:-1] + (vw,), qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * len(grid),
            # a latent walk's tiles outgrow the scoped default (16 MiB of
            # a v5e's 128): [512, 512] float32 scores three times over
            # beside 2,048 query rows of 640, in and out, twice buffered
            **({"vmem_limit_bytes": _LATENT_VMEM} if latent else {}),
        ),
        interpret=interpret,
    )(
        block_tables,
        jnp.asarray(starts, jnp.int32),
        jnp.asarray(n_valid, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        *args,
    )


# tlint: hot-path
@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret", "name", "latent", "window"))
def ragged_paged_attention(
    q: jax.Array,  # [S, C, Hq, hd]
    k_pages: jax.Array,  # [P, Hkv, page, hd]
    v_pages: jax.Array | None,  # [P, Hkv, page, hd]; None: the key rows
    block_tables: jax.Array,  # int32 [S, pages_per_slot]
    starts: jax.Array,  # int32 [S]
    n_valid: jax.Array,  # int32 [S]
    *,
    scale: float,
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # f32 [P, Hkv, page] — int8 pages
    v_scale: jax.Array | None = None,
    layer: jax.Array | None = None,  # int32 scalar — see below
    name: str | None = None,  # the pallas_call's, as a trace shows it
    latent: tuple | None = None,  # a latent cache's walk (_paged_walk)
    window: int | None = None,  # keys q_pos - window < s <= q_pos
) -> jax.Array:
    """Ragged paged attention (TPU); returns ``[S, C, Hq, hd]``
    (``latent``: ``[S, C, Hq, v_width]``). ``window``: each row block's
    walk starts at the KV block of its first query's oldest key
    (:func:`_walk_start`).

    With ``layer``, the pools (and scale planes) are every layer's,
    stacked ``[L, P, ...]`` as the engine's ``PagedKVCache`` holds them,
    and the walk reads layer ``layer`` of the stack in place
    (:func:`_paged_walk`); the result is bitwise that of the same call on
    that layer's ``[P, ...]`` slice.

    The live-span walk (:func:`_paged_walk_kernel`) over the whole-chunk
    query block: grid ``(slot, kv-head block)``, block tables, per-slot
    starts and valid counts on scalar prefetch, the page pool left in
    HBM. Inside a slot, row blocks of up to 128 query rows are walked up
    to ``n_valid`` and each walks KV blocks of whole pages (8 pages of
    16: a 128-wide score tile, the block's kv heads in one batched
    matmul) up to its own causal limit, the next block's page copies in flight under this
    block's arithmetic. A slot with ONE valid position walks a row block
    of that one position (``G`` rows: :func:`_short_positions`). So a
    decode row in the block costs a decode row, a mid-prefill slot its
    chunk against its context, a padding slot its zero output. ONE
    compiled program serves every (prefill/decode mix, offset, length,
    page assignment) — slot roles are data, not shape. Speculative verify slots (k+1 valid rows at a decode slot's
    current start) ride the same causal ``q_pos`` masking — see the
    reference's "Verify mode" note."""
    S, C, Hq, hd = q.shape
    Hkv = k_pages.shape[-3]
    G = Hq // Hkv
    # [S, C, Hq, hd] -> [S, Hkv, C·G, hd]: kv-head-major, so the rows of
    # one kv head are one matmul operand against that head's pages
    qg = (
        q.reshape(S, C, Hkv, G, hd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(S, Hkv, C * G, hd)
    )
    out = _paged_walk(
        "ragged_paged_attention" if name is None else name, qg, k_pages,
        v_pages, block_tables, starts, n_valid, k_scale, v_scale, layer,
        G=G, scale=scale, interpret=interpret,
        **({"shared_kv": True} if v_pages is None else {}),
        **({"latent": latent} if latent else {}),
        **({"window": window} if window is not None else {}),
    )
    return (
        out.reshape(S, Hkv, C, G, out.shape[-1])
        .transpose(0, 2, 1, 3, 4)
        .reshape(S, C, Hq, out.shape[-1])
    )


@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret", "window", "name", "latent"))
def paged_attention(
    q: jax.Array,  # [S, Hq, hd]
    k_pages: jax.Array,  # [P, Hkv, page, hd]
    v_pages: jax.Array | None,  # [P, Hkv, page, hd]; None: the key rows
    block_tables: jax.Array,  # int32 [S, pages_per_slot]
    lengths: jax.Array,  # int32 [S]
    *,
    scale: float,
    interpret: bool = False,
    k_scale: jax.Array | None = None,  # f32 [P, Hkv, page] — int8 pages
    v_scale: jax.Array | None = None,
    layer: jax.Array | None = None,  # int32 scalar: pools are [L, P, ...]
    window: int | None = None,  # keys length - window <= s < length
    name: str | None = None,  # the pallas_call's, as a trace shows it
    latent: tuple | None = None,  # a latent cache's walk (_paged_walk)
) -> jax.Array:
    """Paged decode attention; returns ``[S, Hq, hd]`` (``latent``: ``[S,
    Hq, v_width]``).

    The same live-span walk as :func:`ragged_paged_attention` with one
    query position a slot, at ``lengths - 1``: grid ``(slot, kv-head
    block)``, a loop over the KV blocks the slot's length reaches
    (whole-page copies, double-buffered, the block's kv heads in one
    batched matmul), nothing for a free slot (length 0) but its zero
    output. GQA queries group on the
    kv-head axis, so repeated KV heads are never materialized. One
    compiled program serves every (length mix, page assignment) — the
    block table and lengths are data, not shape.

    ``window`` walks only the last ``window`` positions (the query's own
    counts). ``v_pages=None`` is a latent cache: a position's value is
    its key row, copied once (the caller keeps the value's columns)."""
    S, Hq, hd = q.shape
    Hkv = k_pages.shape[-3]
    lengths = jnp.asarray(lengths, jnp.int32)
    out = _paged_walk(
        "paged_attention" if name is None else name,
        q.reshape(S, Hkv, Hq // Hkv, hd), k_pages,
        v_pages, block_tables, jnp.maximum(lengths - 1, 0),
        jnp.minimum(lengths, 1),
        k_scale, v_scale, layer, G=Hq // Hkv, scale=scale,
        interpret=interpret, window=window, shared_kv=v_pages is None,
        **({"latent": latent} if latent else {}),
    )
    return out.reshape(S, Hq, out.shape[-1])


def block_sparse_attention_ref(
    q: jax.Array,  # [S, Hq, hd]
    k_pages: jax.Array,  # [P, Hkv, page, hd]
    v_pages: jax.Array,
    tables: jax.Array,  # int32 [S, Hkv, n_v]: pages of the kept blocks
    lengths: jax.Array,  # int32 [S, Hkv]: positions of the table attended
    *,
    scale: float,
) -> jax.Array:
    """Pure-jnp :func:`block_sparse_attention`: each (slot, kv head)'s
    queries over the first ``lengths`` positions of the pages its table
    names, in table order. A slot with length 0 reads zero."""
    S, Hq, hd = q.shape
    _, Hkv, page, _ = k_pages.shape
    G = Hq // Hkv
    heads = jnp.arange(Hkv)[None, :, None]
    k = k_pages[tables, heads].reshape(S, Hkv, -1, hd)  # [S, Hkv, K, hd]
    v = v_pages[tables, heads].reshape(S, Hkv, -1, hd)
    sc = jnp.einsum(
        "sgad,sgkd->sgak", q.reshape(S, Hkv, G, hd).astype(jnp.float32),
        k.astype(jnp.float32), preferred_element_type=jnp.float32,
    ) * scale
    ok = (jnp.arange(k.shape[2]) < lengths[..., None])[:, :, None, :]
    w = jax.nn.softmax(jnp.where(ok, sc, NEG_INF), axis=-1)
    w = jnp.where(ok.any(-1, keepdims=True), w, 0.0)
    out = jnp.einsum("sgak,sgkd->sgad", w, v.astype(jnp.float32))
    return out.reshape(S, Hq, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "name"))
def block_sparse_attention(
    q: jax.Array,  # [S, Hq, hd]: one query position a slot
    k_pages: jax.Array,  # [L, P, Hkv, page, hd] with ``layer``, or [P, ...]
    v_pages: jax.Array,
    tables: jax.Array,  # int32 [S, Hkv, n_v]
    lengths: jax.Array,  # int32 [S, Hkv]
    *,
    scale: float,
    interpret: bool = False,
    layer: jax.Array | None = None,
    name: str = "block_sparse_attention",
) -> jax.Array:
    """The page walk over a table of kept blocks a slot and kv head:
    grid ``(slot, kv head)``, the head's ``Hq / Hkv`` query heads on each
    block's keys. The live-span walk (:func:`_paged_walk_kernel`) with a
    block-table row, a start and a count a (slot, kv head): the table
    names the pages of the blocks a selection kept, in position order,
    the query sits at the table's last attended position (the walk's
    causal limit is the table's length), and only the kept blocks' pages
    are copied. Positions carry no rotation in such a layer, so a key's
    place in the table is as good as its place in the context. Returns
    ``[S, Hq, hd]``."""
    S, Hq, hd = q.shape
    Hkv = k_pages.shape[-3]
    lengths = jnp.asarray(lengths, jnp.int32).reshape(S * Hkv)
    out = _paged_walk(
        name, q.reshape(S, Hkv, Hq // Hkv, hd), k_pages, v_pages,
        tables.reshape(S * Hkv, -1), jnp.maximum(lengths - 1, 0),
        jnp.minimum(lengths, 1), None, None, layer, G=Hq // Hkv,
        scale=scale, interpret=interpret, per_head=True,
    )
    return out.reshape(S, Hq, hd)


__all__ = [
    "block_sparse_attention",
    "block_sparse_attention_ref",
    "flash_attention",
    "paged_attention",
    "paged_attention_ref",
    "paged_prefill_attention_ref",
    "ragged_paged_attention",
    "ragged_paged_attention_ref",
]
