"""Pallas kernels of the gated delta rule (Gated DeltaNet, arXiv:2412.06464)
and the plain forms they are pinned against: one position a slot, and a
chunk of rows a slot in the chunkwise (UT-transform) form.

A head's state is ``S`` in ``R^{dv x dk}``, float32; a position with key
``k`` (unit length), value ``v``, decay ``alpha = exp(g)`` in (0, 1] and step
size ``beta`` in [0, 2] does

    S <- alpha S (I - beta k k^T) + beta v k^T,        o = S q

that is, with ``M = S^T`` and ``w = beta (v - alpha M^T k)``: ``M <- alpha M
+ k w^T`` (a decay and a rank-one update; ``w`` is the value the state did
not predict). Both kernels read and write ONE layer of the engine's state
array in place (the layer on scalar prefetch, the array aliased to the
result), so the layer loop carries the array whole.

**The state's layout** is ``[layers, slots, dk, H dv]``: a head's ``M`` is
the ``dv`` lanes from ``h dv`` on. Neither 96 nor 192 is a whole number of
128-lane rows, 30 x 192 = 45 x 128 is, so nothing is padded where the
state is stored or streamed. The kernels take the heads two at a time (384
lanes = 3 x 128, an aligned slice): what differs by head (its key, its
decay) is selected by lane, so each product is computed for both heads of
a pair over the pair's lanes and the right half kept.

**The chunk form.** Over a sub-chunk of ``c`` = 64 rows with ``G_t`` the
running sum of ``g`` and ``Gamma[t, s] = exp(G_t - G_s)``:

    A = tril(diag(beta) (K K^T * Gamma), -1),      T = (I + A)^-1
    W = T diag(beta) (V - diag(exp G) K M_0)
    O = diag(exp G) Q M_0 + tril(Q K^T * Gamma) W
    M_c = exp(G_c) M_0 + (K * exp(G_c - G))^T W

``A`` is strictly lower triangular and ``T`` is built by halves from the
inverses of its diagonal blocks (:func:`_unit_lower_inverse`: twelve
products of 64 x 64, each of blocks of the true inverse). A row
past the slot's live length has ``g = 0`` and ``beta = 0`` (the caller's
:func:`_masked`): it leaves the state as it is, so the state after the
block is the state at the slot's last live row, and a slot without rows
keeps its own. ``fresh`` slots (a sequence's first block) start from zero
whatever the array holds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

STEP_KERNEL = "gated_delta_step"
CHUNK_KERNEL = "gated_delta_chunk"
SUB = 64  # rows of a sub-chunk of the chunk form
_PAIRS = 5  # pairs of heads a grid step of the step kernel holds


# ---------------------------------------------------------------------------
# The plain forms: a scan over positions
# ---------------------------------------------------------------------------


def _to_heads(state, H: int):
    """``[S, dk, H dv]`` as ``[S, H, dk, dv]``."""
    S, dk, hv = state.shape
    return state.reshape(S, dk, H, hv // H).transpose(0, 2, 1, 3)


def _from_heads(m):
    S, H, dk, dv = m.shape
    return m.transpose(0, 2, 1, 3).reshape(S, dk, H * dv)


def _position(m, q, k, v, g, beta):
    """One position of every slot and head: ``m`` ``[S, H, dk, dv]``."""
    md = m * jnp.exp(g)[..., None, None]
    w = beta[..., None] * (v - jnp.einsum("shkv,shk->shv", md, k))
    m = md + k[..., :, None] * w[..., None, :]
    return m, jnp.einsum("shkv,shk->shv", m, q)


def gated_delta_step_ref(q, k, v, g, beta, state, active):
    """One position a slot: ``q`` / ``k`` ``[S, H, dk]``, ``v`` ``[S, H,
    dv]``, ``g`` / ``beta`` ``[S, H]`` (float32), ``state`` ``[S, dk, H
    dv]`` (one layer), ``active`` ``[S]``. Returns ``(o [S, H, dv],
    state)``; an inactive slot keeps its state."""
    with jax.default_matmul_precision("highest"):
        m0 = _to_heads(state, q.shape[1])
        m, o = _position(m0, q, k, v, g, beta)
    m = jnp.where(active[:, None, None, None], m, m0)
    return o, _from_heads(m)


def gated_delta_chunk_ref(q, k, v, g, beta, state, n_valid, fresh):
    """A block of rows a slot, position by position: ``q`` / ``k`` ``[S, C,
    H, dk]``, ``v`` ``[S, C, H, dv]``, ``g`` / ``beta`` ``[S, C, H]``,
    ``state`` ``[S, dk, H dv]``, ``n_valid`` / ``fresh`` ``[S]``. Returns
    ``(o [S, C, H, dv], state)``: the state after each slot's ``n_valid``
    rows (from zero where ``fresh``), rows past them read zero."""
    H = q.shape[2]
    m0 = jnp.where(fresh[:, None, None, None], 0.0, _to_heads(state, H))

    def row(m, xs):
        t, qt, kt, vt, gt, bt = xs
        new, o = _position(m, qt, kt, vt, gt, bt)
        live = (t < n_valid)[:, None, None]
        return (jnp.where(live[..., None], new, m),
                jnp.where(live, o, 0.0))

    with jax.default_matmul_precision("highest"):
        m, o = lax.scan(row, m0, (
            jnp.arange(q.shape[1]),
            *(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))))
    m = jnp.where((n_valid > 0)[:, None, None, None], m,
                  _to_heads(state, H))
    return jnp.moveaxis(o, 0, 1), _from_heads(m)


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

_dot = functools.partial(
    lax.dot_general, preferred_element_type=jnp.float32,
    precision=lax.Precision.HIGHEST)


def _mm(a, b):
    return _dot(a, b, (((1,), (0,)), ((), ())))


def _mm_nt(a, b):  # a b^T
    return _dot(a, b, (((1,), (1,)), ((), ())))


def _mm_tn(a, b):  # a^T b
    return _dot(a, b, (((0,), (0,)), ((), ())))


def _column(row):
    """A row ``[1, n]`` as a column ``[n, 1]``: the diagonal of its
    broadcast, summed over lanes."""
    n = row.shape[1]
    eye = (lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _by_head(dv: int, n: int):
    """``pick(a, b)``: ``a`` on the first head's lanes of a pair ``[.., 2
    dv]``, ``b`` on the second's."""
    first = lax.broadcasted_iota(jnp.int32, (n, 2 * dv), 1) < dv
    return lambda a, b: jnp.where(first, a, b)


def _pair_position(m, q, k, v, alpha, beta, dv: int):
    """One position of a pair of heads, elementwise: ``m`` ``[dk, 2 dv]``,
    ``q`` / ``k`` two rows ``[2, dk]``, ``v`` / ``alpha`` / ``beta`` rows
    over the pair's lanes ``[1, 2 dv]``. Returns ``(m, o [1, 2 dv])``."""
    pick = _by_head(dv, m.shape[0])
    kk = pick(_column(k[0:1]), _column(k[1:2]))
    qq = pick(_column(q[0:1]), _column(q[1:2]))
    md = m * alpha
    w = beta * (v - jnp.sum(md * kk, axis=0, keepdims=True))
    m = md + kk * w
    return m, jnp.sum(m * qq, axis=0, keepdims=True)


def _unit_lower_inverse(a, eye, row, col):
    """``(I + A)^-1`` of a strictly lower triangular ``a`` ``[n, n]`` (``n``
    a power of two), by halves: with ``T`` the inverse of the diagonal
    blocks of size ``b``, the blocks of size ``2 b`` have ``[[T11, 0],
    [-T22 A21 T11, T22]]``, and ``T (A * M_b) T`` puts exactly ``T22 A21
    T11`` where ``M_b`` keeps the lower-left quarter of every ``2 b`` block.
    Two products a level, and every product is of blocks of the TRUE
    inverse: the Neumann product ``(I - A)(I + A^2)(I + A^4) ...`` is exact
    too, but its powers of ``A`` reach ``C(64, 32) |a|^32`` before they
    cancel, and keys that lie close together (``k_t . k_s`` near 1 under a
    step size near 2) made float32 garbage of it on the chip (PERF.md
    section 6, PR 57)."""
    t = eye
    for lvl in range(a.shape[0].bit_length() - 1):
        quarter = ((row >> (lvl + 1)) == (col >> (lvl + 1))) & (
            ((row >> lvl) & 1) == 1) & (((col >> lvl) & 1) == 0)
        t = t - _mm(t, _mm(jnp.where(quarter, a, 0.0), t))
    return t


def _step_kernel(nv_ref, layer_ref, q_ref, k_ref, rows_ref, s_ref, o_ref,
                 s_out, *, dv: int):
    """A slot's block of heads, pair by pair."""
    del layer_ref
    live = nv_ref[pl.program_id(0)] > 0
    q, k = q_ref[0, 0], k_ref[0, 0]  # [2 pairs, dk]
    for p in range(q.shape[0] // 2):
        at = pl.ds(p * 2 * dv, 2 * dv)
        m0 = s_ref[:, at]
        v, alpha, beta = (rows_ref[0, i:i + 1, at] for i in range(3))
        m, o = _pair_position(m0, q[2 * p:2 * p + 2], k[2 * p:2 * p + 2], v,
                              alpha, beta, dv)
        s_out[:, at] = jnp.where(live, m, m0)
        o_ref[0, :, at] = o


def _chunk_kernel(nv_ref, fresh_ref, layer_ref, q_ref, k_ref, v_ref, gb_ref,
                  s_ref, o_ref, s_out, *, dv: int):
    """A slot's pair of heads over the block's sub-chunks (module
    docstring)."""
    del layer_ref
    s = pl.program_id(0)
    n = nv_ref[s]
    C = q_ref.shape[2]
    dk = q_ref.shape[3]
    s_out[...] = jnp.where(fresh_ref[s] > 0, 0.0, s_ref[...])
    pick = _by_head(dv, SUB)
    pick_m = _by_head(dv, dk)
    row = lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    col = lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
    eye = (row == col).astype(jnp.float32)

    for j in range(C // SUB):
        rows = pl.ds(j * SUB, SUB)

        @pl.when(n <= j * SUB)
        def _():
            o_ref[0, rows, :] = jnp.zeros((SUB, 2 * dv), jnp.float32)

        @pl.when(n == j * SUB + 1)
        def _():  # one live row (a decoding slot's): a position
            m0 = s_out[...]
            q = jnp.concatenate(
                [q_ref[0, h, j * SUB:j * SUB + 1, :] for h in (0, 1)])
            k = jnp.concatenate(
                [k_ref[0, h, j * SUB:j * SUB + 1, :] for h in (0, 1)])
            one = _by_head(dv, 1)
            # the running sum's first entry is the row's own g
            alpha = one(*(jnp.exp(jnp.max(gb_ref[0, h, 0:1, rows], axis=1,
                                          keepdims=True)) for h in (0, 1)))
            beta = one(*(jnp.max(gb_ref[0, h, 1:2, rows], axis=1,
                                 keepdims=True) for h in (0, 1)))
            m, o = _pair_position(m0, q, k, v_ref[0, j * SUB:j * SUB + 1, :],
                                  alpha, beta, dv)
            s_out[...] = m
            o_ref[0, rows, :] = jnp.where(
                lax.broadcasted_iota(jnp.int32, (SUB, 1), 0) == 0, o, 0.0)

        @pl.when(n > j * SUB + 1)
        def _():
            m0 = s_out[...]  # [dk, 2 dv]
            v = v_ref[0, rows, :]  # [SUB, 2 dv]
            os_, ms = [], []
            for h in (0, 1):
                q = q_ref[0, h, rows, :]
                k = k_ref[0, h, rows, :]  # [SUB, dk]
                g_row = gb_ref[0, h, 0:1, rows]  # [1, SUB], running sums
                g_col = _column(g_row)
                b_col = _column(gb_ref[0, h, 1:2, rows])
                gamma = jnp.exp(jnp.where(row >= col, g_col - g_row, -1e30))
                a = b_col * _mm_nt(k, k) * jnp.where(row > col, gamma, 0.0)
                t = _unit_lower_inverse(a, eye, row, col)
                decay = jnp.exp(g_col)
                w = _mm(t, b_col * (v - decay * _mm(k, m0)))
                o = decay * _mm(q, m0) + _mm(_mm_nt(q, k) * gamma, w)
                # the running sum never rises: its least is its last
                g_end = jnp.min(g_row, axis=1, keepdims=True)
                m = jnp.exp(g_end) * m0 + _mm_tn(k * jnp.exp(g_end - g_col), w)
                os_.append(o), ms.append(m)
            s_out[...] = pick_m(*ms)
            o_ref[0, rows, :] = jnp.where(
                lax.broadcasted_iota(jnp.int32, (SUB, 1), 0) < n - j * SUB,
                pick(*os_), 0.0)


def _masked(g, beta, n_valid):
    """``(running sums of g within each sub-chunk, beta)`` ``[S, C, H]``
    with the rows past each slot's ``n_valid`` at 0 (they leave the state
    alone)."""
    S, C, H = g.shape
    live = (jnp.arange(C)[None, :] < n_valid[:, None])[..., None]
    g = jnp.where(live, g, 0.0).reshape(S, C // SUB, SUB, H)
    return (jnp.cumsum(g, axis=2).reshape(S, C, H),
            jnp.where(live, beta, 0.0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_step(q, k, v, g, beta, state, active, layer, *,
                     interpret: bool = False):
    """One position a slot through layer ``layer`` of ``state`` ``[L, S,
    dk, H dv]``; arguments as :func:`gated_delta_step_ref` takes them.
    Returns ``(o [S, H, dv] float32, state)``."""
    S, H, dk = q.shape
    dv = v.shape[-1]
    hb = 2 * max(p for p in range(1, _PAIRS + 1) if H % (2 * p) == 0)
    nb = H // hb
    f32 = jnp.float32
    rows = jnp.stack([
        v.astype(f32).reshape(S, H * dv),
        jnp.repeat(jnp.exp(g.astype(f32)), dv, axis=1),
        jnp.repeat(beta.astype(f32), dv, axis=1)], axis=1)  # [S, 3, H dv]
    qk_spec = pl.BlockSpec((1, 1, hb, dk), lambda s, b, *_: (s, b, 0, 0))
    s_spec = pl.BlockSpec(
        (None, None, dk, hb * dv), lambda s, b, nv, li: (li[0], s, 0, b))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, dv=dv),
        name=STEP_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, nb),
            in_specs=[
                qk_spec, qk_spec,
                pl.BlockSpec((1, 3, hb * dv), lambda s, b, *_: (s, 0, b)),
                s_spec,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, hb * dv), lambda s, b, *_: (s, 0, b)),
                s_spec,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, 1, H * dv), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operand 5 (after the two prefetched scalars) is the state array
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(
        jnp.asarray(active, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q.astype(f32).reshape(S, nb, hb, dk),
        k.astype(f32).reshape(S, nb, hb, dk), rows, state,
    )
    return o.reshape(S, H, dv), state


@functools.partial(jax.jit, static_argnames=("interpret",))
def gated_delta_chunk(q, k, v, g, beta, state, n_valid, fresh, layer, *,
                      interpret: bool = False):
    """A block of rows a slot through layer ``layer`` of ``state``;
    arguments as :func:`gated_delta_chunk_ref` takes them (``C`` a whole
    number of sub-chunks of ``SUB`` rows, an even number of heads).
    Returns ``(o [S, C, H, dv] float32, state)``."""
    S, C, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    pad = -C % SUB
    if pad:  # a narrow block: rows past n_valid
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    Cp = C + pad
    gs, bs = _masked(g.astype(f32), beta.astype(f32), n_valid)
    gb = jnp.stack([gs, bs], axis=1).transpose(0, 3, 1, 2)  # [S, H, 2, C]
    qk_spec = pl.BlockSpec((1, 2, Cp, dk), lambda s, p, *_: (s, p, 0, 0))
    v_spec = pl.BlockSpec((1, Cp, 2 * dv), lambda s, p, *_: (s, 0, p))
    s_spec = pl.BlockSpec(
        (None, None, dk, 2 * dv),
        lambda s, p, nv, fr, li: (li[0], s, 0, p))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, dv=dv),
        name=CHUNK_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S, H // 2),
            in_specs=[
                qk_spec, qk_spec, v_spec,
                pl.BlockSpec((1, 2, 2, Cp), lambda s, p, *_: (s, p, 0, 0)),
                s_spec,
            ],
            out_specs=[v_spec, s_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, Cp, H * dv), f32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operand 7 (after the three prefetched scalars) is the state array
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(
        jnp.asarray(n_valid, jnp.int32), jnp.asarray(fresh, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q.astype(f32).transpose(0, 2, 1, 3),
        k.astype(f32).transpose(0, 2, 1, 3),
        v.astype(f32).reshape(S, Cp, H * dv), gb, state,
    )
    return o[:, :C].reshape(S, C, H, dv), state


__all__ = [
    "CHUNK_KERNEL", "STEP_KERNEL", "SUB", "gated_delta_chunk",
    "gated_delta_chunk_ref", "gated_delta_step", "gated_delta_step_ref",
]
