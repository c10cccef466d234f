"""Pallas kernels of the lightning (linear-attention) recurrence
(models/sala.py has the equations and the plain forms these are pinned
against): one position a slot, and a chunk of rows a slot. Both read and
write ONE layer of the engine's state array ``[layers, slots, H, d, d]``
float32 in place (the layer on scalar prefetch, the array aliased to the
result), so the layer loop carries the array whole and nothing cuts a
layer out of it.

Grid ``(slot, block of heads)``. A head's state is ``d x d`` float32 (64
KB at 128): a block of 8 heads moves 512 KB each way, double-buffered.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

STEP_KERNEL = "lightning_attention_step"
CHUNK_KERNEL = "lightning_attention_chunk"
_HEADS = 8  # heads a grid step holds


def _heads_block(H: int) -> int:
    return max(hb for hb in range(1, min(H, _HEADS) + 1) if H % hb == 0)


def _step_kernel(nv_ref, layer_ref, q_ref, k_ref, v_ref, slope_ref, s_ref,
                 o_ref, s_out):
    """``S <- lambda S + k^T v`` and ``o = q S`` for one slot's block of
    heads, elementwise: ``q`` and ``k`` arrive as columns ``[hb, d, 1]``,
    ``v`` as a row ``[hb, 1, d]``."""
    del layer_ref
    live = nv_ref[pl.program_id(0)] > 0
    s0 = s_ref[...]
    lam = jnp.exp(-slope_ref[...])  # [hb, 1, 1]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    new = jnp.where(live, lam * s0 + k * v, s0)
    s_out[...] = new
    q = q_ref[0].astype(jnp.float32)
    o_ref[0] = jnp.sum(q * new, axis=1, keepdims=True)


def _chunk_kernel(nv_ref, layer_ref, q_ref, k_ref, v_ref, slope_ref, s_ref,
                  o_ref, s_out):
    """``O = ((Q K^T) * D) V + diag(lambda^(i+1)) Q S`` and the state after
    the slot's ``n`` valid rows, for one slot's block of heads."""
    del layer_ref
    n = nv_ref[pl.program_id(0)]
    q = q_ref[0].astype(jnp.float32)  # [hb, C, d], scaled by the caller
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    C = q.shape[1]
    sl = slope_ref[...]  # [hb, 1, 1]
    s0 = s_ref[...]  # [hb, d, d]
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    live = ((i >= j) & (j < n))[None]
    gap = jnp.maximum(i - j, 0).astype(jnp.float32)[None]
    decay = jnp.where(live, jnp.exp(-sl * gap), 0.0)  # [hb, C, C]
    dot = functools.partial(
        jax.lax.dot_general, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)
    a = dot(q, k, (((2,), (2,)), ((0,), (0,)))) * decay
    row = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    carry = jnp.exp(-sl * (row + 1).astype(jnp.float32)[None])  # [hb, C, 1]
    o = dot(a, v, (((2,), (1,)), ((0,), (0,)))) + carry * dot(
        q, s0, (((2,), (1,)), ((0,), (0,))))
    o_ref[0] = jnp.where((row < n)[None], o, 0.0)
    left = jnp.maximum(n - 1 - row, 0).astype(jnp.float32)[None]
    kd = k * jnp.where((row < n)[None], jnp.exp(-sl * left), 0.0)
    s_out[...] = jnp.exp(-sl * n.astype(jnp.float32)) * s0 + dot(
        kd, v, (((1,), (1,)), ((0,), (0,))))


def _call(kernel, name, n_valid, layer, q, k, v, slopes, state, o_shape,
          qkv_block, o_block, interpret):
    L, S, H, d, _ = state.shape
    hb = _heads_block(H)

    def at(*tail):
        return lambda s, h, *_: (s, h) + tail

    spec = lambda blk: pl.BlockSpec((1, hb) + blk, at(0, 0))  # noqa: E731
    s_spec = pl.BlockSpec(
        (None, None, hb, d, d), lambda s, h, nv, li: (li[0], s, h, 0, 0))
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, H // hb),
            in_specs=[
                spec(qkv_block[0]), spec(qkv_block[1]), spec(qkv_block[2]),
                pl.BlockSpec((hb, 1, 1), lambda s, h, *_: (h, 0, 0)), s_spec,
            ],
            out_specs=[spec(o_block), s_spec],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(o_shape, jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype),
        ],
        # operand 6 (after the two prefetched scalars) is the state array
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(
        jnp.asarray(n_valid, jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q, k, v, slopes.reshape(H, 1, 1).astype(jnp.float32), state,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def lightning_attention_step(q, k, v, state, slopes, active, layer, *,
                             interpret: bool = False):
    """One position a slot through layer ``layer`` of ``state`` ``[L, S, H,
    d, d]``: ``q`` / ``k`` / ``v`` ``[S, H, d]``. Returns ``(o [S, H, d]
    float32, state)``; :func:`models.sala.lightning_step_ref` on that
    layer's slice."""
    S, H, d = q.shape
    qs = (q.astype(jnp.float32) * d**-0.5)[..., None]  # columns
    o, state = _call(
        _step_kernel, STEP_KERNEL, active, layer, qs, k[..., None],
        v[:, :, None, :], slopes, state, (S, H, 1, d),
        ((d, 1), (d, 1), (1, d)), (1, d), interpret,
    )
    return o[:, :, 0], state


@functools.partial(jax.jit, static_argnames=("interpret",))
def lightning_attention_chunk(q, k, v, state, slopes, n_valid, layer, *,
                              interpret: bool = False):
    """A chunk of rows a slot through layer ``layer`` of ``state``: ``q``
    / ``k`` / ``v`` ``[S, H, C, d]``, ``n_valid`` ``[S]``. Returns ``(o [S,
    H, C, d] float32, state)``; :func:`models.sala.lightning_chunk_ref`
    on that layer's slice."""
    S, H, C, d = q.shape
    qs = q.astype(jnp.float32) * d**-0.5
    blk = (C, d)
    return tuple(_call(
        _chunk_kernel, CHUNK_KERNEL, n_valid, layer, qs, k, v, slopes, state,
        (S, H, C, d), (blk, blk, blk), blk, interpret,
    ))


__all__ = [
    "CHUNK_KERNEL", "STEP_KERNEL", "lightning_attention_chunk",
    "lightning_attention_step",
]
