"""Compiled pipeline parallelism inside one mesh (GPipe schedule).

The reference's pipeline is emergent thread timing: micro-batches run in
Python threads and interleave only by chance (ml/module.py:374-399 — SURVEY
§2.2 "no schedule"). On TPU the schedule is *compiled*: layers are sharded
over a ``stage`` mesh axis, micro-batches stream through the ring via
``lax.ppermute``, and one jit program executes the whole GPipe diagram —
bubble fill/drain included — with XLA overlapping compute and ICI transfer.

This in-mesh pipeline composes with the cross-node stage pipeline
(parallel/planner.py): a *worker* is one mesh (possibly itself pipelined
over its devices), stages between workers ride the P2P transport.
:func:`pipelined_stage_forward` is the product entry point — the worker
executor runs its layer slice through it when the plan's mesh has a
``stage`` axis (ml/worker.py), semantics identical to
``models.transformer.stage_forward`` (parity-tested).

Differentiable end-to-end: ``ppermute`` has a transpose rule, so
``jax.grad`` through :func:`gpipe` yields exactly the 1F1B-equivalent
backward sweep.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

def _vary(x, axis_name: str):
    return lax.pcast(x, axis_name, to="varying")


def _tmap(fn, *trees):
    return jax.tree.map(fn, *trees)


def _gpipe_local(
    stacked_params,  # local layer slice (leading dim L/n_stage)
    micros,  # pytree, each leaf [n_micro, ...] (replicated)
    *,
    stage_fn: Callable,
    axis_name: str,
):
    n_stage = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    n_micro = jax.tree.leaves(micros)[0].shape[0]
    n_ticks = n_micro + n_stage - 1
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]

    act0 = _tmap(lambda m: _vary(jnp.zeros_like(m[0]), axis_name), micros)
    outs0 = _tmap(lambda m: _vary(jnp.zeros_like(m), axis_name), micros)

    def tick(carry, t):
        act_in, outs = carry
        # stage 0 injects micro t (clipped index; masked out-of-range below)
        inject = _tmap(lambda m: m[jnp.clip(t, 0, n_micro - 1)], micros)
        x = _tmap(
            lambda i, a: jnp.where(idx == 0, _vary(i, axis_name), a),
            inject,
            act_in,
        )
        y = stage_fn(stacked_params, x)
        # this stage is working on micro (t - idx); only keep real ticks
        mine = t - idx
        live = (mine >= 0) & (mine < n_micro)
        y = _tmap(lambda yy, aa: jnp.where(live, yy, aa), y, act_in)
        # last stage collects its finished micro
        m_idx = jnp.clip(mine, 0, n_micro - 1)
        collect = (idx == n_stage - 1) & live
        outs = _tmap(
            lambda o, yy: jnp.where(collect, o.at[m_idx].set(yy), o), outs, y
        )
        act_next = _tmap(lambda yy: lax.ppermute(yy, axis_name, perm), y)
        return (act_next, outs), None

    (_, outs), _ = lax.scan(tick, (act0, outs0), jnp.arange(n_ticks))
    return _tmap(lambda o: o[None], outs)  # leading stage dim for out_specs


def gpipe(
    stage_fn: Callable,  # (local_layer_params, x) -> y, applied per stage
    stacked_params,  # pytree, leaves with leading layer dim L (L % n_stage == 0)
    micros,  # pytree of micro stacks, leaves [n_micro, mb, ...]
    mesh: Mesh,
    *,
    axis_name: str = "stage",
):
    """Run ``micros`` through the layer pipeline; returns the same pytree of
    ``[n_micro, ...]`` outputs equal to applying all layers sequentially
    (parity test: tests/test_pipeline.py). ``stage_fn`` must map its input
    pytree to an output of identical structure/shapes (passthrough leaves —
    e.g. per-micro masks — are simply returned unchanged)."""
    n_stage = mesh.shape[axis_name]
    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    micro_specs = jax.tree.map(lambda _: P(), micros)
    out_specs = jax.tree.map(lambda _: P(axis_name), micros)
    fn = jax.shard_map(
        partial(_gpipe_local, stage_fn=stage_fn, axis_name=axis_name),
        mesh=mesh,
        in_specs=(param_specs, micro_specs),
        out_specs=out_specs,
    )
    out = fn(stacked_params, micros)  # leaves [n_stage, n_micro, mb, ...]
    return _tmap(lambda o: o[n_stage - 1], out)


def pipelined_stage_forward(
    params: dict,
    cfg,
    mesh: Mesh,
    *,
    tokens=None,  # int32 [B, T] (first stage)
    hidden=None,  # [B, T, D] (later stages)
    attn_mask=None,  # bool [B, T]
    n_micro: int,
    axis_name: str = "stage",
    first: bool = False,
    last: bool = False,
    remat: bool = False,
):
    """``stage_forward`` semantics with this worker's layer slice itself
    pipelined over ``mesh[axis_name]`` (in-mesh GPipe).

    The batch splits into ``n_micro`` micro-batches that stream through the
    layer pipeline in one compiled program; embedding and head run outside
    the pipelined region (their params are stage-replicated). No KV cache —
    this is the training / full-sequence path; serving plans never carry a
    ``stage`` axis (parallel/planner.py policy).
    """
    from ..models.transformer import (
        _block,
        _embed_tokens,
        _logits,
        _mask_bias,
        _norm,
        _rope_dim,
        rope_tables,
    )

    if first:
        if tokens is None:
            raise ValueError("first stage requires tokens")
        B, T = tokens.shape
    else:
        if hidden is None:
            raise ValueError("non-first stage requires hidden")
        B, T = hidden.shape[:2]
    if B % n_micro != 0:
        raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
    n_stage = mesh.shape[axis_name]
    n_local = jax.tree.leaves(params["layers"])[0].shape[0]
    if n_local % n_stage != 0:
        raise ValueError(
            f"{n_local} layers not divisible by stage axis {n_stage}"
        )
    mb = B // n_micro

    if first:
        x = _embed_tokens(params, tokens, cfg)
        if cfg.pos == "learned":
            pos = jnp.arange(T)[None, :]
            x = x + params["embed"]["pos"][pos].astype(cfg.dtype)
    else:
        x = hidden.astype(cfg.dtype)

    positions = jnp.arange(T)[None, :]  # no cache → absolute = local
    cos = sin = None
    if cfg.pos == "rope":
        cos, sin = rope_tables(positions, _rope_dim(cfg), cfg.rope_theta)
        # [1, T, hd] broadcasts over every micro's batch rows

    if attn_mask is None:
        attn_mask = jnp.ones((B, T), bool)
    qpos = jnp.broadcast_to(positions, (B, T))
    bias = _mask_bias(qpos, T, attn_mask, cfg.sliding_window)  # [B,1,1,T,T]

    block = _block
    if remat:
        block = jax.checkpoint(
            _block,
            policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=(2, 8),  # cfg, attn_fn
        )

    def stage_fn(layer_slice, x_in):
        act, b = x_in

        def scan_fn(carry, lp):
            y, _ = block(carry, lp, cfg, cos, sin, b, None, None, None)
            return y, None

        y, _ = lax.scan(scan_fn, act, layer_slice)
        return (y, b)

    micros = (
        x.reshape(n_micro, mb, T, -1),
        bias.reshape(n_micro, mb, *bias.shape[1:]),
    )
    out, _ = gpipe(
        stage_fn, params["layers"], micros, mesh, axis_name=axis_name
    )
    x = out.reshape(B, T, -1)

    if last:
        x = _norm(x, params["final_norm"], cfg)
        return _logits(params, x, cfg), None
    return x, None
