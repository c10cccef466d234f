"""Ring attention — sequence/context parallelism over a device mesh.

Net-new vs the reference, which scales sequence length only by renting a
bigger worker (``max_seq_len`` appears solely in its memory arithmetic,
ml/utils.py:94-118 — SURVEY §5 long-context notes). Here long sequences are
sharded over a ``seq`` mesh axis and attention runs as a ring:

- each device holds its local Q/K/V blocks ``[B, T/n, H, hd]``,
- K/V blocks rotate around the ring via ``lax.ppermute`` (one ICI hop per
  step, n-1 steps) while each device accumulates flash-style blockwise
  softmax statistics (running max, normalizer, weighted values),
- causal masking is global-position arithmetic: block start offsets rotate
  with the K/V so every device masks exactly the right region,
- GQA contracts un-repeated K/V heads (``[B, S, n_kv, group, hd]``
  grouping), so no repeated KV is ever materialized.

Compute/communication overlap and per-block skipping of fully-masked tiles
are XLA's job once the ring is expressed this way (scaling-book recipe:
annotate, let the compiler schedule).

**Quantized collectives** (EQuARX, arxiv 2506.17615 — the KV-cache logic
applied to ICI traffic): ``ring_attention(..., quantized=True)`` rotates
int8 K/V blocks + per-row scales around the ring — roughly half the bf16
hop bytes; this is the one explicit collective on the serving path and
the only one ``collective_quant`` switches today (tensor-parallel
matmuls are GSPMD-sharded — XLA inserts those collectives, so there is
no call site to swap). :func:`quantized_psum` /
:func:`quantized_all_gather` are the allreduce/allgather building
blocks for explicit shard_map paths that want the same trade. The
reduction dequantizes and sums in f32 over the gathered axis in a FIXED
order, so every participant computes bitwise the same result (plain
``psum``'s ring order can differ per device); divergence vs the
full-precision collective is bounded and test-pinned
(tests/test_ring.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Quantized collectives (EQuARX-style): int8 over the wire, f32 reduction
# ---------------------------------------------------------------------------


# tlint: hot-path
def _quant_chunk(x):
    """Symmetric int8 over the last axis with per-row f32 scales — the
    same granularity as the paged KV cache's page rows
    (models/quant.py::quantize_kv), applied to the tensor headed over
    ICI. Returns ``(int8 [..., d], f32 scale [...])``."""
    from tensorlink_tpu.models.quant import quantize_kv

    return quantize_kv(x)


# tlint: hot-path
def _dequant_chunk(q, scale):
    """f32 view of a quantized chunk; the multiply fuses into the read."""
    return q.astype(jnp.float32) * scale[..., None]


# tlint: hot-path
def quantized_all_gather(x, axis_name: str, *, axis: int = 0, tiled: bool = False):
    """``lax.all_gather`` with int8 payload: each device quantizes its
    shard once, the gather moves int8 + per-row scales (≈½ the bf16
    bytes, ¼ of f32), and the result dequantizes locally to ``x.dtype``.
    Must run inside shard_map over ``axis_name``.

    ``tiled=True`` concatenates the shards along ``axis`` (like
    ``lax.all_gather(..., tiled=True)``) instead of stacking a new
    leading dim — the shape the tensor-parallel serving path needs when
    reassembling activations split along a feature axis. The wire still
    moves int8 + per-row scales; each shard is dequantized with ITS OWN
    scales before the concatenation, and shards concatenate in axis-index
    order, so the result is bitwise identical on every participant (the
    fixed-order contract docs/SHARDING.md pins)."""
    q, s = _quant_chunk(x)
    if tiled:
        qg = lax.all_gather(q, axis_name, axis=0)  # [n, ...] stacked
        sg = lax.all_gather(s, axis_name, axis=0)
        chunks = _dequant_chunk(qg, sg).astype(x.dtype)
        n = chunks.shape[0]
        return jnp.concatenate([chunks[i] for i in range(n)], axis=axis)
    qg = lax.all_gather(q, axis_name, axis=axis)
    sg = lax.all_gather(s, axis_name, axis=axis)
    return _dequant_chunk(qg, sg).astype(x.dtype)


# tlint: hot-path
def quantized_psum(x, axis_name: str):
    """EQuARX-style quantized allreduce: int8 chunk quantize → gather →
    reduce in f32 → rescale to ``x.dtype``. Must run inside shard_map
    over ``axis_name``.

    Determinism: every device gathers the SAME int8 chunks + scales and
    sums them over the gathered axis in the same fixed order, so the
    result is bitwise identical on every participant and across runs —
    unlike a ring-reduce ``psum`` whose accumulation order can vary with
    the device's ring position. That property is what lets the quantized
    collective live on the serving path without breaking the engine's
    bit-determinism contracts (pinned in tests/test_ring.py)."""
    q, s = _quant_chunk(x)
    qg = lax.all_gather(q, axis_name, axis=0)  # [n, ...]
    sg = lax.all_gather(s, axis_name, axis=0)
    return jnp.sum(_dequant_chunk(qg, sg), axis=0).astype(x.dtype)


def _block_scores(q, k, scale):
    """Grouped-query scores. q: [B, Tq, Hkv, G, hd], k: [B, Tk, Hkv, hd]
    → [B, Hkv, G, Tq, Tk] in fp32."""
    return jnp.einsum(
        "bqhgd,bkhd->bhgqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale


def _ring_attention_local(
    q,  # [B, Tq, Hq, hd] this device's query block
    k,  # [B, Tk, Hkv, hd] this device's key block
    v,  # [B, Tk, Hkv, hd]
    *,
    axis_name: str,
    scale: float,
    causal: bool,
    quantized: bool,
):
    """Runs inside shard_map: full ring of n_dev steps, blockwise-stable
    softmax accumulation. ``quantized`` rotates int8 K/V blocks + per-row
    scales instead of full-precision blocks (each shard quantizes ONCE
    before the ring, so hop count never compounds the error), roughly
    halving the per-hop ICI bytes of bf16 activations."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, Tq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Tq, Hkv, G, hd)

    q_pos = idx * Tq + jnp.arange(Tq)  # global query positions
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, _):
        kv_c, kv_start, m, l, o = carry
        if quantized:
            k8, ks, v8, vs = kv_c
            k_blk = _dequant_chunk(k8, ks)
            v_blk = _dequant_chunk(v8, vs)
        else:
            k_blk, v_blk = kv_c
        s = _block_scores(qg, k_blk, scale)  # [B, Hkv, G, Tq, Tk]
        if causal:
            kv_pos = kv_start + jnp.arange(k_blk.shape[1])
            mask = q_pos[:, None] >= kv_pos[None, :]  # [Tq, Tk]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        blk_max = s.max(-1)  # [B, Hkv, G, Tq]
        new_m = jnp.maximum(m, blk_max)
        p = jnp.exp(s - new_m[..., None])
        corr = jnp.exp(m - new_m)
        l_new = l * corr + p.sum(-1)
        pv = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_blk.astype(jnp.float32))
        o_new = o * corr.transpose(0, 3, 1, 2)[..., None] + pv
        # rotate K/V (+ their global start offset) one hop around the
        # ring — in quantized mode the hop moves int8 payload + scales
        kv_nxt = tuple(lax.ppermute(x, axis_name, perm) for x in kv_c)
        start_nxt = lax.ppermute(kv_start, axis_name, perm)
        return (kv_nxt, start_nxt, new_m, l_new, o_new), None

    # initial accumulators must be marked varying over the ring axis or the
    # scan carry types disagree (jax VMA check under shard_map)
    def varying(x):
        return lax.pcast(x, axis_name, to="varying")

    m0 = varying(jnp.full((B, Hkv, G, Tq), NEG_INF, jnp.float32))
    l0 = varying(jnp.zeros((B, Hkv, G, Tq), jnp.float32))
    o0 = varying(jnp.zeros((B, Tq, Hkv, G, hd), jnp.float32))
    kv_start0 = idx * k.shape[1]
    if quantized:
        k8, ks = _quant_chunk(k)
        v8, vs = _quant_chunk(v)
        kv_c0 = (k8, ks, v8, vs)
    else:
        kv_c0 = (k, v)
    (_, _, m, l, o), _ = lax.scan(
        step, (kv_c0, kv_start0, m0, l0, o0), None, length=n
    )
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(B, Tq, Hq, hd).astype(q.dtype)


def ring_attention(
    q,  # [B, S, Hq, hd] GLOBAL arrays (sharded over S by the caller's mesh)
    k,  # [B, S, Hkv, hd]
    v,
    mesh: Mesh,
    *,
    axis_name: str = "seq",
    scale: float | None = None,
    causal: bool = True,
    quantized: bool = False,
):
    """Sequence-parallel attention over ``mesh[axis_name]``.

    Equivalent to full (causal) attention on the unsharded arrays — that
    equivalence is the unit test (tests/test_ring.py). Sequence length must
    divide the axis size. ``quantized`` (ModelConfig.collective_quant)
    rotates int8 K/V + scales around the ring instead of full-precision
    blocks: ≈½ the bf16 ICI bytes per hop, divergence bounded and
    test-pinned."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        partial(
            _ring_attention_local,
            axis_name=axis_name,
            scale=scale,
            causal=causal,
            quantized=bool(quantized),
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def sequence_sharded(mesh: Mesh, x, axis_name: str = "seq", dim: int = 1):
    """Shard an array's sequence dimension over the ring axis."""
    spec = [None] * x.ndim
    spec[dim] = axis_name
    return jax.device_put(x, NamedSharding(mesh, P(*spec)))
