"""Unified decoder-only transformer core (functional, scan-over-layers).

TPU-first design notes (vs. the reference's eager per-``nn.Module`` execution,
ml/worker.py:297-357):

- Parameters are stacked over layers (leading ``L`` axis) and the block is run
  under ``lax.scan`` — XLA compiles ONE block program regardless of depth, and
  the KV cache rides the scan as per-layer xs/ys so decode updates it in place
  (donated).
- Attention is grouped-query by construction: queries are reshaped to
  ``[B, T, n_kv, group, hd]`` and contracted against un-repeated KV, so GQA
  never materializes repeated KV heads in HBM.
- Softmax/norm statistics run in float32 while weights/activations stay in
  bfloat16 (MXU-native).
- All shapes are static; masks are position-index arithmetic, not Python
  control flow, so one compiled program serves any padding.
"""

from __future__ import annotations

import math
import os as _os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .base import KVCache, ModelConfig
from .quant import matmul as _mm  # dequant-on-the-fly for int8 serving

P = jax.sharding.PartitionSpec


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("shape", "scale", "dtype", "sharding"))
def _dense_init(key, shape, scale, dtype, sharding=None):
    """One weight leaf, drawn, scaled and cast in ONE program: the f32 draw
    of a stacked leaf (3.6 GB for qwen3-4b's ``w_gate``) never exists in
    device memory beside the model, only the cast result does. Under a
    ``sharding`` each device draws only its own slice (the threefry bits
    are a function of the element's index, ``jax_threefry_partitionable``),
    so the values are the unsharded draw's, whatever the layout."""
    w = (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
    if sharding is not None:
        w = lax.with_sharding_constraint(w, sharding)
    return w


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype=None, shardings=None
) -> dict:
    """Random-init parameter pytree (shapes double as the loader's schema).

    ``shardings`` is a pytree of ``jax.sharding.Sharding`` matching the
    result (``tp_partition_specs`` on the serving mesh): every leaf is then
    made under its sharding, no leaf whole on one device, with the values
    the same seed gives unsharded (tests/test_tp_load.py pins both)."""
    if cfg.patterned:
        # layers of more than one kind have a tree of their own (lead,
        # periods, tail) and one placement: whole, on one device
        from . import latent, sala

        if shardings is not None:
            raise NotImplementedError(
                "a patterned model's weights are not made under shardings"
            )
        if sala.is_sala(cfg):
            return sala.init_params(cfg, key, dtype)
        return latent.init_params(cfg, key, dtype)
    dt = dtype or cfg.dtype
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    L, V = cfg.n_layers, cfg.vocab_size
    keys = iter(jax.random.split(key, 32))

    # leaves are made last, each under its sharding: until then a leaf is
    # the function that makes it
    def dense(k, *shape, scale=None):
        s = scale if scale is not None else shape[-2] ** -0.5
        return lambda sh: _dense_init(k, shape, float(s), jnp.dtype(dt), sh)

    def const(value, *shape):
        def make(sh):
            x = jnp.full(shape, value, dt)
            return x if sh is None else jax.device_put(x, sh)
        return make

    def zeros(*shape):
        return const(0, *shape)

    def ones(*shape):
        return const(1, *shape)

    def norm_p(with_bias: bool, *shape):
        p = {"scale": ones(*shape)}
        if with_bias:
            p["bias"] = zeros(*shape)
        return p

    ln_bias = cfg.norm == "layernorm"
    attn = {
        "wq": dense(next(keys), L, d, cfg.q_dim),
        "wk": dense(next(keys), L, d, cfg.kv_dim),
        "wv": dense(next(keys), L, d, cfg.kv_dim),
        "wo": dense(next(keys), L, cfg.q_dim, d),
    }
    if cfg.attn_bias:
        attn |= {
            "bq": zeros(L, cfg.q_dim),
            "bk": zeros(L, cfg.kv_dim),
            "bv": zeros(L, cfg.kv_dim),
        }
    if cfg.attn_out_bias or cfg.family == "gpt2":
        attn["bo"] = zeros(L, d)
    if cfg.qk_norm:
        attn |= {"q_norm": ones(L, hd), "k_norm": ones(L, hd)}
    if cfg.qk_norm_full:  # OLMo-2: norm over the whole projection dim
        attn |= {"q_norm": ones(L, cfg.q_dim), "k_norm": ones(L, cfg.kv_dim)}

    if cfg.moe:
        E = cfg.n_experts
        mlp = {
            "router": dense(next(keys), L, d, E),
            "w_gate": dense(next(keys), L, E, d, f),
            "w_up": dense(next(keys), L, E, d, f),
            "w_down": dense(next(keys), L, E, f, d, scale=f**-0.5),
        }
    elif cfg.mlp == "gated":
        mlp = {
            "w_gate": dense(next(keys), L, d, f),
            "w_up": dense(next(keys), L, d, f),
            "w_down": dense(next(keys), L, f, d, scale=f**-0.5),
        }
        if cfg.mlp_bias:
            mlp |= {
                "b_gate": zeros(L, f),
                "b_up": zeros(L, f),
                "b_down": zeros(L, d),
            }
    else:  # fused (GPT-2): up -> act -> down, with biases
        mlp = {
            "w_up": dense(next(keys), L, d, f),
            "b_up": zeros(L, f),
            "w_down": dense(next(keys), L, f, d, scale=f**-0.5),
            "b_down": zeros(L, d),
        }

    params = {
        "embed": {"tok": dense(next(keys), V, d, scale=0.02)},
        "layers": {
            "ln1": norm_p(ln_bias, L, d),
            "attn": attn,
            "ln2": norm_p(ln_bias, L, d),
            "mlp": mlp,
        },
        "final_norm": norm_p(ln_bias, d),
    }
    if cfg.pos == "learned":
        params["embed"]["pos"] = dense(next(keys), cfg.max_seq_len, d, scale=0.02)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(keys), d, V)
    if shardings is None:
        return jax.tree.map(lambda make: make(None), params)
    return jax.tree.map(lambda make, sh: make(sh), params, shardings)


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def _norm(x: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * lax.rsqrt(var + cfg.norm_eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        scale = p["scale"].astype(jnp.float32)
        if cfg.norm_plus_one:  # Gemma stores the rmsnorm weight as an offset
            scale = scale + 1.0
        var = (xf**2).mean(-1, keepdims=True)
        out = xf * lax.rsqrt(var + cfg.norm_eps) * scale
    return out.astype(x.dtype)


def _rms_head_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """Qwen3 per-head RMSNorm over head_dim."""
    xf = x.astype(jnp.float32)
    out = xf * lax.rsqrt((xf**2).mean(-1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def yarn_inv_freq(head_dim: int, theta: float, scaling: tuple):
    """YaRN's rotary frequencies and amplitude for ``scaling`` =
    ``(factor, original length, beta_fast, beta_slow, mscale,
    mscale_all_dim)``: ``(inv_freq [head_dim / 2] float32, amplitude)``.
    A dimension that turns more than ``beta_fast`` times over the
    original length keeps its frequency, one that turns less than
    ``beta_slow`` times is interpolated (divided by ``factor``), and a
    linear ramp lies between; cos and sin are multiplied by
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``.
    Shapes are static: numpy in float64, rounded once."""
    from .base import yarn_mscale

    factor, orig, beta_fast, beta_slow, mscale, all_dim = scaling
    half = head_dim // 2
    freq = theta ** (-np.arange(half, dtype=np.float64) / half)

    def corr(turns):  # the dimension that turns ``turns`` times in ``orig``
        return head_dim * math.log(orig / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), head_dim - 1)
    ramp = np.clip(
        (np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = freq / factor * ramp + freq * (1.0 - ramp)
    amp = yarn_mscale(factor, mscale) / yarn_mscale(factor, all_dim)
    return inv_freq.astype(np.float32), float(amp)


def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                scaling: tuple | None = None):
    """cos/sin tables ``[B, T, head_dim]`` in the HF half-split convention
    (rotate_half): frequencies repeat over the two halves. ``scaling``:
    YaRN's numbers (:func:`yarn_inv_freq`)."""
    half = head_dim // 2
    amp = 1.0
    if scaling is None:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        inv_freq, amp = yarn_inv_freq(head_dim, theta, scaling)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, T, half]
    ang = jnp.concatenate([ang, ang], axis=-1)
    if amp != 1.0:
        return jnp.cos(ang) * amp, jnp.sin(ang) * amp
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, T, H, hd]; cos/sin: [B, T, hd] (HF rotate_half convention)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    xf = x.astype(jnp.float32)
    out = xf * cos[..., None, :] + rotated.astype(jnp.float32) * sin[..., None, :]
    return out.astype(x.dtype)


def _rope_dim(cfg: ModelConfig) -> int:
    """Rotary dims per head (GPT-NeoX applies rotary to a prefix only)."""
    rd = int(cfg.head_dim * cfg.rope_pct)
    return rd - rd % 2


def _embed_tokens(params: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = params["embed"]["tok"][tokens].astype(cfg.dtype)
    if cfg.embed_scale:  # Gemma normalizer, cast to activation dtype like HF
        x = x * jnp.asarray(cfg.d_model**0.5, cfg.dtype)
    if cfg.embed_mult != 1.0:  # MiniCPM's scale_emb
        x = x * jnp.asarray(cfg.embed_mult, cfg.dtype)
    return x


def _act(x: jax.Array, name: str) -> jax.Array:
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu_exact":
        return jax.nn.gelu(x, approximate=False)  # GPT-NeoX "gelu"
    return jax.nn.gelu(x, approximate=True)  # GPT-2 gelu_new


def attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k: jax.Array,  # [B, S, Hkv, hd]
    v: jax.Array,  # [B, S, Hkv, hd]
    mask_bias: jax.Array,  # [B, 1, 1, T, S] float32 additive
    scale: float,
) -> jax.Array:
    """Grouped-query attention without materializing repeated KV."""
    B, T, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, T, Hkv, G, hd)
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale + mask_bias
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", w, v)
    return out.reshape(B, T, Hq, hd)


TP_GATHER = "tlink.tp_gather"


# tlint: hot-path
def _tp_gather(h: jax.Array, tp_axis: str | None, quant: bool) -> jax.Array:
    """Reassemble an activation whose LAST axis is split over ``tp_axis``.

    Identity when ``tp_axis`` is None (the single-device trace is
    unchanged). Inside shard_map, shards concatenate in axis-index order
    — ``lax.all_gather(tiled=True)`` — so the full activation is bitwise
    identical on every participant and to the unsharded compute.
    ``quant`` swaps in the EQuARX-style int8 gather
    (parallel/ring.py::quantized_all_gather): same fixed order, ≈½/¼ the
    wire bytes, bounded divergence (opt-in via collective_quant)."""
    if tp_axis is None:
        return h
    # the program's own name for its collectives (metadata only, like the
    # step's phase scopes): a trace attributes their time to it
    with jax.named_scope(TP_GATHER):
        if quant:
            from ..parallel.ring import quantized_all_gather

            return quantized_all_gather(
                h, tp_axis, axis=h.ndim - 1, tiled=True
            )
        return lax.all_gather(h, tp_axis, axis=h.ndim - 1, tiled=True)


def _mlp(
    h: jax.Array,
    p: dict,
    cfg: ModelConfig,
    tp_axis: str | None = None,
    tp_quant: bool = False,
) -> jax.Array:
    """MLP block. Under tensor parallelism (``tp_axis``) w_gate/w_up hold
    LOCAL output columns and w_down holds the FULL hidden dim but LOCAL
    output columns — biases are sliced to match, applied before each
    gather (elementwise add commutes with concatenation), and the hidden
    and output reassemble via :func:`_tp_gather`."""
    if cfg.moe:
        return _moe_mlp(h, p, cfg)
    if cfg.mlp == "gated":
        g = _mm(h, p["w_gate"])
        u = _mm(h, p["w_up"])
        if "b_gate" in p:
            g = g + p["b_gate"]
            u = u + p["b_up"]
        mid = _tp_gather(_act(g, cfg.act) * u, tp_axis, tp_quant)
        out = _mm(mid, p["w_down"])
        if "b_down" in p:
            out = out + p["b_down"]
        return _tp_gather(out, tp_axis, tp_quant)
    mid = _tp_gather(_act(_mm(h, p["w_up"]) + p["b_up"], cfg.act), tp_axis, tp_quant)
    out = _mm(mid, p["w_down"]) + p["b_down"]
    return _tp_gather(out, tp_axis, tp_quant)


def _moe_mlp(h: jax.Array, p: dict, cfg: ModelConfig) -> jax.Array:
    """Mixtral-style top-k MoE.

    ``cfg.moe_dispatch == "sparse"`` routes to the capacity-factor top-k
    all-to-all dispatch (parallel/expert.py) — ~E/K× fewer expert FLOPs,
    used when the expert mesh axis is active. The default here is the
    dense-dispatch formulation: every expert sees every token and results
    combine with the (sparse) top-k routing weights — numerically identical
    to gather-based routing, exact, and GSPMD-friendly at small scale.
    """
    if cfg.moe_dispatch == "sparse":
        from ..parallel.expert import sparse_moe_mlp

        return sparse_moe_mlp(h, p, cfg)
    B, T, d = h.shape
    E, K = cfg.n_experts, cfg.n_experts_per_tok
    router_logits = _mm(h, p["router"]).astype(jnp.float32)  # [B, T, E]
    topw, topi = lax.top_k(router_logits, K)
    topw = jax.nn.softmax(topw, axis=-1)  # normalize over selected experts
    gates = jnp.zeros_like(router_logits).at[
        jnp.arange(B)[:, None, None],
        jnp.arange(T)[None, :, None],
        topi,
    ].set(topw)  # [B, T, E] sparse weights
    g = jnp.einsum("btd,edf->btef", h, p["w_gate"])
    u = jnp.einsum("btd,edf->btef", h, p["w_up"])
    y = jnp.einsum("btef,efd->bted", _act(g, cfg.act) * u, p["w_down"])
    return jnp.einsum("bted,bte->btd", y, gates.astype(h.dtype))


def _quant_kv(t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 over head_dim: per-(row, position, head) scales —
    the int8 KV-cache write path."""
    tf = t.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1, keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(tf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _block(
    x: jax.Array,
    lp: dict,
    cfg: ModelConfig,
    cos: jax.Array | None,
    sin: jax.Array | None,
    mask_bias: jax.Array,
    # this layer's cache slice: None | (k, v) | (k, v, k_scale, v_scale)
    # — the 4-tuple is the int8 cache (see KVCache int8 mode)
    cache_kv: tuple | None,
    write_at: jax.Array | None,  # [B] int32 write offsets
    attn_fn=None,  # static override: (q, k, v, mask_bias, scale) -> out
):
    B, T, _ = x.shape
    post = cfg.norm_position == "post"  # OLMo-2: norm the sublayer output
    h = x if post else _norm(x, lp["ln1"], cfg)
    ap = lp["attn"]
    q = _mm(h, ap["wq"])
    k = _mm(h, ap["wk"])
    v = _mm(h, ap["wv"])
    if "bq" in ap:
        q, k, v = q + ap["bq"], k + ap["bk"], v + ap["bv"]
    if cfg.qk_norm_full:  # OLMo-2: full-projection-dim RMSNorm pre-reshape
        q = _rms_head_norm(q, ap["q_norm"], cfg.norm_eps)
        k = _rms_head_norm(k, ap["k_norm"], cfg.norm_eps)
    q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = _rms_head_norm(q, ap["q_norm"], cfg.norm_eps)
        k = _rms_head_norm(k, ap["k_norm"], cfg.norm_eps)
    if cos is not None:
        rd = cos.shape[-1]
        if rd == cfg.head_dim:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        else:  # partial rotary (GPT-NeoX): prefix rotates, rest passes
            q = jnp.concatenate(
                [apply_rope(q[..., :rd], cos, sin), q[..., rd:]], axis=-1
            )
            k = jnp.concatenate(
                [apply_rope(k[..., :rd], cos, sin), k[..., rd:]], axis=-1
            )

    new_cache_kv = cache_kv
    if cache_kv is not None:
        upd = jax.vmap(
            lambda c, u, o: lax.dynamic_update_slice(
                c, u, (o,) + (0,) * (c.ndim - 1)
            )
        )
        if len(cache_kv) == 4:  # int8 cache: quantize writes, dequant reads
            ck, cv, cks, cvs = cache_kv
            k8, ks = _quant_kv(k)
            v8, vs = _quant_kv(v)
            ck = upd(ck, k8, write_at)
            cv = upd(cv, v8, write_at)
            cks = upd(cks, ks, write_at)
            cvs = upd(cvs, vs, write_at)
            k_all = (ck.astype(jnp.float32) * cks).astype(x.dtype)
            v_all = (cv.astype(jnp.float32) * cvs).astype(x.dtype)
            new_cache_kv = (ck, cv, cks, cvs)
        else:
            ck, cv = cache_kv
            ck = upd(ck, k.astype(ck.dtype), write_at)
            cv = upd(cv, v.astype(cv.dtype), write_at)
            k_all, v_all = ck, cv
            new_cache_kv = (ck, cv)
    else:
        k_all, v_all = k, v

    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.head_dim**-0.5
    impl = attn_fn or attention
    attn_out = impl(q, k_all.astype(q.dtype), v_all.astype(q.dtype), mask_bias, scale)
    attn_out = _mm(attn_out.reshape(B, T, cfg.q_dim), ap["wo"])
    if "bo" in ap:
        attn_out = attn_out + ap["bo"]
    if post:  # OLMo-2: ln1 == post_attention, ln2 == post_feedforward
        x = x + _norm(attn_out, lp["ln1"], cfg)
        x = x + _norm(_mlp(x, lp["mlp"], cfg), lp["ln2"], cfg)
    elif cfg.parallel_residual:  # GPT-NeoX: both branches read the block input
        x = x + attn_out + _mlp(_norm(x, lp["ln2"], cfg), lp["mlp"], cfg)
    else:
        x = x + attn_out
        x = x + _mlp(_norm(x, lp["ln2"], cfg), lp["mlp"], cfg)
    return x, new_cache_kv


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _mask_bias(
    q_pos: jax.Array,  # [B, T] absolute query positions
    kv_len: int,
    valid_kv: jax.Array,  # [B, S] bool — which kv slots hold real tokens
    sliding_window: int | None,
) -> jax.Array:
    """Additive float32 mask ``[B, 1, 1, T, S]``: causal (+ window) over
    absolute positions; padding handled via ``valid_kv``."""
    kv_idx = jnp.arange(kv_len)[None, None, :]  # [1, 1, S]
    qp = q_pos[:, :, None]  # [B, T, 1]
    ok = kv_idx <= qp
    if sliding_window is not None:
        ok &= kv_idx > qp - sliding_window
    ok &= valid_kv[:, None, :]
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)[:, None, None]


@partial(
    jax.jit,
    static_argnames=(
        "cfg", "remat", "return_hidden", "seq_mesh", "seq_axis",
        "flash_prefill", "flash_mesh",
    ),
)
def forward(
    params: dict,
    tokens: jax.Array,  # int32 [B, T]
    cfg: ModelConfig,
    cache: KVCache | None = None,
    attn_mask: jax.Array | None = None,  # bool [B, T] valid-token mask
    positions: jax.Array | None = None,  # int32 [B, T] absolute positions
    remat: bool = False,
    return_hidden: bool = False,
    seq_mesh=None,  # Mesh with a ring axis → sequence-parallel attention
    seq_axis: str = "seq",
    # static promise that the cache is FRESH (offset 0) — lets the serving
    # engine's prefill route attention through the Pallas flash kernel
    # when cfg.flash_attention is set (ops/attention.py)
    flash_prefill: bool = False,
    # serving mesh (GSPMD has no partitioning rule for the Pallas kernel, so
    # under a mesh the flash call runs inside shard_map over data/tensor —
    # attention is independent per (batch, head), no collectives needed)
    flash_mesh=None,
):
    """Full forward. Returns ``(logits, new_cache)``.

    - Training / no-cache: causal self-attention over the sequence.
    - Prefill: pass a fresh ``cache``; keys/values land at positions
      ``cache.length + arange(T)`` per row.
    - Decode: same call with ``T=1`` — one compiled program per (B, T) bucket.

    Implemented as the single-stage case of :func:`_stage_impl` — the
    stage-chained pipeline path and this whole-model path share one
    implementation, which is what keeps the "stage chain == forward" parity
    tests (tests/test_stages.py) meaningful.
    """
    if return_hidden:
        x, new_cache = _stage_impl(
            params, cfg, tokens=tokens, cache=cache, attn_mask=attn_mask,
            positions=positions, first=True, last=False, remat=remat,
            seq_mesh=seq_mesh, seq_axis=seq_axis, flash_prefill=flash_prefill,
            flash_mesh=flash_mesh,
        )
        return _norm(x, params["final_norm"], cfg), new_cache
    return _stage_impl(
        params, cfg, tokens=tokens, cache=cache, attn_mask=attn_mask,
        positions=positions, first=True, last=True, remat=remat,
        seq_mesh=seq_mesh, seq_axis=seq_axis, flash_prefill=flash_prefill,
        flash_mesh=flash_mesh,
    )


def _logits(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    tp_axis: str | None = None,
    tp_quant: bool = False,
) -> jax.Array:
    """LM head. Under tensor parallelism a tied head computes the full
    vocab locally (the embedding is replicated — no collective); an
    untied ``lm_head`` holds LOCAL vocab columns and the logits reassemble
    via :func:`_tp_gather` so sampling sees the full distribution,
    identical on every shard."""
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = x @ params["embed"]["tok"].T.astype(cfg.dtype)
        else:
            logits = _tp_gather(_mm(x, params["lm_head"]), tp_axis, tp_quant)
        if cfg.logit_cap is not None:
            logits = cfg.logit_cap * jnp.tanh(logits / cfg.logit_cap)
    return logits


# ---------------------------------------------------------------------------
# Stage-wise forward (pipeline parallelism)
# ---------------------------------------------------------------------------
#
# A pipeline stage holds a contiguous layer slice (params["layers"] stacked
# over just those layers) plus, per the plan flags, the embedding
# (StagePlan.first) and final norm + head (StagePlan.holds_head). Chaining
# stage_forward over all stages reproduces forward() exactly — that
# equivalence is the unit test replacing the reference's "logits match the
# unsharded model" check (reference assembles per-worker nn.Module
# fragments, ml/graphing.py).
#
# Flag mapping for executors: pass ``first=stage.first`` and
# ``last=stage.last and stage.holds_head``. When embeddings are tied across
# a multi-stage plan the head lives on stage 0 (holds_head=True there), so
# the final stage returns hidden and the driver finishes with
# :func:`head_forward` on stage 0.


@partial(
    jax.jit,
    static_argnames=("cfg", "first", "last", "remat", "seq_mesh", "seq_axis"),
)
def stage_forward(
    params: dict,
    cfg: ModelConfig,  # FULL model config (stage layer count comes from params)
    *,
    tokens: jax.Array | None = None,  # int32 [B, T] (first stage)
    hidden: jax.Array | None = None,  # [B, T, D] (later stages)
    cache: KVCache | None = None,  # this stage's cache (its layers only)
    attn_mask: jax.Array | None = None,  # bool [B, T]
    positions: jax.Array | None = None,  # int32 [B, T]
    first: bool = False,
    last: bool = False,
    remat: bool = False,
    seq_mesh=None,  # Mesh with a ring axis → sequence-parallel attention
    seq_axis: str = "seq",
):
    """Run one pipeline stage. Returns ``(out, new_cache)`` where ``out`` is
    logits when ``last`` else the hidden state to ship to the next stage.

    ``seq_mesh`` switches attention to the ring formulation
    (parallel/ring.py) with activations sequence-sharded over
    ``mesh[seq_axis]`` — the long-context product path (SURVEY §5: the
    reference scales sequence only by renting a bigger worker). Ring mode
    requires no KV cache, no padding mask, and no sliding window."""
    return _stage_impl(
        params, cfg, tokens=tokens, hidden=hidden, cache=cache,
        attn_mask=attn_mask, positions=positions, first=first, last=last,
        remat=remat, seq_mesh=seq_mesh, seq_axis=seq_axis,
    )


def _stage_impl(
    params: dict,
    cfg: ModelConfig,
    *,
    tokens: jax.Array | None = None,
    hidden: jax.Array | None = None,
    cache: KVCache | None = None,
    attn_mask: jax.Array | None = None,
    positions: jax.Array | None = None,
    first: bool,
    last: bool,
    remat: bool,
    seq_mesh=None,
    seq_axis: str = "seq",
    flash_prefill: bool = False,
    flash_mesh=None,
):
    if cfg.patterned:
        raise NotImplementedError(
            "a model with layers of more than one kind (latent attention, "
            "windows, routed experts: models/latent.py) runs on the slot "
            "engine's step only (engine/paged.py): the dense-cache forward, "
            "stage chains and training do not know its layers"
        )
    attn_fn = None
    T_in = tokens.shape[1] if tokens is not None else (
        hidden.shape[1] if hidden is not None else 1
    )
    B_in = tokens.shape[0] if tokens is not None else (
        hidden.shape[0] if hidden is not None else 1
    )
    if (
        flash_prefill
        and cfg.flash_attention
        and cache is not None
        and T_in > 1
        and T_in % min(128, T_in) == 0  # irregular bucket -> einsum, not a
        and seq_mesh is None  # trace-time crash of serving
        # off the TPU the kernel only runs in interpret mode, which is
        # pure overhead over the einsum — fall through to einsum there
        # unless a test opts in explicitly
        and (
            jax.default_backend() == "tpu"
            or _os.environ.get("TLTPU_FLASH_INTERPRET") == "1"
        )
    ):
        from ..ops.attention import flash_attention

        interp = jax.default_backend() != "tpu"  # env opt-in: interpret mode
        T_flash = T_in
        win = cfg.sliding_window

        def _flash(q, k_all, v_all, scale):
            # fresh cache (offset 0): keys beyond T are zeros the causal
            # mask would hide anyway — attend over the written prefix only
            return flash_attention(
                q, k_all[:, :T_flash], v_all[:, :T_flash],
                scale=scale, interpret=interp, window=win,
            )

        if flash_mesh is None:
            def attn_fn(q, k_all, v_all, _bias, scale):
                return _flash(q, k_all, v_all, scale)
        else:
            # GSPMD cannot partition a pallas_call, so run it manually via
            # shard_map: batch shards on data, heads on tensor — attention
            # is independent per (batch, head), so no collectives. The
            # pallas_call's out_shape carries no varying-axis metadata, so
            # the VMA check is off.
            sizes = dict(flash_mesh.shape)
            dp = (
                "data"
                if sizes.get("data", 1) > 1 and B_in % sizes["data"] == 0
                else None
            )
            tp = (
                "tensor"
                if sizes.get("tensor", 1) > 1
                and cfg.n_heads % sizes["tensor"] == 0
                and cfg.n_kv_heads % sizes["tensor"] == 0
                else None
            )
            spec = P(dp, None, tp, None)

            def attn_fn(q, k_all, v_all, _bias, scale):
                return jax.shard_map(
                    lambda ql, kl, vl: _flash(ql, kl, vl, scale),
                    mesh=flash_mesh,
                    in_specs=(spec, spec, spec),
                    out_specs=spec,
                    check_vma=False,
                )(q, k_all, v_all)
    if seq_mesh is not None:
        if cache is not None:
            raise ValueError("sequence-parallel attention has no KV cache path")
        if attn_mask is not None:
            raise ValueError(
                "sequence-parallel attention does not support padding masks"
            )
        if cfg.sliding_window is not None:
            raise ValueError(
                "sequence-parallel attention does not support sliding windows"
            )
        from ..parallel.ring import ring_attention

        def attn_fn(q, k, v, _bias, scale):  # causal masking is global-
            # position arithmetic inside the ring; _bias is unused
            return ring_attention(
                q, k, v, seq_mesh, axis_name=seq_axis, scale=scale,
                causal=True, quantized=cfg.collective_quant,
            )

    if first:
        if tokens is None:
            raise ValueError("first stage requires tokens")
        B, T = tokens.shape
    else:
        if hidden is None:
            raise ValueError("non-first stage requires hidden")
        B, T = hidden.shape[:2]
    if attn_mask is None:
        attn_mask = jnp.ones((B, T), bool)
    offset = cache.length if cache is not None else jnp.zeros((B,), jnp.int32)
    if positions is None:
        positions = offset[:, None] + jnp.arange(T)[None, :]

    if first:
        x = _embed_tokens(params, tokens, cfg)
        if cfg.pos == "learned":
            x = x + params["embed"]["pos"][positions].astype(cfg.dtype)
    else:
        x = hidden.astype(cfg.dtype)

    cos = sin = None
    if cfg.pos == "rope":
        cos, sin = rope_tables(positions, _rope_dim(cfg), cfg.rope_theta)

    if cache is not None:
        S = cache.max_len
        kv_idx = jnp.arange(S)[None, :]
        new_len = offset + attn_mask.sum(-1).astype(jnp.int32)
        valid_kv = kv_idx < new_len[:, None]
    else:
        valid_kv = attn_mask
    bias = _mask_bias(positions, valid_kv.shape[-1], valid_kv, cfg.sliding_window)

    block = _block
    if remat:
        block = jax.checkpoint(
            _block,
            policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=(2, 8),  # cfg, attn_fn
        )

    layers = params.get("layers")
    new_cache = cache
    if layers is not None:
        if cache is not None:
            arrays = (cache.k, cache.v)
            if cache.quantized:
                arrays += (cache.k_scale, cache.v_scale)

            def scan_fn(carry, xs):
                lp = xs[0]
                y, ckv = block(
                    carry, lp, cfg, cos, sin, bias, tuple(xs[1:]), offset,
                    attn_fn,
                )
                return y, ckv

            x, outs = lax.scan(scan_fn, x, (layers,) + arrays)
            new_cache = KVCache(
                k=outs[0],
                v=outs[1],
                length=offset + attn_mask.sum(-1).astype(jnp.int32),
                k_scale=outs[2] if cache.quantized else None,
                v_scale=outs[3] if cache.quantized else None,
            )
        else:

            def scan_fn(carry, lp):
                y, _ = block(
                    carry, lp, cfg, cos, sin, bias, None, None, attn_fn
                )
                return y, None

            x, _ = lax.scan(scan_fn, x, layers)

    if last:
        x = _norm(x, params["final_norm"], cfg)
        return _logits(params, x, cfg), new_cache
    return x, new_cache


@partial(jax.jit, static_argnames=("cfg",))
def head_forward(params: dict, hidden: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final norm + lm head only — serves the tied-embedding hop where the
    last pipeline stage ships hidden states back to stage 0 for logits
    (planner.py marks stage 0 ``last`` when embeddings are tied)."""
    x = _norm(hidden.astype(cfg.dtype), params["final_norm"], cfg)
    return _logits(params, x, cfg)


def slice_stage_params(
    params: dict, lo: int, hi: int, *, first: bool, holds_head: bool
) -> dict:
    """Cut a full parameter tree down to one stage's tree (host-side; used by
    tests and by single-host multi-stage simulations — real workers load only
    their slice from the checkpoint, engine/loader.py)."""
    if "layers" not in params:
        # a patterned model (models/latent.py) is one stage, whole
        if not (first and holds_head and lo == 0):
            raise NotImplementedError(
                "a patterned model is served as one whole stage"
            )
        return dict(params)
    out: dict = {}
    if first:
        out["embed"] = params["embed"]
    if holds_head:
        out["final_norm"] = params["final_norm"]
        if "lm_head" in params:
            out["lm_head"] = params["lm_head"]
        if "embed" not in out and "lm_head" not in params:
            out["embed"] = params["embed"]  # tied head needs the embedding
    if hi > lo:
        # a stage that holds every layer takes the stacked leaves as they
        # are: slicing [0:L] would copy the whole model beside itself
        out["layers"] = jax.tree.map(
            lambda a: a if (lo, hi) == (0, a.shape[0]) else a[lo:hi],
            params["layers"],
        )
    return out


# ---------------------------------------------------------------------------
# Sharding
# ---------------------------------------------------------------------------


def partition_specs(
    cfg: ModelConfig,
    *,
    tensor_axis: str | None = "tensor",
    expert_axis: str | None = None,
    fsdp_axis: str | None = None,
) -> dict:
    """Megatron-style PartitionSpec pytree matching :func:`init_params`.

    The TPU replacement for the reference's per-worker module assignment
    (ml/graphing.py:730-761): sharding is declared per-parameter and GSPMD
    inserts the collectives. qkv/gate/up shard their output dim on
    ``tensor_axis`` (column-parallel); wo/down shard their input dim
    (row-parallel) so each pair needs one psum. ``fsdp_axis`` additionally
    shards the remaining large dim (ZeRO-3 style). Experts shard on
    ``expert_axis``.
    """
    t, e, fs = tensor_axis, expert_axis, fsdp_axis

    def spec(*names):
        return P(*names)

    ln = {"scale": spec(None, None)}
    if cfg.norm == "layernorm":
        ln["bias"] = spec(None, None)
    attn = {
        "wq": spec(None, fs, t),
        "wk": spec(None, fs, t),
        "wv": spec(None, fs, t),
        "wo": spec(None, t, fs),
    }
    if cfg.attn_bias:
        attn |= {"bq": spec(None, t), "bk": spec(None, t), "bv": spec(None, t)}
    if cfg.attn_out_bias or cfg.family == "gpt2":  # must match init_params
        attn["bo"] = spec(None, None)
    if cfg.qk_norm:
        attn |= {"q_norm": spec(None, None), "k_norm": spec(None, None)}
    if cfg.qk_norm_full:  # scales align with the column-sharded projections
        attn |= {"q_norm": spec(None, t), "k_norm": spec(None, t)}

    if cfg.moe:
        mlp = {
            "router": spec(None, None, None),
            "w_gate": spec(None, e, fs, t),
            "w_up": spec(None, e, fs, t),
            "w_down": spec(None, e, t, fs),
        }
    elif cfg.mlp == "gated":
        mlp = {
            "w_gate": spec(None, fs, t),
            "w_up": spec(None, fs, t),
            "w_down": spec(None, t, fs),
        }
        if cfg.mlp_bias:
            mlp |= {
                "b_gate": spec(None, t),
                "b_up": spec(None, t),
                "b_down": spec(None, None),
            }
    else:
        mlp = {
            "w_up": spec(None, fs, t),
            "b_up": spec(None, t),
            "w_down": spec(None, t, fs),
            "b_down": spec(None, None),
        }

    specs = {
        "embed": {"tok": spec(t, fs)},
        "layers": {"ln1": ln, "attn": attn, "ln2": dict(ln), "mlp": mlp},
        "final_norm": {"scale": spec(None)}
        | ({"bias": spec(None)} if cfg.norm == "layernorm" else {}),
    }
    if cfg.pos == "learned":
        specs["embed"]["pos"] = spec(None, fs)
    if not cfg.tie_embeddings:
        specs["lm_head"] = spec(fs, t)
    return specs


def tp_shardable(cfg: ModelConfig, tp: int) -> str | None:
    """Why ``cfg`` can NOT shard ``tp`` ways on the explicit serving TP
    path, or ``None`` when it can.

    The explicit path (``tp_partition_specs`` + shard_map in
    engine/paged.py) slices heads/columns head-major-contiguously and
    reassembles with exact tiled all_gathers, so the constraints are pure
    divisibility plus two structural refusals: MoE (routing is global)
    and ``qk_norm_full`` (its RMSNorm spans the FULL projection dim — a
    local head slice would normalize over the wrong statistics)."""
    tp = int(tp)
    if tp <= 1:
        return None
    if cfg.moe:
        return "MoE routing is not tensor-shardable on the serving path"
    if cfg.qk_norm_full:
        return "qk_norm_full normalizes over the full projection dim"
    if cfg.patterned:
        return ("a model with layers of more than one kind is served whole "
                "on one chip: its pools and states have no partition specs")
    for name in ("n_heads", "n_kv_heads", "d_ff", "d_model"):
        val = int(getattr(cfg, name))
        if val % tp:
            return f"{name}={val} is not divisible by tp={tp}"
    if not cfg.tie_embeddings and cfg.vocab_size % tp:
        return f"untied vocab_size={cfg.vocab_size} is not divisible by tp={tp}"
    return None


def tp_partition_specs(cfg: ModelConfig, axis: str = "tp") -> dict:
    """PartitionSpec pytree for the EXPLICIT (shard_map) serving TP path
    — matches :func:`init_params`, walkable by dot-path (engine/loader).

    Unlike the GSPMD :func:`partition_specs` (where wo/w_down are
    row-parallel and XLA inserts psums), every matmul weight here shards
    its OUTPUT dim and activations reassemble with exact tiled
    all_gathers — column-slice matmuls are bitwise equal to slicing the
    full product, and a fixed-order concat is bitwise associative-free,
    which is what keeps tp=N streams bit-identical to tp=1
    (docs/SHARDING.md). Biases shard with the outputs they add onto;
    embeddings/norms replicate; per-head qk_norm scales (``[L, hd]``)
    replicate and apply to local heads unchanged. Gate with
    :func:`tp_shardable` first."""
    if cfg.moe:
        raise ValueError("MoE params have no explicit-TP partition specs")
    t = axis
    rep2, rep1 = P(None, None), P(None)

    ln = {"scale": rep2}
    if cfg.norm == "layernorm":
        ln["bias"] = rep2
    attn = {
        "wq": P(None, None, t),
        "wk": P(None, None, t),
        "wv": P(None, None, t),
        "wo": P(None, None, t),  # output (d_model) columns — input q_dim FULL
    }
    if cfg.attn_bias:
        attn |= {"bq": P(None, t), "bk": P(None, t), "bv": P(None, t)}
    if cfg.attn_out_bias or cfg.family == "gpt2":  # must match init_params
        attn["bo"] = P(None, t)
    if cfg.qk_norm:
        attn |= {"q_norm": rep2, "k_norm": rep2}
    if cfg.qk_norm_full:  # refused by tp_shardable; specs stay replicated
        attn |= {"q_norm": rep2, "k_norm": rep2}

    if cfg.mlp == "gated":
        mlp = {
            "w_gate": P(None, None, t),
            "w_up": P(None, None, t),
            "w_down": P(None, None, t),  # output (d_model) columns — f FULL
        }
        if cfg.mlp_bias:
            mlp |= {"b_gate": P(None, t), "b_up": P(None, t), "b_down": P(None, t)}
    else:
        mlp = {
            "w_up": P(None, None, t),
            "b_up": P(None, t),
            "w_down": P(None, None, t),
            "b_down": P(None, t),
        }

    specs = {
        "embed": {"tok": rep2},
        "layers": {"ln1": ln, "attn": attn, "ln2": dict(ln), "mlp": mlp},
        "final_norm": {"scale": rep1}
        | ({"bias": rep1} if cfg.norm == "layernorm" else {}),
    }
    if cfg.pos == "learned":
        specs["embed"]["pos"] = rep2
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, t)
    return specs


def cache_specs(
    cfg: ModelConfig, *, data_axis="data", tensor_axis="tensor",
    quantized: bool = False,
):
    """KV cache sharding: batch on data, kv heads on tensor (when they
    divide; the planner degrades to replicated heads otherwise)."""
    kv = P(None, data_axis, None, tensor_axis, None)
    return KVCache(
        k=kv,
        v=kv,
        length=P(data_axis),
        k_scale=kv if quantized else None,
        v_scale=kv if quantized else None,
    )
