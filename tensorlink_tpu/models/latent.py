"""Layers of more than one kind: latent attention, windows, learned
selection of context, and a chip's share of routed experts.

A :class:`~tensorlink_tpu.models.base.ModelConfig` with ``layer_kinds``
names each layer's attention kind; ``latent`` holds the sizes of each
(:class:`~tensorlink_tpu.models.base.LatentAttn`). This module holds what
the serving step (engine/latent.py) needs of such a model that the dense
GQA core (models/transformer.py) does not have: the layer pattern, the
parameter tree, the projections of latent attention in both of its forms,
and the expert layer.

**The pattern** (:func:`pattern_of`). Layers are grouped as ``lead`` (the
leading layers that keep the dense MLP), whole ``periods`` of the shortest
repeating run of kinds after them, and a ``tail`` of less than one period.
The parameters of the periods are stacked over the periods, one stack per
place in the period, so the step scans periods (one traced copy of a
period whatever the depth) and unrolls only lead and tail.

**Latent attention** (:func:`latent_qkv`, :func:`attend_materialised`,
:func:`attend_absorbed`). A position caches one row: the normalised
key/value latent and one rotated key shared by all heads. Materialised,
keys and values are projected up from the rows per head (the ragged
pass's sliding layers: one span of rows serves every query of a slot).
Absorbed, the up-projection of the keys moves onto the queries and that of
the values behind the softmax, so the rows are read as they are cached
(continuation steps; and every selected read, where each query has rows of
its own). The two are the same sums in another order.

**Experts** (:func:`moe_mlp`). The router scores every published expert;
this program holds ``experts_held`` of them from ``experts_first`` and
computes their part only, plus the shared expert. Dropless, with the
grouping as data: the (row, expert) pairs that fall on held experts are
counted per expert, each expert's rows are laid in whole tiles of
``MOE_TILE`` rows, and one loop runs over the tiles that hold a row — a
trip count the routing decides, in one compiled program. What the absent
experts would add is left out (the other chips of the group add it).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .base import GatedDelta, GqaAttn, LatentAttn, ModelConfig, ShortConv
from .quant import matmul as _mm
from .transformer import apply_rope, rope_tables

NEG_INF = -1e30
MOE_TILE = 128  # rows of one expert a trip of the expert loop computes

LATENT_ATTN = "tlink.latent_attn"
WINDOW_ATTN = "tlink.window_attn"
INDEX_SELECT = "tlink.index_select"
SHORT_CONV = "tlink.short_conv"
GATED_DELTA_SCOPE = "tlink.gated_delta"
MOE = "tlink.moe"

# what a step counts of its own routing and selection, in this order, in
# the cache's ``stats`` vector (engine/latent.py; read with the chunk's
# one sync by engine/continuous.py)
STEP_STATS = (
    "moe_rows_routed_local", "moe_rows_computed", "moe_rows_busiest_expert",
    "moe_experts_touched", "moe_experts_held", "moe_rows_in_group",
    "moe_rows_valid",
    "sparse_positions_kept", "sparse_positions_scored",
)
N_MOE_STATS = 7  # the expert layer's counts (:func:`moe_mlp`) lead
# a layer's routed experts, stacked ``[E, ...]`` (``[periods, E, ...]``
# where ``moe["stacked"]`` names the period: engine/latent.py::layer_loop)
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


class Pattern(NamedTuple):
    lead: tuple  # kinds of the leading dense-MLP layers
    period: tuple  # kinds of one period
    n_periods: int
    tail: tuple  # kinds of the layers after the last whole period

    def kind_index(self, kind: str):
        """``(base, per_period, offsets)``: the index among the layers of
        ``kind`` of the first period's first such layer, how many a period
        holds, and each place's offset inside its period (None where the
        place is of another kind)."""
        base = self.lead.count(kind)
        offs, n = [], 0
        for k in self.period:
            offs.append(n if k == kind else None)
            n += k == kind
        return base, n, tuple(offs)


def pattern_of(cfg: ModelConfig) -> Pattern:
    kinds = tuple(cfg.layer_kinds)
    n_lead = min(cfg.n_dense_layers, len(kinds))
    rest = kinds[n_lead:]
    period: tuple = ()
    for p in range(1, len(rest) + 1):
        if all(rest[i] == rest[i % p] for i in range(len(rest))):
            period = rest[:p]
            break
    n_periods = len(rest) // len(period) if period else 0
    return Pattern(
        kinds[:n_lead], period, n_periods, rest[n_periods * len(period):]
    )


def kind_counts(cfg: ModelConfig) -> dict:
    """Layers of each kind: what sizes a kind's page pool."""
    return {k: cfg.layer_kinds.count(k) for k, _ in cfg.latent}


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> dict:
    """Random-init parameter tree of a patterned model: ``embed``,
    ``lead`` / ``tail`` (a list of layers), ``periods`` (a tuple over the
    places of a period, each leaf stacked over the periods),
    ``final_norm``, ``lm_head``. A layer is ``ln1``, ``attn`` (the kind's
    projections), ``ln2`` and ``mlp`` (dense) or ``moe`` (router, selection
    bias, the held experts and the shared one)."""
    return _init_tree(key, cfg, jnp.dtype(dtype or cfg.dtype))


@functools.partial(jax.jit, static_argnames=("cfg", "dt"))
def _init_tree(key, cfg: ModelConfig, dt) -> dict:
    """The whole tree in ONE program (a leaf a program, as the dense core
    makes its ten, would be ~60 compiles of a cold start here); each
    leaf's float32 draw is cast as it is made and never kept."""
    d = cfg.d_model
    pat = pattern_of(cfg)
    counter = iter(range(1 << 20))

    def draw(shape, scale, dtype):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def dense(stack, *shape, scale=None):
        s = scale if scale is not None else shape[-2] ** -0.5
        return draw(tuple(stack) + shape, float(s), dt)

    def ones(stack, *shape):
        return jnp.ones(tuple(stack) + shape, dt)

    def attn(kind: str, stack):
        la = cfg.latent_of(kind)
        if isinstance(la, ShortConv):
            # the operator's projections in the place of an attention's:
            # ``w_in`` to (B, C, g), the taps ``[kernel, width]`` (tap j
            # weighs position t - (kernel - 1) + j; of order one over the
            # kernel, so that z keeps its size), ``w_out``
            return {
                "w_in": dense(stack, d, 3 * la.width),
                "taps": dense(stack, la.kernel, la.width,
                              scale=la.kernel**-0.5),
                "w_out": dense(stack, la.width, d),
            }
        H = la.n_heads
        if isinstance(la, GatedDelta):
            # q, k and v in one projection (the convolution runs over its
            # channels in that order), the output gate, the decay's and
            # the step size's inputs; ``A_log`` / ``dt_bias`` (float32)
            # seeded so that a head forgets a tenth to a quarter a position
            v = H * la.value_dim
            f32 = lambda *shape, at, scale: at + draw(  # noqa: E731
                tuple(stack) + shape, scale, jnp.float32)
            return {
                "w_qkv": dense(stack, d, la.conv_width),
                "taps": dense(stack, la.kernel, la.conv_width,
                              scale=la.kernel**-0.5),
                "w_a": dense(stack, d, H), "w_b": dense(stack, d, H),
                "A_log": f32(H, at=0.0, scale=0.5),
                "dt_bias": f32(H, at=-2.0, scale=0.5),
                "w_g": dense(stack, d, v),
                "o_norm": 1 + dense(stack, la.value_dim, scale=0.1),
                "wo": dense(stack, v, d),
            }
        if isinstance(la, GqaAttn):
            # fan-in scale: unit-RMS inputs give q and k entries of order
            # one and scores of order one under 1 / sqrt(head_dim)
            q, kv = H * la.head_dim, la.n_kv_heads * la.head_dim
            return {
                "wq": dense(stack, d, q), "wk": dense(stack, d, kv),
                "wv": dense(stack, d, kv),
                **({"w_g": dense(stack, d, H)} if la.gate else {}),
                # seeded off one, so that a norm left out is not a no-op
                **({"q_norm": 1 + dense(stack, la.head_dim, scale=0.1),
                    "k_norm": 1 + dense(stack, la.head_dim, scale=0.1)}
                   if la.qk_norm else {}),
                # ... or over the whole projection
                **({"q_norm": 1 + dense(stack, q, scale=0.1),
                    "k_norm": 1 + dense(stack, kv, scale=0.1)}
                   if la.qk_norm_full else {}),
                "wo": dense(stack, q, d),
            }
        p = {
            "w_dq": dense(stack, d, la.q_rank),
            "q_norm": ones(stack, la.q_rank),
            # the up-projections take the latents' rescale out of their
            # seed scale: attention logits of order one, as trained
            # weights give, where the plain fan-in scale would give a
            # softmax of a handful of positions (std ~6 at the published
            # sizes) that turns with every rounding; likewise what scaled
            # positions put on the softmax scale
            "w_uq": dense(stack, la.q_rank, H * la.qk_dim,
                          scale=la.q_rank**-0.5 / la.q_scale
                          / la.temperature),
            "w_dkv": dense(stack, d, la.row_dim),
            "kv_norm": ones(stack, la.kv_rank),
            "w_ukv": dense(stack, la.kv_rank, H * (la.nope_dim + la.v_dim),
                           scale=la.kv_rank**-0.5 / la.kv_scale),
            **({"w_g": dense(stack, d, H)} if la.gate else {}),
            "wo": dense(stack, H * la.v_dim, d),
        }
        if la.index_heads:
            p |= {
                "w_iq": dense(stack, la.q_rank, la.index_heads * la.index_dim),
                "w_ik": dense(stack, d, la.index_dim),
                "ik_norm": {
                    "scale": ones(stack, la.index_dim),
                    "bias": jnp.zeros(tuple(stack) + (la.index_dim,), dt),
                },
                "w_iw": dense(stack, d, la.index_heads),
            }
        return p

    def gated(stack, f):
        return {
            "w_gate": dense(stack, d, f),
            "w_up": dense(stack, d, f),
            "w_down": dense(stack, f, d, scale=f**-0.5),
        }

    def layer(kind: str, dense_mlp: bool, stack=()):
        p = {
            "ln1": {"scale": ones(stack, d)},
            "attn": attn(kind, stack),
            "ln2": {"scale": ones(stack, d)},
        }
        if dense_mlp:
            p["mlp"] = gated(stack, cfg.d_ff)
            return p
        E, f = cfg.n_held, cfg.moe_d_ff
        p["moe"] = {
            "router": dense(stack, d, cfg.n_experts),
            # the selection bias moves which experts are chosen, never a
            # weight; seeded small so that it is not a no-op under test
            **({"bias": draw(tuple(stack) + (cfg.n_experts,), 0.02,
                             jnp.float32)}
               if cfg.moe_router == "sigmoid" else {}),
            "w_gate": dense(stack, E, d, f),
            "w_up": dense(stack, E, d, f),
            "w_down": dense(stack, E, f, d, scale=f**-0.5),
        }
        if cfg.n_shared_experts:
            p["moe"]["shared"] = gated(stack, f * cfg.n_shared_experts)
        return p

    return {
        "embed": {"tok": dense((), cfg.vocab_size, d, scale=0.02)},
        "lead": [layer(k, True) for k in pat.lead],
        # a model without experts keeps the dense MLP in every layer
        "periods": tuple(
            layer(k, not cfg.n_experts, (pat.n_periods,)) for k in pat.period
        ),
        "tail": [layer(k, not cfg.n_experts) for k in pat.tail],
        "final_norm": {"scale": ones((), d)},
        # a tied head is the embedding (transformer._logits)
        **({} if cfg.tie_embeddings
           else {"lm_head": dense((), d, cfg.vocab_size)}),
    }


# ---------------------------------------------------------------------------
# Latent attention
# ---------------------------------------------------------------------------


def _rms(x, w, eps: float, s: float = 1.0):
    """RMSNorm in float32, times ``s`` (a latent's rescale)."""
    xf = x.astype(jnp.float32)
    out = xf * lax.rsqrt((xf**2).mean(-1, keepdims=True) + eps)
    return (out * (w.astype(jnp.float32) * s)).astype(x.dtype)


def _layernorm(x, p, eps: float):
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    out = (xf - mu) * lax.rsqrt(var + eps)
    out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return out.astype(x.dtype)


def _rope_prefix(x, cos, sin, n: int):
    """Rope on the first ``n`` dims of ``x`` ``[B, T, H, hd]``."""
    if n == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate(
        [apply_rope(x[..., :n], cos, sin), x[..., n:]], axis=-1
    )


def rope_by_kind(cfg: ModelConfig, positions: jax.Array) -> dict:
    """cos/sin tables of each kind's own theta (and scaling) at
    ``positions`` [B, T]."""
    return {
        k: rope_tables(positions, la.rope_dim, la.rope_theta, la.rope_scaling)
        for k, la in cfg.latent if la.rope_dim  # 0: no positional rotation
    }


def gqa_qkv(h, ap: dict, ga: GqaAttn, cos, sin, eps: float = 0.0) -> dict:
    """The projections of one grouped-query layer over ``h`` ``[B, T, d]``:
    ``q`` ``[B, T, H, hd]`` and ``k`` ``[B, T, Hkv, hd]``, each head
    normalised first where the kind has ``qk_norm`` (``eps``: the
    model's), or the whole projections where it has ``qk_norm_full``, the
    first ``rope_dim`` dims of each head rotated (``cos`` None: none),
    ``v`` ``[B, T, Hkv, hd]`` and, with a gate, ``gate`` ``[B, T, H]``
    (float32)."""
    B, T = h.shape[:2]
    hd = ga.head_dim

    def project(w, norm, n_heads):
        p = _mm(h, ap[w])
        if ga.qk_norm_full:  # over the whole projection
            p = _rms(p, ap[norm], eps)
        return p.reshape(B, T, n_heads, hd)

    q = project("wq", "q_norm", ga.n_heads)
    k = project("wk", "k_norm", ga.n_kv_heads)
    if "q_norm" in ap and not ga.qk_norm_full:
        q, k = _rms(q, ap["q_norm"], eps), _rms(k, ap["k_norm"], eps)
    if cos is not None:
        q = _rope_prefix(q, cos, sin, ga.rope_dim)
        k = _rope_prefix(k, cos, sin, ga.rope_dim)
    out = {
        "q": q, "k": k,
        "v": _mm(h, ap["wv"]).reshape(B, T, ga.n_kv_heads, hd),
    }
    if "w_g" in ap:
        out["gate"] = jax.nn.sigmoid(_mm(h, ap["w_g"]).astype(jnp.float32))
    return out


def short_conv_in(h, ap: dict):
    """A short-convolution layer's input side over ``h`` ``[B, T, d]``:
    ``(z, gate)`` = ``(B * g, C)`` of ``[B, C, g] = split3(h W_in)``, no
    activation."""
    b, c, g = jnp.split(_mm(h, ap["w_in"]), 3, axis=-1)
    return b * g, c


def short_conv_taps(zc, taps, n: int):
    """The depthwise causal convolution over ``zc`` ``[B, tail + n, W]``
    (a slot's carried tail, then its ``n`` new positions): ``c_t = sum_j
    taps[j] * zc[t + j]`` as float32 ``[B, n, W]``."""
    w = taps.astype(jnp.float32)
    return sum(w[j] * zc[:, j:j + n].astype(jnp.float32)
               for j in range(w.shape[0]))


def gated_delta_in(h, ap: dict, gd: GatedDelta):
    """A gated delta-rule layer's input side over ``h`` ``[B, T, d]``:
    ``(z [B, T, conv_width], gate [B, T, H dv], g, beta [B, T, H])``: the
    convolution's input (q, k and v's channels, in that order), the output
    gate before its SiLU, the log of the decay ``g = -exp(A_log) softplus(h
    W_a + dt_bias)`` and the step size ``beta = (2) sigmoid(h W_b)`` (both
    float32)."""
    f32 = jnp.float32
    a = _mm(h, ap["w_a"]).astype(f32) + ap["dt_bias"].astype(f32)
    g = -jnp.exp(ap["A_log"].astype(f32)) * jax.nn.softplus(a)
    beta = jax.nn.sigmoid(_mm(h, ap["w_b"]).astype(f32))
    return (_mm(h, ap["w_qkv"]), _mm(h, ap["w_g"]), g,
            beta * (2.0 if gd.neg_eigval else 1.0))


L2_EPS = 1e-6  # under the root of q's and k's l2 norm


def gated_delta_qkv(c, gd: GatedDelta):
    """The convolution's output ``c`` ``[.., conv_width]`` (float32) as
    ``q`` / ``k`` ``[.., H, dk]`` and ``v`` ``[.., H, dv]``: SiLU, then a
    head at a time ``q = l2norm(q) dk^-0.5``, ``k = l2norm(k)``."""
    H, dk = gd.n_heads, gd.key_dim
    x = jax.nn.silu(c)
    q, k, v = jnp.split(x, (H * dk, 2 * H * dk), axis=-1)

    def l2(a):
        a = a.reshape(a.shape[:-1] + (H, dk))
        return a * lax.rsqrt((a * a).sum(-1, keepdims=True) + L2_EPS)

    return l2(q) * dk**-0.5, l2(k), v.reshape(v.shape[:-1] + (H, -1))


def gated_delta_out(o, gate, ap: dict, eps: float, dtype):
    """What the layer adds: the heads' reads ``o`` ``[.., H, dv]`` (float32)
    through the RMSNorm a head, times ``silu(gate)``, through ``wo``."""
    y = _rms(o, ap["o_norm"], eps)
    y = y.reshape(gate.shape) * jax.nn.silu(gate.astype(jnp.float32))
    return _mm(y.astype(dtype), ap["wo"])


def latent_qkv(h, ap: dict, la: LatentAttn, eps: float, cos, sin) -> dict:
    """The projections of one latent attention layer over ``h`` ``[B, T,
    d]``: ``q_n`` / ``q_r`` ``[B, T, H, nope | rope]`` (rotated), ``row``
    ``[B, T, pool_dim]`` (what the position caches: latent, rotated key,
    zero lanes up to a whole lane row), with a gate ``gate`` ``[B, T, H]``
    (float32) and, with an indexer, ``qi`` ``[B, T, Hi, Di]``, ``ki`` ``[B,
    T, Di]`` and ``wi`` ``[B, T, Hi]`` (float32)."""
    B, T = h.shape[:2]
    H = la.n_heads
    cq = _rms(_mm(h, ap["w_dq"]), ap["q_norm"], eps, la.q_scale)
    q = _mm(cq, ap["w_uq"]).reshape(B, T, H, la.qk_dim)
    ckv = _mm(h, ap["w_dkv"])
    c = _rms(ckv[..., :la.kv_rank], ap["kv_norm"], eps, la.kv_scale)
    k_r = apply_rope(ckv[..., None, la.kv_rank:], cos, sin)[:, :, 0]
    pad = jnp.zeros((B, T, la.pool_dim - la.row_dim), c.dtype)
    out = {
        "q_n": q[..., :la.nope_dim],
        "q_r": apply_rope(q[..., la.nope_dim:], cos, sin),
        "row": jnp.concatenate([c, k_r, pad], axis=-1),
    }
    if "w_g" in ap:
        out["gate"] = jax.nn.sigmoid(_mm(h, ap["w_g"]).astype(jnp.float32))
    if la.index_heads:
        qi = _mm(cq, ap["w_iq"]).reshape(B, T, la.index_heads, la.index_dim)
        ki = _layernorm(_mm(h, ap["w_ik"]), ap["ik_norm"], eps)
        out |= {
            "qi": _rope_prefix(qi, cos, sin, la.index_rope_dim),
            "ki": _rope_prefix(
                ki[:, :, None], cos, sin, la.index_rope_dim
            )[:, :, 0],
            "wi": _mm(h, ap["w_iw"]).astype(jnp.float32)
            * (la.index_heads**-0.5 * la.index_dim**-0.5),
        }
    return out


def _up(ap: dict, la: LatentAttn):
    """The key/value up-projection per head: ``(w_uk [rank, H, nope],
    w_uv [rank, H, v])``."""
    w = ap["w_ukv"].reshape(la.kv_rank, la.n_heads, la.nope_dim + la.v_dim)
    return w[..., :la.nope_dim], w[..., la.nope_dim:]


def _softmax_rows(sc, mask):
    """Softmax of ``sc`` over its last axis where ``mask``; a query with
    no key reads zero (a padding row, an idle slot), not a uniform row."""
    sc = jnp.where(mask, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.where(mask.any(-1, keepdims=True), p, 0.0)


def attend_materialised(q_n, q_r, rows, mask, ap, la: LatentAttn):
    """Queries ``q_n`` / ``q_r`` ``[B, R, H, ·]`` over the cached ``rows``
    ``[B, K, pool_dim]`` that all ``R`` queries of a batch row share
    (``mask`` ``[B, R, K]``): keys and values projected up per head from
    the rows. Returns ``[B, R, H, v]``."""
    w_uk, w_uv = _up(ap, la)
    c = rows[..., :la.kv_rank]
    k_r = rows[..., la.kv_rank:la.row_dim]
    k_n = jnp.einsum("bkc,chn->bkhn", c, w_uk)
    v = jnp.einsum("bkc,chv->bkhv", c, w_uv)
    sc = jnp.einsum(
        "brhn,bkhn->bhrk", q_n, k_n, preferred_element_type=jnp.float32
    ) + jnp.einsum(
        "brhe,bke->bhrk", q_r, k_r, preferred_element_type=jnp.float32
    )
    p = _softmax_rows(sc * la.softmax_scale, mask[:, None])
    return jnp.einsum("bhrk,bkhv->brhv", p.astype(v.dtype), v)


def absorbed_query(q_n, q_r, ap, la: LatentAttn):
    """The query against a cached row as it lies in the pool: ``[.., H,
    pool_dim]`` = (``q_n`` through the keys' up-projection, ``q_r``, zero
    lanes), so that its dot with a row is the head's score."""
    w_uk, _ = _up(ap, la)
    qt = jnp.einsum("...hn,chn->...hc", q_n, w_uk)
    pad = jnp.zeros(qt.shape[:-1] + (la.pool_dim - la.row_dim,), qt.dtype)
    return jnp.concatenate([qt, q_r.astype(qt.dtype), pad], axis=-1)


def absorbed_output(ctx, ap, la: LatentAttn):
    """Softmax-weighted rows ``[.., H, >= rank]`` through the values'
    up-projection: ``[.., H, v]``."""
    _, w_uv = _up(ap, la)
    return jnp.einsum("...hc,chv->...hv", ctx[..., :la.kv_rank], w_uv)


def attend_absorbed(q_n, q_r, rows, mask, ap, la: LatentAttn):
    """Queries ``[R, H, ·]`` each over rows of its own ``[R, K,
    pool_dim]`` (``mask`` ``[R, K]``), the rows read as cached. Returns
    ``[R, H, v]``."""
    qa = absorbed_query(q_n, q_r, ap, la)
    sc = jnp.einsum(
        "rhw,rkw->rhk", qa, rows, preferred_element_type=jnp.float32
    )
    p = _softmax_rows(sc * la.softmax_scale, mask[:, None])
    ctx = jnp.einsum("rhk,rkw->rhw", p.astype(rows.dtype), rows)
    return absorbed_output(ctx, ap, la)


def top_k_few(x, k: int):
    """``lax.top_k`` for a handful of picks out of a short row (the
    router's 8 of 256): ``k`` passes of argmax-and-mask instead of the
    full sort a top-k is on the chip. Same picks in the same order (equal
    values: the lower index first). Returns ``(values, indices)``."""
    vals, idxs = [], []
    cols = jnp.arange(x.shape[-1])
    for _ in range(k):
        i = jnp.argmax(x, axis=-1)
        vals.append(jnp.take_along_axis(x, i[..., None], -1)[..., 0])
        idxs.append(i)
        x = jnp.where(cols == i[..., None], -jnp.inf, x)
    return jnp.stack(vals, -1), jnp.stack(idxs, -1).astype(jnp.int32)


_LANES = 128


def _block_counts(m):
    """A boolean ``m`` ``[R, K]`` (``K`` a multiple of 128, at most 128 x
    128) counted in blocks of 128 lanes: ``(inside [R, B, 128], upto [R,
    B])``, the inclusive count of set lanes inside each block and the
    inclusive count of set lanes up to each block's end. Matmuls with a
    triangle of ones, exact in float32: a ``cumsum`` over 16,384 lanes is
    a window reduction of that width on the chip."""
    R, K = m.shape
    B = K // _LANES
    tri = jnp.triu(jnp.ones((_LANES, _LANES), jnp.float32))  # i <= j
    blocks = m.reshape(R, B, _LANES).astype(jnp.float32)
    inside = jnp.einsum("rbi,ij->rbj", blocks, tri,
                        precision=lax.Precision.HIGHEST)
    upto = jnp.einsum(
        "rb,bc->rc", inside[..., -1], jnp.triu(jnp.ones((B, B), jnp.float32)),
        precision=lax.Precision.HIGHEST,
    )
    return inside, upto


def top_k_positions(x, k: int):
    """The positions of the ``k`` largest values of each row of ``x``
    ``[R, K]`` (float32, ``K > k``), in position order, without a sort and
    without a gather: the k-th largest value is found bit by bit (32
    counting passes over an order-preserving integer image of the
    floats), the values above it are taken with as many of its equals as
    still fit, earlier positions first (``lax.top_k``'s own rule for ties,
    so the SET is the same), and the j-th taken position is read off
    block counts: its block is the number of blocks that end at or before
    j taken lanes, its lane the number of that block's lanes that do. A
    full sort of ``[16, 16384]`` cost more than the rest of a continuation
    step on a v5e, a binary search through the prefix counts (15 gathers
    of every output) three times the sort (PERF.md section 6, PR 32).
    Returns int32 ``[R, k]``."""
    R, K0 = x.shape
    pad = -K0 % _LANES
    if pad:  # padding sorts below everything real
        x = jnp.pad(x, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    K = K0 + pad
    if K > _LANES * _LANES:
        return jnp.sort(lax.top_k(x, k)[1], axis=-1)
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    # floats order as sign-magnitude; this image orders as unsigned ints
    u = lax.bitcast_convert_type(
        jnp.where(bits < 0, ~bits, bits | jnp.int32(-(2**31))), jnp.uint32
    )

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = (u >= cand[:, None]).sum(-1) >= k
        return jnp.where(enough, cand, t)

    kth = lax.fori_loop(0, 32, bit, jnp.zeros((R,), jnp.uint32))
    above = u > kth[:, None]
    equal = u == kth[:, None]
    room = (k - above.sum(-1, keepdims=True)).astype(jnp.float32)
    eq_inside, eq_upto = _block_counts(equal)
    eq_rank = eq_inside + (eq_upto - eq_inside[..., -1])[..., None]
    take = above | (equal & (eq_rank.reshape(R, K) <= room))
    inside, upto = _block_counts(take)  # upto reaches k in every row
    j = jnp.arange(k, dtype=jnp.float32)  # taken lanes before the j-th
    block = (upto[:, None, :] <= j[None, :, None]).sum(-1)  # [R, k]
    B = K // _LANES
    onehot = (block[..., None] == jnp.arange(B)).astype(jnp.bfloat16)
    # the picked block's inside counts (<= 128: exact in bfloat16)
    row = jnp.einsum("rjb,rbl->rjl", onehot, inside.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    first = upto - inside[..., -1]  # taken lanes before each block
    local = j[None, :] - jnp.einsum(
        "rjb,rb->rj", onehot.astype(jnp.float32), first,
        precision=lax.Precision.HIGHEST,
    )
    lane = (row <= local[..., None]).sum(-1)
    return (block * _LANES + lane).astype(jnp.int32)


INDEX_HEAD_GROUPS = 8  # the selector's heads are scored a group at a time


def index_scores(qi, wi, ki_ctx):
    """The selector's score of every cached position for each query:
    ``sum_j wi[r, j] * relu(qi[r, j] . ki_ctx[k])`` as ``[R, K]`` float32
    (``qi`` ``[R, Hi, Di]``, ``wi`` ``[R, Hi]``, ``ki_ctx`` ``[K, Di]``).
    Heads go a group at a time so that the per-head scores of a whole
    context (``R x Hi x K`` float32) never exist at once."""
    R, Hi, _ = qi.shape
    g = INDEX_HEAD_GROUPS if Hi % INDEX_HEAD_GROUPS == 0 and R > 1 else 1
    qg = qi.reshape(R, g, Hi // g, -1).transpose(1, 0, 2, 3)
    wg = wi.reshape(R, g, Hi // g).transpose(1, 0, 2)

    def one(acc, x):
        q, w = x
        s = jnp.einsum(
            "rhd,kd->rhk", q, ki_ctx, preferred_element_type=jnp.float32
        )
        return acc + jnp.einsum("rhk,rh->rk", jax.nn.relu(s), w), None

    acc, _ = lax.scan(
        one, jnp.zeros((R, ki_ctx.shape[0]), jnp.float32), (qg, wg)
    )
    return acc


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def gated_mlp(h, p: dict):
    return _mm(jax.nn.silu(_mm(h, p["w_gate"])) * _mm(h, p["w_up"]),
               p["w_down"])


def _group_limit(sc, n_group: int, topk_group: int):
    """``sc`` ``[N, E]`` with the scores outside each row's ``topk_group``
    best groups set to 0, and the kept groups ``[N, n_group]`` (bool). A
    group is ``E / n_group`` consecutive experts and scores as its best
    one."""
    N, E = sc.shape
    best = sc.reshape(N, n_group, E // n_group).max(-1)
    _, gi = top_k_few(best, topk_group)
    kept = (gi[..., None] == jnp.arange(n_group)).any(1)  # [N, n_group]
    return jnp.where(jnp.repeat(kept, E // n_group, axis=1), sc, 0.0), kept


def route(h, mp: dict, cfg: ModelConfig):
    """Which experts each row of ``h`` ``[N, d]`` goes to and with what
    weight: ``(experts [N, K] int32 over the published experts, weights
    [N, K] float32)``."""
    return _route(h, mp, cfg)[:2]


def _route(h, mp: dict, cfg: ModelConfig):
    """:func:`route` and ``kept`` ``[N, n_group]``, the groups a row may
    pick from (None where routing has no group limit)."""
    # float32 scores: a product rounded to the activations' dtype first
    # ties experts that a float32 router tells apart
    logits = jnp.matmul(
        h, mp["router"], preferred_element_type=jnp.float32
    )
    K = cfg.n_experts_per_tok
    if cfg.moe_router == "softmax":
        topw, topi = top_k_few(logits, K)
        return topi, jax.nn.softmax(topw, axis=-1), None
    kept = None
    if cfg.moe_router == "sigmoid":
        sc = jax.nn.sigmoid(logits)
        _, topi = top_k_few(sc + mp["bias"].astype(jnp.float32), K)
        topw = jnp.take_along_axis(sc, topi, axis=-1)
    else:  # "softmax_all"
        sc = jax.nn.softmax(logits, axis=-1)
        if cfg.moe_n_group:
            sc, kept = _group_limit(sc, cfg.moe_n_group, cfg.moe_topk_group)
        topw, topi = top_k_few(sc, K)
    if cfg.moe_norm_topk:
        topw = topw / (topw.sum(-1, keepdims=True) + cfg.moe_norm_eps)
    return topi, topw * cfg.moe_scale, kept


def moe_mlp(h, mp: dict, cfg: ModelConfig, valid, rowwise=None):
    """The expert layer over rows ``h`` ``[N, d]`` (``valid`` ``[N]``:
    rows that carry a token; the others are routed nowhere): the shared
    expert plus this program's share of the routed ones. ``rowwise(fn,
    h)``: how the layer's position-wise pieces (the router, the shared
    expert) run over ``h`` (None: at once; the ragged pass's row tiles,
    engine/paged.py::FlatRows.by_tile). Returns ``(y [N, d], stats)`` with
    ``stats`` the first :data:`N_MOE_STATS` of :data:`STEP_STATS`."""
    N, d = h.shape
    K = cfg.n_experts_per_tok
    E = cfg.n_held
    T = min(MOE_TILE, N)
    if rowwise is None:
        rowwise = lambda fn, h: fn(h)
    topi, topw, kept = rowwise(lambda h: _route(h, mp, cfg), h)
    local = (
        (topi >= cfg.experts_first) & (topi < cfg.experts_first + E)
        & valid[:, None]
    )
    e_loc = jnp.where(local, topi - cfg.experts_first, E).reshape(-1)
    # a pair's place: its expert's first tile, then its rank among that
    # expert's pairs (a row meets an expert once: ranks are below N)
    onehot = (e_loc[:, None] == jnp.arange(E)[None, :]).astype(jnp.int32)
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0), jnp.minimum(e_loc, E - 1)[:, None], 1
    )[:, 0] - 1
    counts = onehot.sum(0)  # [E]
    tiles = (counts + T - 1) // T
    tile_end = jnp.cumsum(tiles)
    n_tiles = tile_end[-1]
    max_tiles = min(N * K // T + E, E * (-(-N // T)))
    dest = jnp.where(
        e_loc < E, (tile_end - tiles)[jnp.minimum(e_loc, E - 1)] * T + rank,
        max_tiles * T,
    )
    # a place no pair fell on reads a row of its own past the block
    # (zeros) and adds nothing: every index of a tile is distinct
    slot_row = (N + jnp.arange(max_tiles * T) % T).at[dest].set(
        jnp.arange(N * K) // K, mode="drop"
    )
    slot_w = jnp.zeros((max_tiles * T,), jnp.float32).at[dest].set(
        topw.reshape(-1), mode="drop"
    )
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(max_tiles), side="right"), E - 1
    )
    hp = jnp.concatenate([h, jnp.zeros((T, d), h.dtype)])
    period = (mp["stacked"],) if "stacked" in mp else ()

    def tile(t, out):
        e = period + (tile_expert[t],)
        rows = lax.dynamic_slice_in_dim(slot_row, t * T, T)
        w = lax.dynamic_slice_in_dim(slot_w, t * T, T)
        x = hp[rows]
        y = _mm(
            jax.nn.silu(_mm(x, mp["w_gate"][e])) * _mm(x, mp["w_up"][e]),
            mp["w_down"][e],
        )
        return out.at[rows].add(
            y.astype(jnp.float32) * w[:, None], unique_indices=True
        )

    out = lax.fori_loop(
        0, n_tiles, tile, jnp.zeros((N + T, d), jnp.float32)
    )[:N]
    if "shared" in mp:
        out = out + rowwise(
            lambda h: gated_mlp(h, mp["shared"]), h).astype(jnp.float32)
    reach = valid
    if kept is not None:
        # a held expert's group is one this row kept: without a group
        # limit every row can reach this program's experts
        per = cfg.n_experts // cfg.moe_n_group
        mine = jnp.arange(cfg.moe_n_group)
        mine = (mine >= cfg.experts_first // per) & (
            mine <= (cfg.experts_first + E - 1) // per)
        reach = valid & (kept & mine).any(-1)
    stats = jnp.stack([
        counts.sum(), n_tiles * T, counts.max(), (counts > 0).sum(),
        jnp.asarray(E, jnp.int32), reach.sum(), valid.sum(),
    ]).astype(jnp.int32)
    return out.astype(h.dtype), stats


__all__ = [
    "EXPERT_STACKS", "INDEX_SELECT", "LATENT_ATTN", "MOE", "N_MOE_STATS",
    "STEP_STATS",
    "WINDOW_ATTN",
    "SHORT_CONV", "GATED_DELTA_SCOPE", "gated_delta_in", "gated_delta_out",
    "gated_delta_qkv",
    "Pattern", "absorbed_output", "absorbed_query", "attend_absorbed",
    "attend_materialised", "gated_mlp", "gqa_qkv", "index_scores",
    "init_params",
    "kind_counts", "latent_qkv", "moe_mlp", "pattern_of", "rope_by_kind",
    "route", "short_conv_in", "short_conv_taps",
]
