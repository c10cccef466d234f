"""Block-sparse GQA layers and lightning (linear-attention) layers: the two
layer kinds of the ``minicpm_sala`` family (``models/base.py::SparseAttn``,
``LinearAttn``), beside the latent kinds of models/latent.py in the one
patterned step (engine/latent.py, engine/sala.py).

**Sparse layer.** Keys and values of ``n_kv_heads`` heads in pages, no
rotary positions. A query past ``dense_len`` scores *pooled keys* (the mean
of ``pool`` keys every ``stride`` positions) with its own heads, sums the
softmaxed scores over the heads of a kv group, gives each block of
``block`` positions the largest score of the pooled keys that overlap it,
and attends the ``topk`` best blocks, the first block and the window's
blocks forced among them (:func:`select_blocks`). The cache keeps, beside
the pages, one float32 SUM of keys a page (``page == stride``, ``pool == 2
stride``): pooled key ``j`` is the sum of logical pages ``j`` and ``j + 1``
over ``pool``, so a page's entry depends on that page alone and is shared,
copied and evicted with it.

**Lightning layer.** ``S_t = lambda S_(t-1) + k_t^T v_t``, ``o_t = q_t
d^-1/2 S_t`` per head in float32, ``lambda_a = exp(-slope_a)``. One step
(:func:`lightning_step_ref`) and a chunk of rows (:func:`lightning_chunk_ref`:
``O = ((Q K^T) * D) V + diag(lambda^(i+1)) Q S``, rows past ``n_valid``
leaving ``S`` untouched) are the same sums; the Pallas kernels
(ops/lightning.py) compute what these two compute.

**The layer order has no period** (runs of 8, 6, 4, 6 lightning layers
between sparse ones in the published model), so the parameters are ONE
stack a kind and the step loops over *runs* of one kind (:func:`runs_of`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .base import SALA_KINDS, ModelConfig, SparseAttn
from .latent import NEG_INF, STEP_STATS, top_k_few

BLOCK_SELECT = "tlink.block_select"
SPARSE_ATTN = "tlink.sparse_attn"
LIGHTNING = "tlink.lightning"

# what a step of such a model counts beside STEP_STATS, in this order
# (the cache's ``stats`` vector is as long as the model's own list):
# blocks attended and blocks a query could see, summed over query rows, kv
# groups, sparse layers and passes; query rows under ``dense_len`` (rows x
# sparse layers); rows the recurrence took (rows x lightning layers)
SALA_STATS = (
    "sparse_blocks_kept", "sparse_blocks_visible", "sparse_rows_dense",
    "lightning_rows",
)
FORCED = 1e9  # a forced block's score: above any sum of 16 probabilities


def is_sala(cfg: ModelConfig) -> bool:
    return any(k in SALA_KINDS for k in cfg.layer_kinds)


def step_stats(cfg: ModelConfig) -> tuple:
    """The names of the step's own counts for ``cfg``, in the order of the
    cache's ``stats`` vector."""
    return STEP_STATS + (SALA_STATS if is_sala(cfg) else ())


def runs_of(kinds) -> tuple:
    """``kinds`` as runs of one kind: ``((kind, first index among the
    layers of its kind, length), ...)`` in layer order."""
    out, seen = [], {}
    for k in kinds:
        if out and out[-1][0] == k:
            out[-1][2] += 1
        else:
            out.append([k, seen.get(k, 0), 1])
        seen[k] = seen.get(k, 0) + 1
    return tuple(tuple(r) for r in out)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> dict:
    """Random-init tree: ``embed``, one stack a kind (``sparse``,
    ``lightning``: each leaf ``[layers of the kind, ...]``), ``final_norm``,
    ``lm_head``. A layer is ``ln1``, ``attn``, ``ln2``, ``mlp``. Fan-in
    scale throughout: ``q`` and ``k`` are RMS-normed a head, so attention
    logits are of order one whatever the projections' scale."""
    return _init_tree(key, cfg, jnp.dtype(dtype or cfg.dtype))


@functools.partial(jax.jit, static_argnames=("cfg", "dt"))
def _init_tree(key, cfg: ModelConfig, dt) -> dict:
    d = cfg.d_model
    counter = iter(range(1 << 20))

    def dense(n, *shape):
        k = jax.random.fold_in(key, next(counter))
        w = jax.random.normal(k, (n,) + shape, jnp.float32)
        return (w * float(shape[-2] ** -0.5)).astype(dt)

    def ones(n, *shape):
        return jnp.ones((n,) + shape, dt)

    def attn(kind: str, n: int):
        sz = cfg.latent_of(kind)
        q = sz.n_heads * sz.head_dim
        kv = q if kind == "lightning" else sz.n_kv_heads * sz.head_dim
        p = {
            "wq": dense(n, d, q), "wk": dense(n, d, kv),
            "wv": dense(n, d, kv),
            "q_norm": ones(n, sz.head_dim), "k_norm": ones(n, sz.head_dim),
            "w_g": dense(n, d, q), "wo": dense(n, q, d),
        }
        if kind == "lightning":
            p["o_norm"] = ones(n, q)
        return p

    def stack(kind: str):
        n = cfg.layer_kinds.count(kind)
        return {
            "ln1": {"scale": ones(n, d)}, "attn": attn(kind, n),
            "ln2": {"scale": ones(n, d)},
            "mlp": {
                "w_gate": dense(n, d, cfg.d_ff), "w_up": dense(n, d, cfg.d_ff),
                "w_down": dense(n, cfg.d_ff, d),
            },
        }

    k = jax.random.fold_in(key, next(counter))
    return {
        "embed": {"tok": (jax.random.normal(
            k, (cfg.vocab_size, d), jnp.float32) * 0.02).astype(dt)},
        **{kind: stack(kind) for kind, _ in cfg.latent
           if kind in cfg.layer_kinds},
        "final_norm": {"scale": jnp.ones((d,), dt)},
        "lm_head": dense(1, d, cfg.vocab_size)[0],
    }


# ---------------------------------------------------------------------------
# The sparse layer's selection
# ---------------------------------------------------------------------------


def check_sparse(sa: SparseAttn, page: int) -> str | None:
    """Why the cache cannot hold this layer's pooled keys at this page
    size; None when it can."""
    if sa.stride != page or sa.pool != 2 * sa.stride:
        return (f"pooled keys of {sa.pool} positions every {sa.stride} at a "
                f"page of {page} (served: a page a stride, two a pooled key)")
    if sa.block % sa.stride:
        return f"blocks of {sa.block} positions at a stride of {sa.stride}"
    return None


def pooled_keys(page_sums, sa: SparseAttn):
    """A slot's pooled keys ``[J, Hkv, d]`` float32 from its pages' key
    sums ``[J, Hkv, d]`` in logical order: key ``j`` is pages ``j`` and ``j
    + 1`` (the last has no page after it and is never visible)."""
    nxt = jnp.concatenate([page_sums[1:], jnp.zeros_like(page_sums[:1])])
    return (page_sums + nxt) / sa.pool


def block_scores(q, pooled, q_pos, sa: SparseAttn):
    """``B_g(b)`` for queries ``q`` ``[R, H, d]`` at positions ``q_pos``
    ``[R]`` over a slot's pooled keys ``[J, Hkv, d]``: softmax over the
    pooled keys that lie wholly at or before the query (``stride j + pool
    <= t + 1``) a head, summed over a kv group's heads, and a block's score
    the largest of the pooled keys that overlap it. ``[R, Hkv, NB]``
    float32, ``NB = J stride / block``; 0 where no pooled key is visible."""
    R, H, d = q.shape
    J, Hkv, _ = pooled.shape
    per = sa.block // sa.stride  # pooled keys that start inside one block
    j = jnp.arange(J)
    ok = (sa.stride * j + sa.pool)[None, :] <= (q_pos + 1)[:, None]  # [R, J]
    sc = jnp.einsum(
        "rgad,jgd->rgaj", q.reshape(R, Hkv, H // Hkv, d).astype(jnp.float32),
        pooled, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    ) * sa.softmax_scale
    sc = jnp.where(ok[:, None, None, :], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(ok[:, None, None, :], p, 0.0).sum(2)  # [R, Hkv, J]
    pad = -J % per
    if pad:
        p = jnp.pad(p, ((0, 0), (0, 0), (0, pad)))
    inner = p.reshape(R, Hkv, -1, per)
    # the pooled key that starts in the block before and reaches into this
    before = jnp.pad(inner[:, :, :-1, -1], ((0, 0), (0, 0), (1, 0)))
    return jnp.maximum(inner.max(-1), before)


def select_blocks(scores, q_pos, sa: SparseAttn):
    """The blocks each query attends, ``[R, Hkv, NB]`` bool: every block
    that holds a position ``<= t`` where ``t < dense_len``; else the
    ``topk`` best by ``scores`` with the first ``init_blocks`` and the
    blocks of positions ``t - window + 1 .. t`` forced (they count; where
    they alone are more than ``topk``, they are what is kept). Equal
    scores: the lower block first (``top_k_few``)."""
    NB = scores.shape[-1]
    b = jnp.arange(NB)
    t = q_pos[:, None, None]
    visible = b <= t // sa.block
    first_w = jnp.maximum(t - (sa.window - 1), 0) // sa.block
    forced = (b < sa.init_blocks) | (b >= first_w)
    ranked = jnp.where(visible, jnp.where(forced, FORCED, scores), -jnp.inf)
    _, idx = top_k_few(ranked, min(sa.topk, NB))
    top = (idx[..., None] == b).any(-2)
    return jnp.where(t < sa.dense_len, visible, (top | forced) & visible)


def kept_table(kept, n_max: int):
    """The kept blocks of ``kept`` ``[.., NB]`` in position order:
    ``(table [.., n_max] int32, count [..])``; places past the count hold
    the last kept block (any live page will do: never attended)."""
    NB = kept.shape[-1]
    order = jnp.sort(jnp.where(kept, jnp.arange(NB), NB), axis=-1)
    order = order[..., :n_max]  # n_max <= NB
    count = jnp.minimum(kept.sum(-1), n_max).astype(jnp.int32)
    last = jnp.take_along_axis(
        order, jnp.maximum(count - 1, 0)[..., None], -1)
    return jnp.where(order < NB, order, jnp.minimum(last, NB - 1)).astype(
        jnp.int32), count


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------


def _head_norm(x, w, eps: float):
    xf = x.astype(jnp.float32)
    out = xf * lax.rsqrt((xf**2).mean(-1, keepdims=True) + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def qkv(h, ap: dict, n_q: int, n_kv: int, hd: int, eps: float, mm):
    """``q`` ``[B, T, n_q, hd]`` and ``k`` (both RMS-normed a head), ``v``
    ``[B, T, n_kv, hd]`` and the output gate ``[B, T, n_q hd]`` (float32)
    of either kind over ``h`` ``[B, T, d]``."""
    B, T = h.shape[:2]
    q = _head_norm(mm(h, ap["wq"]).reshape(B, T, n_q, hd), ap["q_norm"], eps)
    k = _head_norm(mm(h, ap["wk"]).reshape(B, T, n_kv, hd), ap["k_norm"], eps)
    v = mm(h, ap["wv"]).reshape(B, T, n_kv, hd)
    gate = jax.nn.sigmoid(mm(h, ap["w_g"]).astype(jnp.float32))
    return q, k, v, gate


# ---------------------------------------------------------------------------
# The lightning recurrence (what ops/lightning.py's kernels compute)
# ---------------------------------------------------------------------------


def lightning_step_ref(q, k, v, state, slopes, active):
    """One position a slot: ``q`` / ``k`` / ``v`` ``[S, H, d]``, ``state``
    ``[S, H, d, d]`` float32, ``slopes`` ``[H]``, ``active`` ``[S]``.
    Returns ``(o [S, H, d] float32, state)``; an inactive slot's state is
    untouched (its ``o`` is not read)."""
    d = q.shape[-1]
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    lam = jnp.exp(-slopes)[None, :, None, None]
    new = lam * state + kf[..., :, None] * vf[..., None, :]
    new = jnp.where(active[:, None, None, None], new, state)
    o = jnp.einsum("shd,shde->she", qf * d**-0.5, new,
                   precision=lax.Precision.HIGHEST)
    return o, new


def lightning_chunk_ref(q, k, v, state, slopes, n_valid):
    """A chunk of rows a slot: ``q`` / ``k`` / ``v`` ``[S, H, C, d]``,
    ``state`` ``[S, H, d, d]`` float32, ``n_valid`` ``[S]`` rows of each
    slot's chunk that carry a token (the others leave the state alone and
    read zero). Returns ``(o [S, H, C, d] float32, state)``."""
    S, H, C, d = q.shape
    hp = lax.Precision.HIGHEST
    qf = q.astype(jnp.float32) * d**-0.5
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    sl = slopes[None, :, None, None]
    i = jnp.arange(C)[:, None]
    j = jnp.arange(C)[None, :]
    n = n_valid[:, None, None, None]
    live = (i >= j)[None, None] & (j[None, None] < n)
    decay = jnp.where(live, jnp.exp(-sl * jnp.maximum(i - j, 0)), 0.0)
    a = jnp.einsum("shid,shjd->shij", qf, kf, precision=hp) * decay
    o = jnp.einsum("shij,shjd->shid", a, vf, precision=hp)
    o = o + jnp.exp(-sl * (i + 1)) * jnp.einsum(
        "shid,shde->shie", qf, state, precision=hp)
    o = jnp.where(i[None, None] < n, o, 0.0)
    rows = jnp.arange(C)[None, None, :, None]
    kd = kf * jnp.where(
        rows < n, jnp.exp(-sl * jnp.maximum(n - 1 - rows, 0)), 0.0)
    new = jnp.exp(-sl * n) * state + jnp.einsum(
        "shjd,shje->shde", kd, vf, precision=hp)
    return o, new


__all__ = [
    "BLOCK_SELECT", "LIGHTNING", "SALA_STATS", "SPARSE_ATTN",
    "block_scores", "check_sparse", "init_params", "is_sala", "kept_table",
    "lightning_chunk_ref", "lightning_step_ref", "pooled_keys", "qkv",
    "runs_of", "select_blocks", "step_stats",
]
