"""The plain reference of ``minicpm_sala`` (MiniCPM-SALA): block-sparse GQA
layers (``minicpm4``) and lightning linear-attention layers
(``lightning-attn``) in the order ``mixer_types`` gives, every layer with a
dense SwiGLU, MiniCPM's scaling keys. ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``; no cache, no kernels, the
recurrence as the plain scan over positions, the selection per query row,
one sequence at a time. Written from the published ``config.json`` keys and
ISSUE 42's equations, not from ``tensorlink_tpu/models/sala.py``.

``h`` is a layer's RMS-normed input (eps ``rms_norm_eps``), ``d`` = head_dim:

  whole model:  x_0 = embed[ids] * scale_emb
    x <- x + r * Mixer(rmsnorm(x));  x <- x + r * MLP(rmsnorm(x))
    r = scale_depth / sqrt(PUBLISHED depth)  (``published.num_hidden_layers``)
    MLP(h) = W_down(silu(W_gate h) * W_up h)
    logits = W_head(rmsnorm(x) / (hidden_size / dim_model_base))
  sparse layer (no rotary positions):
    q = norm_h(W_q h) (H heads), k = norm_h(W_k h), v = W_v h (Hkv heads);
    norm_h a learned RMSNorm over each head's d; scale d^-1/2
    t < dense_len: causal softmax attention over every s <= t
    else: c_j = mean(k_s : stride j <= s < stride j + kernel) for every j with
      stride j + kernel <= t + 1, per kv head
      p_a(j) = softmax_j(q_a . c_j d^-1/2);  P_g(j) = sum of p_a over group g
      B_g(b) = max(P_g(j) : (block / stride) b - 1 <= j <= (block / stride) b
        + block / stride - 1), blocks of ``block`` positions
      kept: the first ``init_blocks`` blocks and the blocks that hold
      positions t - window + 1 .. t, then by largest B_g until ``topk`` are
      kept (the forced ones count, and all of them stay where they alone
      are more; equal scores: the lower block first)
      softmax attention of the group's heads over the s <= t of the kept blocks
    out = W_o(concat(o) * sigmoid(W_g h))
  lightning layer:
    q = norm_h(W_q h), k = norm_h(W_k h), v = W_v h, H heads each;
    rotate-half RoPE (theta, all d dims) on q and k
    S_t = lambda_a S_(t-1) + k_t^T v_t,  S_(-1) = 0,  o_t = (q_t d^-1/2) S_t
    lambda_a = exp(-2^(-8 (a + 1) / H))
    out = W_o(rmsnorm(concat(o)) * sigmoid(W_g h))

Departures from the published model, each by the configuration file
(``assumed``): the ``sparse_config`` sizes are the family's (MiniCPM4's
``config.json``); the pooling is a plain mean and a block's score the max
over the pooled keys that overlap it; the decay is Lightning Attention's
head slopes; both gates are one value a channel; the output norm runs over
the concatenated channels; the layers are the configuration's 16 with the
published depth's residual scale.

Weights are upcast to float32 where they are used, a layer at a time;
queries go in blocks (one compiled function, the offset as data), so that
33k positions fit beside the served model.

What ``correct`` holds (:func:`served_gaps`): the served tokens against the
reference's logits and, one layer at a time on the reference's own input
(:class:`ServedLayers`), what a sparse layer adds to the residual stream
and the state a lightning layer ends with after the probe took a snapshot
part-way, restored it into another slot and went on there.
"""

from __future__ import annotations

import functools
import time
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 64  # query rows a call of the sparse layer attends
ROW_BLOCK = 4096  # rows on the device at a time (the rest waits on the host)
FF_BLOCK = 4096  # MLP channels, and vocabulary columns, upcast at a time
MIXERS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
SPARSE_DEFAULTS = dict(
    kernel_size=32, kernel_stride=16, block_size=64, init_blocks=1,
    window_size=2048, topk=64, dense_len=8192,
)


def arch_of(hf: dict) -> dict:
    """The sizes and switches the forward needs, from ``config.json`` keys."""
    sp = {**SPARSE_DEFAULTS, **(hf.get("sparse_config") or {})}
    hd = int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"])
    depth = int((hf.get("published") or {}).get(
        "num_hidden_layers", hf["num_hidden_layers"]))
    return {
        "layers": int(hf["num_hidden_layers"]),
        "mixers": tuple(MIXERS[m] for m in hf["mixer_types"]),
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "heads": int(hf["num_attention_heads"]),
        "kv_heads": int(hf["num_key_value_heads"]), "hd": hd,
        "lheads": int(hf["lightning_nh"]),
        "lhd": int(hf.get("lightning_head_dim", hd)),
        "theta": float(hf.get("rope_theta", 1e4)),
        "scale_emb": float(hf.get("scale_emb", 1.0)),
        "residual": float(hf.get("scale_depth", 1.0)) / depth**0.5,
        "logit_div": hf["hidden_size"] / hf.get(
            "dim_model_base", hf["hidden_size"]),
        "kernel": int(sp["kernel_size"]), "stride": int(sp["kernel_stride"]),
        "block": int(sp["block_size"]), "init_blocks": int(sp["init_blocks"]),
        "window": int(sp["window_size"]), "topk": int(sp["topk"]),
        "dense_len": int(sp["dense_len"]),
        # controls (benchmarks/tests/test_minicpm_sala.py, and the builder's
        # chip run): a fault each with the served program sound, and the
        # precision below the served one (the state rounded to bfloat16
        # after every position)
        "select": True, "force_window": True, "decay_reversed": False,
        "state_bf16": False, "snapshot_short": False,
        # the whole file: the layer-matched comparison builds the program's
        # own ModelConfig and caches from it (:class:`ServedLayers`)
        "config": dict(hf),
    }


def _static(arch: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in arch.items()
                        if not isinstance(v, (dict, list))))


def _hp(fn):
    @functools.wraps(fn)
    def run(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return run


def _w(x):
    return x.astype(jnp.float32)


def _done(x):
    """``x`` once it is computed: one call's temporaries at a time stand
    beside the served model (PERF.md section 6, PR 32, lesson (e))."""
    return jax.block_until_ready(x)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


SECONDS: dict = defaultdict(float)  # where the reference's time went, by part


@contextmanager
def _timed(part: str):
    t = time.monotonic()
    try:
        yield
    finally:
        SECONDS[part] += time.monotonic() - t


def _row_blocks(T: int):
    return [(i, min(i + ROW_BLOCK, T)) for i in range(0, T, ROW_BLOCK)]


@functools.partial(jax.jit, static_argnames=("eps",))
@_hp
def _norm_rows(x, scale, *, eps):
    return _rmsnorm(x, _w(scale), eps)


def layer_tree(params: dict, i: int, arch: dict):
    """Layer ``i``'s parameters out of the program's tree (one stack a
    kind: ``params["sparse"]``, ``params["lightning"]``)."""
    kind = arch["mixers"][i]
    j = arch["mixers"][:i].count(kind)
    return jax.tree.map(lambda a: a[j], params[kind])


# ---------------------------------------------------------------------------
# The sparse layer
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("heads", "hd", "eps"))
@_hp
def _project(h, w, norm, *, heads, hd, eps):
    x = (h @ _w(w)).reshape(h.shape[0], heads, hd)
    return x if norm is None else _rmsnorm(x, _w(norm), eps)


@functools.partial(jax.jit, static_argnames=("k",))
@_hp
def _pooled(keys, *, k):
    """``c_j`` ``[J, Hkv, d]`` of ``keys`` ``[T, Hkv, d]``."""
    a = dict(k)
    T = keys.shape[0]
    J = max((T - a["kernel"]) // a["stride"] + 1, 0)
    idx = a["stride"] * jnp.arange(J)[:, None] + jnp.arange(a["kernel"])
    return keys[idx].mean(1)


def kept_blocks(q, pooled, pos, a: dict):
    """The blocks each query keeps, ``[R, Hkv, NB]`` bool with ``NB`` from
    ``a["n_blocks"]``: ``q`` ``[R, H, d]`` at positions ``pos`` ``[R]``."""
    R, H, d = q.shape
    Hkv, NB = a["kv_heads"], a["n_blocks"]
    J = pooled.shape[0]
    per = a["block"] // a["stride"]
    t = pos[:, None, None]
    b = jnp.arange(NB)
    visible = b <= t // a["block"]
    if J:
        j = jnp.arange(J)
        ok = (a["stride"] * j + a["kernel"])[None, :] <= (pos + 1)[:, None]
        sc = jnp.einsum("rgad,jgd->rgaj", q.reshape(R, Hkv, H // Hkv, d),
                        pooled) * d**-0.5
        sc = jnp.where(ok[:, None, None, :], sc, -jnp.inf)
        p = jnp.where(ok[:, None, None, :], jax.nn.softmax(sc, -1), 0.0)
        p = jnp.nan_to_num(p).sum(2)  # [R, Hkv, J]; no visible key: zeros
        # the pooled keys that overlap block b: per b - 1 .. per b + per - 1
        jb = per * b[:, None] + jnp.arange(-1, per)[None, :]  # [NB, per + 1]
        inside = (jb >= 0) & (jb < J)
        score = jnp.where(
            inside, p[:, :, jnp.clip(jb, 0, J - 1)], 0.0).max(-1)
    else:
        score = jnp.zeros((R, Hkv, NB), jnp.float32)
    forced = b < a["init_blocks"]
    if a["force_window"]:
        forced = forced | (
            b >= jnp.maximum(t - (a["window"] - 1), 0) // a["block"])
    ranked = jnp.where(visible, jnp.where(forced, jnp.inf, score), -jnp.inf)
    order = jnp.argsort(-ranked, axis=-1, stable=True)  # ties: lower first
    top = (order[..., :a["topk"], None] == b).any(-2)
    return jnp.where(t < a["dense_len"], visible, (top | forced) & visible)


@functools.partial(jax.jit, static_argnames=("k",))
@_hp
def _sparse_block(q, keys, values, pooled, start, *, k):
    """``QUERY_BLOCK`` queries from position ``start`` on: ``(o [R, H, d],
    kept [R, Hkv, NB])``."""
    a = dict(k)
    T, Hkv, d = keys.shape
    a["n_blocks"] = -(-T // a["block"])
    R, H, _ = q.shape
    pos = start + jnp.arange(R)
    s = jnp.arange(T)
    if a["select"]:
        kept = kept_blocks(q, pooled, pos, a)
    else:
        kept = jnp.broadcast_to(
            jnp.arange(a["n_blocks"]) <= (pos // a["block"])[:, None, None],
            (R, Hkv, a["n_blocks"]))
    mask = jnp.repeat(kept, a["block"], axis=-1)[..., :T] & (
        s[None, None, :] <= pos[:, None, None])
    sc = jnp.einsum("rgad,sgd->rgas", q.reshape(R, Hkv, H // Hkv, d),
                    keys) * d**-0.5
    sc = jnp.where(mask[:, :, None, :], sc, -jnp.inf)
    o = jnp.einsum("rgas,sgd->rgad", jax.nn.softmax(sc, -1), values)
    return o.reshape(R, H, d), kept


def sparse_mixer(x, ln1, ap: dict, arch: dict):
    """``(what the layer's attention gives [T, d_model], (kept blocks of
    the last query block's rows, that block's first position))`` over the
    layer's input ``x`` ``[T, d]`` (a host array: a block of rows at a time
    is on the device, beside every position's keys and values)."""
    k = _static(arch)
    H, Hkv, hd, eps = arch["heads"], arch["kv_heads"], arch["hd"], arch["eps"]
    T = x.shape[0]
    normed = lambda i, j: _norm_rows(jnp.asarray(x[i:j]), ln1, eps=eps)  # noqa: E731
    keys, values = [], []
    for i, j in _row_blocks(T):
        h = normed(i, j)
        keys.append(_project(h, ap["wk"], ap["k_norm"], heads=Hkv, hd=hd, eps=eps))
        values.append(_done(_project(h, ap["wv"], None, heads=Hkv, hd=hd, eps=eps)))
    keys, values = jnp.concatenate(keys), jnp.concatenate(values)
    pooled = _pooled(keys, k=k)
    mixed = np.zeros(x.shape, np.float32)
    kept = first = None
    for i, j in _row_blocks(T):
        h = normed(i, j)
        q = _project(h, ap["wq"], ap["q_norm"], heads=H, hd=hd, eps=eps)
        q = jnp.pad(q, ((0, -(j - i) % QUERY_BLOCK), (0, 0), (0, 0)))
        outs = []
        for s in range(0, j - i, QUERY_BLOCK):
            o, kept = _done(_sparse_block(
                jax.lax.dynamic_slice_in_dim(q, s, QUERY_BLOCK), keys, values,
                pooled, jnp.int32(i + s), k=k))
            outs.append(o)
            first = i + s
        o = jnp.concatenate(outs)[:j - i].reshape(j - i, H * hd)
        mixed[i:j] = np.asarray(_gated_out(o, h, ap["w_g"], ap["wo"], None))
    return mixed, (kept, first)


@jax.jit
@_hp
def _gated_out(o, h, w_g, wo, norm):
    """``W_o((norm(o)) * sigmoid(W_g h))`` over rows ``o`` and ``h``."""
    if norm is not None:
        o = _rmsnorm(o, _w(norm[0]), norm[1])
    return (o * jax.nn.sigmoid(h @ _w(w_g))) @ _w(wo)


# ---------------------------------------------------------------------------
# The lightning layer
# ---------------------------------------------------------------------------


def slopes(arch: dict) -> np.ndarray:
    H = arch["lheads"]
    s = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    return (s[::-1].copy() if arch["decay_reversed"] else s).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("theta",))
def _rope(x, pos, *, theta):
    """Rotate-half rotary positions over all of ``x`` ``[T, H, d]``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("bf16",))
@_hp
def _recurrence(q, k, v, lam, S0, *, bf16):
    """The plain scan from the state ``S0``: ``(o [T, H, d], S_T [H, d,
    d])``."""
    d = q.shape[-1]

    def step(S, x):
        qt, kt, vt = x
        S = lam[:, None, None] * S + kt[:, :, None] * vt[:, None, :]
        if bf16:  # the precision below: the state kept in bfloat16 (an
            # astype pair is dropped on the chip: XLA allows excess precision)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("hd,hde->he", qt * d**-0.5, S)

    S, o = jax.lax.scan(step, S0, (q, k, v))
    return o, S


def lightning_mixer(x, ln1, ap: dict, arch: dict):
    """``(what the layer's mixer gives [T, d_model], the state after the
    last position [H, d, d])`` over the layer's input ``x`` ``[T, d]`` (a
    host array): the scan goes over a block of rows at a time, the state
    carried from block to block."""
    H, hd, eps = arch["lheads"], arch["lhd"], arch["eps"]
    lam = jnp.exp(-jnp.asarray(slopes(arch)))
    S = jnp.zeros((H, hd, hd), jnp.float32)
    mixed = np.zeros(x.shape, np.float32)
    for i, j in _row_blocks(x.shape[0]):
        h = _norm_rows(jnp.asarray(x[i:j]), ln1, eps=eps)
        pos = jnp.arange(i, j)
        proj = lambda w, n: _project(h, w, n, heads=H, hd=hd, eps=eps)  # noqa: E731
        q = _rope(proj(ap["wq"], ap["q_norm"]), pos, theta=arch["theta"])
        k = _rope(proj(ap["wk"], ap["k_norm"]), pos, theta=arch["theta"])
        o, S = _done(_recurrence(q, k, proj(ap["wv"], None), lam, S,
                                 bf16=arch["state_bf16"]))
        mixed[i:j] = np.asarray(_gated_out(
            o.reshape(j - i, H * hd), h, ap["w_g"], ap["wo"],
            (ap["o_norm"], eps)))
    return mixed, S


# ---------------------------------------------------------------------------
# The whole model
# ---------------------------------------------------------------------------


@jax.jit
@_hp
def _mlp(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _w(w_gate)) * (h @ _w(w_up))) @ _w(w_down)


def mlp(h, mp: dict) -> jnp.ndarray:
    """The SwiGLU over rows ``h``, ``FF_BLOCK`` of its channels at a time."""
    out = 0.0
    for f in range(0, mp["w_gate"].shape[1], FF_BLOCK):
        out = out + _done(_mlp(
            h, mp["w_gate"][:, f:f + FF_BLOCK], mp["w_up"][:, f:f + FF_BLOCK],
            mp["w_down"][f:f + FF_BLOCK]))
    return out


def hidden_states(params: dict, tokens, arch: dict,
                  layers: int | None = None, observe=None) -> np.ndarray:
    """The residual stream ``[T, d]`` (a host array) after ``layers``
    layers (all by default) of one sequence ``tokens`` ``[T]``.
    ``observe(i, lt, x, mixed, info)`` sees each layer's input ``x``, what
    its mixer gave (before the residual scale; both host arrays) and the
    mixer's own ``info`` (a sparse layer's kept blocks of its last query
    block with that block's first position, a lightning layer's last
    state)."""
    tok = np.asarray(tokens, np.int32)
    r, eps = arch["residual"], arch["eps"]
    x = np.concatenate([
        np.asarray(_w(params["embed"]["tok"][jnp.asarray(tok[i:j])]))
        for i, j in _row_blocks(len(tok))]) * np.float32(arch["scale_emb"])
    for i in range(arch["layers"] if layers is None else layers):
        lt = layer_tree(params, i, arch)
        kind = arch["mixers"][i]
        mixer = sparse_mixer if kind == "sparse" else lightning_mixer
        with _timed(kind):
            mixed, info = mixer(x, lt["ln1"]["scale"], lt["attn"], arch)
        if observe is not None:
            with _timed(f"probe_{kind}"):
                observe(i, lt, x, mixed, info)
        x = x + np.float32(r) * mixed
        del mixed
        with _timed("mlp"):
            for a, b in _row_blocks(len(tok)):
                h = _norm_rows(jnp.asarray(x[a:b]), lt["ln2"]["scale"], eps=eps)
                x[a:b] += np.float32(r) * np.asarray(mlp(h, lt["mlp"]))
    return x


@functools.partial(jax.jit, static_argnames=("eps", "div"))
@_hp
def _head_rows(h, norm, *, eps, div):
    return _rmsnorm(h, _w(norm), eps) / div


@jax.jit
@_hp
def _head_cols(h, w):
    return h @ _w(w)


def forward_logits(params: dict, tokens: np.ndarray, arch: dict,
                   positions: slice, device=None, observe=None) -> np.ndarray:
    """Reference logits ``[B, len(positions), V]`` of a full (teacher-
    forced) forward over ``tokens`` ``[B, T]``, one sequence at a time;
    ``observe`` sees the first sequence's layers (:func:`hidden_states`)."""
    rows = []
    w = params["lm_head"]
    for b, seq in enumerate(np.asarray(tokens)):
        h = hidden_states(params, seq, arch,
                          observe=None if b else observe)[positions]
        h = _head_rows(jnp.asarray(h), params["final_norm"]["scale"],
                       eps=arch["eps"], div=arch["logit_div"])
        rows.append(np.concatenate([
            np.asarray(_head_cols(h, w[:, v:v + 2 * FF_BLOCK]))
            for v in range(0, w.shape[1], 2 * FF_BLOCK)], -1))
    return np.stack(rows)


def token_gaps(params: dict, prompts: list[list[int]],
               served: list[list[int]], arch: dict, observe=None) -> np.ndarray:
    """For each served token of each sequence (all of one length), how far
    its reference logit lies under the reference's largest logit at that
    position, in units of that position's standard deviation of the
    reference logits over the vocabulary (0 = the reference's own greedy
    choice). Returns ``[sequences, tokens]``."""
    seq = np.asarray([list(p) + list(s) for p, s in zip(prompts, served)],
                     np.int32)
    P, n = len(prompts[0]), len(served[0])
    logits = forward_logits(params, seq[:, :-1], arch, slice(P - 1, P + n - 1),
                            observe=observe)
    got = np.take_along_axis(logits, np.asarray(served)[:, :, None], -1)[..., 0]
    return (logits.max(axis=-1) - got) / logits.std(axis=-1)


# ---------------------------------------------------------------------------
# The layer-matched comparison
# ---------------------------------------------------------------------------


def _rel(got, want) -> float:
    got = jnp.asarray(got).astype(jnp.float32)
    want = jnp.asarray(want).astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


class ServedLayers:
    """The program's side of the layer-matched comparison: each layer's
    mixer (``engine/paged.py::make_layer_probe``: the step's two passes'
    placing, the deployment's page size and prefill chunk, the kernels on
    the chip) takes the reference's own input to the layer rounded to the
    served dtype, chunked prefill then ``n_dec`` continuation steps through
    a cache of its own, and

    * a sparse layer: what it added to the residual stream (before the
      residual scale) over the last prefill chunk and the continuation
      steps (the masked dense walk and the table walk both) is held
      against the reference's at those positions: ``sparse``. Beside it
      (printed, not held): ``agree``, the share of the blocks the reference
      kept for the last query block's rows that the program's selection
      (bfloat16 queries, the cache's float32 key sums) kept too;
    * a lightning layer: prefill runs in slot 0 to the chunk boundary one
      whole chunk before the last prefill chunk, the engine's own
      ``take_snapshot``
      stores the state there, ``restore_snapshot`` puts it into slot 1,
      and prefill and the continuation steps go on in slot 1: the state it
      ends with is held against the reference's last state, ``state``.
      The control ``snapshot_short`` goes on one chunk further than where
      the snapshot was taken: a snapshot restored at the wrong length.

    What it does NOT see is the engine's own pages, states and snapshot
    pool: the comparison runs the program's layer code on the engine's
    weights beside the engine."""

    def __init__(self, hf: dict, dtype, T: int, n_dec: int):
        from tensorlink_tpu.engine.latent import LatentPagedCache
        from tensorlink_tpu.engine.paged import make_layer_probe
        from tensorlink_tpu.models.registry import config_from_hf

        ml = hf.get("deployment", {}).get("ml", {})
        self.cfg = config_from_hf(dict(hf), dtype=dtype)
        self.chunk = int(ml.get("prefill_chunk", 128))
        self.page = int(ml.get("cont_page_size", 16))
        self.n_dec = n_dec
        kernel = jax.default_backend() == "tpu"
        self.probes = {kind: make_layer_probe(self.cfg, kind, kernel=kernel)
                       for kind in ("sparse", "lightning")}
        blk = self.cfg.latent_of("sparse").block
        cache = LatentPagedCache.init(
            self.cfg, 2, page_size=self.page, max_len=-(-T // blk) * blk)
        n_pp = cache.pages_per_slot
        self.cache = replace(cache, block_tables=jnp.arange(
            1, 2 * n_pp + 1, dtype=jnp.int32).reshape(2, n_pp))
        self.gaps: dict = {"sparse": {}, "state": {}, "agree": {}}

    def _rows(self, x, slot: int, pos: int, n: int):
        """Rows ``pos .. pos + n - 1`` of the host array ``x`` (short of
        ``n`` at its end: zeros) as ``slot``'s block of a two-slot batch,
        in the served dtype."""
        blk = np.zeros((2, n, x.shape[1]), np.float32)
        part = x[pos:pos + n]
        blk[slot, :len(part)] = part
        return jnp.asarray(blk).astype(self.cfg.dtype)

    def _run(self, kind, lp, li, x, cache, slot, lo, hi, n_dec=0):
        """Positions ``lo .. hi - 1`` of ``x`` through ``slot``: prefill in
        chunks, the last ``n_dec`` as continuation steps. Returns ``(what
        the last chunk and the steps added, the last chunk's first
        position, cache)``."""
        ragged, decode = self.probes[kind]
        C = self.chunk
        zero = jnp.zeros((2,), jnp.int32)
        n_pre, pos, out, n = hi - n_dec, lo, None, 0
        while pos < n_pre:
            n = min(C, n_pre - pos)
            out, cache = _done(ragged(
                lp, self._rows(x, slot, pos, C), cache, li,
                zero.at[slot].set(pos), zero.at[slot].set(n)))
            pos += n
        outs = [out[slot, :n]] if out is not None else []
        for t in range(n_pre, hi):
            out, cache = decode(lp, self._rows(x, slot, t, 1), cache, li,
                                jnp.zeros((2,), bool).at[slot].set(True))
            outs.append(out[slot])
        return (jnp.concatenate(outs) if outs else None), n_pre - n, cache

    def layer(self, i: int, lt: dict, x, mixed, info, arch: dict):
        """Layer ``i`` over the reference's input ``x`` ``[T, d]``;
        ``mixed`` what the reference's mixer gave, ``info`` its own."""
        from tensorlink_tpu.engine.sala import restore_snapshot, take_snapshot

        kind = arch["mixers"][i]
        li = jnp.int32(arch["mixers"][:i].count(kind))
        lp = {"ln1": lt["ln1"], "attn": lt["attn"]}
        T, C = x.shape[0], self.chunk
        cache = replace(self.cache, lengths=jnp.zeros((2,), jnp.int32))
        if kind == "sparse":
            got, first, cache = self._run(
                kind, lp, li, x, cache, 0, 0, T, self.n_dec)
            self.gaps["sparse"][i] = _rel(got, mixed[first:])
            self.gaps["agree"][i] = self._agreement(lp, li, x, cache, info)
        else:
            # one whole chunk before the last prefill chunk: what follows
            # the restore is what follows a session's restore, a chunk or
            # two and the steps (a state forgets: from the middle of 33k
            # positions nothing of a lost chunk would be left to see)
            mid = max(((T - self.n_dec - 1) // C - 1) * C, C)
            cache = replace(cache, state=jnp.zeros_like(cache.state))
            _, _, cache = self._run(kind, lp, li, x, cache, 0, 0, mid)
            snaps = jnp.zeros((1,) + cache.state.shape[:1]
                              + cache.state.shape[2:], jnp.float32)
            snaps = take_snapshot(snaps, cache.state, jnp.int32(0),
                                  jnp.int32(0))
            cache = restore_snapshot(cache, snaps, jnp.int32(1), jnp.int32(0))
            cache = replace(cache, lengths=cache.lengths.at[1].set(mid))
            resume = mid + C if arch["snapshot_short"] else mid
            _, _, cache = self._run(
                kind, lp, li, x, cache, 1, resume, T, self.n_dec)
            self.gaps["state"][i] = _rel(cache.state[li, 1], info)
        self.cache = cache

    def _agreement(self, lp, li, x, cache, info) -> float:
        """The share of the blocks the reference kept for its last query
        block's rows that the program's selection keeps too."""
        from tensorlink_tpu.models import sala
        from tensorlink_tpu.models.latent import _rms

        want, first = info
        cfg, sa = self.cfg, self.cfg.latent_of("sparse")
        R = want.shape[0]
        h = _rms(self._rows(x, 0, first, R)[0], lp["ln1"]["scale"],
                 cfg.norm_eps)
        q, _, _, _ = sala.qkv(
            h[None], lp["attn"], sa.n_heads, sa.n_kv_heads, sa.head_dim,
            cfg.norm_eps, jnp.matmul)
        pos = first + jnp.arange(R)
        sums = cache.ksum[li, cache.block_tables[0]]
        got = sala.select_blocks(sala.block_scores(
            q[0], sala.pooled_keys(sums, sa), pos, sa), pos, sa)
        n = min(got.shape[-1], want.shape[-1])
        live = (pos < x.shape[0])[:, None, None]
        want = want[..., :n] & live
        return float((got[..., :n] & want).sum() / jnp.maximum(want.sum(), 1))

    def worst(self) -> dict:
        return {name: max(by_layer.values(), default=0.0)
                for name, by_layer in self.gaps.items() if name != "agree"}


def _observer(served: ServedLayers, arch: dict):
    def observe(i, lt, x, mixed, info):
        served.layer(i, lt, x, mixed, info, arch)
    return observe


def layer_gaps(params: dict, tokens, arch: dict, n_dec: int) -> dict:
    """``{"sparse", "state"}``: the worst layer's gap of each
    (:class:`ServedLayers`) over one sequence ``tokens`` ``[T]``, and
    ``"by_layer"`` (with ``agree``)."""
    tokens = np.asarray(tokens, np.int32)
    served = ServedLayers(arch["config"], params["embed"]["tok"].dtype,
                          len(tokens), n_dec)
    hidden_states(params, tokens, arch, observe=_observer(served, arch))
    return {**served.worst(), "by_layer": served.gaps}


HELD = (("sparse", "max_sparse_gap"), ("state", "max_state_gap"))


def served_gaps(params: dict, prompts: list[list[int]],
                served: list[list[int]], arch: dict, device=None) -> np.ndarray:
    """What ``harness/correct.py`` holds against ``max_gap_sigmas``:
    :func:`token_gaps` ``[sequences, tokens]`` and, where the tolerance file
    sets the layer-matched limits (:data:`HELD`), one more column for each:
    the first sequence's layer-matched gap (:class:`ServedLayers`) over its
    own limit, times ``max_gap_sigmas`` -- the harness compares ONE number
    with one limit, so each held number is put on that limit's scale and
    the largest decides (the line printed here gives each beside its own
    limit)."""
    from benchmarks.harness.spec import load_tolerance

    tol = load_tolerance(arch["config"])
    probe = None
    if all(key in tol for _, key in HELD):
        probe = ServedLayers(
            arch["config"], params["embed"]["tok"].dtype,
            len(prompts[0]) + len(served[0]) - 1, len(served[0]) - 1)
    gaps = token_gaps(params, prompts, served, arch,
                      observe=probe and _observer(probe, arch))
    if probe is None:
        return gaps
    worst = probe.worst()
    print("reference: served-token gap %.4f deviations (limit %s); layer-"
          "matched, worst layer: %s; by layer %s; seconds by part %s" % (
              gaps.max(), tol["max_gap_sigmas"],
              ", ".join(f"{n} {worst[n]:.5f} (limit {tol[key]})"
                        for n, key in HELD),
              {n: {i: round(v, 5) for i, v in by.items()}
               for n, by in probe.gaps.items()},
              {k: round(v, 1) for k, v in SECONDS.items()}), flush=True)
    cols = [np.full((len(gaps), 1), worst[n] / float(tol[key])
                    * float(tol["max_gap_sigmas"])) for n, key in HELD]
    return np.concatenate([gaps] + cols, axis=1)
