"""The plain reference of ``lfm2_moe`` (LFM2-8B-A1B): gated short-convolution
layers and grouped-query attention layers by ``layer_types``, leading dense
layers and then sigmoid-routed experts with a selection bias and no shared
expert, the head tied to the embedding. ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``; no cache, no tail, no kernels,
no batching (one sequence at a time). Written from the published
``config.json`` keys, not from ``tensorlink_tpu/models/latent.py``.

d = hidden_size, rmsnorm with a learned weight and eps = norm_eps everywhere.
x_0 = E[token] (no scale). Layer l over the positions t of one sequence:

  h  = x + op_l(rmsnorm(x))           (operator_norm)
  x' = h + ffn_l(rmsnorm(h))          (ffn_norm)

  op of a "conv" layer, L = conv_L_cache taps, u its normed input:
    [B, C, g] = split3(u W_in)        (W_in: d -> 3d, in that order)
    z = B * g                         (elementwise, no activation)
    c_t = sum_{j < L} w[j] * z_{t - (L - 1) + j}   (depthwise, causal, zeros
                                       before position 0; w: [L, d])
    op = (C * c) W_out
  op of a "full_attention" layer, H query heads over Hkv heads of keys and
  values, head_dim hd:
    q_j = rope(rmsnorm_hd(u W_q,j; q_layernorm), t), j < H
    k_m = rope(rmsnorm_hd(u W_k,m; k_layernorm), t), v_m = u W_v,m
    rope: rotate-half on all hd dims, theta = rope_theta, no scaling
    a = softmax over s <= t of q_j . k_(j // (H / Hkv)),s / sqrt(hd)
    op = concat_j(sum_s a_s v_s) W_o             (no bias, no gate)
  ffn of the first num_dense_layers layers: (silu(u W_1) * (u W_3)) W_2
  ffn of the others: s = sigmoid(u W_g) (float32, num_experts scores); the
    num_experts_per_tok largest of s + b (b: the expert bias, in selection
    only); weights s_e / (sum of the picked s + 1e-6) (norm_topk_prob) times
    routed_scaling_factor; sum_e weight_e expert_e(u); no shared expert
  logits = rmsnorm(x_last; embedding_norm) E^T   (the head is the embedding)

Departures from the published description, each by the configuration file
(``assumed`` in ``configs/lfm2-8b-a1b-l12.json``): the tied head, head_dim =
hidden / heads, the per-head q/k norms and the final norm, the split order
(B, C, g) with no activation, the 1e-6 in the weights' normalisation; and
one stage of a pipeline: the file's first ``num_hidden_layers`` layers, the
final norm and the head beside them.

Every row-wise function sees blocks of ONE shape: the sequence is padded to
whole blocks of ``ROW_BLOCK`` rows once (causal: a padding row reaches no
real one), so each function compiles once whatever the length, and weights
are upcast to float32 where they are used, a projection or one expert at a
time, so that 13k positions fit beside the served program. The reference's
equations take one thing from the program, its parameter tree
(:func:`layer_tree`).

What ``correct`` holds (:func:`served_gaps`): the served tokens against the
reference's logits, and one layer at a time the PROGRAM's layer code on the
reference's own hidden states (:class:`ServedLayers`): what a conv layer
adds across a prefill chunk's edge and after a restored snapshot, what an
attention layer adds through the pages, the keys and values a position
caches, the experts picked, and the picked weights and the routed sum, each
by a limit of its own in the tolerance file.
"""

from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1600  # rows a row-wise function takes at a time
QUERY_BLOCK = 320  # queries attended at a time: a kv head's scores are
# [4, 320, T] float32 (66 MB at 12,800 positions); divides ROW_BLOCK
KINDS = {"conv": "conv", "full_attention": "gqa_full"}
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def arch_of(hf: dict) -> dict:
    """The sizes and switches the forward needs, from ``config.json`` keys."""
    L = int(hf["num_hidden_layers"])
    heads = int(hf["num_attention_heads"])
    return {
        "layers": L, "kinds": list(hf["layer_types"][:L]),
        "eps": float(hf.get("norm_eps", 1e-5)),
        "taps": int(hf.get("conv_L_cache", 3)),
        "heads": heads, "kv_heads": int(hf["num_key_value_heads"]),
        "head_dim": int(hf.get("head_dim") or hf["hidden_size"] // heads),
        "theta": float(hf.get("rope_theta", 1000000.0)),
        "experts_per_tok": int(hf["num_experts_per_tok"]),
        "experts": int(hf["num_experts"]),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "routed_scale": float(hf.get("routed_scaling_factor", 1.0)),
        # controls (tests/test_lfm2.py, benchmarks/tests/test_lfm2_moe.py and
        # the builder's chip run): a fault each, and the precision below the
        # served one (cached keys and values rounded to int8, a scale a head)
        "edge_zeroed": 0,  # n > 0: z before every n-th position reads zero
        "taps_reversed": False, "snapshot_off": 0, "qk_norm": True,
        "select_bias": True, "weights_biased": False, "int8_rows": False,
        # the whole file: the layer-matched comparison builds the program's
        # own ModelConfig and page cache from it (:class:`ServedLayers`)
        "config": dict(hf),
    }


def _hp(fn):
    @functools.wraps(fn)
    def run(*a, **kw):
        with jax.default_matmul_precision("highest"):
            return fn(*a, **kw)
    return run


def _w(x):
    """A stored weight as the reference computes with it: float32."""
    return x.astype(jnp.float32)


def _done(x):
    """``x`` once it is computed: calls dispatched ahead of the device each
    hold their output and temporaries while they wait in line, beside the
    served model (PERF.md section 6, PR 32)."""
    return jax.block_until_ready(x)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _static(arch: dict) -> tuple:
    """``arch``'s scalars as a static (hashable) argument."""
    return tuple(sorted((k, v) for k, v in arch.items()
                        if not isinstance(v, (dict, list))))


def _blocks(fn, x, *args, **kw):
    """``fn`` over ``x`` ``[T, ...]`` a block of ``ROW_BLOCK`` rows at a
    time (``T`` is a whole number of blocks: :func:`_padded`)."""
    return jnp.concatenate([
        _done(fn(x[t:t + ROW_BLOCK], *args, **kw))
        for t in range(0, x.shape[0], ROW_BLOCK)])


def _padded(tokens) -> tuple:
    """``(tokens padded with 0 to whole row blocks, their number)``."""
    tok = np.asarray(tokens, np.int32)
    return np.pad(tok, (0, -len(tok) % ROW_BLOCK)), len(tok)


# -- the short convolution ---------------------------------------------------


@functools.partial(jax.jit, static_argnames=("eps",))
@_hp
def _conv_in(x, ln, w_in, *, eps):
    """``(z, C)`` of a block of rows: ``z = B * g``."""
    u = _rmsnorm(x, _w(ln), eps)
    b, c, g = jnp.split(u @ _w(w_in), 3, axis=-1)
    return jnp.concatenate([b * g, c], axis=-1)


@functools.partial(jax.jit, static_argnames=("arch",))
@_hp
def _conv_taps(z, taps, *, arch):
    """``c_t = sum_j w[j] z_{t - (L - 1) + j}`` over the whole sequence
    ``z`` ``[T, d]``."""
    arch = dict(arch)
    w = _w(taps)
    L = w.shape[0]
    if arch["taps_reversed"]:
        w = w[::-1]
    T = z.shape[0]
    t = jnp.arange(T)
    c = jnp.zeros_like(z)
    for j in range(L):
        back = L - 1 - j  # z_{t - back}
        shifted = jnp.pad(z, ((back, 0), (0, 0)))[:T]
        reach = t - back >= 0
        if arch["edge_zeroed"]:  # the control: nothing before a chunk's edge
            reach &= t - back >= t // arch["edge_zeroed"] * arch["edge_zeroed"]
        c = c + w[j] * jnp.where(reach[:, None], shifted, 0.0)
    return c


@jax.jit
@_hp
def _conv_out(x, zc, c, w_out):
    d = x.shape[1]
    return x + (zc[:, d:] * c) @ _w(w_out)


def conv_layer(x, lt: dict, arch: dict):
    """x -> x + op(rmsnorm(x)) of one short-convolution layer over ``x``
    ``[T, d]``."""
    ap = lt["attn"]
    zc = _blocks(_conv_in, x, lt["ln1"]["scale"], ap["w_in"], eps=arch["eps"])
    c = _done(_conv_taps(zc[:, :x.shape[1]], ap["taps"], arch=_static(arch)))
    return jnp.concatenate([
        _done(_conv_out(x[t:t + ROW_BLOCK], zc[t:t + ROW_BLOCK],
                        c[t:t + ROW_BLOCK], ap["w_out"]))
        for t in range(0, x.shape[0], ROW_BLOCK)])


# -- attention ---------------------------------------------------------------


def _rope(x, pos, theta: float):
    """rotate-half rope on all dims of ``x`` ``[T, H, hd]`` at ``pos``
    ``[T]``."""
    hd = x.shape[-1]
    freq = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rot * sin


def _head_norm(x, scale, arch: dict):
    return _rmsnorm(x, _w(scale), arch["eps"]) if arch["qk_norm"] else x


@functools.partial(jax.jit, static_argnames=("arch",))
@_hp
def _keys_values(x, ln1, ap, t0, *, arch):
    """``(u, keys, values)`` of a block of rows from position ``t0`` on: the
    normed input and the rows' normalised, rotated keys and values ``[n,
    Hkv, hd]``, side by side."""
    arch = dict(arch)
    u = _rmsnorm(x, _w(ln1), arch["eps"])
    n, Hkv, hd = u.shape[0], arch["kv_heads"], arch["head_dim"]
    key = _head_norm((u @ _w(ap["wk"])).reshape(n, Hkv, hd), ap["k_norm"],
                     arch)
    key = _rope(key, t0 + jnp.arange(n), arch["theta"])
    val = (u @ _w(ap["wv"])).reshape(n, Hkv, hd)
    if arch["int8_rows"]:  # the control: what a position caches, in int8
        def int8(a):
            step = jnp.max(jnp.abs(a), -1, keepdims=True) / 127.0
            return jnp.round(a / step) * step
        key, val = int8(key), int8(val)
    return jnp.concatenate([u, key.reshape(n, -1), val.reshape(n, -1)], -1)


@functools.partial(jax.jit, static_argnames=("arch", "n"))
@_hp
def _query_group(x, u, key, val, ap, g0, *, arch, n):
    """x + attention for the ``n`` queries from position ``g0`` on, against
    the whole sequence, one kv head's group of query heads at a time."""
    arch = dict(arch)
    T, H, Hkv, hd = u.shape[0], arch["heads"], arch["kv_heads"], arch[
        "head_dim"]
    G = H // Hkv
    q_pos = g0 + jnp.arange(n)
    uq = jax.lax.dynamic_slice_in_dim(u, g0, n)
    mask = jnp.arange(T)[None, :] <= q_pos[:, None]
    wq = ap["wq"].reshape(u.shape[1], Hkv, G, hd)
    wo = ap["wo"].reshape(Hkv, G, hd, -1)

    def kv_head(m, out):
        q = jnp.einsum("td,dgh->tgh", uq, _w(wq[:, m]))
        q = _rope(_head_norm(q, ap["q_norm"], arch), q_pos, arch["theta"])
        s = jnp.einsum("tgh,sh->gts", q, key[:, m]) * hd**-0.5
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("gts,sh->tgh", jax.nn.softmax(s, -1), val[:, m])
        return out + jnp.einsum("tgh,gho->to", o, _w(wo[m]))

    return jax.lax.fori_loop(
        0, Hkv, kv_head, jax.lax.dynamic_slice_in_dim(x, g0, n))


def keys_values(x, lt: dict, arch: dict):
    """``(u [T, d], keys, values [T, Hkv, hd])`` of one attention layer."""
    d, kv = x.shape[1], arch["kv_heads"] * arch["head_dim"]
    out = jnp.concatenate([
        _done(_keys_values(x[t:t + ROW_BLOCK], lt["ln1"]["scale"], lt["attn"],
                           jnp.int32(t), arch=_static(arch)))
        for t in range(0, x.shape[0], ROW_BLOCK)])
    shape = (x.shape[0], arch["kv_heads"], arch["head_dim"])
    return (out[:, :d], out[:, d:d + kv].reshape(shape),
            out[:, d + kv:].reshape(shape))


def attention_layer(x, lt: dict, arch: dict):
    """x -> x + attention(rmsnorm(x)) of one layer over ``x`` ``[T, d]``,
    ``QUERY_BLOCK`` queries a call of one compiled function (its offset is
    data)."""
    u, keys, vals = keys_values(x, lt, arch)
    n = min(QUERY_BLOCK, x.shape[0])
    return jnp.concatenate([
        _done(_query_group(x, u, keys, vals, lt["attn"], jnp.int32(g0),
                           arch=_static(arch), n=n))
        for g0 in range(0, x.shape[0], n)])


# -- MLPs --------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("eps",))
@_hp
def _dense(x, ln2, w_gate, w_up, w_down, *, eps):
    a = _rmsnorm(x, _w(ln2), eps)
    return x + (jax.nn.silu(a @ _w(w_gate)) * (a @ _w(w_up))) @ _w(w_down)


def layer_tree(params: dict, i: int):
    """Layer ``i`` of the program's parameter tree, as stored: ``lead`` /
    ``tail`` lists of layers, ``periods`` a tuple over the places of a
    period with leaves stacked over the periods. A period layer's leaves
    are taken out of their stacks but for its experts' (0.7 GB a layer):
    ``moe["stacked"]`` is then the layer's index in those stacks."""
    n_lead, places = len(params["lead"]), len(params["periods"])
    if i < n_lead:
        return params["lead"][i]
    j = i - n_lead
    n_periods = (
        jax.tree.leaves(params["periods"])[0].shape[0] if places else 0)
    if j >= places * n_periods:
        return params["tail"][j - places * n_periods]
    lt = dict(params["periods"][j % places])
    moe = lt.pop("moe", None)

    def pick(tree):
        return jax.tree.map(lambda a: a[j // places], tree)

    out = pick(lt)
    if moe is not None:
        out["moe"] = {
            **pick({k: v for k, v in moe.items() if k not in EXPERT_STACKS}),
            **{k: moe[k] for k in EXPERT_STACKS}, "stacked": j // places}
    return out


@functools.partial(jax.jit, static_argnames=("arch",))
@_hp
def _route(x, ln2, router, bias, *, arch):
    """``(normed input, experts [n, K], weights [n, K])`` of a block of
    rows, side by side."""
    arch = dict(arch)
    a = _rmsnorm(x, _w(ln2), arch["eps"])
    sc = jax.nn.sigmoid(a @ _w(router))
    biased = sc + _w(bias)
    _, experts = jax.lax.top_k(biased if arch["select_bias"] else sc,
                               arch["experts_per_tok"])
    w = jnp.take_along_axis(biased if arch["weights_biased"] else sc,
                            experts, axis=-1)
    if arch["norm_topk"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
    return jnp.concatenate(
        [a, experts.astype(jnp.float32), w * arch["routed_scale"]], -1)


def route(x, lt: dict, arch: dict):
    mp, d, K = lt["moe"], x.shape[1], arch["experts_per_tok"]
    out = _blocks(_route, x, lt["ln2"]["scale"], mp["router"], mp["bias"],
                  arch=_static(arch))
    return out[:, :d], out[:, d:d + K].astype(jnp.int32), out[:, d + K:]


@jax.jit
@_hp
def _expert(y, a, experts, weights, mp, e, pub):
    """y + (the weight each row gives expert ``pub``) x the expert at index
    ``e`` (``(expert,)``, or ``(layer, expert)`` into stacks over the
    periods) of the stacks in ``mp`` applied to ``a``."""
    w_e = jnp.where(experts == pub, weights, 0.0).sum(-1)
    gate, up, down = (_w(mp[n][e]) for n in EXPERT_STACKS)
    return y + w_e[:, None] * ((jax.nn.silu(a @ gate) * (a @ up)) @ down)


def routed_sum(a, experts, weights, mp: dict, arch: dict):
    """``sum_e w_e expert_e(a)`` over every expert."""
    stacks = {n: mp[n] for n in EXPERT_STACKS}
    layer = (jnp.int32(mp["stacked"]),) if "stacked" in mp else ()
    y = jnp.zeros_like(a)
    for e in range(arch["experts"]):
        y = _done(_expert(y, a, experts, weights, stacks,
                          layer + (jnp.int32(e),), jnp.int32(e)))
    return y


def mlp_layer(x, lt: dict, arch: dict, observe=None):
    if "mlp" in lt:
        m = lt["mlp"]
        return _blocks(_dense, x, lt["ln2"]["scale"], m["w_gate"], m["w_up"],
                       m["w_down"], eps=arch["eps"])
    a, experts, weights = route(x, lt, arch)
    routed = routed_sum(a, experts, weights, lt["moe"], arch)
    if observe is not None:
        observe(lt, a, experts, weights, routed)
    return x + routed


def hidden_states(params: dict, tokens, arch: dict, observe=None):
    """The residual stream ``[T, d]`` after every layer of one sequence
    ``tokens`` ``[T]``. ``observe.operator(i, lt, x, a)`` sees each layer's
    input ``x`` and ``a = x + op``, ``observe.experts(i, lt, normed,
    experts, weights, routed)`` an expert layer's routing and routed sum
    (all over the ``n`` real rows)."""
    tok, n = _padded(tokens)
    x = _w(params["embed"]["tok"][jnp.asarray(tok)])
    for i in range(arch["layers"]):
        lt = layer_tree(params, i)
        layer = conv_layer if arch["kinds"][i] == "conv" else attention_layer
        a = layer(x, lt, arch)
        if observe is not None:
            observe.operator(i, lt, x[:n], a[:n])
        x = mlp_layer(a, lt, arch, observe=observe and (
            lambda lt, na, e, w, r, i=i: observe.experts(
                i, lt, na[:n], e[:n], w[:n], r[:n])))
    return x[:n]


@functools.partial(jax.jit, static_argnames=("eps",))
@_hp
def _head(h, norm, embed, *, eps):
    return _rmsnorm(h, _w(norm), eps) @ _w(embed).T


def forward_logits(params: dict, tokens: np.ndarray, arch: dict,
                   positions: slice, device=None, observe=None) -> np.ndarray:
    """Reference logits ``[B, len(positions), V]`` of a full (teacher-
    forced) forward over ``tokens`` ``[B, T]``, one sequence at a time;
    ``observe`` sees the first sequence's layers."""
    rows = []
    for b, seq in enumerate(np.asarray(tokens)):
        h = hidden_states(params, seq, arch,
                          observe=None if b else observe)[positions]
        rows.append(np.asarray(_head(
            h, params["final_norm"]["scale"], params["embed"]["tok"],
            eps=arch["eps"])))
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


# -- the program's layers on the reference's hidden states -------------------


@functools.partial(jax.jit, static_argnames=("n",))
def _rows_at(x, start, *, n):
    """``x[start : start + n]`` with the offset as data (one program)."""
    return jax.lax.dynamic_slice_in_dim(x, start, n)


@functools.lru_cache(maxsize=None)
def _program(name: str):
    """``models/latent.py::<name>`` jitted once (the config is static)."""
    from tensorlink_tpu.models import latent

    return jax.jit(getattr(latent, name), static_argnums=2)


def _rel(got, want) -> float:
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _worst_row(got, want) -> float:
    """The largest ``|got_t - want_t|`` of any row over the rows' mean
    ``|want_t|``: one row that is wrong reads as wrong, among hundreds
    that are not."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want, axis=-1).max()
                 / jnp.linalg.norm(want, axis=-1).mean())


class ServedLayers:
    """The program's side of the layer-matched comparison. The served
    tokens say little of the arithmetic that chose them (with seeded weights
    the discrete step, which 4 of 32 experts, parts a bf16 stream from the
    float32 one by more than a tail zeroed at an edge does), so each layer
    is ALSO compared on the reference's own input to it. The program's
    operator of that one layer (``engine/paged.py::make_layer_probe``: the
    step's two passes' placing, the deployment's page size and prefill
    chunk, the kernels on the chip) takes the reference's hidden states
    rounded to the served dtype through a cache of its own, chunked prefill
    then ``n_dec`` continuation steps, and

    * ``conv``: what a conv layer adds to the residual stream, its worst
      ROW (:func:`_worst_row`): prefilled in slot 0 up to a chunk's edge a
      chunk or more before the last prefill chunk, the tail there taken as
      a snapshot with the engine's own ``take_snapshot``, restored into slot
      1 with ``restore_snapshot``, and slot 1 goes on through the last
      chunks (across a chunk's edge) and the continuation steps; the rows
      compared are slot 1's. The control ``snapshot_off`` goes on that many
      positions past where the snapshot was taken;
    * ``full``: what an attention layer's adds over the last prefill chunk
      and the continuation steps, ``|served - reference| / |reference|``
      (the q/k norms, theta, a head of 64 beside its values in the page);
    * ``rows``: the keys and values an attention layer cached over every
      position against the reference's (the precision of a page);
    * ``picks``: the share of an expert layer's rows where the program's
      router (``models/latent.py::route`` on the reference's normed input)
      picks another set of experts than the reference;
    * ``route``: the largest difference of a picked expert's weight on the
      rows where both picked the same, and ``experts``: the program's
      routed sum (``moe_mlp``) against the reference's over those rows.

    What it does NOT see is the engine's own pages, tails and snapshot
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
        self.probes = {k: make_layer_probe(self.cfg, k, kernel=kernel)
                       for k in KINDS.values()}
        cache = LatentPagedCache.init(
            self.cfg, 2, page_size=self.page, max_len=T + 1,
            prefill_chunk=self.chunk)
        self.n_pp = cache.pages_per_slot
        tables = 1 + jnp.arange(2 * self.n_pp, dtype=jnp.int32)
        self.cache = replace(cache, block_tables=tables.reshape(2, self.n_pp))
        self.gaps: dict = {"conv": {}, "full": {}, "rows": {}, "picks": {},
                           "route": {}, "experts": {}}

    def _run(self, kind, lp, x, li, cache, slot: int, lo: int, hi: int,
             n_pre: int):
        """Positions ``lo .. hi - 1`` of ``x`` through ``slot``: prefill
        chunks up to ``n_pre``, then one continuation step a position.
        Returns ``(what the operator added at each, cache)``."""
        ragged, decode = self.probes[kind]
        C = self.chunk
        xp = jnp.pad(x, ((0, C), (0, 0)))
        outs, pos = [], lo
        while pos < min(hi, n_pre):
            # a chunk ends where the engine's would: at a multiple of C
            n = min(C - pos % C, min(hi, n_pre) - pos)
            blk = jnp.zeros((2, C, x.shape[1]), x.dtype).at[slot].set(
                _rows_at(xp, jnp.int32(pos), n=C))
            starts = jnp.zeros((2,), jnp.int32).at[slot].set(pos)
            nv = jnp.zeros((2,), jnp.int32).at[slot].set(n)
            out, cache = _done(ragged(lp, blk, cache, li, starts, nv))
            outs.append(out[slot, :n])
            pos += n
        active = jnp.zeros((2,), bool).at[slot].set(True)
        for t in range(pos, hi):
            blk = jnp.zeros((2, 1, x.shape[1]), x.dtype).at[slot].set(
                _rows_at(xp, jnp.int32(t), n=1))
            out, cache = decode(lp, blk, cache, li, active)
            outs.append(out[slot])
        return outs, cache

    def operator(self, i: int, lt: dict, h, a, arch: dict):
        """Layer ``i`` through pages or tail over the reference's input
        ``h`` ``[T, d]``; ``a`` the reference's ``h + op``."""
        from tensorlink_tpu.engine.sala import restore_snapshot, take_snapshot

        kinds = arch["kinds"]
        kind = KINDS[kinds[i]]
        li = jnp.int32([KINDS[x] for x in kinds[:i]].count(kind))
        lp = {"ln1": lt["ln1"], "attn": lt["attn"]}
        T, C = h.shape[0], self.chunk
        x = h.astype(self.cfg.dtype)
        n_pre = T - self.n_dec
        first = (n_pre - 1) // C * C  # the last prefill chunk's first position
        cache = replace(self.cache, lengths=jnp.zeros((2,), jnp.int32))
        if kind == "gqa_full":
            outs, cache = self._run(kind, lp, x, li, cache, 0, 0, T, n_pre)
            got = jnp.concatenate(outs)[first:]
            _, keys, vals = keys_values(
                jnp.pad(h, ((0, -T % ROW_BLOCK), (0, 0))), lt, arch)
            pages = cache.block_tables[0]
            # [n_pp, Hkv, page, 2 hd] -> [T, Hkv, 2 hd]: key beside value
            rows = cache.k[li, pages].transpose(0, 2, 1, 3)
            rows = rows.reshape((-1,) + rows.shape[2:])[:T]
            hd = arch["head_dim"]
            self.gaps["rows"][i] = max(_rel(rows[..., :hd], keys[:T]),
                                       _rel(rows[..., hd:], vals[:T]))
            self.gaps["full"][i] = _rel(got, (a - h)[first:])
        else:
            # the snapshot's chunk edge, a chunk or more before ``first``
            edge = max(first - C, 0)
            _, cache = self._run(kind, lp, x, li, cache, 0, 0, edge, n_pre)
            if edge:
                st = cache.state
                snaps = jnp.zeros((1,) + st.shape[:1] + st.shape[2:], st.dtype)
                snaps = take_snapshot(snaps, st, jnp.int32(0), jnp.int32(0))
                cache = restore_snapshot(cache, snaps, jnp.int32(1),
                                         jnp.int32(0))
            resume = edge + (arch["snapshot_off"] if edge else 0)
            cache = replace(cache, lengths=cache.lengths.at[1].set(resume))
            outs, cache = self._run(kind, lp, x, li, cache, 1, resume, T,
                                    n_pre)
            self.gaps["conv"][i] = _worst_row(
                jnp.concatenate(outs), (a - h)[resume:])
        self.cache = cache

    def experts(self, i: int, lt: dict, a, experts, weights, routed,
                arch: dict):
        """The program's routing and routed sum of expert layer ``i`` on
        the reference's normed input ``a`` ``[T, d]``."""
        x = a.astype(self.cfg.dtype)
        mp = lt["moe"]
        pick, w = _done(_program("route")(x, mp, self.cfg))
        order = jnp.argsort(pick, -1)
        ref_order = jnp.argsort(experts, -1)
        same = (jnp.take_along_axis(pick, order, -1)
                == jnp.take_along_axis(experts, ref_order, -1)).all(-1)
        dw = jnp.abs(jnp.take_along_axis(w, order, -1)
                     - jnp.take_along_axis(weights, ref_order, -1)).max(-1)
        y, _ = _done(_program("moe_mlp")(
            x, mp, self.cfg, jnp.ones((x.shape[0],), bool)))
        self.gaps["picks"][i] = 1.0 - float(same.mean())
        self.gaps["route"][i] = float(jnp.where(same, dw, 0.0).max())
        keep = same[:, None]
        self.gaps["experts"][i] = _rel(jnp.where(keep, y, 0.0),
                                       jnp.where(keep, routed, 0.0))

    def worst(self) -> dict:
        return {name: max(by_layer.values(), default=0.0)
                for name, by_layer in self.gaps.items()}


class _Observer:
    def __init__(self, served: ServedLayers, arch: dict):
        self.served, self.arch = served, arch

    def operator(self, i, lt, h, a):
        self.served.operator(i, lt, h, a, self.arch)

    def experts(self, i, lt, a, experts, weights, routed):
        self.served.experts(i, lt, a, experts, weights, routed, self.arch)


def layer_gaps(params: dict, tokens, arch: dict, n_dec: int) -> dict:
    """The worst layer's number of each kind (:class:`ServedLayers`) over
    one sequence ``tokens`` ``[T]``, and ``"by_layer"``."""
    tokens = np.asarray(tokens, np.int32)
    served = ServedLayers(arch["config"], params["embed"]["tok"].dtype,
                          len(tokens), n_dec)
    hidden_states(params, tokens, arch, observe=_Observer(served, arch))
    return {**served.worst(), "by_layer": served.gaps}


HELD = (("conv", "max_conv_gap"), ("full", "max_full_gap"),
        ("rows", "max_row_gap"), ("picks", "max_pick_gap"),
        ("route", "max_route_gap"), ("experts", "max_expert_gap"))


def served_gaps(params: dict, prompts: list[list[int]],
                served: list[list[int]], arch: dict, device=None) -> np.ndarray:
    """What ``harness/correct.py`` holds against ``max_gap_sigmas``:
    :func:`token_gaps` ``[sequences, tokens]`` and, where the tolerance file
    sets the limits of :data:`HELD`, one more column for each: the first
    sequence's layer-matched number (:class:`ServedLayers`) over its own
    limit, times ``max_gap_sigmas`` -- the harness compares ONE number with
    one limit, so each held number is put on that limit's scale and the
    largest decides (the line printed here gives each beside its own
    limit)."""
    from benchmarks.harness.spec import load_tolerance

    tol = load_tolerance(arch["config"])
    probe = None
    if all(key in tol for _, key in HELD):
        probe = ServedLayers(
            arch["config"], params["embed"]["tok"].dtype,
            len(prompts[0]) + len(served[0]) - 1, len(served[0]) - 1)
    gaps = token_gaps(params, prompts, served, arch,
                      observe=probe and _Observer(probe, arch))
    if probe is None:
        return gaps
    worst = probe.worst()
    print("reference: served-token gap %.4f deviations (limit %s); layer-"
          "matched, worst layer: %s; by layer %s" % (
              gaps.max(), tol["max_gap_sigmas"],
              ", ".join(f"{n} {worst[n]:.5f} (limit {tol[key]})"
                        for n, key in HELD),
              {n: {i: round(v, 5) for i, v in by.items()}
               for n, by in probe.gaps.items()}), flush=True)
    cols = [np.full((len(gaps), 1), worst[n] / float(tol[key])
                    * float(tol["max_gap_sigmas"])) for n, key in HELD]
    return np.concatenate([gaps] + cols, axis=1)
