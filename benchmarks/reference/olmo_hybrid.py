"""The plain reference of ``olmo_hybrid`` (Olmo-Hybrid-7B): gated delta-rule
layers and full attention layers by ``layer_types``, the OLMo block (the norm
AFTER each branch, an RMSNorm over the whole query and key projections), a
dense SwiGLU in every layer, the head untied. ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``; the recurrence as a plain scan
over positions; no cache, no state or tail carried, no kernels, no batching
(one sequence at a time). Written from the published ``config.json`` keys and
Gated DeltaNet as published (arXiv:2412.06464), not from
``tensorlink_tpu/models/latent.py``.

d = hidden_size, rmsnorm with a learned weight and eps = rms_norm_eps, no bias
anywhere. x_0 = E[token]. Layer l over the positions t of one sequence:

  h  = x + rmsnorm(op_l(x))           (the norm after the branch, none before)
  x' = h + rmsnorm(mlp_l(h))

  op of a "linear_attention" layer, H = linear_num_key_heads (= value heads),
  dk = linear_key_head_dim, dv = linear_value_head_dim, K =
  linear_conv_kernel_dim:
    [q~, k~, v~] = x W_qkv            (H dk | H dk | H dv channels)
    c_t = silu(sum_{j < K} w[j] * [q~ k~ v~]_{t - (K - 1) + j})
                                      (depthwise, causal, zeros before 0)
    a head at a time  q = l2norm(c_q) dk^-0.5,  k = l2norm(c_k),  v = c_v
                                      (l2norm(a) = a / sqrt(sum a^2 + 1e-6))
    beta_t  = 2 sigmoid(x W_b)        (the 2: linear_allow_neg_eigval)
    alpha_t = exp(-exp(A_log) softplus(x W_a + dt_bias))
    S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t) k_t^T
                                      (S in R^{dv x dk} a head, float32, 0
                                       before position 0)
    o_t = S_t q_t
    y = rmsnorm_dv(o_t; o_norm) * silu(x W_g)   a head
    op = concat(y) W_o
  op of a "full_attention" layer, H heads of hd for q, k and v:
    q = rmsnorm(x W_q; q_norm), k = rmsnorm(x W_k; k_norm)  over the WHOLE
    projection, v = x W_v; no rotary positions (rope_theta null);
    a = softmax over s <= t of q_j . k_(j // (H / Hkv)),s / sqrt(hd)
    op = concat_j(sum_s a_s v_s) W_o
  mlp: (silu(u W_1) * (u W_3)) W_2

logits = rmsnorm(x_L) W_head. Departures from the published model: the stated
stage holds layers 0-15 of 32 (arch_of reads num_hidden_layers), the final
norm and the head beside them; q, k and v are one stored projection (their
channels in that order).

Every row-wise function sees blocks of ONE shape: the sequence is padded to
whole blocks of ``ROW_BLOCK`` rows once (causal: a padding row reaches no real
one), so each function compiles once whatever the length. The reference takes
one thing from the program, its parameter tree (:func:`layer_tree`).

What ``correct`` holds (:func:`served_gaps`): the served tokens against the
reference's logits, and one layer at a time the PROGRAM's layer code on the
reference's own hidden states (:class:`ServedLayers`): what a gated-delta
layer adds across a prefill chunk's edge and after a restored snapshot of
state AND tail, the state itself after the last position, what an attention
layer adds through the pages, and the keys and values a position caches, each
by a limit of its own in the tolerance file.
"""

from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

ROW_BLOCK = 1024  # rows a row-wise function takes at a time
QUERY_BLOCK = 256  # queries attended at a time; divides ROW_BLOCK
KINDS = {"linear_attention": "gated_delta", "full_attention": "gqa_full"}
L2_EPS = 1e-6


def arch_of(hf: dict) -> dict:
    """The sizes and switches the forward needs, from ``config.json`` keys."""
    L = int(hf["num_hidden_layers"])
    heads = int(hf["num_attention_heads"])
    theta = (hf.get("rope_parameters") or {}).get("rope_theta")
    return {
        "layers": L, "kinds": list(hf["layer_types"][:L]),
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "heads": heads, "kv_heads": int(hf["num_key_value_heads"]),
        "head_dim": int(hf.get("head_dim") or hf["hidden_size"] // heads),
        "theta": float(theta) if theta else 0.0,  # 0: no rotation
        "lin_heads": int(hf["linear_num_key_heads"]),
        "dk": int(hf["linear_key_head_dim"]),
        "dv": int(hf["linear_value_head_dim"]),
        "taps": int(hf["linear_conv_kernel_dim"]),
        "beta_scale": 2.0 if hf.get("linear_allow_neg_eigval") else 1.0,
        # controls (tests/test_olmo_hybrid.py, benchmarks/tests and the
        # builder's chip run): a fault each, and the precision below the
        # served one (cached keys and values rounded to int8, a scale a head)
        "decay": True, "delta": True, "l2norm": True, "conv_silu": True,
        "taps_reversed": False, "out_gate": True, "norm_after": True,
        "qk_norm_full": True, "state_bf16": False, "int8_rows": False,
        "edge_zeroed": 0,  # n > 0: nothing before every n-th position
        "snapshot_off": 0,
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
    """``x`` once it is computed (calls dispatched ahead of the device each
    hold their temporaries while they wait, beside the served model)."""
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


def _branch(x, added, ln, arch):
    """``x + rmsnorm(added)``: the norm AFTER the branch (the control
    ``norm_after`` false: the branch was given the normed input instead)."""
    return x + (_rmsnorm(added, _w(ln), arch["eps"]) if arch["norm_after"]
                else added)


def _branch_in(x, ln, arch):
    return x if arch["norm_after"] else _rmsnorm(x, _w(ln), arch["eps"])


# -- the gated delta rule ----------------------------------------------------


@functools.partial(jax.jit, static_argnames=("arch",))
@_hp
def _delta_in(x, ln1, ap, *, arch):
    """``[z | gate | g | beta]`` of a block of rows: the convolution's
    input, the output gate before its SiLU, ``g = log alpha`` and ``beta``."""
    arch = dict(arch)
    u = _branch_in(x, ln1, arch)
    g = -jnp.exp(_w(ap["A_log"])) * jax.nn.softplus(
        u @ _w(ap["w_a"]) + _w(ap["dt_bias"]))
    beta = arch["beta_scale"] * jax.nn.sigmoid(u @ _w(ap["w_b"]))
    return jnp.concatenate(
        [u @ _w(ap["w_qkv"]), u @ _w(ap["w_g"]), g, beta], axis=-1)


@functools.partial(jax.jit, static_argnames=("arch",))
@_hp
def _conv(z, taps, *, arch):
    """``silu(sum_j w[j] z_{t - (K - 1) + j})`` over the whole sequence ``z``
    ``[T, W]``."""
    arch = dict(arch)
    w = _w(taps)
    K = w.shape[0]
    if arch["taps_reversed"]:
        w = w[::-1]
    T = z.shape[0]
    t = jnp.arange(T)
    c = jnp.zeros_like(z)
    for j in range(K):
        back = K - 1 - j  # z_{t - back}
        shifted = jnp.pad(z, ((back, 0), (0, 0)))[:T]
        reach = t - back >= 0
        if arch["edge_zeroed"]:  # the control: nothing before a chunk's edge
            reach &= t - back >= t // arch["edge_zeroed"] * arch["edge_zeroed"]
        c = c + w[j] * jnp.where(reach[:, None], shifted, 0.0)
    return jax.nn.silu(c) if arch["conv_silu"] else c


@functools.partial(jax.jit, static_argnames=("arch",))
@_hp
def _recurrence(c, g, beta, at, *, arch):
    """The delta rule over the whole sequence, position by position: ``c``
    ``[T, W]`` (after the convolution), ``g`` / ``beta`` ``[T, H]``. Returns
    ``(o [T, H, dv], the state after position at - 1 [H, dv, dk])``."""
    arch = dict(arch)
    H, dk, dv = arch["lin_heads"], arch["dk"], arch["dv"]
    T = c.shape[0]
    q, k, v = jnp.split(c, (H * dk, 2 * H * dk), axis=-1)

    def l2(a):
        a = a.reshape(T, H, dk)
        if not arch["l2norm"]:
            return a
        return a / jnp.sqrt((a * a).sum(-1, keepdims=True) + L2_EPS)

    q, k, v = l2(q) * dk**-0.5, l2(k), v.reshape(T, H, dv)
    alpha = jnp.exp(g) if arch["decay"] else jnp.ones_like(g)

    def position(carry, xs):
        S, kept = carry
        t, qt, kt, vt, at_, bt = xs
        Sd = at_[:, None, None] * S
        seen = jnp.einsum("hvk,hk->hv", Sd, kt) if arch["delta"] else 0.0
        S = Sd + (bt[:, None] * (vt - seen))[:, :, None] * kt[:, None, :]
        if arch["state_bf16"]:  # the control: the state kept in bfloat16
            # (a cast there and back is dropped by the chip's compiler)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        kept = jnp.where(t == at - 1, S, kept)
        return (S, kept), jnp.einsum("hvk,hk->hv", S, qt)

    zero = jnp.zeros((H, dv, dk), jnp.float32)
    (_, kept), o = jax.lax.scan(
        position, (zero, zero), (jnp.arange(T), q, k, v, alpha, beta))
    return o, kept


@functools.partial(jax.jit, static_argnames=("arch",))
@_hp
def _delta_out(x, o, gate, ln1, ap, *, arch):
    arch = dict(arch)
    y = _rmsnorm(o, _w(ap["o_norm"]), arch["eps"])
    if arch["out_gate"]:
        y = y * jax.nn.silu(gate.reshape(o.shape))
    return _branch(x, y.reshape(x.shape[0], -1) @ _w(ap["wo"]), ln1, arch)


def delta_layer(x, lt: dict, arch: dict, at=None):
    """x -> x + rmsnorm(op(x)) of one gated-delta layer over ``x`` ``[T,
    d]``, and the layer's state after position ``at - 1`` (``[H, dv, dk]``;
    default: the last row)."""
    ap, ln1 = lt["attn"], lt["ln1"]["scale"]
    H, dk, dv = arch["lin_heads"], arch["dk"], arch["dv"]
    W, V = H * (2 * dk + dv), H * dv
    st = _static(arch)
    rows = _blocks(_delta_in, x, ln1, ap, arch=st)
    c = _done(_conv(rows[:, :W], ap["taps"], arch=st))
    o, state = _done(_recurrence(
        c, rows[:, W + V:W + V + H], rows[:, W + V + H:],
        jnp.int32(x.shape[0] if at is None else at), arch=st))
    out = jnp.concatenate([
        _done(_delta_out(x[t:t + ROW_BLOCK], o[t:t + ROW_BLOCK],
                         rows[t:t + ROW_BLOCK, W:W + V], ln1, ap, arch=st))
        for t in range(0, x.shape[0], ROW_BLOCK)])
    return out, state


# -- attention ---------------------------------------------------------------


def _rope(x, pos, theta: float):
    """rotate-half rope on all dims of ``x`` ``[T, H, hd]`` at ``pos``
    ``[T]`` (only the control rotates: the published model has no theta)."""
    hd = x.shape[-1]
    freq = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rot * sin


def _qk(p, scale, n_heads: int, pos, arch: dict):
    """A query or key projection ``p`` ``[n, H hd]`` normed and as heads."""
    hd = arch["head_dim"]
    if arch["qk_norm_full"]:
        p = _rmsnorm(p, _w(scale), arch["eps"]).reshape(-1, n_heads, hd)
    else:  # the control: a norm a head
        p = _rmsnorm(p.reshape(-1, n_heads, hd),
                     _w(scale).reshape(n_heads, hd), arch["eps"])
    return _rope(p, pos, arch["theta"]) if arch["theta"] else p


@functools.partial(jax.jit, static_argnames=("arch",))
@_hp
def _keys_values(x, ln1, ap, t0, *, arch):
    """``[u | keys | values]`` of a block of rows from position ``t0`` on."""
    arch = dict(arch)
    u = _branch_in(x, ln1, arch)
    n, Hkv, hd = u.shape[0], arch["kv_heads"], arch["head_dim"]
    key = _qk(u @ _w(ap["wk"]), ap["k_norm"], Hkv, t0 + jnp.arange(n), arch)
    val = (u @ _w(ap["wv"])).reshape(n, Hkv, hd)
    if arch["int8_rows"]:  # the control: what a position caches, in int8
        def int8(a):
            step = jnp.max(jnp.abs(a), -1, keepdims=True) / 127.0
            return jnp.round(a / step) * step
        key, val = int8(key), int8(val)
    return jnp.concatenate([u, key.reshape(n, -1), val.reshape(n, -1)], -1)


@functools.partial(jax.jit, static_argnames=("arch", "n"))
@_hp
def _query_group(x, u, key, val, ln1, ap, g0, *, arch, n):
    """x + rmsnorm(attention) for the ``n`` queries from position ``g0`` on,
    against the whole sequence, one kv head's group of heads at a time."""
    arch = dict(arch)
    T, H, Hkv, hd = u.shape[0], arch["heads"], arch["kv_heads"], arch[
        "head_dim"]
    G = H // Hkv
    q_pos = g0 + jnp.arange(n)
    uq = jax.lax.dynamic_slice_in_dim(u, g0, n)
    q = _qk(uq @ _w(ap["wq"]), ap["q_norm"], H, q_pos, arch).reshape(
        n, Hkv, G, hd)
    mask = jnp.arange(T)[None, :] <= q_pos[:, None]
    wo = ap["wo"].reshape(Hkv, G, hd, -1)

    def kv_head(m, out):
        qm = jax.lax.dynamic_index_in_dim(q, m, 1, keepdims=False)
        km = jax.lax.dynamic_index_in_dim(key, m, 1, keepdims=False)
        vm = jax.lax.dynamic_index_in_dim(val, m, 1, keepdims=False)
        s = jnp.einsum("tgh,sh->gts", qm, km) * hd**-0.5
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("gts,sh->tgh", jax.nn.softmax(s, -1), vm)
        return out + jnp.einsum("tgh,gho->to", o, _w(wo[m]))

    op = jax.lax.fori_loop(0, Hkv, kv_head, jnp.zeros((n, x.shape[1])))
    return _branch(jax.lax.dynamic_slice_in_dim(x, g0, n), op, ln1, arch)


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
    """x -> x + rmsnorm(attention(x)) of one layer over ``x`` ``[T, d]``,
    ``QUERY_BLOCK`` queries a call of one compiled function."""
    u, keys, vals = keys_values(x, lt, arch)
    n = min(QUERY_BLOCK, x.shape[0])
    return jnp.concatenate([
        _done(_query_group(x, u, keys, vals, lt["ln1"]["scale"], lt["attn"],
                           jnp.int32(g0), arch=_static(arch), n=n))
        for g0 in range(0, x.shape[0], n)])


# -- the MLP, the stream, the head -------------------------------------------


@functools.partial(jax.jit, static_argnames=("arch",))
@_hp
def _mlp(x, ln2, m, *, arch):
    arch = dict(arch)
    u = _branch_in(x, ln2, arch)
    y = (jax.nn.silu(u @ _w(m["w_gate"])) * (u @ _w(m["w_up"]))) @ _w(
        m["w_down"])
    return _branch(x, y, ln2, arch)


def layer_tree(params: dict, i: int):
    """Layer ``i`` of the program's parameter tree, as stored: ``lead`` /
    ``tail`` lists of layers, ``periods`` a tuple over the places of a
    period with leaves stacked over the periods."""
    n_lead, places = len(params["lead"]), len(params["periods"])
    if i < n_lead:
        return params["lead"][i]
    j = i - n_lead
    n_periods = (
        jax.tree.leaves(params["periods"])[0].shape[0] if places else 0)
    if j >= places * n_periods:
        return params["tail"][j - places * n_periods]
    return jax.tree.map(lambda a: a[j // places], params["periods"][j % places])


def hidden_states(params: dict, tokens, arch: dict, observe=None):
    """The residual stream ``[T, d]`` after every layer of one sequence
    ``tokens`` ``[T]``. ``observe(i, lt, x, a, state)`` sees each layer's
    input ``x``, ``a = x + rmsnorm(op)`` (over the ``n`` real rows) and,
    of a gated-delta layer, its state after the last real row."""
    tok, n = _padded(tokens)
    x = _w(params["embed"]["tok"][jnp.asarray(tok)])
    for i in range(arch["layers"]):
        lt = layer_tree(params, i)
        state = None
        if arch["kinds"][i] == "linear_attention":
            a, state = delta_layer(x, lt, arch, at=n)
        else:
            a = attention_layer(x, lt, arch)
        if observe is not None:
            observe(i, lt, x[:n], a[:n], state)
        x = _blocks(_mlp, a, lt["ln2"]["scale"], lt["mlp"],
                    arch=_static(arch))
    return x[:n]


@functools.partial(jax.jit, static_argnames=("eps",))
@_hp
def _head(h, norm, head, *, eps):
    return _rmsnorm(h, _w(norm), eps) @ _w(head)


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
            h, params["final_norm"]["scale"], params["lm_head"],
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
    """The program's side of the layer-matched comparison: each layer is
    compared on the reference's own input to it. The program's operator of
    that one layer (``engine/paged.py::make_layer_probe``: the step's two
    passes' placing, the deployment's page size and prefill chunk, the
    kernels on the chip) takes the reference's hidden states rounded to the
    served dtype through a cache of its own, chunked prefill then ``n_dec``
    continuation steps, and

    * ``delta``: what a gated-delta layer adds to the residual stream, its
      worst ROW (:func:`_worst_row`): prefilled in slot 0 up to a chunk's
      edge a chunk or more before the last prefill chunk, state AND tail
      there taken as one snapshot with the engine's own ``take_snapshot``,
      restored into slot 1 with ``restore_snapshot``, and slot 1 goes on
      through the last chunks (across a chunk's edge) and the continuation
      steps; the rows compared are slot 1's. The control ``snapshot_off``
      goes on that many positions past where the snapshot was taken;
    * ``state``: slot 1's state of that layer after the last position
      against the reference's (``|served - reference| / |reference|``), the
      worst layer; and ``state0``: the FIRST gated-delta layer's alone. Its
      input is the embedding, exact in the served dtype, so that number
      reads the recurrence's own arithmetic and nothing an input's rounding
      adds: it is what tells a state kept in the precision below apart (a
      deeper layer's input is rounded to bf16 first, which costs its state
      as much as a bf16 state costs it);
    * ``full``: what an attention layer adds over the last prefill chunk and
      the continuation steps, through the page walk at one query head a kv
      head;
    * ``rows``: the keys and values an attention layer cached over every
      position against the reference's (the precision of a page).

    What it does NOT see is the engine's own pages, states, tails and
    snapshot pool: the comparison runs the program's layer code on the
    engine's weights beside the engine."""

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
        # two slots (a snapshot goes from slot 0 to slot 1) over ONE slot's
        # pages: an attention layer runs in slot 0 alone and a gated-delta
        # layer writes no page (0.37 GB less beside the served model)
        n_pp = -(-(T + 1) // self.page)
        cache = LatentPagedCache.init(
            self.cfg, 2, page_size=self.page, max_len=T + 1,
            prefill_chunk=self.chunk, n_pages=1 + n_pp)
        tables = 1 + jnp.arange(n_pp, dtype=jnp.int32)
        self.cache = replace(cache, block_tables=jnp.stack([tables, tables]))
        self.gaps: dict = {"delta": {}, "state": {}, "state0": {}, "full": {},
                           "rows": {}}

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

    def operator(self, i: int, lt: dict, h, a, state, arch: dict):
        """Layer ``i`` through pages, or state and tail, over the
        reference's input ``h`` ``[T, d]``; ``a`` the reference's ``h +
        rmsnorm(op)``, ``state`` its state after the last row."""
        from tensorlink_tpu.engine.sala import (
            held, restore_snapshot, snapshot_pool, take_snapshot,
        )

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

            def rows(pool):  # [n_pp, Hkv, page, hd] -> [T, Hkv, hd]
                r = pool[li, pages].transpose(0, 2, 1, 3)
                return r.reshape((-1,) + r.shape[2:])[:T]

            self.gaps["rows"][i] = max(_rel(rows(cache.k), keys[:T]),
                                       _rel(rows(cache.v), vals[:T]))
            self.gaps["full"][i] = _rel(got, (a - h)[first:])
        else:
            # the snapshot's chunk edge, a chunk or more before ``first``
            edge = max(first - C, 0)
            _, cache = self._run(kind, lp, x, li, cache, 0, 0, edge, n_pre)
            if edge:  # state AND tail, one snapshot
                snaps = take_snapshot(snapshot_pool(cache, 1), held(cache),
                                      jnp.int32(0), jnp.int32(0))
                cache = restore_snapshot(cache, snaps, jnp.int32(1),
                                         jnp.int32(0))
            resume = edge + (arch["snapshot_off"] if edge else 0)
            cache = replace(cache, lengths=cache.lengths.at[1].set(resume))
            outs, cache = self._run(kind, lp, x, li, cache, 1, resume, T,
                                    n_pre)
            self.gaps["delta"][i] = _worst_row(
                jnp.concatenate(outs), (a - h)[resume:])
            # the program's [dk, H dv] against the reference's [H, dv, dk]
            want = state.transpose(2, 0, 1).reshape(state.shape[2], -1)
            self.gaps["state"][i] = _rel(cache.state[li, 1], want)
            if i == kinds.index(kinds[i]):  # the first layer of its kind
                self.gaps["state0"][i] = self.gaps["state"][i]
        self.cache = cache

    def worst(self) -> dict:
        return {name: max(by_layer.values(), default=0.0)
                for name, by_layer in self.gaps.items()}


def _observer(served: ServedLayers, arch: dict):
    return lambda i, lt, h, a, state: served.operator(i, lt, h, a, state, arch)


def layer_gaps(params: dict, tokens, arch: dict, n_dec: int) -> dict:
    """The worst layer's number of each kind (:class:`ServedLayers`) over
    one sequence ``tokens`` ``[T]``, and ``"by_layer"``."""
    tokens = np.asarray(tokens, np.int32)
    served = ServedLayers(arch["config"], params["embed"]["tok"].dtype,
                          len(tokens), n_dec)
    hidden_states(params, tokens, arch, observe=_observer(served, arch))
    return {**served.worst(), "by_layer": served.gaps}


HELD = (("delta", "max_delta_gap"), ("state", "max_state_gap"),
        ("state0", "max_state0_gap"), ("full", "max_full_gap"),
        ("rows", "max_row_gap"))


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
                      observe=probe and _observer(probe, arch))
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
