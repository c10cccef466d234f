"""The plain reference of ``dots3_note`` (dots3-note-prev): latent attention
of two kinds, a learned top-k selector on the full layers, a window on the
sliding ones, sigmoid-routed experts beside a shared one. ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``; no cache, no kernels,
no absorbed products, no batching (one sequence at a time). Written from the
published ``config.json`` keys and the model card's description, not from
``tensorlink_tpu/models/latent.py``.

Per token, x in R^hidden, h = rmsnorm(x), t its position:

  full layer (``layer_types[i] == "full_attention"``; H heads):
    c_q = s_q rmsnorm(h W_dq);  [q_n | q_r]_j = c_q W_uq,j;  q_r <- rope(q_r, t)
    [c_kv | k_r] = h W_dkv;  c_kv <- s_kv rmsnorm(c_kv);  k_r <- rope(k_r, t)
    [k_n,j | v_j] = c_kv W_ukv,j
    selector: q_I,j = rope(c_q W_iq,j),  k_I = rope(layernorm(h W_ik)),
      w = (h W_iw) / sqrt(index heads) / sqrt(index dim)
      I(t, s) = sum_j w_t,j relu(q_I,t,j . k_I,s)
      S_t = the index_topk positions s <= t of largest I(t, s) (all while
      t < index_topk)
    a = softmax over s in S_t of (q_n,j . k_n,s,j + q_r,j . k_r,s) / sqrt(nope + rope)
    o_j = sigmoid(h W_g)_j  sum_s a_s v_s,j;   x <- x + concat(o) W_o
  sliding layer: the same with its own sizes (``swa_*``), no selector,
    S_t = {s : t - window < s <= t}
  mlp: the first ``first_k_dense_replace`` layers SwiGLU(hidden ->
    intermediate -> hidden); the others sc = sigmoid(h W_r) (float32), the
    ``num_experts_per_tok`` experts of largest sc + bias, weights sc_i / sum
    of the chosen (``norm_topk_prob``) times ``routed_scaling_factor``;
    x <- x + shared(h) + sum_i w_i expert_i(h)
  logits = rmsnorm(x) W_head

Departures from the published description, each by the configuration file:
  * conventions its keys do not settle (``assumed`` in
    ``configs/dots3-note-prev-ep8.json``): s_q, s_kv = sqrt(hidden / rank)
    for ``apply_mla_qkv_lora_rescale``; the headwise gate is a sigmoid of
    h and multiplies each head's output before W_o; the window counts the
    token itself; rope (rotate_half) is on the LAST rope dims of q and k and
    on the FIRST rope dims of the selector's;
  * one chip's share of an expert group: the router scores every published
    expert, only experts ``first_expert .. first_expert + n_routed_experts
    - 1`` are held, and what the absent ones would add is LEFT OUT (the
    other chips of the group add it), here as in the program;
  * the vocabulary is the configuration's slice; the vision and audio
    towers and the multi-token-prediction module are not in the language
    model's config and are left out.

Weights are upcast to float32 where they are used, a layer's projection or
one expert at a time, so that the reference fits beside the served model.
Queries and heads go in blocks, each block a call of one compiled function
with its offset as data (a Python slice per block would compile a program
per offset: ~200 of them, two minutes of a cold run). The reference's
equations take one thing from the program, its parameter tree
(:func:`layer_tree`): ``lead`` / ``tail`` lists of layers, ``periods`` a
tuple over the places of a period with leaves stacked over the periods.

What ``correct`` holds (:func:`served_gaps`): the served tokens against the
reference's logits, and one layer at a time the PROGRAM's attention through
pages on the reference's own hidden states (:class:`ServedLayers`, the one
place that runs the program's code) against the reference's: the rows a
position caches and what a sliding layer adds, each by a limit of its own
(``max_row_gap``, ``max_window_gap`` in the tolerance file).
"""

from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256  # queries attended at a time
HEAD_BLOCK = 8  # heads attended at a time: a block's scores are [8, 256, T]
# float32 (105 MB at 12,800 positions), so that the reference's peak stays
# under the served model's own (weights + pools + the step's reserve)
INDEX_HEAD_BLOCK = 8
ROW_BLOCK = 3200  # rows the dense MLP takes at a time
NEG = -1e30
KINDS = {"full_attention": "full", "sliding_attention": "sliding"}  # the program's names


def arch_of(hf: dict) -> dict:
    """The sizes and switches the forward needs, from ``config.json`` keys."""
    hidden = int(hf["hidden_size"])
    rescale = bool(hf.get("apply_mla_qkv_lora_rescale", False))

    def kind(prefix: str, **more) -> dict:
        def g(k):
            return hf[prefix + k]

        return {
            "heads": int(g("num_attention_heads")),
            "q_rank": int(g("q_lora_rank")), "kv_rank": int(g("kv_lora_rank")),
            "nope": int(g("qk_nope_head_dim")), "rope": int(g("qk_rope_head_dim")),
            "v": int(g("v_head_dim")), "theta": float(g("rope_theta")),
            "s_q": (hidden / g("q_lora_rank")) ** 0.5 if rescale else 1.0,
            "s_kv": (hidden / g("kv_lora_rank")) ** 0.5 if rescale else 1.0,
            **more,
        }

    return {
        "layers": int(hf["num_hidden_layers"]),
        "kinds": list(hf["layer_types"][: int(hf["num_hidden_layers"])]),
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "full_attention": kind(
            "", window=None, index_heads=int(hf["index_n_heads"]),
            index_dim=int(hf["index_head_dim"]), index_topk=int(hf["index_topk"]),
        ),
        "sliding_attention": kind(
            "swa_", window=int(hf["sliding_window_size"]), index_heads=0,
        ),
        "experts_per_tok": int(hf["num_experts_per_tok"]),
        "experts_held": int(hf["n_routed_experts"]),
        "first_expert": int((hf.get("expert_group") or {}).get("first_expert", 0)),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "routed_scale": float(hf.get("routed_scaling_factor", 1.0)),
        # controls (benchmarks/tests/test_dots3_note.py, and the builder's
        # chip run): a fault each, and the precision below the served one
        # (the cached rows rounded to int8 with one scale a row)
        "select": True, "shared": True, "int8_rows": False,
        # the whole file: the layer-matched comparison builds the program's
        # own ModelConfig and page cache from it (:class:`ServedLayers`)
        "config": dict(hf),
    }


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, pos, theta):
    """rotate_half rope over the whole last dim of ``x`` ``[T, ..., r]``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


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
    """``x`` once it is computed. Calls are dispatched ahead of the device,
    and each one waiting in line already holds its output and its
    temporaries: 25 attention calls or 32 experts in line fill whatever
    memory the served model leaves (15.4 GB of 16.9, my chip run, PR 32).
    One call at a time, the reference's peak is one call's."""
    return jax.block_until_ready(x)


@functools.partial(jax.jit, static_argnames=("k",))
@_hp
def _latents(x, ln1, ap, *, k):
    """What of a layer's attention is per token and not per head, over the
    normed input ``rmsnorm(x)``: the two latents, the shared rotated key,
    the gate, and the selector's queries, keys and weights."""
    k = dict(k)
    h = _rmsnorm(x, _w(ln1), k["eps"])
    pos = jnp.arange(h.shape[0])
    c_q = k["s_q"] * _rmsnorm(h @ _w(ap["w_dq"]), _w(ap["q_norm"]), k["eps"])
    ckv = h @ _w(ap["w_dkv"])
    c_kv = k["s_kv"] * _rmsnorm(
        ckv[:, :k["kv_rank"]], _w(ap["kv_norm"]), k["eps"])
    k_r = _rope(ckv[:, k["kv_rank"]:], pos, k["theta"])
    if k["int8_rows"]:  # the control: what a position caches, in int8
        row = jnp.concatenate([c_kv, k_r], -1)
        step = jnp.max(jnp.abs(row), -1, keepdims=True) / 127.0
        row = jnp.round(row / step) * step
        c_kv, k_r = row[:, :k["kv_rank"]], row[:, k["kv_rank"]:]
    out = {"c_q": c_q, "c_kv": c_kv, "k_r": k_r,
           "gate": jax.nn.sigmoid(h @ _w(ap["w_g"]))}
    if k["index_heads"]:
        Hi, Di, rope = k["index_heads"], k["index_dim"], k["rope"]
        ki = _layernorm(h @ _w(ap["w_ik"]), _w(ap["ik_norm"]["scale"]),
                        _w(ap["ik_norm"]["bias"]), k["eps"])
        # the selector's queries are made a block of queries at a time
        # (:func:`_mask_block`): all of them are 0.4 GB, several times over
        out["ki"] = jnp.concatenate(
            [_rope(ki[:, :rope], pos, k["theta"]), ki[:, rope:]], -1)
        out["wi"] = (h @ _w(ap["w_iw"])) * Hi**-0.5 * Di**-0.5
    return out


def _mask_block(p, ap, t0, n: int, k: dict, select: bool):
    """S_t of the ``n`` queries from ``t0`` on, as a mask ``[n, T]``: the
    causal positions, cut to the window where the layer has one, to the
    ``index_topk`` positions of largest selector score where it selects
    (every causal position while there are no more than that; equal scores
    go to the earlier position: an exact 0 where every selector head's relu
    is 0 is common with 4 heads and never seen with 64)."""
    T = p["c_q"].shape[0]
    q_pos = t0 + jnp.arange(n)
    s_pos = jnp.arange(T)
    causal = s_pos[None, :] <= q_pos[:, None]
    if k["window"] is not None:  # the token itself counts
        return causal & (s_pos[None, :] > q_pos[:, None] - k["window"])
    if not (k["index_heads"] and select) or T <= k["index_topk"]:
        return causal
    Hi, Di, rope = k["index_heads"], k["index_dim"], k["rope"]
    qi = (jax.lax.dynamic_slice_in_dim(p["c_q"], t0, n)
          @ _w(ap["w_iq"])).reshape(n, Hi, Di)
    qi = jnp.concatenate(
        [_rope(qi[..., :rope], q_pos, k["theta"]), qi[..., rope:]], -1)
    wi = jax.lax.dynamic_slice_in_dim(p["wi"], t0, n)
    sc = jnp.zeros((n, T), jnp.float32)
    for a in range(0, k["index_heads"], INDEX_HEAD_BLOCK):  # I(t, s)
        s = jnp.einsum("thd,sd->ths", qi[:, a:a + INDEX_HEAD_BLOCK], p["ki"])
        sc = sc + jnp.einsum("ths,th->ts", jax.nn.relu(s),
                             wi[:, a:a + INDEX_HEAD_BLOCK])
    top, idx = jax.lax.top_k(jnp.where(causal, sc, NEG), k["index_topk"])
    picked = jnp.zeros((n, T), bool).at[jnp.arange(n)[:, None], idx].set(
        top > NEG / 2)
    return causal & picked


@functools.partial(jax.jit, static_argnames=("k", "select", "n", "heads"))
@_hp
def _query_group(x, p, ap, g0, *, k, select, n, heads):
    """x + attention for the ``n`` queries from position ``g0`` on, against
    the whole sequence: each query's set S_t (``QUERY_BLOCK`` queries at a
    time), then ``heads`` heads at a time their queries, keys and values
    from the latents, softmax over S_t, the gate, their rows of W_o.
    Blocking, not batching: the sums are those of the equations."""
    k = dict(k)
    nope, rope, v, H = k["nope"], k["rope"], k["v"], k["heads"]
    T = x.shape[0]
    sizes = [min(QUERY_BLOCK, n - t) for t in range(0, n, QUERY_BLOCK)]
    masks, t = [], 0
    for m in sizes:
        masks.append(_mask_block(p, ap, g0 + t, m, k, select))
        t += m
    c_q = jax.lax.dynamic_slice_in_dim(p["c_q"], g0, n)
    gate = jax.lax.dynamic_slice_in_dim(p["gate"], g0, n)
    w_uq = ap["w_uq"].reshape(-1, H, nope + rope)
    w_ukv = ap["w_ukv"].reshape(-1, H, nope + v)
    w_o = ap["wo"].reshape(H, v, -1)
    scale = float(nope + rope) ** -0.5

    def head_block(i, out):
        a = i * heads

        def part(w, axis):
            return _w(jax.lax.dynamic_slice_in_dim(w, a, heads, axis=axis))

        q = jnp.einsum("tc,chd->thd", c_q, part(w_uq, 1))
        q = jnp.concatenate(
            [q[..., :nope],
             _rope(q[..., nope:], g0 + jnp.arange(n), k["theta"])], -1)
        kv = jnp.einsum("tc,chd->thd", p["c_kv"], part(w_ukv, 1))
        key = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(p["k_r"][:, None], (T, heads, rope))], -1)
        outs, t = [], 0
        for m in masks:
            s = jnp.einsum("thd,shd->hts", q[t:t + m.shape[0]], key) * scale
            s = jnp.where(m[None], s, -jnp.inf)
            outs.append(jnp.einsum(
                "hts,shd->thd", jax.nn.softmax(s, -1), kv[..., nope:]))
            t += m.shape[0]
        o = jnp.concatenate(outs) * jax.lax.dynamic_slice_in_dim(
            gate, a, heads, axis=1)[..., None]
        return out + jnp.einsum("thd,hdo->to", o, part(w_o, 0))

    return jax.lax.fori_loop(
        0, H // heads, head_block, jax.lax.dynamic_slice_in_dim(x, g0, n))


@jax.jit
@_hp
def _gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _w(w_gate)) * (h @ _w(w_up))) @ _w(w_down)


EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def layer_tree(params: dict, i: int):
    """Layer ``i`` of the program's parameter tree, as stored. A period
    layer's leaves are taken out of their stacks over the periods, but for
    its experts' (1.5 GB a layer, a copy that would stand beside the served
    model): ``moe["stacked"]`` is then the layer's index in those stacks
    and :func:`_expert` reads one expert through it."""
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


GROUP_BLOCKS = 2  # query blocks a call attends: a call's temporaries grow
# with it, and they stand beside the served model (:func:`_done`)


def attention_layer(h, lt: dict, k: dict, arch: dict):
    """x -> x + attention(rmsnorm(x)) of one layer over ``h`` ``[T, d]``,
    ``GROUP_BLOCKS x QUERY_BLOCK`` queries a call of one compiled function
    (its offset is data)."""
    key = tuple(sorted({**k, "eps": arch["eps"],
                        "int8_rows": arch["int8_rows"]}.items()))
    p = _latents(h, lt["ln1"]["scale"], lt["attn"], k=key)
    T, H = h.shape[0], k["heads"]
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else H
    step = QUERY_BLOCK * GROUP_BLOCKS
    return jnp.concatenate([
        _done(_query_group(h, p, lt["attn"], jnp.int32(g0), k=key,
                           select=arch["select"], n=min(step, T - g0),
                           heads=hb))
        for g0 in range(0, T, step)])


@functools.partial(jax.jit, static_argnames=("arch",))
@_hp
def _route(x, ln2, mp, *, arch):
    """``(normed input, experts [T, K], weights [T, K])`` over the
    published experts."""
    arch = dict(arch)
    a = _rmsnorm(x, _w(ln2), arch["eps"])
    sc = jax.nn.sigmoid(a @ _w(mp["router"]))
    _, experts = jax.lax.top_k(sc + _w(mp["bias"]), arch["experts_per_tok"])
    w = jnp.take_along_axis(sc, experts, axis=-1)
    if arch["norm_topk"]:
        w = w / w.sum(-1, keepdims=True)
    return a, experts, w * arch["routed_scale"]


@jax.jit
@_hp
def _expert(y, a, experts, weights, mp, e, pub):
    """y + (the weight each row gives published expert ``pub``) x the held
    expert at index ``e`` (``(expert,)``, or ``(layer, expert)`` into
    stacks over the periods) of the stacks in ``mp`` applied to ``a``."""
    w_e = jnp.where(experts == pub, weights, 0.0).sum(-1)
    gate, up, down = (_w(mp[n][e]) for n in EXPERT_STACKS)
    return y + w_e[:, None] * ((jax.nn.silu(a @ gate) * (a @ up)) @ down)


def mlp_layer(h, lt: dict, arch: dict) -> jnp.ndarray:
    key = tuple(sorted((k, v) for k, v in arch.items()
                       if not isinstance(v, (dict, list))))
    if "mlp" in lt:
        a = _rmsnorm(h, _w(lt["ln2"]["scale"]), arch["eps"])
        m = lt["mlp"]
        # rows in blocks: [12,800, 13,824] float32 is 0.7 GB, three times over
        return h + jnp.concatenate([
            _done(_gated(a[t:t + ROW_BLOCK], m["w_gate"], m["w_up"],
                         m["w_down"]))
            for t in range(0, a.shape[0], ROW_BLOCK)])
    mp = lt["moe"]
    a, experts, weights = _route(h, lt["ln2"]["scale"], mp, arch=key)
    y = jnp.zeros_like(h)
    if arch["shared"] and "shared" in mp:
        sh = mp["shared"]
        y = y + _gated(a, sh["w_gate"], sh["w_up"], sh["w_down"])
    # held expert e is published expert first_expert + e; an expert that
    # is not held adds nothing here (another chip of the group adds it)
    stacks = {n: mp[n] for n in EXPERT_STACKS}
    layer = (jnp.int32(mp["stacked"]),) if "stacked" in mp else ()
    for e in range(arch["experts_held"]):
        y = _done(_expert(
            y, a, experts, weights, stacks, layer + (jnp.int32(e),),
            jnp.int32(arch["first_expert"] + e)))
    return h + y


def hidden_states(params: dict, tokens, arch: dict,
                  layers: int | None = None, observe=None) -> jnp.ndarray:
    """The residual stream ``[T, d]`` after ``layers`` layers (all by
    default) of one sequence ``tokens`` ``[T]``. ``observe(i, lt, h, a)``
    sees each layer's input ``h`` and ``a = h + attention``."""
    tok = jnp.asarray(np.asarray(tokens, np.int32))
    h = _w(params["embed"]["tok"][tok])
    for i in range(arch["layers"] if layers is None else layers):
        lt = layer_tree(params, i)
        a = attention_layer(h, lt, arch[arch["kinds"][i]], arch)
        if observe is not None:
            observe(i, lt, h, a)
        h = mlp_layer(a, lt, arch)
    return h


@functools.partial(jax.jit, static_argnames=("eps",))
@_hp
def _head(h, norm, w, *, eps):
    return _rmsnorm(h, _w(norm), eps) @ _w(w)


def forward_logits(params: dict, tokens: np.ndarray, arch: dict,
                   positions: slice, device=None, observe=None) -> np.ndarray:
    """Reference logits ``[B, len(positions), V]`` of a full (teacher-
    forced) forward over ``tokens`` ``[B, T]``, one sequence at a time;
    ``observe`` sees the first sequence's layers (:func:`hidden_states`)."""
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


@functools.partial(jax.jit, static_argnames=("n",))
def _rows_at(x, start, *, n):
    """``x[start : start + n]`` with the offset as data (one program)."""
    return jax.lax.dynamic_slice_in_dim(x, start, n)


def _rel(got, want) -> float:
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


class ServedLayers:
    """The program's side of the layer-matched comparison. The served
    tokens say little of the arithmetic that chose them: with seeded
    weights the discrete steps (which 2,048 positions, which 8 experts)
    part a bf16 stream from the float32 one by more than int8 rows or a
    window off by one do. So each layer is ALSO compared on the reference's
    own input to it: the program's attention of that one layer
    (``engine/paged.py::make_layer_probe``: the step's two passes' placing,
    the deployment's page size and prefill chunk, the kernel on the chip)
    takes the reference's hidden states rounded to the served dtype,
    chunked prefill then ``n_dec`` continuation steps through a page cache
    of its own, and

    * the rows it cached (latent | rotated key, and the selector's keys)
      are held against the reference's, ``|served - reference| /
      |reference|`` over every position: ``rows``;
    * what it added to the residual stream over the last prefill chunk and
      the continuation steps is held against the reference's attention at
      those positions: ``window`` on a sliding layer (no discrete step
      inside), ``full`` on a full layer (the selected sets differ in a few
      of 2,048 positions: reported, held to nothing).

    What it does NOT see is the engine's own pages: the comparison runs the
    program's layer code on the engine's weights beside the engine."""

    def __init__(self, hf: dict, dtype, T: int, n_dec: int):
        from tensorlink_tpu.engine.latent import LatentPagedCache
        from tensorlink_tpu.engine.paged import make_layer_probe
        from tensorlink_tpu.models.registry import config_from_hf

        ml = hf.get("deployment", {}).get("ml", {})
        self.cfg = config_from_hf(dict(hf), dtype=dtype)
        self.chunk = int(ml.get("prefill_chunk", 128))
        self.n_dec = n_dec
        kernel = jax.default_backend() == "tpu"
        self.probes = {k: make_layer_probe(self.cfg, k, kernel=kernel)
                       for k in KINDS.values()}
        cache = LatentPagedCache.init(
            self.cfg, 1, page_size=int(ml.get("cont_page_size", 16)),
            max_len=T)
        self.n_pp = cache.pages_per_slot
        self.cache = replace(cache, block_tables=jnp.arange(
            1, self.n_pp + 1, dtype=jnp.int32)[None])
        self.gaps: dict = {"rows": {}, "window": {}, "full": {}}

    def _pool_rows(self, pool, li, T: int):
        x = pool[li, 1:1 + self.n_pp, 0]
        return x.reshape(-1, x.shape[-1])[:T]

    def layer(self, i: int, kinds: list, lt: dict, h, a, k: dict, arch: dict):
        """Layer ``i`` through the pages over the reference's input ``h``
        ``[T, d]``; ``a`` the reference's ``h + attention``."""
        kind = KINDS[kinds[i]]
        li = jnp.int32([KINDS[x] for x in kinds[:i]].count(kind))
        ragged, decode = self.probes[kind]
        lp = {"ln1": lt["ln1"], "attn": lt["attn"]}
        T, C = h.shape[0], self.chunk
        x = h.astype(self.cfg.dtype)
        xp = jnp.pad(x, ((0, C), (0, 0)))
        cache = replace(self.cache, lengths=jnp.zeros((1,), jnp.int32))
        n_pre, pos, out = T - self.n_dec, 0, None
        while pos < n_pre:
            n = min(C, n_pre - pos)
            out, cache = _done(ragged(
                lp, _rows_at(xp, jnp.int32(pos), n=C)[None], cache, li,
                jnp.asarray([pos], jnp.int32), jnp.asarray([n], jnp.int32)))
            pos += n
        first = n_pre - n  # the last chunk's first position
        outs = [out[0, :n]]
        for t in range(n_pre, T):
            out, cache = decode(lp, _rows_at(xp, jnp.int32(t), n=1)[None],
                                cache, li, jnp.asarray([True]))
            outs.append(out[0])
        self.cache = cache
        # what the layer adds to the residual stream, before the sum
        self.gaps["window" if kind == "sliding" else "full"][i] = _rel(
            jnp.concatenate(outs), (a - h)[first:])
        key = tuple(sorted({**k, "eps": arch["eps"],
                            "int8_rows": arch["int8_rows"]}.items()))
        p = _latents(h, lt["ln1"]["scale"], lt["attn"], k=key)
        pool = cache.slide if kind == "sliding" else cache.full
        rows = self._pool_rows(pool, li, T)[:, :k["kv_rank"] + k["rope"]]
        gap = _rel(rows, jnp.concatenate([p["c_kv"], p["k_r"]], -1))
        if k["index_heads"]:
            gap = max(gap, _rel(self._pool_rows(cache.index, li, T), p["ki"]))
        self.gaps["rows"][i] = gap

    def worst(self) -> dict:
        return {name: max(by_layer.values(), default=0.0)
                for name, by_layer in self.gaps.items()}


def layer_gaps(params: dict, tokens, arch: dict, n_dec: int) -> dict:
    """``{"rows", "window", "full"}``: the worst layer's relative gap of
    each kind (:class:`ServedLayers`) over one sequence ``tokens`` ``[T]``,
    and ``"by_layer"``."""
    tokens = np.asarray(tokens, np.int32)
    served = ServedLayers(arch["config"], params["embed"]["tok"].dtype,
                          len(tokens), n_dec)
    hidden_states(params, tokens, arch, observe=_observer(served, arch))
    return {**served.worst(), "by_layer": served.gaps}


def _observer(served: ServedLayers, arch: dict):
    def observe(i, lt, h, a):
        served.layer(i, arch["kinds"], lt, h, a, arch[arch["kinds"][i]], arch)
    return observe


HELD = (("rows", "max_row_gap"), ("window", "max_window_gap"))


def served_gaps(params: dict, prompts: list[list[int]],
                served: list[list[int]], arch: dict, device=None) -> np.ndarray:
    """What ``harness/correct.py`` holds against ``max_gap_sigmas``:
    :func:`token_gaps` ``[sequences, tokens]`` and, where the tolerance file
    sets ``max_row_gap`` and ``max_window_gap``, one more column for each:
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
          "matched, worst layer: %s; by layer %s" % (
              gaps.max(), tol["max_gap_sigmas"],
              ", ".join(f"{n} {worst[n]:.5f}" + (
                  f" (limit {tol[key]})" if key else " (held to nothing)")
                  for n, key in HELD + (("full", None),)),
              {n: {i: round(v, 5) for i, v in by.items()}
               for n, by in probe.gaps.items()}), flush=True)
    cols = [np.full((len(gaps), 1), worst[n] / float(tol[key])
                    * float(tol["max_gap_sigmas"])) for n, key in HELD]
    return np.concatenate([gaps] + cols, axis=1)
