"""The plain reference of ``laguna`` (Laguna-S-2.1): grouped-query attention
of two kinds by ``layer_types`` (full layers; sliding layers of 512 keys),
each kind with its own number of query heads and its own rotary positions, a
sigmoid gate a query head, a dense lead and then sigmoid-routed experts
beside one shared expert. ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``; no cache, no ring, no kernels,
no batching (one sequence at a time). Written from the published
``config.json`` keys, not from ``tensorlink_tpu/models/latent.py``.

Per token, x in R^hidden, h = rmsnorm(x), t its position, layer i:

  attention, H = num_attention_heads_per_layer[i] query heads over Hkv = 8
  heads of keys and values, head_dim 128:
    q_j = rope_i(h W_q,j, t), j < H;  k_m = rope_i(h W_k,m, t), v_m = h W_v,m
    rope_i by layer_types[i] (``rope_parameters``):
      full_attention: rotate-half on the FIRST 64 of 128 dims
        (partial_rotary_factor 0.5), theta 500,000, YaRN (factor 128 over
        8,192, beta 32 / 1: a dim that turns more than 32 times in 8,192
        positions keeps its frequency, one that turns less than once is
        divided by 128, a linear ramp between), cos and sin times
        attention_factor
      sliding_attention: rotate-half on all 128 dims, theta 10,000
    a = softmax over s in S_t of q_j . k_(j // (H / Hkv)),s / sqrt(128)
      S_t = {s <= t} (full) or {t - 512 < s <= t} (sliding: the token counts)
    o_j = sigmoid(h W_g)_j  sum_s a_s v_s;   x <- x + concat(o) W_o
  mlp by mlp_layer_types[i]: "dense" SwiGLU(hidden -> intermediate_size ->
    hidden); "sparse" sc = sigmoid(h W_r) (float32), the
    ``num_experts_per_tok`` experts of largest sc + bias, weights sc_e / sum
    of the chosen (``norm_topk_prob``) times ``moe_routed_scaling_factor``;
    x <- x + shared(h) + sum_e w_e expert_e(h)
  logits = rmsnorm(x) W_head

Departures from the published description, each by the configuration file:
  * conventions its keys do not settle (``assumed`` in
    ``configs/laguna-s-2.1-ep8.json``): the router's scoring (sigmoid +
    selection bias, normalised, times 2.5), no per-head q/k norm, the shared
    expert ungated, rotate-half on the stored order, the window counting
    the token itself;
  * one chip's share of an expert group: the router scores every published
    expert, only experts ``first_expert .. first_expert + num_experts - 1``
    are held, and what the absent ones would add is LEFT OUT (the other
    chips of the group add it), here as in the program;
  * the vocabulary is the configuration's slice.

Weights are upcast to float32 where they are used, a projection or one
expert at a time, and queries go in blocks, each block a call of one
compiled function with its offset as data, so that 13k positions fit beside
the served program. The reference's equations take one thing from the
program, its parameter tree (:func:`layer_tree`).

What ``correct`` holds (:func:`served_gaps`): the served tokens against the
reference's logits, and one layer at a time the PROGRAM's layer code on the
reference's own hidden states (:class:`ServedLayers`, the one place that
runs the program's code): what a full layer's attention adds through the
pages, what a sliding layer's adds through the ring, a snapshot and a
restore, the keys and values a position caches, and the routed experts'
sum, each by a limit of its own in the tolerance file.
"""

from __future__ import annotations

import functools
import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256  # queries attended at a time: a kv head's scores are
# [9, 256, T] float32 (118 MB at 12,800 positions)
ROW_BLOCK = 3200  # rows the dense MLP takes at a time
KINDS = {"full_attention": "gqa_full", "sliding_attention": "gqa_window"}
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def arch_of(hf: dict) -> dict:
    """The sizes and switches the forward needs, from ``config.json`` keys."""
    L = int(hf["num_hidden_layers"])
    hd = int(hf["head_dim"])
    types = list(hf["layer_types"][:L])
    heads = list(hf["num_attention_heads_per_layer"][:L])

    def kind(name: str) -> dict:
        rp = hf["rope_parameters"][name]
        mine = {h for h, t in zip(heads, types) if t == name}
        return {
            "heads": mine.pop() if mine else 0,
            "kv_heads": int(hf["num_key_value_heads"]), "head_dim": hd,
            "rope_dim": int(hd * float(rp.get("partial_rotary_factor", 1))),
            "theta": float(rp["rope_theta"]),
            "yarn": None if rp.get("rope_type", "default") != "yarn" else (
                float(rp["factor"]),
                float(rp["original_max_position_embeddings"]),
                float(rp.get("beta_fast", 32)), float(rp.get("beta_slow", 1)),
                float(rp.get("attention_factor") or (
                    0.1 * math.log(float(rp["factor"])) + 1.0))),
            "window": (int(hf["sliding_window"])
                       if name == "sliding_attention" else None),
        }

    return {
        "layers": L, "kinds": types, "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "full_attention": kind("full_attention"),
        "sliding_attention": kind("sliding_attention"),
        "experts_per_tok": int(hf["num_experts_per_tok"]),
        "experts_held": int(hf["num_experts"]),
        "first_expert": int((hf.get("expert_group") or {}).get("first_expert", 0)),
        "norm_topk": bool(hf.get("norm_topk_prob", True)),
        "routed_scale": float(hf.get("moe_routed_scaling_factor", 1.0)),
        # controls (tests/test_laguna.py, benchmarks/tests/test_laguna.py and
        # the builder's chip run): a fault each, and the precision below the
        # served one (cached keys and values rounded to int8, a scale a head)
        "window_delta": 0, "sliding_heads": None, "no_yarn": False,
        "full_rotary": False, "gate": True, "router": "sigmoid",
        "int8_rows": False, "shared": True,
        # the whole file: the layer-matched comparison builds the program's
        # own ModelConfig and page cache from it (:class:`ServedLayers`)
        "config": dict(hf),
    }


def _kind(arch: dict, i: int) -> dict:
    """Layer ``i``'s attention sizes with the controls applied."""
    name = arch["kinds"][i]
    k = dict(arch[name])
    if name == "sliding_attention":
        k["window"] += arch["window_delta"]
        k["heads"] = arch["sliding_heads"] or k["heads"]
    else:
        if arch["no_yarn"]:
            k["yarn"] = None
        if arch["full_rotary"]:
            k["rope_dim"] = k["head_dim"]
    return {**k, "eps": arch["eps"], "gate": arch["gate"],
            "int8_rows": arch["int8_rows"]}


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


def inv_freq(k: dict) -> tuple:
    """``(frequencies [rope_dim / 2], amplitude)`` of a layer kind, in
    float64: ``theta ** (-2 i / rope_dim)``, under YaRN interpolated by
    dimension."""
    r = k["rope_dim"]
    freq = k["theta"] ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if k["yarn"] is None:
        return freq, 1.0
    factor, orig, fast, slow, amp = k["yarn"]

    def dim_of(turns):  # the dim that turns ``turns`` times in ``orig``
        return r * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(k["theta"]))

    low = max(math.floor(dim_of(fast)), 0)
    high = min(math.ceil(dim_of(slow)), r - 1)
    ramp = np.clip((np.arange(r // 2) - low) / max(high - low, 1e-3), 0, 1)
    return freq / factor * ramp + freq * (1 - ramp), amp


def _rope(x, pos, k: dict):
    """rotate-half rope on the first ``rope_dim`` dims of ``x`` ``[T, H,
    hd]`` at positions ``pos`` ``[T]``."""
    r = k["rope_dim"]
    freq, amp = inv_freq(k)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freq, jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None] * amp
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None] * amp
    a = x[..., :r]
    rot = jnp.concatenate([-a[..., r // 2:], a[..., :r // 2]], -1)
    return jnp.concatenate([a * cos + rot * sin, x[..., r:]], -1)


@functools.partial(jax.jit, static_argnames=("k",))
@_hp
def _keys_values(x, ln1, ap, *, k):
    """``(h, keys, values)``: the normed input and every position's rotated
    keys and values ``[T, Hkv, hd]``."""
    k = dict(k)
    h = _rmsnorm(x, _w(ln1), k["eps"])
    T, Hkv, hd = h.shape[0], k["kv_heads"], k["head_dim"]
    key = _rope((h @ _w(ap["wk"])).reshape(T, Hkv, hd), jnp.arange(T), k)
    val = (h @ _w(ap["wv"])).reshape(T, Hkv, hd)
    if k["int8_rows"]:  # the control: what a position caches, in int8
        def int8(a):
            step = jnp.max(jnp.abs(a), -1, keepdims=True) / 127.0
            return jnp.round(a / step) * step
        key, val = int8(key), int8(val)
    return h, key, val


@functools.partial(jax.jit, static_argnames=("k", "n"))
@_hp
def _query_group(x, h, key, val, ap, g0, *, k, n):
    """x + attention for the ``n`` queries from position ``g0`` on, against
    the whole sequence, one kv head's group of query heads at a time."""
    k = dict(k)
    T, H, Hkv, hd = h.shape[0], k["heads"], k["kv_heads"], k["head_dim"]
    G = H // Hkv
    q_pos = g0 + jnp.arange(n)
    hq = jax.lax.dynamic_slice_in_dim(h, g0, n)
    s_pos = jnp.arange(T)
    mask = s_pos[None, :] <= q_pos[:, None]
    if k["window"] is not None:  # the token itself counts
        mask &= s_pos[None, :] > q_pos[:, None] - k["window"]
    # a control with fewer heads reads the first ones' columns and rows
    wq = ap["wq"].reshape(h.shape[1], -1, hd)[:, :H].reshape(-1, Hkv, G, hd)
    wo = ap["wo"].reshape(-1, hd, h.shape[1])[:H].reshape(Hkv, G, hd, -1)
    gate = jnp.ones((n, H))
    if k["gate"]:
        gate = jax.nn.sigmoid(hq @ _w(ap["w_g"]))[:, :H]
    gate = gate.reshape(n, Hkv, G)

    def kv_head(m, out):
        q = jnp.einsum("td,dgh->tgh", hq, _w(wq[:, m]))
        q = _rope(q, q_pos, k)
        s = jnp.einsum("tgh,sh->gts", q, key[:, m]) * hd**-0.5
        s = jnp.where(mask[None], s, -jnp.inf)
        o = jnp.einsum("gts,sh->tgh", jax.nn.softmax(s, -1), val[:, m])
        return out + jnp.einsum("tgh,gho->to", o * gate[:, m, :, None],
                                _w(wo[m]))

    return jax.lax.fori_loop(
        0, Hkv, kv_head, jax.lax.dynamic_slice_in_dim(x, g0, n))


def _freeze(k: dict) -> tuple:
    """``k`` as a static (hashable) argument."""
    return tuple(sorted(k.items()))


def attention_layer(x, lt: dict, k: dict):
    """x -> x + attention(rmsnorm(x)) of one layer over ``x`` ``[T, d]``,
    ``QUERY_BLOCK`` queries a call of one compiled function (its offset is
    data)."""
    key = _freeze(k)
    h, keys, vals = _keys_values(x, lt["ln1"]["scale"], lt["attn"], k=key)
    T = x.shape[0]
    return jnp.concatenate([
        _done(_query_group(x, h, keys, vals, lt["attn"], jnp.int32(g0),
                           k=key, n=min(QUERY_BLOCK, T - g0)))
        for g0 in range(0, T, QUERY_BLOCK)])


@jax.jit
@_hp
def _gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _w(w_gate)) * (h @ _w(w_up))) @ _w(w_down)


def layer_tree(params: dict, i: int):
    """Layer ``i`` of the program's parameter tree, as stored: ``lead`` /
    ``tail`` lists of layers, ``periods`` a tuple over the places of a
    period with leaves stacked over the periods. A period layer's leaves
    are taken out of their stacks but for its experts' (0.6 GB a layer):
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
def _route(x, ln2, mp, *, arch):
    """``(normed input, experts [T, K], weights [T, K])`` over the
    published experts."""
    arch = dict(arch)
    a = _rmsnorm(x, _w(ln2), arch["eps"])
    logits = a @ _w(mp["router"])
    if arch["router"] == "softmax":  # the control: softmax over the picked
        top, experts = jax.lax.top_k(logits, arch["experts_per_tok"])
        return a, experts, jax.nn.softmax(top, -1) * arch["routed_scale"]
    sc = jax.nn.sigmoid(logits)
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


def _scalars(arch: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in arch.items()
                        if not isinstance(v, (dict, list))))


def routed_sum(a, experts, weights, mp: dict, arch: dict, held=None):
    """The held experts' part of a sparse layer over normed rows ``a``:
    ``sum_e w_e expert_e(a)`` over the experts ``held`` (published indices;
    default: this chip's)."""
    stacks = {n: mp[n] for n in EXPERT_STACKS}
    layer = (jnp.int32(mp["stacked"]),) if "stacked" in mp else ()
    first = arch["first_expert"]
    y = jnp.zeros_like(a)
    for pub in (range(first, first + arch["experts_held"])
                if held is None else held):
        y = _done(_expert(y, a, experts, weights, stacks,
                          layer + (jnp.int32(pub - first),), jnp.int32(pub)))
    return y


def mlp_layer(x, lt: dict, arch: dict, observe=None):
    if "mlp" in lt:
        a = _rmsnorm(x, _w(lt["ln2"]["scale"]), arch["eps"])
        m = lt["mlp"]
        # rows in blocks: [12,800, 12,288] float32 is 0.6 GB, three times over
        return x + jnp.concatenate([
            _done(_gated(a[t:t + ROW_BLOCK], m["w_gate"], m["w_up"],
                         m["w_down"]))
            for t in range(0, a.shape[0], ROW_BLOCK)])
    mp = lt["moe"]
    a, experts, weights = _route(x, lt["ln2"]["scale"], mp,
                                 arch=_scalars(arch))
    routed = routed_sum(a, experts, weights, mp, arch)
    if observe is not None:
        observe(lt, a, experts, weights, routed)
    y = routed
    if arch["shared"] and "shared" in mp:
        sh = mp["shared"]
        y = y + _gated(a, sh["w_gate"], sh["w_up"], sh["w_down"])
    return x + y


def hidden_states(params: dict, tokens, arch: dict,
                  layers: int | None = None, observe=None) -> jnp.ndarray:
    """The residual stream ``[T, d]`` after ``layers`` layers (all by
    default) of one sequence ``tokens`` ``[T]``. ``observe.attention(i, lt,
    h, a)`` sees each layer's input ``h`` and ``a = h + attention``,
    ``observe.experts(i, lt, normed, experts, weights, routed)`` a sparse
    layer's routing and routed sum."""
    tok = jnp.asarray(np.asarray(tokens, np.int32))
    h = _w(params["embed"]["tok"][tok])
    for i in range(arch["layers"] if layers is None else layers):
        lt = layer_tree(params, i)
        a = attention_layer(h, lt, _kind(arch, i))
        if observe is not None:
            observe.attention(i, lt, h, a)
        h = mlp_layer(a, lt, arch, observe=observe and functools.partial(
            observe.experts, i))
    return h


@functools.partial(jax.jit, static_argnames=("eps",))
@_hp
def _head(h, norm, w, *, eps):
    return _rmsnorm(h, _w(norm), eps) @ _w(w)


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


class ServedLayers:
    """The program's side of the layer-matched comparison. The served
    tokens say little of the arithmetic that chose them (with seeded weights
    the discrete step, which 10 of 256 experts, parts a bf16 stream from
    the float32 one by more than a window off by one does), so each layer
    is ALSO compared on the reference's own input to it. The program's
    attention of that one layer (``engine/paged.py::make_layer_probe``: the
    step's two passes' placing, the deployment's page size and prefill
    chunk, the kernels on the chip) takes the reference's hidden states
    rounded to the served dtype through a page cache of its own, chunked
    prefill then ``n_dec`` continuation steps, and

    * ``full``: what a full layer's attention adds to the residual stream
      over the last prefill chunk and the continuation steps, ``|served -
      reference| / |reference|`` (its head count, the rotary share, YaRN
      and its amplitude, the head gate);
    * ``window``: the same of a sliding layer, whose keys and values go
      through the RING: prefilled in slot 0 up to a page edge one chunk
      before the last prefill chunk, the window there taken as a snapshot
      with the engine's own ``take_window``, restored into slot 1 with
      ``restore_window``, and slot 1 goes on through the last chunks and
      the continuation steps (the window's edges, 72 heads, the ring after
      it has wrapped, a snapshot and a restore);
    * ``rows``: the keys and values a full layer cached over every
      position against the reference's (the precision of a page);
    * ``experts``: the program's routed sum (``models/latent.py::moe_mlp``
      without the shared expert) on the reference's normed input against
      the reference's, over the rows where both picked the same experts
      (``agree``: the share of such rows), and ``route``: the largest
      difference of a picked expert's weight on those rows.

    What it does NOT see is the engine's own pages, rings and snapshot
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
        self.gaps: dict = {"full": {}, "window": {}, "rows": {},
                           "experts": {}, "route": {}, "agree": {}}

    def _run(self, kind, lp, x, li, cache, slot: int, lo: int, hi: int,
             n_pre: int):
        """Positions ``lo .. hi - 1`` of ``x`` through ``slot``: prefill
        chunks up to ``n_pre``, then one continuation step a position.
        Returns ``(what the attention added at each, cache)``."""
        ragged, decode = self.probes[kind]
        C = self.chunk
        xp = jnp.pad(x, ((0, C), (0, 0)))
        outs, pos = [], lo
        while pos < min(hi, n_pre):
            n = min(C, min(hi, n_pre) - pos)
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

    def attention(self, i: int, lt: dict, h, a, arch: dict):
        """Layer ``i`` through pages or ring over the reference's input
        ``h`` ``[T, d]``; ``a`` the reference's ``h + attention``."""
        from tensorlink_tpu.engine.latent import (
            restore_window, take_window, window_snapshot_pool)

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
            k = _kind(arch, i)
            _, keys, vals = _keys_values(
                h, lt["ln1"]["scale"], lt["attn"], k=_freeze(k))
            pages = cache.block_tables[0]

            def cached(pool):  # [n_pp, Hkv, page, hd] -> [T, Hkv, hd]
                rows = pool[li, pages].transpose(0, 2, 1, 3)
                return rows.reshape((-1,) + rows.shape[2:])[:T]

            self.gaps["rows"][i] = max(_rel(cached(cache.k), keys),
                                       _rel(cached(cache.v), vals))
            name = "full"
        else:
            # the snapshot's page edge, a chunk or more before ``first``
            edge = max(first - C, 0) // self.page * self.page
            _, cache = self._run(kind, lp, x, li, cache, 0, 0, edge, n_pre)
            if edge:
                snaps = window_snapshot_pool(
                    cache, 1, self.cfg.ring_window)
                snaps = take_window(snaps, cache, jnp.int32(0), jnp.int32(0),
                                    jnp.int32(edge))
                cache = restore_window(cache, snaps, jnp.int32(1),
                                       jnp.int32(0), jnp.int32(edge))
            cache = replace(cache, lengths=cache.lengths.at[1].set(edge))
            outs, cache = self._run(kind, lp, x, li, cache, 1, edge, T, n_pre)
            got = jnp.concatenate(outs)[first - edge:]
            name = "window"
        self.cache = cache
        # what the layer adds to the residual stream, before the sum
        self.gaps[name][i] = _rel(got, (a - h)[first:])

    def experts(self, i: int, lt: dict, a, experts, weights, routed,
                arch: dict):
        """The program's routing and routed sum of sparse layer ``i`` on
        the reference's normed input ``a`` ``[T, d]``."""
        mp = {k: v for k, v in lt["moe"].items() if k != "shared"}
        x = a.astype(self.cfg.dtype)
        pick, w = _done(_program("route")(x, mp, self.cfg))
        order = jnp.argsort(pick, -1)
        ref_order = jnp.argsort(experts, -1)
        same = (jnp.take_along_axis(pick, order, -1)
                == jnp.take_along_axis(experts, ref_order, -1)).all(-1)
        dw = jnp.abs(jnp.take_along_axis(w, order, -1)
                     - jnp.take_along_axis(weights, ref_order, -1)).max(-1)
        y, _ = _done(_program("moe_mlp")(
            x, mp, self.cfg, jnp.ones((x.shape[0],), bool)))
        self.gaps["agree"][i] = float(same.mean())
        self.gaps["route"][i] = float(jnp.where(same, dw, 0.0).max())
        keep = same[:, None]
        self.gaps["experts"][i] = _rel(jnp.where(keep, y, 0.0),
                                       jnp.where(keep, routed, 0.0))

    def worst(self) -> dict:
        return {name: (min if name == "agree" else max)(
                    by_layer.values(), default=0.0)
                for name, by_layer in self.gaps.items()}


class _Observer:
    def __init__(self, served: ServedLayers, arch: dict):
        self.served, self.arch = served, arch

    def attention(self, i, lt, h, a):
        self.served.attention(i, lt, h, a, self.arch)

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


HELD = (("full", "max_full_gap"), ("window", "max_window_gap"),
        ("rows", "max_row_gap"), ("experts", "max_expert_gap"),
        ("route", "max_route_gap"))


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
          "matched, worst layer: %s; rows where both picked the same experts "
          "%.4f; by layer %s" % (
              gaps.max(), tol["max_gap_sigmas"],
              ", ".join(f"{n} {worst[n]:.5f} (limit {tol[key]})"
                        for n, key in HELD), worst["agree"],
              {n: {i: round(v, 5) for i, v in by.items()}
               for n, by in probe.gaps.items()}), flush=True)
    cols = [np.full((len(gaps), 1), worst[n] / float(tol[key])
                    * float(tol["max_gap_sigmas"])) for n, key in HELD]
    return np.concatenate([gaps] + cols, axis=1)
