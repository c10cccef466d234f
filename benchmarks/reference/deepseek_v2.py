"""The plain reference of ``deepseek_v2`` (DeepSeek-V2): latent attention
over the whole context in every layer, YaRN-scaled rotary positions with the
``mscale`` softmax scale, a leading dense layer, then softmax-scored experts
with group-limited greedy routing beside the shared ones. ``jax.numpy``,
float32, ``jax.default_matmul_precision("highest")``; no cache, no kernels,
no absorbed products, materialised keys and values, one sequence at a time.
Written from the published ``config.json`` keys and the model's report, not
from ``tensorlink_tpu/models/latent.py``.

Per token, x in R^hidden, h = rmsnorm(x) (eps ``rms_norm_eps``), t its position:

  attention, every layer (H heads):
    c_q = rmsnorm(h W_dq);  [q_n | q_r]_i = c_q W_uq,i;  q_r <- rope(q_r, t)
    [c | k_r] = h W_dkv;  c <- rmsnorm(c);  k_r <- rope(k_r, t), one for all heads
    [k_n,i | v_i] = c W_ukv,i
    a = softmax over ALL s <= t of (q_n,i . k_n,s,i + q_r,i . k_r,s) x (nope + rope)^-0.5 x m^2
    x <- x + concat_i(sum_s a_s v_s,i) W_o           (no bias, no gate, no rescale)
  rope (YaRN; d = rope dims, base = rope_theta; ``rope_scaling``):
    f_j = base^(-2j/d), j < d/2;  corr(r) = d ln(original / (2 pi r)) / (2 ln base)
    low = max(floor(corr(beta_fast)), 0), high = min(ceil(corr(beta_slow)), d - 1)
    ramp_j = clip((j - low) / (high - low), 0, 1)
    inv_freq_j = (f_j / factor) ramp_j + f_j (1 - ramp_j)
    cos / sin of t x inv_freq, times mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    mscale(s, a) = 0.1 a ln s + 1;  m = mscale(factor, mscale_all_dim)
  mlp: the first ``first_k_dense_replace`` layers SwiGLU(hidden -> intermediate
    -> hidden); the others s = softmax(h W_r) over the published experts
    (float32); a group (``n_routed_experts / n_group`` consecutive experts)
    scores as its largest s; the ``topk_group`` best groups stay and the
    others' scores are set to 0; the ``num_experts_per_tok`` largest of what
    is left are chosen, weights their own s (``norm_topk_prob`` false) times
    ``routed_scaling_factor``;  x <- x + shared(h) + sum_e w_e expert_e(h),
    shared ONE SwiGLU ``n_shared_experts x moe_intermediate_size`` wide
  logits = rmsnorm(x) W_head

Departures from the published model, each by the configuration file:
  * the checkpoint stores a head's rotary dims interleaved; with seeded
    weights that is a permutation of the columns of W_uq and W_dkv, and
    rotate_half is applied to the stored order (``assumed.rope``);
  * one chip's share of an expert group: the router scores every published
    expert, only experts ``first_expert .. first_expert + n_routed_experts
    - 1`` are held (one routing group), and what the absent ones would add
    is LEFT OUT (the other chips add it), here as in the program;
  * the vocabulary is the configuration's slice, the layers its first six.

Weights are upcast to float32 where they are used, a layer's projection or
one expert at a time, so that the reference fits beside the served model;
queries and heads go in blocks, each a call of one compiled function with
its offset as data. The equations take one thing from the program, its
parameter tree (:func:`layer_tree`).

What ``correct`` holds (:func:`served_gaps`): the served tokens against the
reference's logits, and one layer at a time the PROGRAM's layer code on the
reference's own hidden states (:class:`ServedLayers`): through the pages,
the rows a position caches (``max_row_gap``) and what the layer's attention
adds to the residual stream (``max_full_gap``: no discrete step inside, so
rotary frequencies and the softmax scale show); and of the expert layer,
the share of rows whose six picks differ (``max_route_gap``: the group limit)
and what the experts add where the picks agree (``max_expert_gap``: the
shared experts, the weights, the scale). A served token is a discrete
thing: where bf16 and float32 scores order two routing groups differently,
this chip's whole routed part of a token comes or goes (one group a chip),
and the stream parts from the reference's by more than any of these faults.
"""

from __future__ import annotations

import functools
import math
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256  # queries attended at a time
HEAD_BLOCK = 8  # heads attended at a time: a block's scores are [8, 256, T]
GROUP_BLOCKS = 2  # query blocks a call attends
ROW_BLOCK = 3200  # rows the dense MLP takes at a time
MOE_ROWS = 2048  # the last rows of a sequence the expert layer is compared on
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def yarn(hf: dict) -> dict | None:
    """``rope_scaling`` as the numbers the equations use: ``inv_freq``
    (float64), the cos / sin amplitude and m."""
    rs = hf.get("rope_scaling")
    d, base = int(hf["qk_rope_head_dim"]), float(hf.get("rope_theta", 1e4))
    f = base ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)
    if not rs:
        return {"inv_freq": tuple(f), "amp": 1.0, "m": 1.0}
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def corr(r):
        return d * math.log(orig / (2 * math.pi * r)) / (2 * math.log(base))

    def mscale(a):
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0

    low = max(math.floor(corr(float(rs.get("beta_fast", 32)))), 0)
    high = min(math.ceil(corr(float(rs.get("beta_slow", 1)))), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = f / factor * ramp + f * (1 - ramp)
    return {
        "inv_freq": tuple(inv),
        "amp": mscale(float(rs.get("mscale", 1)))
        / mscale(float(rs.get("mscale_all_dim", 0))),
        "m": mscale(float(rs.get("mscale_all_dim", 0))),
    }


def arch_of(hf: dict) -> dict:
    """The sizes and switches the forward needs, from ``config.json`` keys."""
    held = int(hf["n_routed_experts"])
    grouped = hf.get("topk_method", "greedy") == "group_limited_greedy"
    return {
        "layers": int(hf["num_hidden_layers"]),
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "heads": int(hf["num_attention_heads"]),
        "kv_rank": int(hf["kv_lora_rank"]),
        "nope": int(hf["qk_nope_head_dim"]), "rope": int(hf["qk_rope_head_dim"]),
        "v": int(hf["v_head_dim"]), "theta": float(hf.get("rope_theta", 1e4)),
        "experts_per_tok": int(hf["num_experts_per_tok"]),
        "experts_held": held,
        "experts_published": int(
            (hf.get("published") or {}).get("n_routed_experts", held)),
        "first_expert": int((hf.get("expert_group") or {}).get("first_expert", 0)),
        "n_group": int(hf.get("n_group") or 0) if grouped else 0,
        "topk_group": int(hf.get("topk_group") or 0) if grouped else 0,
        "norm_topk": bool(hf.get("norm_topk_prob", False)),
        "routed_scale": float(hf.get("routed_scaling_factor", 1.0)),
        # controls (benchmarks/tests/test_deepseek_v2.py, and the builder's
        # chip run): a fault each with the served program sound, and the
        # precision below the served one (the cached rows rounded to int8
        # with one scale a row)
        "yarn": True, "mscale": True, "group_limit": True,
        "shared_halved": False, "int8_rows": False,
        # the whole file: the layer-matched comparison builds the program's
        # own ModelConfig and page cache from it (:class:`ServedLayers`)
        "config": dict(hf),
    }


def _static(arch: dict) -> tuple:
    """``arch`` as a hashable static argument (its numbers and switches)."""
    return tuple(sorted((k, v) for k, v in arch.items()
                        if not isinstance(v, (dict, list))))


def _positions(arch: dict) -> dict:
    """The rotary numbers in force: YaRN's, or the plain ones of the
    controls (``yarn`` false: plain frequencies; ``mscale`` false: m = 1)."""
    hf = arch["config"]
    y = yarn(hf if arch["yarn"] else {**hf, "rope_scaling": None})
    m = yarn(hf)["m"] if arch["mscale"] else 1.0
    return {"inv_freq": y["inv_freq"], "amp": y["amp"], "m": m}


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
    """``x`` once it is computed: one call's temporaries at a time stand
    beside the served model (PERF.md section 6, PR 32, lesson (e))."""
    return jax.block_until_ready(x)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, inv_freq, amp):
    """rotate_half rope over the whole last dim of ``x`` ``[T, ..., r]``."""
    inv = jnp.asarray(np.asarray(inv_freq, np.float32))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (inv.shape[0],))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * amp
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * amp
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("k", "inv_freq", "amp"))
@_hp
def _latents(x, ln1, ap, *, k, inv_freq, amp):
    """What of a layer's attention is per token and not per head: the two
    latents and the shared rotated key."""
    k = dict(k)
    h = _rmsnorm(x, _w(ln1), k["eps"])
    pos = jnp.arange(h.shape[0])
    c_q = _rmsnorm(h @ _w(ap["w_dq"]), _w(ap["q_norm"]), k["eps"])
    ckv = h @ _w(ap["w_dkv"])
    c = _rmsnorm(ckv[:, :k["kv_rank"]], _w(ap["kv_norm"]), k["eps"])
    k_r = _rope(ckv[:, k["kv_rank"]:], pos, inv_freq, amp)
    if k["int8_rows"]:  # the control: what a position caches, in int8
        row = jnp.concatenate([c, k_r], -1)
        step = jnp.max(jnp.abs(row), -1, keepdims=True) / 127.0
        row = jnp.round(row / step) * step
        c, k_r = row[:, :k["kv_rank"]], row[:, k["kv_rank"]:]
    return {"c_q": c_q, "c": c, "k_r": k_r}


@functools.partial(
    jax.jit, static_argnames=("k", "inv_freq", "amp", "m", "n", "heads"))
@_hp
def _query_group(x, p, ap, g0, *, k, inv_freq, amp, m, n, heads):
    """x + attention for the ``n`` queries from position ``g0`` on, against
    every position at or before each: ``heads`` heads at a time their
    queries, keys and values from the latents, the causal softmax,
    their rows of W_o. Blocking, not batching: the sums are the equations'."""
    k = dict(k)
    nope, rope, v, H = k["nope"], k["rope"], k["v"], k["heads"]
    T = x.shape[0]
    q_pos = g0 + jnp.arange(n)
    causal = jnp.arange(T)[None, :] <= q_pos[:, None]  # [n, T]
    c_q = jax.lax.dynamic_slice_in_dim(p["c_q"], g0, n)
    w_uq = ap["w_uq"].reshape(-1, H, nope + rope)
    w_ukv = ap["w_ukv"].reshape(-1, H, nope + v)
    w_o = ap["wo"].reshape(H, v, -1)
    scale = float(nope + rope) ** -0.5 * m * m

    def head_block(i, out):
        a = i * heads

        def part(w, axis):
            return _w(jax.lax.dynamic_slice_in_dim(w, a, heads, axis=axis))

        q = jnp.einsum("tc,chd->thd", c_q, part(w_uq, 1))
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], q_pos, inv_freq, amp)], -1)
        kv = jnp.einsum("tc,chd->thd", p["c"], part(w_ukv, 1))
        key = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(p["k_r"][:, None], (T, heads, rope))], -1)
        outs = []
        for t in range(0, n, QUERY_BLOCK):
            s = jnp.einsum("thd,shd->hts", q[t:t + QUERY_BLOCK], key) * scale
            s = jnp.where(causal[None, t:t + QUERY_BLOCK], s, -jnp.inf)
            outs.append(jnp.einsum(
                "hts,shd->thd", jax.nn.softmax(s, -1), kv[..., nope:]))
        return out + jnp.einsum(
            "thd,hdo->to", jnp.concatenate(outs), part(w_o, 0))

    return jax.lax.fori_loop(
        0, H // heads, head_block, jax.lax.dynamic_slice_in_dim(x, g0, n))


def attention_layer(h, lt: dict, arch: dict):
    """x -> x + attention(rmsnorm(x)) of one layer over ``h`` ``[T, d]``,
    ``GROUP_BLOCKS x QUERY_BLOCK`` queries a call of one compiled function
    (its offset is data)."""
    key, pos = _static(arch), _positions(arch)
    p = _latents(h, lt["ln1"]["scale"], lt["attn"], k=key,
                 inv_freq=pos["inv_freq"], amp=pos["amp"])
    T, H = h.shape[0], arch["heads"]
    hb = HEAD_BLOCK if H % HEAD_BLOCK == 0 else H
    step = QUERY_BLOCK * GROUP_BLOCKS
    return jnp.concatenate([
        _done(_query_group(h, p, lt["attn"], jnp.int32(g0), k=key,
                           n=min(step, T - g0), heads=hb, **pos))
        for g0 in range(0, T, step)])


@jax.jit
@_hp
def _gated(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ _w(w_gate)) * (h @ _w(w_up))) @ _w(w_down)


def layer_tree(params: dict, i: int):
    """Layer ``i`` of the program's parameter tree, as stored. A period
    layer's leaves are taken out of their stacks over the periods, but for
    its experts' (a copy that would stand beside the served model):
    ``moe["stacked"]`` is then the layer's index in those stacks and
    :func:`_expert` reads one expert through it."""
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
def _route(x, ln2, router, *, arch):
    """``(normed input, experts [T, K], weights [T, K])`` over the
    published experts."""
    arch = dict(arch)
    a = _rmsnorm(x, _w(ln2), arch["eps"])
    s = jax.nn.softmax(a @ _w(router), axis=-1)
    if arch["n_group"] and arch["group_limit"]:
        T, E = s.shape
        G = arch["n_group"]
        best = s.reshape(T, G, E // G).max(-1)
        _, gi = jax.lax.top_k(best, arch["topk_group"])
        keep = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None], gi].set(True)
        s = jnp.where(jnp.repeat(keep, E // G, axis=1), s, 0.0)
    w, experts = jax.lax.top_k(s, arch["experts_per_tok"])
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
    if "mlp" in lt:
        a = _rmsnorm(h, _w(lt["ln2"]["scale"]), arch["eps"])
        m = lt["mlp"]
        return h + jnp.concatenate([
            _done(_gated(a[t:t + ROW_BLOCK], m["w_gate"], m["w_up"],
                         m["w_down"]))
            for t in range(0, a.shape[0], ROW_BLOCK)])
    mp = lt["moe"]
    a, experts, weights = _route(
        h, lt["ln2"]["scale"], mp["router"], arch=_static(arch))
    y = jnp.zeros_like(h)
    if "shared" in mp:
        sh = mp["shared"]
        f = sh["w_gate"].shape[-1]
        if arch["shared_halved"]:  # the control: one shared expert for two
            f //= 2
        y = y + _gated(a, sh["w_gate"][:, :f], sh["w_up"][:, :f],
                       sh["w_down"][:f])
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
    default) of one sequence ``tokens`` ``[T]``. ``observe(i, lt, h, a,
    out)`` sees each layer's input ``h``, ``a = h + attention`` and its
    output ``out = a + mlp``."""
    tok = jnp.asarray(np.asarray(tokens, np.int32))
    h = _w(params["embed"]["tok"][tok])
    for i in range(arch["layers"] if layers is None else layers):
        lt = layer_tree(params, i)
        a = attention_layer(h, lt, arch)
        out = mlp_layer(a, lt, arch)
        if observe is not None:
            observe(i, lt, h, a, out)
        h = out
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
    """The program's side of the layer-matched comparison. Each layer is
    compared on the reference's own input to it: the program's attention of
    that one layer (``engine/paged.py::make_layer_probe``: the step's two
    passes' placing, the deployment's page size and prefill chunk, the
    kernel on the chip) takes the reference's hidden states rounded to the
    served dtype, chunked prefill then ``n_dec`` continuation steps through
    a page cache of its own, and

    * the rows it cached (latent | rotated key) are held against the
      reference's, ``|served - reference| / |reference|`` over every
      position: ``rows``;
    * what it added to the residual stream over the last prefill chunk and
      the continuation steps (both passes' walk) is held against the
      reference's attention at those positions: ``full``. Nothing discrete
      lies inside a layer's attention, so the rotary frequencies and the
      softmax scale show here whatever the experts of the layers before
      chose;
    * the program's expert layer (``models/latent.py::moe_mlp`` and
      ``route``, this chip's share) takes the reference's ``h + attention``
      of the last ``MOE_ROWS`` positions: ``route`` is the share of rows
      whose set of picks differs from the reference router's, ``experts``
      the relative gap of what the layer adds over the rows whose picks
      agree (a row whose picks differ computes other experts: it says
      nothing of the arithmetic).

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
        self.probe = make_layer_probe(self.cfg, "full", kernel=kernel)
        cache = LatentPagedCache.init(
            self.cfg, 1, page_size=int(ml.get("cont_page_size", 16)),
            max_len=T)
        self.n_pp = cache.pages_per_slot
        self.cache = replace(cache, block_tables=jnp.arange(
            1, self.n_pp + 1, dtype=jnp.int32)[None])
        self.gaps: dict = {"rows": {}, "full": {}, "route": {}, "experts": {}}

    def experts(self, i: int, lt: dict, a, out, arch: dict):
        """Layer ``i``'s expert layer over the reference's ``a = h +
        attention`` (its last ``MOE_ROWS`` rows); ``out`` the reference's
        ``a + experts``."""
        a, out = a[-MOE_ROWS:], out[-MOE_ROWS:]
        _, want_i, _ = _route(a, lt["ln2"]["scale"], lt["moe"]["router"],
                              arch=_static(arch))
        got, got_i = _served_experts(
            a.astype(self.cfg.dtype), lt["ln2"]["scale"], lt["moe"],
            cfg=self.cfg)
        same = (jnp.sort(got_i, -1) == jnp.sort(want_i, -1)).all(-1)
        self.gaps["route"][i] = float(1.0 - same.mean())
        keep = same[:, None].astype(jnp.float32)
        self.gaps["experts"][i] = (
            _rel(got.astype(jnp.float32) * keep, (out - a) * keep)
            if bool(same.any()) else 0.0)

    def layer(self, i: int, lt: dict, h, a, out, arch: dict):
        """Layer ``i`` through the pages over the reference's input ``h``
        ``[T, d]``; ``a`` the reference's ``h + attention``, ``out`` its
        ``a + mlp``."""
        if "moe" in lt:
            self.experts(i, lt, a, out, arch)
        li = jnp.int32(i)
        ragged, decode = self.probe
        lp = {"ln1": lt["ln1"], "attn": lt["attn"]}
        T, C = h.shape[0], self.chunk
        xp = jnp.pad(h.astype(self.cfg.dtype), ((0, C), (0, 0)))
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
        self.gaps["full"][i] = _rel(jnp.concatenate(outs), (a - h)[first:])
        pos_ = _positions(arch)
        p = _latents(h, lt["ln1"]["scale"], lt["attn"], k=_static(arch),
                     inv_freq=pos_["inv_freq"], amp=pos_["amp"])
        x = cache.full[li, 1:1 + self.n_pp, 0]
        rows = x.reshape(-1, x.shape[-1])[:T, :arch["kv_rank"] + arch["rope"]]
        self.gaps["rows"][i] = _rel(
            rows, jnp.concatenate([p["c"], p["k_r"]], -1))

    def worst(self) -> dict:
        return {name: max(by_layer.values(), default=0.0)
                for name, by_layer in self.gaps.items()}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _served_experts(x, ln2, mp, *, cfg):
    """The program's expert layer over ``x`` (every row carries a token):
    ``(what it adds [N, d], picks [N, K])``."""
    from tensorlink_tpu.models import latent as ml

    h = ml._rms(x, ln2, cfg.norm_eps)
    y, _ = ml.moe_mlp(h, mp, cfg, jnp.ones((h.shape[0],), bool))
    return y, ml.route(h, mp, cfg)[0]


def _observer(served: ServedLayers, arch: dict):
    def observe(i, lt, h, a, out):
        served.layer(i, lt, h, a, out, arch)
    return observe


def layer_gaps(params: dict, tokens, arch: dict, n_dec: int) -> dict:
    """``{"rows", "full", "route", "experts"}``: the worst layer's gap of
    each (:class:`ServedLayers`) over one sequence ``tokens`` ``[T]``, and
    ``"by_layer"``."""
    tokens = np.asarray(tokens, np.int32)
    served = ServedLayers(arch["config"], params["embed"]["tok"].dtype,
                          len(tokens), n_dec)
    hidden_states(params, tokens, arch, observe=_observer(served, arch))
    return {**served.worst(), "by_layer": served.gaps}


HELD = (("rows", "max_row_gap"), ("full", "max_full_gap"),
        ("route", "max_route_gap"), ("experts", "max_expert_gap"))


def served_gaps(params: dict, prompts: list[list[int]],
                served: list[list[int]], arch: dict, device=None) -> np.ndarray:
    """What ``harness/correct.py`` holds against ``max_gap_sigmas``:
    :func:`token_gaps` ``[sequences, tokens]`` and, where the tolerance file
    sets the four layer-matched limits (:data:`HELD`), one more column for each:
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
              ", ".join(f"{n} {worst[n]:.5f} (limit {tol[key]})"
                        for n, key in HELD),
              {n: {i: round(v, 5) for i, v in by.items()}
               for n, by in probe.gaps.items()}), flush=True)
    cols = [np.full((len(gaps), 1), worst[n] / float(tol[key])
                    * float(tol["max_gap_sigmas"])) for n, key in HELD]
    return np.concatenate([gaps] + cols, axis=1)
