"""The plain reference: one dense pre-norm GQA decoder forward in
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``; no
kernels, no cache, no batching tricks. Written from the published
descriptions (the Qwen2.5 and Qwen3 model cards and ``modeling_qwen2.py`` /
``modeling_qwen3.py`` of ``transformers``), not from
``tensorlink_tpu/models/transformer.py``:

    h   = embed[tokens]
    for each layer:
        a   = rmsnorm(h, input_layernorm)
        q,k,v = a Wq (+bq), a Wk (+bk), a Wv (+bv)      # bias: Qwen2 family
        q,k = rmsnorm over head_dim of each head (q_norm, k_norm)   # Qwen3
        q,k = rope(q), rope(k)          # rotate_half, theta = rope_theta
        o   = softmax(q k^T / sqrt(head_dim) + causal) v, query head h
              reading kv head h // (heads / kv_heads)
        h   = h + o Wo
        m   = rmsnorm(h, post_attention_layernorm)
        h   = h + (silu(m Wgate) * (m Wup)) Wdown
    logits = rmsnorm(h, norm) W_head    # W_head = embed^T when tied

Both configurations are covered by data: ``model_type`` ``qwen3`` turns on
the per-head q/k norm, ``qwen2`` the q/k/v biases; ``tie_word_embeddings``
picks the head. Departures from the published models: none in the
mathematics; weights are the hosted job's own (seeded, bf16), upcast to
float32 one layer at a time so that the reference fits beside the served
model.

The only thing taken from the program is its parameter tree, whose layout
(:func:`layer_weights`) is: ``layers.attn.wq`` ``[L, d, heads*hd]`` (the
transpose of ``q_proj.weight``), likewise ``wk wv wo``, ``bq bk bv``,
``q_norm k_norm`` ``[L, hd]``, ``layers.ln1.scale`` / ``ln2.scale``
``[L, d]``, ``layers.mlp.w_gate w_up`` ``[L, d, f]``, ``w_down``
``[L, f, d]``, ``embed.tok`` ``[V, d]``, ``final_norm.scale`` ``[d]``,
``lm_head`` ``[d, V]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_BLOCK = 16384


def arch_of(hf: dict) -> dict:
    """The sizes and switches the forward needs, from ``config.json`` keys."""
    heads = int(hf["num_attention_heads"])
    return {
        "heads": heads,
        "kv_heads": int(hf.get("num_key_value_heads") or heads),
        "head_dim": int(hf.get("head_dim") or hf["hidden_size"] // heads),
        "eps": float(hf.get("rms_norm_eps", 1e-6)),
        "theta": float(hf.get("rope_theta", 10000.0)),
        "qk_norm": hf["model_type"] == "qwen3",
        "tied": bool(hf.get("tie_word_embeddings", False)),
        "layers": int(hf["num_hidden_layers"]),
    }


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    # x: [B, T, H, hd]; rotate_half convention, positions 0..T-1
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                             "eps", "theta", "qk_norm"))
def block(h, w, *, heads, kv_heads, head_dim, eps, theta, qk_norm):
    with jax.default_matmul_precision("highest"):
        B, T, _ = h.shape
        a = _rmsnorm(h, w["ln1"], eps)
        q, k, v = a @ w["wq"], a @ w["wk"], a @ w["wv"]
        if "bq" in w:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q = q.reshape(B, T, heads, head_dim)
        k = k.reshape(B, T, kv_heads, head_dim)
        v = v.reshape(B, T, kv_heads, head_dim)
        if qk_norm:
            q = _rmsnorm(q, w["q_norm"], eps)
            k = _rmsnorm(k, w["k_norm"], eps)
        q, k = _rope(q, theta), _rope(k, theta)
        group = heads // kv_heads
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        s = jnp.einsum("bthd,bshd->bhts", q, k) / jnp.sqrt(float(head_dim))
        causal = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhts,bshd->bthd", p, v).reshape(B, T, heads * head_dim)
        h = h + o @ w["wo"]
        m = _rmsnorm(h, w["ln2"], eps)
        return h + (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]


@jax.jit
def _head(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w


def _f32(x, device):
    return jax.device_put(x, device).astype(jnp.float32)


def layer_weights(params: dict, i: int, arch: dict, device) -> dict:
    """Layer ``i`` of the program's stacked parameter tree, float32, on
    one device."""
    L = params["layers"]
    w = {
        "ln1": L["ln1"]["scale"][i], "ln2": L["ln2"]["scale"][i],
        "wq": L["attn"]["wq"][i], "wk": L["attn"]["wk"][i],
        "wv": L["attn"]["wv"][i], "wo": L["attn"]["wo"][i],
        "w_gate": L["mlp"]["w_gate"][i], "w_up": L["mlp"]["w_up"][i],
        "w_down": L["mlp"]["w_down"][i],
    }
    if "bq" in L["attn"]:
        w |= {k: L["attn"][k][i] for k in ("bq", "bk", "bv")}
    if arch["qk_norm"]:
        w |= {k: L["attn"][k][i] for k in ("q_norm", "k_norm")}
    return {k: _f32(v, device) for k, v in w.items()}


def forward_logits(params: dict, tokens: np.ndarray, arch: dict,
                   positions: slice, device=None) -> np.ndarray:
    """Reference logits ``[B, len(positions), V]`` of a full (teacher-
    forced) forward over ``tokens`` ``[B, T]``."""
    device = device or jax.devices()[0]
    tok = jnp.asarray(np.asarray(tokens, np.int32))
    h = _f32(params["embed"]["tok"][tok], device)
    kw = {k: arch[k] for k in ("heads", "kv_heads", "head_dim", "eps",
                               "theta", "qk_norm")}
    for i in range(arch["layers"]):
        h = block(h, layer_weights(params, i, arch, device), **kw)
    h = _rmsnorm(h, _f32(params["final_norm"]["scale"], device), arch["eps"])
    h = h[:, positions]
    V = params["embed"]["tok"].shape[0]
    outs = []
    for a in range(0, V, VOCAB_BLOCK):
        b = min(a + VOCAB_BLOCK, V)
        if arch["tied"] or "lm_head" not in params:
            w = _f32(params["embed"]["tok"][a:b], device).T
        else:
            w = _f32(params["lm_head"][:, a:b], device)
        outs.append(np.asarray(_head(h, w)))
    return np.concatenate(outs, axis=-1)


def served_gaps(params: dict, prompts: list[list[int]],
                served: list[list[int]], arch: dict, device=None) -> np.ndarray:
    """For each served token of each sequence (all of one length), how far
    its reference logit lies under the reference's largest logit at that
    position, in units of that position's standard deviation of the
    reference logits over the vocabulary (0 = the reference's own greedy
    choice; a token the reference ranks like a random id lies about as far
    under the maximum as the maximum lies over the mean: 3 to 5 units over
    150,000 ids). Returns ``[sequences, tokens]``."""
    seq = np.asarray([list(p) + list(s) for p, s in zip(prompts, served)],
                     np.int32)
    P, n = len(prompts[0]), len(served[0])
    logits = forward_logits(params, seq[:, :-1], arch, slice(P - 1, P + n - 1),
                            device)
    got = np.take_along_axis(logits, np.asarray(served)[:, :, None], -1)[..., 0]
    return (logits.max(axis=-1) - got) / logits.std(axis=-1)
