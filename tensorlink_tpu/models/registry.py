"""HF architecture registry: transformers config → :class:`ModelConfig`.

The reference accepts any HF causal LM and splits its module tree by memory
(ml/graphing.py); here each supported family declares how its HF config maps
onto the unified core and how its checkpoint tensor names map onto our
parameter tree (consumed by engine/loader.py). Families cover everything the
reference's tests, docs, and BASELINE configs exercise: gpt2 / SmolLM (llama)
/ Qwen2.5 / Qwen3 / Llama-3 / Mistral / Mixtral.
"""

from __future__ import annotations

import math
from typing import Any, Callable

import jax.numpy as jnp

from .base import (
    GatedDelta, GqaAttn, LatentAttn, LinearAttn, ModelConfig, ShortConv,
    SparseAttn,
)

# tlint: disable=TL006(family registry — populated at import, read-only after)
_FAMILY_BUILDERS: dict[str, Callable[[dict], ModelConfig]] = {}


def register_family(model_type: str):
    def deco(fn):
        _FAMILY_BUILDERS[model_type] = fn
        return fn

    return deco


def config_from_hf(hf_config: Any, dtype=jnp.bfloat16) -> ModelConfig:
    """Build a ModelConfig from a ``transformers`` config object or dict."""
    d = hf_config if isinstance(hf_config, dict) else hf_config.to_dict()
    mt = d.get("model_type")
    if mt not in _FAMILY_BUILDERS:
        raise ValueError(
            f"unsupported model_type {mt!r}; supported: {sorted(_FAMILY_BUILDERS)}"
        )
    return _FAMILY_BUILDERS[mt](d).with_(dtype=dtype)


@register_family("gpt2")
def _gpt2(d: dict) -> ModelConfig:
    n_embd = d["n_embd"]
    return ModelConfig(
        family="gpt2",
        vocab_size=d["vocab_size"],
        d_model=n_embd,
        n_layers=d["n_layer"],
        n_heads=d["n_head"],
        n_kv_heads=d["n_head"],
        head_dim=n_embd // d["n_head"],
        d_ff=d.get("n_inner") or 4 * n_embd,
        max_seq_len=d["n_positions"],
        norm_eps=d.get("layer_norm_epsilon", 1e-5),
        act="gelu",
        pos="learned",
        attn_bias=True,
        mlp="fused",
        norm="layernorm",
        tie_embeddings=True,
    )


def _llama_like(d: dict, **overrides) -> ModelConfig:
    n_heads = d["num_attention_heads"]
    head_dim = d.get("head_dim") or d["hidden_size"] // n_heads
    kw: dict[str, Any] = dict(
        family="llama",
        vocab_size=d["vocab_size"],
        d_model=d["hidden_size"],
        n_layers=d["num_hidden_layers"],
        n_heads=n_heads,
        n_kv_heads=d.get("num_key_value_heads") or n_heads,
        head_dim=head_dim,
        d_ff=d["intermediate_size"],
        max_seq_len=d.get("max_position_embeddings", 4096),
        norm_eps=d.get("rms_norm_eps", 1e-6),
        act="silu",
        pos="rope",
        rope_theta=d.get("rope_theta", 10000.0),
        mlp="gated",
        norm="rmsnorm",
        tie_embeddings=d.get("tie_word_embeddings", False),
        attn_bias=d.get("attention_bias", False),
        mlp_bias=d.get("mlp_bias", False),
    )
    kw.update(overrides)
    return ModelConfig(**kw)


@register_family("llama")
def _llama(d: dict) -> ModelConfig:
    return _llama_like(d)


@register_family("mistral")
def _mistral(d: dict) -> ModelConfig:
    return _llama_like(
        d, family="mistral", sliding_window=d.get("sliding_window")
    )


@register_family("qwen2")
def _qwen2(d: dict) -> ModelConfig:
    # Qwen2/2.5: llama core + qkv biases
    return _llama_like(d, family="qwen2", attn_bias=True)


@register_family("qwen3")
def _qwen3(d: dict) -> ModelConfig:
    # Qwen3: llama core + per-head q/k RMSNorm, no biases
    return _llama_like(d, family="qwen3", qk_norm=True, attn_bias=False)


@register_family("mixtral")
def _mixtral(d: dict) -> ModelConfig:
    return _llama_like(
        d,
        family="mixtral",
        n_experts=d["num_local_experts"],
        n_experts_per_tok=d["num_experts_per_tok"],
        sliding_window=d.get("sliding_window"),
    )


@register_family("dots3_note")
def _dots3_note(d: dict) -> ModelConfig:
    """dots3-note: latent attention of two kinds by ``layer_types`` (full
    layers with a learned top-k selector, sliding layers with sizes of
    their own, ``swa_*``), a leading dense layer, then sigmoid-routed
    experts beside a shared one. The keys say what the layers are; four
    conventions they do not settle are this reader's (docs/MODELS.md
    "dots3_note", and ``assumed`` in the benchmark's configuration file):
    the latent rescale ``apply_mla_qkv_lora_rescale`` is sqrt(hidden /
    rank) on each normalised latent; the headwise gate is a sigmoid of the
    layer's normed input times each head's output; the window counts the
    token itself; rope is on the LAST ``qk_rope_head_dim`` dims of q/k.

    A chip's share of an expert group: :func:`_expert_share`."""
    hidden = d["hidden_size"]
    rescale = bool(d.get("apply_mla_qkv_lora_rescale", False))

    def scale(rank: int) -> float:
        return (hidden / rank) ** 0.5 if rescale else 1.0

    full = LatentAttn(
        n_heads=d["num_attention_heads"],
        q_rank=d["q_lora_rank"], kv_rank=d["kv_lora_rank"],
        nope_dim=d["qk_nope_head_dim"], rope_dim=d["qk_rope_head_dim"],
        v_dim=d["v_head_dim"], rope_theta=float(d["rope_theta"]),
        q_scale=scale(d["q_lora_rank"]), kv_scale=scale(d["kv_lora_rank"]),
        index_heads=d.get("index_n_heads", 0),
        index_dim=d.get("index_head_dim", 0),
        index_rope_dim=d["qk_rope_head_dim"] if d.get("index_n_heads") else 0,
        index_topk=d.get("index_topk", 0),
    )
    sliding = LatentAttn(
        n_heads=d["swa_num_attention_heads"],
        q_rank=d["swa_q_lora_rank"], kv_rank=d["swa_kv_lora_rank"],
        nope_dim=d["swa_qk_nope_head_dim"], rope_dim=d["swa_qk_rope_head_dim"],
        v_dim=d["swa_v_head_dim"], rope_theta=float(d["swa_rope_theta"]),
        window=d["sliding_window_size"],
        q_scale=scale(d["swa_q_lora_rank"]),
        kv_scale=scale(d["swa_kv_lora_rank"]),
    )
    kinds = tuple(
        {"full_attention": "full", "sliding_attention": "sliding"}[t]
        for t in d["layer_types"][: d["num_hidden_layers"]]
    )
    if len(kinds) != d["num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(kinds)} layers, num_hidden_layers "
            f"{d['num_hidden_layers']}"
        )
    if d.get("scoring_func", "sigmoid") != "sigmoid" or d.get("n_group"):
        raise ValueError(
            "dots3_note: only the sigmoid router without expert groups "
            "is implemented"
        )
    return ModelConfig(
        family="dots3_note",
        vocab_size=d["vocab_size"],
        d_model=hidden,
        n_layers=d["num_hidden_layers"],
        # the GQA fields name the full layers' heads: nothing of the
        # patterned path reads them
        n_heads=full.n_heads, n_kv_heads=1, head_dim=full.qk_dim,
        d_ff=d["intermediate_size"],
        max_seq_len=d.get("max_position_embeddings", 4096),
        norm_eps=d.get("rms_norm_eps", 1e-6),
        rope_theta=full.rope_theta,
        tie_embeddings=d.get("tie_word_embeddings", False),
        layer_kinds=kinds,
        latent=(("full", full), ("sliding", sliding)),
        n_dense_layers=d.get("first_k_dense_replace", 0),
        n_experts_per_tok=d["num_experts_per_tok"],
        moe_d_ff=d["moe_intermediate_size"],
        n_shared_experts=d.get("n_shared_experts", 0),
        moe_router="sigmoid",
        moe_norm_topk=bool(d.get("norm_topk_prob", True)),
        moe_scale=float(d.get("routed_scaling_factor", 1.0)),
        **_expert_share(d),
    )


# MiniCPM4's ``sparse_config`` (the family's published values): what a
# ``minicpm_sala`` config that lacks the block takes
# tlint: disable=TL006(read-only table: merged into a copy)
SALA_SPARSE_DEFAULTS = dict(
    kernel_size=32, kernel_stride=16, block_size=64, init_blocks=1,
    window_size=2048, topk=64, dense_len=8192,
)
# tlint: disable=TL006(read-only table)
_SALA_MIXERS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


@register_family("minicpm_sala")
def _minicpm_sala(d: dict) -> ModelConfig:
    """MiniCPM-SALA: block-sparse GQA layers (``minicpm4``: per-head q/k
    RMSNorm, no rotary positions, a sigmoid gate a channel) and lightning
    linear-attention layers (``lightning-attn``: per-head q/k RMSNorm,
    rotary positions, head-wise decay, output norm and gate) in the order
    ``mixer_types`` gives, every layer with the dense SwiGLU; MiniCPM's
    scaling keys: ``scale_emb`` on the embeddings, ``scale_depth /
    sqrt(published depth)`` on what a sublayer adds, ``hidden_size /
    dim_model_base`` under the head. A cut config keeps the residual
    scale of the published depth (``published.num_hidden_layers``)."""
    mixers = list(d["mixer_types"])
    unknown = sorted(set(mixers) - set(_SALA_MIXERS))
    if unknown or len(mixers) != d["num_hidden_layers"]:
        raise ValueError(
            f"minicpm_sala: mixer_types {unknown or len(mixers)} for "
            f"{d['num_hidden_layers']} layers (built: {sorted(_SALA_MIXERS)})"
        )
    flags = ("qk_norm", "use_output_gate", "use_output_norm",
             "attn_use_output_gate", "lightning_use_rope")
    off = [k for k in flags if not d.get(k, True)]
    if off or d.get("attn_use_rope", False) or d.get("attention_bias"):
        raise ValueError(
            f"minicpm_sala: only the published mixers are built (off: {off}, "
            f"attn_use_rope {d.get('attn_use_rope')}, attention_bias "
            f"{d.get('attention_bias')})"
        )
    sp = {**SALA_SPARSE_DEFAULTS, **(d.get("sparse_config") or {})}
    hd = d.get("head_dim") or d["hidden_size"] // d["num_attention_heads"]
    sparse = SparseAttn(
        n_heads=d["num_attention_heads"],
        n_kv_heads=d["num_key_value_heads"], head_dim=hd,
        pool=sp["kernel_size"], stride=sp["kernel_stride"],
        block=sp["block_size"], init_blocks=sp["init_blocks"],
        window=sp["window_size"], topk=sp["topk"], dense_len=sp["dense_len"],
    )
    if d.get("lightning_nkv", d["lightning_nh"]) != d["lightning_nh"]:
        raise ValueError("minicpm_sala: lightning_nkv != lightning_nh")
    lightning = LinearAttn(
        n_heads=d["lightning_nh"],
        head_dim=d.get("lightning_head_dim", hd),
        rope_theta=float(d.get("rope_theta", 10000.0)),
    )
    depth = (d.get("published") or {}).get(
        "num_hidden_layers", d["num_hidden_layers"])
    return ModelConfig(
        family="minicpm_sala",
        vocab_size=d["vocab_size"],
        d_model=d["hidden_size"],
        n_layers=d["num_hidden_layers"],
        n_heads=d["num_attention_heads"],
        n_kv_heads=d["num_key_value_heads"],
        head_dim=hd,
        d_ff=d["intermediate_size"],
        max_seq_len=d.get("max_position_embeddings", 4096),
        norm_eps=d.get("rms_norm_eps", 1e-6),
        rope_theta=float(d.get("rope_theta", 10000.0)),
        tie_embeddings=d.get("tie_word_embeddings", False),
        layer_kinds=tuple(_SALA_MIXERS[m] for m in mixers),
        latent=(("sparse", sparse), ("lightning", lightning)),
        n_dense_layers=d["num_hidden_layers"],
        embed_mult=float(d.get("scale_emb", 1.0)),
        residual_mult=float(d.get("scale_depth", 1.0)) / depth**0.5,
        logit_div=d["hidden_size"] / d.get("dim_model_base", d["hidden_size"]),
    )

# tlint: disable=TL006(read-only table)
_LAGUNA_KINDS = {"full_attention": "gqa_full", "sliding_attention": "gqa_window"}


def _laguna_rope(rp: dict, head_dim: int) -> dict:
    """``rope_dim``, ``rope_theta`` and ``rope_scaling`` of one layer kind
    from its block of ``rope_parameters``."""
    rope_dim = int(head_dim * float(rp.get("partial_rotary_factor", 1.0)))
    scaling = None
    kind = rp.get("rope_type", "default")
    if kind == "yarn":
        factor = float(rp["factor"])
        a = rp.get("attention_factor")
        scaling = (
            factor, float(rp["original_max_position_embeddings"]),
            float(rp.get("beta_fast", 32)), float(rp.get("beta_slow", 1)),
            # the amplitude on cos and sin as a YaRN mscale (GqaAttn)
            1.0 if a is None else round(
                (float(a) - 1.0) / (0.1 * math.log(factor)), 12),
            0.0,
        )
    elif kind != "default":
        raise ValueError(
            f"laguna: rope_type {kind!r} is not built (default and yarn are)")
    return dict(rope_dim=rope_dim - rope_dim % 2,
                rope_theta=float(rp["rope_theta"]), rope_scaling=scaling)


@register_family("laguna")
def _laguna(d: dict) -> ModelConfig:
    """Laguna: grouped-query layers of two kinds by ``layer_types`` (full
    layers; sliding layers with ``sliding_window`` keys), each kind with
    its own number of query heads (``num_attention_heads_per_layer``) and
    its own rotary positions (``rope_parameters``: share of a head that
    rotates, theta, YaRN with a given ``attention_factor``), a sigmoid
    gate a query head on the attention output (``gating`` "per-head"),
    leading dense layers and then routed experts beside one shared
    expert (``mlp_layer_types``). The keys name no scoring function:
    ``norm_topk_prob`` with a ``moe_routed_scaling_factor`` is read as
    sigmoid scores, the best by score + selection bias, normalised, times
    the factor (``assumed`` in the benchmark's configuration file, with
    the other conventions the keys do not settle: no per-head q/k norm, no
    gate on the shared expert, rotate-half on the stored order). The
    per-layer lists are read at their first ``num_hidden_layers``
    entries. A chip's share of an expert group: ``num_experts`` is what
    this program holds, ``published.num_experts`` what the router scores,
    ``expert_group.first_expert`` where the held ones start."""
    L = d["num_hidden_layers"]

    def per_layer(key: str) -> list:
        got = list(d[key])[:L]
        if len(got) != L:
            raise ValueError(
                f"laguna: {key} names {len(got)} layers, num_hidden_layers {L}")
        return got

    types = per_layer("layer_types")
    unknown = sorted(set(types) - set(_LAGUNA_KINDS))
    if unknown:
        raise ValueError(
            f"laguna: layer_types {unknown} (built: {sorted(_LAGUNA_KINDS)})")
    gating = {d.get("gating", "per-head").replace("_", "-")} | {
        g.replace("_", "-") for g in d.get("gating_types", [])[:L]}
    if gating != {"per-head"}:
        raise ValueError(
            f"laguna: gating {sorted(gating)} is not built (per-head is: a "
            "sigmoid gate a query head on the attention output)")
    mlps = per_layer("mlp_layer_types")
    n_dense = mlps.index("sparse") if "sparse" in mlps else L
    if mlps != ["dense"] * n_dense + ["sparse"] * (L - n_dense):
        raise ValueError(
            f"laguna: mlp_layer_types {mlps} is not built (leading dense "
            "layers, then sparse ones, is)")
    if d.get("moe_apply_router_weight_on_input"):
        raise ValueError(
            "laguna: moe_apply_router_weight_on_input is not built (the "
            "router's weight multiplies an expert's output)")
    if d.get("moe_router_logit_softcapping"):
        raise ValueError(
            "laguna: moe_router_logit_softcapping "
            f"{d['moe_router_logit_softcapping']} is not built (0 is)")
    if d.get("attention_bias"):
        raise ValueError("laguna: attention_bias is not built")
    heads = per_layer("num_attention_heads_per_layer")
    hd, kv = d["head_dim"], d["num_key_value_heads"]
    sizes = []
    for name, kind in _LAGUNA_KINDS.items():
        n = {h for h, t in zip(heads, types) if t == name}
        if len(n) > 1 or any(h % kv for h in n):
            raise ValueError(
                f"laguna: {sorted(n)} query heads on the {name} layers over "
                f"{kv} kv heads (built: one count a kind, whole groups)")
        if n:
            sizes.append((kind, GqaAttn(
                n_heads=n.pop(), n_kv_heads=kv, head_dim=hd,
                window=(d["sliding_window"] if kind == "gqa_window" else None),
                **_laguna_rope(d["rope_parameters"][name], hd),
            )))
    shared, f = d.get("shared_expert_intermediate_size", 0), d[
        "moe_intermediate_size"]
    if shared % f:
        raise ValueError(
            f"laguna: a shared expert of {shared} beside experts of {f}")
    held = d["num_experts"]
    published = (d.get("published") or {}).get("num_experts", held)
    return ModelConfig(
        family="laguna",
        vocab_size=d["vocab_size"],
        d_model=d["hidden_size"],
        n_layers=L,
        n_heads=d["num_attention_heads"], n_kv_heads=kv, head_dim=hd,
        d_ff=d["intermediate_size"],
        max_seq_len=d.get("max_position_embeddings", 4096),
        norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_embeddings=d.get("tie_word_embeddings", False),
        layer_kinds=tuple(_LAGUNA_KINDS[t] for t in types),
        latent=tuple(sizes),
        n_dense_layers=n_dense,
        n_experts_per_tok=d["num_experts_per_tok"],
        moe_d_ff=f,
        n_shared_experts=shared // f,
        moe_router="sigmoid",
        moe_norm_topk=bool(d.get("norm_topk_prob", True)),
        moe_scale=float(d.get("moe_routed_scaling_factor", 1.0)),
        n_experts=published,
        experts_first=(d.get("expert_group") or {}).get("first_expert", 0),
        experts_held=held if held != published else 0,
    )


# tlint: disable=TL006(read-only table)
_LFM2_KINDS = {"conv": "conv", "full_attention": "gqa_full"}


@register_family("lfm2_moe")
def _lfm2_moe(d: dict) -> ModelConfig:
    """LFM2-MoE: gated short-convolution layers (``conv_L_cache`` taps,
    depthwise, causal) and grouped-query attention layers by
    ``layer_types``, the attention with an RMSNorm a head on queries and
    keys before the rotation and no gate; ``num_dense_layers`` leading
    layers keep the dense MLP, the others route over ``num_experts``
    sigmoid-scored experts, the best ``num_experts_per_tok`` by score +
    selection bias (``use_expert_bias``), their scores normalised
    (``norm_topk_prob``: over the sum + 1e-6) times
    ``routed_scaling_factor``; no shared expert, every expert held; the
    head is the embedding unless ``tie_word_embeddings`` says otherwise.
    ``head_dim``: ``hidden_size / num_attention_heads`` where the keys do
    not name it. The per-layer list is read at its first
    ``num_hidden_layers`` entries (a stage of a pipeline holds a run of
    layers)."""
    L = d["num_hidden_layers"]
    types = list(d["layer_types"])[:L]
    if len(types) != L:
        raise ValueError(
            f"lfm2_moe: layer_types names {len(types)} layers, "
            f"num_hidden_layers {L}")
    unknown = sorted(set(types) - set(_LFM2_KINDS))
    if unknown:
        raise ValueError(
            f"lfm2_moe: layer_types {unknown} (built: {sorted(_LFM2_KINDS)})")
    if d.get("conv_bias"):
        raise ValueError(
            "lfm2_moe: conv_bias is not built (the operator's three "
            "projections and its taps carry no bias)")
    taps = int(d.get("conv_L_cache", 3))
    if taps < 2:
        raise ValueError(
            f"lfm2_moe: conv_L_cache {taps} is not built (two taps or more: "
            "a slot carries the last conv_L_cache - 1 positions)")
    n_dense = int(d.get("num_dense_layers", 0))
    if not 0 < n_dense <= L:
        raise ValueError(
            f"lfm2_moe: num_dense_layers {n_dense} of {L} layers is not "
            "built (the leading layers keep the dense MLP, one or more)")
    if not d.get("use_expert_bias", True):
        raise ValueError(
            "lfm2_moe: use_expert_bias false is not built (the router picks "
            "by score + selection bias)")
    if d.get("num_shared_experts") or d.get("n_shared_experts"):
        raise ValueError("lfm2_moe: a shared expert is not built")
    hidden, heads = d["hidden_size"], d["num_attention_heads"]
    hd = int(d.get("head_dim") or hidden // heads)
    kv = d["num_key_value_heads"]
    if heads % kv:
        raise ValueError(
            f"lfm2_moe: {heads} query heads over {kv} kv heads (built: whole "
            "groups)")
    sizes = []
    if "conv" in types:
        sizes.append(("conv", ShortConv(kernel=taps, width=hidden)))
    if "full_attention" in types:
        sizes.append(("gqa_full", GqaAttn(
            n_heads=heads, n_kv_heads=kv, head_dim=hd, rope_dim=hd,
            rope_theta=float(d.get("rope_theta", 1000000.0)), gate=False,
            qk_norm=True,
        )))
    return ModelConfig(
        family="lfm2_moe",
        vocab_size=d["vocab_size"],
        d_model=hidden,
        n_layers=L,
        n_heads=heads, n_kv_heads=kv, head_dim=hd,
        d_ff=d["intermediate_size"],
        max_seq_len=d.get("max_position_embeddings", 4096),
        norm_eps=d.get("norm_eps", 1e-5),
        tie_embeddings=d.get("tie_word_embeddings", True),
        layer_kinds=tuple(_LFM2_KINDS[t] for t in types),
        latent=tuple(sizes),
        n_dense_layers=n_dense,
        n_experts=d["num_experts"],
        n_experts_per_tok=d["num_experts_per_tok"],
        moe_d_ff=d["moe_intermediate_size"],
        moe_router="sigmoid",
        moe_norm_topk=bool(d.get("norm_topk_prob", True)),
        moe_norm_eps=1e-6,
        moe_scale=float(d.get("routed_scaling_factor", 1.0)),
    )


# tlint: disable=TL006(read-only table)
_OLMO_HYBRID_KINDS = {"linear_attention": "gated_delta",
                      "full_attention": "gqa_full"}


@register_family("olmo_hybrid")
def _olmo_hybrid(d: dict) -> ModelConfig:
    """Olmo-Hybrid: gated delta-rule layers (``linear_*`` keys: heads, key
    and value widths, the taps of the causal convolution in front of q, k
    and v, ``linear_allow_neg_eigval``: a step size of ``2 sigmoid``) and
    full attention layers by ``layer_types``, the OLMo family's block in
    both: an RMSNorm over the WHOLE query and key projections, and the
    norm AFTER each branch (``x + norm(op(x))``), none before; a dense
    SwiGLU in every layer, the head untied. ``rope_parameters.rope_theta``
    null: the attention layers rotate nothing (order comes from the
    recurrent layers); a number: rotate-half over the whole head.
    ``head_dim``: ``hidden_size / num_attention_heads`` where the keys do
    not name it. The per-layer list is read at its first
    ``num_hidden_layers`` entries (a stage of a pipeline holds a run of
    layers)."""
    L = d["num_hidden_layers"]
    types = list(d["layer_types"])[:L]
    if len(types) != L:
        raise ValueError(
            f"olmo_hybrid: layer_types names {len(types)} layers, "
            f"num_hidden_layers {L}")
    unknown = sorted(set(types) - set(_OLMO_HYBRID_KINDS))
    if unknown:
        raise ValueError(
            f"olmo_hybrid: layer_types {unknown} (built: "
            f"{sorted(_OLMO_HYBRID_KINDS)})")
    if d.get("attention_bias"):
        raise ValueError("olmo_hybrid: attention_bias is not built")
    if d.get("hidden_act", "silu") != "silu":
        raise ValueError(
            f"olmo_hybrid: hidden_act {d['hidden_act']!r} is not built "
            "(silu is)")
    hidden, heads = d["hidden_size"], d["num_attention_heads"]
    hd = int(d.get("head_dim") or hidden // heads)
    kv = d["num_key_value_heads"]
    if heads % kv:
        raise ValueError(
            f"olmo_hybrid: {heads} query heads over {kv} kv heads (built: "
            "whole groups)")
    sizes = []
    if "linear_attention" in types:
        kh, vh = d["linear_num_key_heads"], d["linear_num_value_heads"]
        if kh != vh:
            raise ValueError(
                f"olmo_hybrid: linear_num_key_heads {kh} != "
                f"linear_num_value_heads {vh} is not built (one key head a "
                "value head is: a state a head)")
        taps = int(d["linear_conv_kernel_dim"])
        if taps < 2:
            raise ValueError(
                f"olmo_hybrid: linear_conv_kernel_dim {taps} is not built "
                "(two taps or more: a slot carries the last "
                "linear_conv_kernel_dim - 1 positions)")
        sizes.append(("gated_delta", GatedDelta(
            n_heads=kh, key_dim=d["linear_key_head_dim"],
            value_dim=d["linear_value_head_dim"], kernel=taps,
            neg_eigval=bool(d.get("linear_allow_neg_eigval", False)),
        )))
    if "full_attention" in types:
        theta = (d.get("rope_parameters") or {}).get(
            "rope_theta", d.get("rope_theta"))
        sizes.append(("gqa_full", GqaAttn(
            n_heads=heads, n_kv_heads=kv, head_dim=hd,
            rope_dim=hd if theta else 0, rope_theta=float(theta or 0.0),
            gate=False, qk_norm_full=True,
        )))
    return ModelConfig(
        family="olmo_hybrid",
        vocab_size=d["vocab_size"],
        d_model=hidden,
        n_layers=L,
        n_heads=heads, n_kv_heads=kv, head_dim=hd,
        d_ff=d["intermediate_size"],
        max_seq_len=d.get("max_position_embeddings", 4096),
        norm_eps=d.get("rms_norm_eps", 1e-6),
        tie_embeddings=d.get("tie_word_embeddings", False),
        norm_position="post",
        layer_kinds=tuple(_OLMO_HYBRID_KINDS[t] for t in types),
        latent=tuple(sizes),
    )


def _expert_share(d: dict) -> dict:
    """A chip's share of an expert group, as a configuration states it:
    ``n_routed_experts`` is what this program holds,
    ``published.n_routed_experts`` what the router scores,
    ``expert_group.first_expert`` where the held ones start."""
    held = d["n_routed_experts"]
    published = (d.get("published") or {}).get("n_routed_experts", held)
    return dict(
        n_experts=published,
        experts_first=(d.get("expert_group") or {}).get("first_expert", 0),
        experts_held=held if held != published else 0,
    )


@register_family("deepseek_v2")
def _deepseek_v2(d: dict) -> ModelConfig:
    """DeepSeek-V2: latent attention over the whole context in every layer
    (no window, no selector, no gate, no rescale of the latents), YaRN
    positions with the ``mscale`` softmax scale, leading dense layers,
    then softmax-scored experts with group-limited greedy routing beside
    the shared ones. The published checkpoint stores each head's rotary
    dims interleaved; this reader rotates halves of the stored order (a
    permutation of the columns of the two projections that feed them;
    docs/SERVING.md "Layers of more than one kind"). A chip's share of an
    expert group:
    :func:`_expert_share`."""
    if d.get("scoring_func", "softmax") != "softmax":
        raise ValueError(
            f"deepseek_v2: scoring_func {d.get('scoring_func')!r} is not "
            "built (softmax over the published experts is)"
        )
    method = d.get("topk_method", "greedy")
    if method not in ("greedy", "group_limited_greedy"):
        raise ValueError(
            f"deepseek_v2: topk_method {method!r} is not built (greedy and "
            "group_limited_greedy are)"
        )
    if d.get("moe_layer_freq", 1) != 1:
        raise ValueError(
            "deepseek_v2: moe_layer_freq other than 1 is not built (every "
            "layer after the leading dense ones routes)"
        )
    if not d.get("q_lora_rank"):
        raise ValueError(
            "deepseek_v2: queries without a latent (q_lora_rank null, the "
            "Lite models) are not built"
        )
    rs = d.get("rope_scaling")
    scaling = None
    if rs:
        if rs.get("type", rs.get("rope_type")) != "yarn":
            raise ValueError(
                f"deepseek_v2: rope_scaling {rs!r} is not built (yarn is)"
            )
        scaling = (
            float(rs["factor"]),
            float(rs["original_max_position_embeddings"]),
            float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
            float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0)),
        )
    hidden = d["hidden_size"]
    full = LatentAttn(
        n_heads=d["num_attention_heads"],
        q_rank=d["q_lora_rank"], kv_rank=d["kv_lora_rank"],
        nope_dim=d["qk_nope_head_dim"], rope_dim=d["qk_rope_head_dim"],
        v_dim=d["v_head_dim"], rope_theta=float(d.get("rope_theta", 1e4)),
        rope_scaling=scaling, gate=False,
    )
    grouped = method == "group_limited_greedy"
    return ModelConfig(
        family="deepseek_v2",
        vocab_size=d["vocab_size"],
        d_model=hidden,
        n_layers=d["num_hidden_layers"],
        n_heads=full.n_heads, n_kv_heads=1, head_dim=full.qk_dim,
        d_ff=d["intermediate_size"],
        max_seq_len=d.get("max_position_embeddings", 4096),
        norm_eps=d.get("rms_norm_eps", 1e-6),
        rope_theta=full.rope_theta,
        tie_embeddings=d.get("tie_word_embeddings", False),
        layer_kinds=("full",) * d["num_hidden_layers"],
        latent=(("full", full),),
        n_dense_layers=d.get("first_k_dense_replace", 0),
        n_experts_per_tok=d["num_experts_per_tok"],
        moe_d_ff=d["moe_intermediate_size"],
        n_shared_experts=d.get("n_shared_experts") or 0,
        moe_router="softmax_all",
        moe_norm_topk=bool(d.get("norm_topk_prob", False)),
        moe_scale=float(d.get("routed_scaling_factor", 1.0)),
        moe_n_group=int(d.get("n_group") or 0) if grouped else 0,
        moe_topk_group=int(d.get("topk_group") or 0) if grouped else 0,
        **_expert_share(d),
    )


@register_family("gemma")
def _gemma(d: dict) -> ModelConfig:
    # Gemma: llama layout + sqrt(d_model)-scaled embeddings, (1+w) rmsnorm,
    # tanh-approx gelu, always-tied embeddings, explicit head_dim
    return _llama_like(
        d,
        family="gemma",
        act="gelu",
        embed_scale=True,
        norm_plus_one=True,
        tie_embeddings=True,
    )


@register_family("phi3")
def _phi3(d: dict) -> ModelConfig:
    # Phi-3: llama compute with fused qkv_proj / gate_up_proj checkpoints
    if d.get("rope_scaling"):
        # longrope rescales rotary frequencies at every context length —
        # loading such a checkpoint with plain rope would generate fluent
        # garbage; refuse instead (128k-context Phi-3 variants)
        raise ValueError(
            "phi3 rope_scaling (longrope) is not supported; use a "
            "non-rope-scaled Phi-3 checkpoint"
        )
    return _llama_like(
        d, family="phi3", sliding_window=d.get("sliding_window")
    )


@register_family("olmo2")
def _olmo2(d: dict) -> ModelConfig:
    # OLMo-2: llama layout reordered — RMSNorm on sublayer OUTPUTS
    # (post_attention / post_feedforward), full-projection-dim q/k norms
    return _llama_like(
        d, family="olmo2", norm_position="post", qk_norm_full=True
    )


@register_family("gpt_neox")
def _gpt_neox(d: dict) -> ModelConfig:
    # GPT-NeoX / Pythia: layernorm with biases, parallel attn+mlp residual,
    # partial rotary (rotary_pct), fused-mlp with biases, exact gelu
    n_heads = d["num_attention_heads"]
    return ModelConfig(
        family="gpt_neox",
        vocab_size=d["vocab_size"],
        d_model=d["hidden_size"],
        n_layers=d["num_hidden_layers"],
        n_heads=n_heads,
        n_kv_heads=n_heads,
        head_dim=d["hidden_size"] // n_heads,
        d_ff=d["intermediate_size"],
        max_seq_len=d.get("max_position_embeddings", 2048),
        norm_eps=d.get("layer_norm_eps", 1e-5),
        act="gelu_exact" if d.get("hidden_act", "gelu") == "gelu" else "gelu",
        pos="rope",
        rope_theta=d.get("rotary_emb_base", 10000.0),
        rope_pct=d.get("rotary_pct", 0.25),
        attn_bias=d.get("attention_bias", True),
        attn_out_bias=d.get("attention_bias", True),
        mlp="fused",
        norm="layernorm",
        parallel_residual=d.get("use_parallel_residual", True),
        tie_embeddings=d.get("tie_word_embeddings", False),
    )


# ---------------------------------------------------------------------------
# Checkpoint tensor-name mapping (engine/loader.py)
# ---------------------------------------------------------------------------
# Our tree path -> HF tensor name template ({i} = layer). "~T" marks weights
# stored transposed in HF (torch Linear stores [out, in]; we use [in, out]).
# GPT-2's Conv1D already stores [in, out] (no ~T) and fuses qkv (split rule).


def hf_name_map(cfg: ModelConfig) -> dict[str, Any]:
    if cfg.family == "gpt2":
        return {
            "embed.tok": "wte.weight",
            "embed.pos": "wpe.weight",
            "layers.ln1.scale": "h.{i}.ln_1.weight",
            "layers.ln1.bias": "h.{i}.ln_1.bias",
            "layers.attn.wq": ("split3.0", "h.{i}.attn.c_attn.weight"),
            "layers.attn.wk": ("split3.1", "h.{i}.attn.c_attn.weight"),
            "layers.attn.wv": ("split3.2", "h.{i}.attn.c_attn.weight"),
            "layers.attn.bq": ("split3.0", "h.{i}.attn.c_attn.bias"),
            "layers.attn.bk": ("split3.1", "h.{i}.attn.c_attn.bias"),
            "layers.attn.bv": ("split3.2", "h.{i}.attn.c_attn.bias"),
            "layers.attn.wo": "h.{i}.attn.c_proj.weight",
            "layers.attn.bo": "h.{i}.attn.c_proj.bias",
            "layers.ln2.scale": "h.{i}.ln_2.weight",
            "layers.ln2.bias": "h.{i}.ln_2.bias",
            "layers.mlp.w_up": "h.{i}.mlp.c_fc.weight",
            "layers.mlp.b_up": "h.{i}.mlp.c_fc.bias",
            "layers.mlp.w_down": "h.{i}.mlp.c_proj.weight",
            "layers.mlp.b_down": "h.{i}.mlp.c_proj.bias",
            "final_norm.scale": "ln_f.weight",
            "final_norm.bias": "ln_f.bias",
        }

    if cfg.family == "phi3":
        # fused qkv_proj ([q+2kv, d]) and gate_up_proj ([2f, d]) checkpoints
        q, kv, f = cfg.q_dim, cfg.kv_dim, cfg.d_ff
        qkv = "layers.{i}.self_attn.qkv_proj.weight"
        gu = "layers.{i}.mlp.gate_up_proj.weight"
        m = {
            "embed.tok": "embed_tokens.weight",
            "layers.ln1.scale": "layers.{i}.input_layernorm.weight",
            "layers.attn.wq": (f"rowsT.0.{q}", qkv),
            "layers.attn.wk": (f"rowsT.{q}.{q + kv}", qkv),
            "layers.attn.wv": (f"rowsT.{q + kv}.{q + 2 * kv}", qkv),
            "layers.attn.wo": "~T layers.{i}.self_attn.o_proj.weight",
            "layers.ln2.scale": "layers.{i}.post_attention_layernorm.weight",
            "layers.mlp.w_gate": (f"rowsT.0.{f}", gu),
            "layers.mlp.w_up": (f"rowsT.{f}.{2 * f}", gu),
            "layers.mlp.w_down": "~T layers.{i}.mlp.down_proj.weight",
            "final_norm.scale": "norm.weight",
        }
        if not cfg.tie_embeddings:
            m["lm_head"] = "~T ^lm_head.weight"
        return m

    if cfg.family == "gpt_neox":
        # fused query_key_value with per-head-interleaved q/k/v rows
        qkv_w = "layers.{i}.attention.query_key_value.weight"
        qkv_b = "layers.{i}.attention.query_key_value.bias"
        m = {
            "embed.tok": "embed_in.weight",
            "layers.ln1.scale": "layers.{i}.input_layernorm.weight",
            "layers.ln1.bias": "layers.{i}.input_layernorm.bias",
            "layers.attn.wq": ("neox_qkv.0", qkv_w),
            "layers.attn.wk": ("neox_qkv.1", qkv_w),
            "layers.attn.wv": ("neox_qkv.2", qkv_w),
            "layers.attn.bq": ("neox_qkvb.0", qkv_b),
            "layers.attn.bk": ("neox_qkvb.1", qkv_b),
            "layers.attn.bv": ("neox_qkvb.2", qkv_b),
            "layers.attn.wo": "~T layers.{i}.attention.dense.weight",
            "layers.attn.bo": "layers.{i}.attention.dense.bias",
            "layers.ln2.scale": "layers.{i}.post_attention_layernorm.weight",
            "layers.ln2.bias": "layers.{i}.post_attention_layernorm.bias",
            "layers.mlp.w_up": "~T layers.{i}.mlp.dense_h_to_4h.weight",
            "layers.mlp.b_up": "layers.{i}.mlp.dense_h_to_4h.bias",
            "layers.mlp.w_down": "~T layers.{i}.mlp.dense_4h_to_h.weight",
            "layers.mlp.b_down": "layers.{i}.mlp.dense_4h_to_h.bias",
            "final_norm.scale": "final_layer_norm.weight",
            "final_norm.bias": "final_layer_norm.bias",
        }
        if not cfg.tie_embeddings:
            m["lm_head"] = "~T ^embed_out.weight"
        return m

    m = {
        "embed.tok": "embed_tokens.weight",
        "layers.ln1.scale": "layers.{i}.input_layernorm.weight",
        "layers.attn.wq": "~T layers.{i}.self_attn.q_proj.weight",
        "layers.attn.wk": "~T layers.{i}.self_attn.k_proj.weight",
        "layers.attn.wv": "~T layers.{i}.self_attn.v_proj.weight",
        "layers.attn.wo": "~T layers.{i}.self_attn.o_proj.weight",
        "layers.ln2.scale": "layers.{i}.post_attention_layernorm.weight",
        "final_norm.scale": "norm.weight",
    }
    if cfg.attn_bias:
        m |= {
            "layers.attn.bq": "layers.{i}.self_attn.q_proj.bias",
            "layers.attn.bk": "layers.{i}.self_attn.k_proj.bias",
            "layers.attn.bv": "layers.{i}.self_attn.v_proj.bias",
        }
    if cfg.qk_norm or cfg.qk_norm_full:
        m |= {
            "layers.attn.q_norm": "layers.{i}.self_attn.q_norm.weight",
            "layers.attn.k_norm": "layers.{i}.self_attn.k_norm.weight",
        }
    if cfg.family == "olmo2":
        # post-norm reordering: our ln1 holds post_attention_layernorm, ln2
        # holds post_feedforward_layernorm (no input norms exist)
        m["layers.ln1.scale"] = "layers.{i}.post_attention_layernorm.weight"
        m["layers.ln2.scale"] = "layers.{i}.post_feedforward_layernorm.weight"
    if cfg.moe:
        m |= {
            "layers.mlp.router": "~T layers.{i}.block_sparse_moe.gate.weight",
            "layers.mlp.w_gate": (
                "stackE",
                "~T layers.{i}.block_sparse_moe.experts.{e}.w1.weight",
            ),
            "layers.mlp.w_down": (
                "stackE",
                "~T layers.{i}.block_sparse_moe.experts.{e}.w2.weight",
            ),
            "layers.mlp.w_up": (
                "stackE",
                "~T layers.{i}.block_sparse_moe.experts.{e}.w3.weight",
            ),
        }
    else:
        m |= {
            "layers.mlp.w_gate": "~T layers.{i}.mlp.gate_proj.weight",
            "layers.mlp.w_up": "~T layers.{i}.mlp.up_proj.weight",
            "layers.mlp.w_down": "~T layers.{i}.mlp.down_proj.weight",
        }
        if cfg.mlp_bias:
            m |= {
                "layers.mlp.b_gate": "layers.{i}.mlp.gate_proj.bias",
                "layers.mlp.b_up": "layers.{i}.mlp.up_proj.bias",
                "layers.mlp.b_down": "layers.{i}.mlp.down_proj.bias",
            }
    if not cfg.tie_embeddings:
        m["lm_head"] = "~T ^lm_head.weight"  # ^ = top-level, outside prefix
    return m


# Prefix inside the checkpoint for the backbone tensors, e.g. HF llama stores
# "model.layers.0...." and "lm_head.weight" at top level.
def hf_prefix(cfg: ModelConfig) -> str:
    if cfg.family == "gpt2":
        return "transformer."
    if cfg.family == "gpt_neox":
        return "gpt_neox."
    return "model."


def config_presets() -> dict[str, ModelConfig]:
    """Named presets for tests/benchmarks (no network access needed)."""
    return {
        "gpt2-small": ModelConfig(
            family="gpt2",
            vocab_size=50257,
            d_model=768,
            n_layers=12,
            n_heads=12,
            n_kv_heads=12,
            head_dim=64,
            d_ff=3072,
            max_seq_len=1024,
            norm_eps=1e-5,
            act="gelu",
            pos="learned",
            attn_bias=True,
            mlp="fused",
            norm="layernorm",
            tie_embeddings=True,
        ),
        "qwen3-8b": ModelConfig(
            family="qwen3",
            vocab_size=151936,
            d_model=4096,
            n_layers=36,
            n_heads=32,
            n_kv_heads=8,
            head_dim=128,
            d_ff=12288,
            max_seq_len=40960,
            norm_eps=1e-6,
            rope_theta=1e6,
            qk_norm=True,
            tie_embeddings=False,
        ),
        "qwen3-4b": ModelConfig(
            family="qwen3",
            vocab_size=151936,
            d_model=2560,
            n_layers=36,
            n_heads=32,
            n_kv_heads=8,
            head_dim=128,
            d_ff=9728,
            max_seq_len=40960,
            norm_eps=1e-6,
            rope_theta=1e6,
            qk_norm=True,
            tie_embeddings=True,
        ),
        "qwen3-0p6b": ModelConfig(
            family="qwen3",
            vocab_size=151936,
            d_model=1024,
            n_layers=28,
            n_heads=16,
            n_kv_heads=8,
            head_dim=128,
            d_ff=3072,
            max_seq_len=40960,
            norm_eps=1e-6,
            rope_theta=1e6,
            qk_norm=True,
            tie_embeddings=True,
        ),
        "qwen3-1p7b": ModelConfig(
            family="qwen3",
            vocab_size=151936,
            d_model=2048,
            n_layers=28,
            n_heads=16,
            n_kv_heads=8,
            head_dim=128,
            d_ff=6144,
            max_seq_len=40960,
            norm_eps=1e-6,
            rope_theta=1e6,
            qk_norm=True,
            tie_embeddings=True,
        ),
        "qwen2p5-7b": ModelConfig(
            family="qwen2",
            vocab_size=152064,
            d_model=3584,
            n_layers=28,
            n_heads=28,
            n_kv_heads=4,
            head_dim=128,
            d_ff=18944,
            max_seq_len=32768,
            norm_eps=1e-6,
            rope_theta=1e6,
            attn_bias=True,
        ),
        "llama3-70b": ModelConfig(
            family="llama",
            vocab_size=128256,
            d_model=8192,
            n_layers=80,
            n_heads=64,
            n_kv_heads=8,
            head_dim=128,
            d_ff=28672,
            max_seq_len=8192,
            norm_eps=1e-5,
            rope_theta=5e5,
        ),
        "gemma-7b": ModelConfig(
            family="gemma",
            vocab_size=256000,
            d_model=3072,
            n_layers=28,
            n_heads=16,
            n_kv_heads=16,
            head_dim=256,
            d_ff=24576,
            max_seq_len=8192,
            act="gelu",
            embed_scale=True,
            norm_plus_one=True,
            tie_embeddings=True,
        ),
        "phi3-mini": ModelConfig(
            family="phi3",
            vocab_size=32064,
            d_model=3072,
            n_layers=32,
            n_heads=32,
            n_kv_heads=32,
            head_dim=96,
            d_ff=8192,
            max_seq_len=4096,
            norm_eps=1e-5,
        ),
        "pythia-1b": ModelConfig(
            family="gpt_neox",
            vocab_size=50304,
            d_model=2048,
            n_layers=16,
            n_heads=8,
            n_kv_heads=8,
            head_dim=256,
            d_ff=8192,
            max_seq_len=2048,
            norm_eps=1e-5,
            act="gelu_exact",
            rope_pct=0.25,
            attn_bias=True,
            attn_out_bias=True,
            mlp="fused",
            norm="layernorm",
            parallel_residual=True,
        ),
        "olmo2-7b": ModelConfig(
            family="olmo2",
            vocab_size=100352,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=32,
            head_dim=128,
            d_ff=11008,
            max_seq_len=4096,
            norm_eps=1e-6,
            rope_theta=5e5,
            norm_position="post",
            qk_norm_full=True,
        ),
        "mixtral-8x7b": ModelConfig(
            family="mixtral",
            vocab_size=32000,
            d_model=4096,
            n_layers=32,
            n_heads=32,
            n_kv_heads=8,
            head_dim=128,
            d_ff=14336,
            max_seq_len=32768,
            norm_eps=1e-5,
            rope_theta=1e6,
            n_experts=8,
            n_experts_per_tok=2,
        ),
    }
