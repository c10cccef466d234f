"""Model configuration and KV cache structures.

The reference treats a model as an opaque ``nn.Module`` tree to be split by
memory (ml/graphing.py:202); here a model is data: a :class:`ModelConfig`
plus a parameter pytree. The KV cache is an explicit, donated pytree —
the TPU-native replacement for HF ``DynamicCache`` objects the reference
serializes over the wire (ml/utils.py:569-660).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core import serialization


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 mscale ln(factor) + 1``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


@dataclass(frozen=True)
class LatentAttn:
    """Sizes of one kind of latent (low-rank) attention layer: queries
    and keys/values are projected down to ``q_rank`` / ``kv_rank``,
    normalised, and up again per head; a head's key is ``nope_dim``
    values from the latent and ``rope_dim`` rotated values shared by all
    heads. What a position caches is the latent and the rotated key
    (``row_dim`` values), whatever the head count.

    ``rope_scaling``: None, or YaRN's ``(factor, original length,
    beta_fast, beta_slow, mscale, mscale_all_dim)``: the rotary
    frequencies are interpolated by dimension
    (models/transformer.py::yarn_inv_freq) and the softmax scale carries
    ``yarn_mscale(factor, mscale_all_dim) ** 2`` (:attr:`softmax_scale`).
    ``gate``: a sigmoid gate a head on the attention output.

    ``window``: keys ``t - window < s <= t`` (the token itself counts);
    None = causal. ``index_heads > 0``: a learned selector scores every
    earlier position with ``index_heads`` small heads of ``index_dim``
    (rope on the first ``index_rope_dim``) and attention reads the
    ``index_topk`` best; it caches one ``index_dim`` key a position."""

    n_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    window: int | None = None
    q_scale: float = 1.0  # on the normalised query latent
    kv_scale: float = 1.0  # on the normalised key/value latent
    index_heads: int = 0
    index_dim: int = 0
    index_rope_dim: int = 0
    index_topk: int = 0
    rope_scaling: tuple | None = None
    gate: bool = True

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def temperature(self) -> float:
        """The square of YaRN's ``mscale`` where the positions are scaled
        (what the softmax scale carries beside ``qk_dim ** -0.5``), else
        1."""
        if self.rope_scaling is None:
            return 1.0
        factor, _, _, _, _, all_dim = self.rope_scaling
        return yarn_mscale(factor, all_dim) ** 2

    @property
    def softmax_scale(self) -> float:
        """What multiplies a head's scores."""
        return self.qk_dim**-0.5 * self.temperature

    @property
    def row_dim(self) -> int:
        """Values cached a position: the latent and the rotated key."""
        return self.kv_rank + self.rope_dim

    @property
    def pool_dim(self) -> int:
        """``row_dim`` in whole 128-lane rows, as the page pool stores it
        (a kernel's page copy cannot slice a narrower minor dim)."""
        return -(-self.row_dim // 128) * 128

    def param_count(self, d: int) -> int:
        n = (
            d * self.q_rank + self.q_rank
            + self.q_rank * self.n_heads * self.qk_dim
            + d * self.row_dim + self.kv_rank
            + self.kv_rank * self.n_heads * (self.nope_dim + self.v_dim)
            + (d * self.n_heads if self.gate else 0)  # the headwise gate
            + self.n_heads * self.v_dim * d
        )
        if self.index_heads:
            n += (
                self.q_rank * self.index_heads * self.index_dim
                + d * self.index_dim + 2 * self.index_dim
                + d * self.index_heads
            )
        return n


@dataclass(frozen=True)
class SparseAttn:
    """Sizes of a block-sparse GQA layer (layer kind ``"sparse"``: keys and
    values of ``n_kv_heads`` heads in pages, per-head RMSNorm on ``q`` and
    ``k``, no rotary positions, a sigmoid gate a channel on the output).
    A query at position ``t >= dense_len`` attends ``topk`` blocks of
    ``block`` positions a kv group, chosen by its own heads' scores against
    pooled keys (the mean of ``pool`` keys every ``stride`` positions), the
    first ``init_blocks`` blocks and the blocks of the last ``window``
    positions always among them; below ``dense_len`` every ``s <= t``."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    pool: int = 32  # positions under one pooled key
    stride: int = 16  # ... and between two pooled keys' first positions
    block: int = 64
    init_blocks: int = 1
    window: int = 2048
    topk: int = 64
    dense_len: int = 8192
    rope_dim: int = 0  # no positional rotation (rope_by_kind skips it)

    @property
    def softmax_scale(self) -> float:
        return self.head_dim**-0.5

    @property
    def max_kept(self) -> int:
        """Blocks a query can attend: the selection's, every block under
        ``dense_len``, or the forced ones where they alone are more."""
        forced = self.init_blocks + (self.window - 1) // self.block + 2
        return max(self.topk, -(-self.dense_len // self.block), forced)

    def param_count(self, d: int) -> int:
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        return d * q + 2 * d * kv + 2 * self.head_dim + d * q + q * d


@dataclass(frozen=True)
class LinearAttn:
    """Sizes of a lightning (linear-attention) layer (kind
    ``"lightning"``): ``n_heads`` heads of ``head_dim``, per-head RMSNorm
    on ``q`` and ``k``, rotate-half rotary positions on all of both, a
    float32 state ``[n_heads, head_dim, head_dim]`` a slot that decays by
    ``exp(-slope_a)`` a position, an RMSNorm over the concatenated heads
    and a sigmoid gate a channel on the output. ``slope_a = 2 ** (-8 (a +
    1) / n_heads)`` (Lightning Attention's head slopes)."""

    n_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rope_scaling: tuple | None = None

    @property
    def rope_dim(self) -> int:
        return self.head_dim

    @property
    def state_bytes(self) -> int:
        """One slot's state of one layer, float32."""
        return self.n_heads * self.head_dim * self.head_dim * 4

    def slopes(self) -> tuple:
        return tuple(
            2.0 ** (-8.0 * (a + 1) / self.n_heads)
            for a in range(self.n_heads)
        )

    def param_count(self, d: int) -> int:
        q = self.n_heads * self.head_dim
        return 3 * d * q + 2 * self.head_dim + d * q + q + q * d


@dataclass(frozen=True)
class GqaAttn:
    """Sizes of one kind of grouped-query layer of a model whose layers
    differ (kinds ``"gqa_full"`` / ``"gqa_window"``): ``n_heads`` query
    heads over ``n_kv_heads`` heads of keys and values in pages, a head
    count, a window and rotary positions of the kind's own. The first
    ``rope_dim`` dims of each head rotate (rotate-half on the stored
    order) with ``rope_theta``; ``rope_scaling``: None, or YaRN's
    ``(factor, original length, beta_fast, beta_slow, mscale,
    mscale_all_dim)``, whose amplitude ``yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)`` multiplies cos and sin (a
    published ``attention_factor`` a: ``mscale = (a - 1) / (0.1 ln
    factor)``, ``mscale_all_dim = 0``). ``window``: keys ``t - window < s
    <= t`` (the token itself counts), and the slot engine then holds the
    layer's keys and values as a ring a slot (engine/latent.py); None =
    causal. ``gate``: a sigmoid gate a query head on the attention
    output."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_dim: int
    rope_theta: float
    window: int | None = None
    rope_scaling: tuple | None = None
    gate: bool = True
    # an RMSNorm with a learned weight over the ``head_dim`` dims of every
    # query and key head, before the rotation
    qk_norm: bool = False
    # ... or over the WHOLE query and key projections (the OLMo family's);
    # ``rope_dim`` 0: no rotation (order comes from other layers)
    qk_norm_full: bool = False

    @property
    def softmax_scale(self) -> float:
        return self.head_dim**-0.5

    @property
    def row_dim(self) -> int:
        """Values a position caches a layer: keys and values."""
        return 2 * self.n_kv_heads * self.head_dim

    def param_count(self, d: int) -> int:
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        return (d * q + 2 * d * kv + (d * self.n_heads if self.gate else 0)
                + (2 * self.head_dim if self.qk_norm else 0)
                + (q + kv if self.qk_norm_full else 0) + q * d)


@dataclass(frozen=True)
class ShortConv:
    """Sizes of a gated short-convolution layer (kind ``"conv"``): ``[B, C,
    g] = split3(W_in u)``, ``z = B * g``, a depthwise causal convolution of
    ``kernel`` taps over ``z`` (zeros before position 0), ``y = C * conv``,
    ``W_out y``; every stream ``width`` wide. What a slot holds of such a
    layer is the last ``kernel - 1`` positions of ``z``, its *tail*
    (engine/latent.py): no page describes it."""

    kernel: int
    width: int

    @property
    def rope_dim(self) -> int:
        """No positions: the convolution is causal by order."""
        return 0

    @property
    def tail(self) -> int:
        """Positions of ``z`` a slot carries from one pass to the next."""
        return self.kernel - 1

    def param_count(self, d: int) -> int:
        return d * 3 * self.width + self.width * self.kernel + self.width * d


@dataclass(frozen=True)
class GatedDelta:
    """Sizes of a gated delta-rule layer (kind ``"gated_delta"``; Gated
    DeltaNet, arXiv:2412.06464): ``n_heads`` heads of keys ``key_dim`` and
    values ``value_dim`` wide, a depthwise causal convolution of ``kernel``
    taps and SiLU in front of q, k and v, a decay and a step size a head
    that are data, a float32 state ``[key_dim, n_heads value_dim]`` a slot
    (ops/gated_delta.py has the recurrence and the layout), an RMSNorm a
    head and a SiLU gate on the output. ``neg_eigval``: the step size is
    ``2 sigmoid`` (a transition's eigenvalue may go negative), else
    ``sigmoid``. What a slot holds of such a layer is the state AND the
    last ``kernel - 1`` positions of the convolution's input, its *tail*
    (engine/latent.py): no page describes either."""

    n_heads: int
    key_dim: int
    value_dim: int
    kernel: int
    neg_eigval: bool = True

    @property
    def rope_dim(self) -> int:
        """No positions: the recurrence and the convolution are causal by
        order."""
        return 0

    @property
    def tail(self) -> int:
        return self.kernel - 1

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: q, k and v."""
        return self.n_heads * (2 * self.key_dim + self.value_dim)

    @property
    def state_bytes(self) -> int:
        """One slot's state of one layer, float32."""
        return self.n_heads * self.key_dim * self.value_dim * 4

    def param_count(self, d: int) -> int:
        H, v = self.n_heads, self.n_heads * self.value_dim
        # q, k, v and the output gate; the decay's and the step size's
        # projections; the taps; A_log, dt_bias; the output norm; w_o
        return (d * (self.conv_width + v) + 2 * d * H
                + self.conv_width * self.kernel + 2 * H + self.value_dim
                + v * d)


# layer kinds whose cache is not a latent row: engine/sala.py serves them
SALA_KINDS = ("sparse", "lightning")
# ... and the grouped-query kinds of a model whose layers differ
# (engine/latent.py): pages under the slot's table, a ring a slot
GQA_KINDS = ("gqa_full", "gqa_window")
# ... beside which gated short-convolution layers may stand
# (engine/latent.py): a tail a slot and layer
CONV_KIND = "conv"
# ... or gated delta-rule layers: a state AND a tail a slot and layer
GATED_DELTA = "gated_delta"


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters for the unified decoder-only core.

    Families covered (reference supports any HF causal LM via module
    offloading; we cover the families its tests/docs/baseline actually use —
    gpt2, Llama, Qwen2/2.5, Qwen3, Mistral, Mixtral, SmolLM, Gemma, Phi-3,
    GPT-NeoX/Pythia — via config):

    - ``pos="learned"``, ``mlp="fused"``, ``norm="layernorm"`` → GPT-2.
    - ``pos="rope"``, ``mlp="gated"``, ``norm="rmsnorm"`` → Llama-family.
    - ``qk_norm=True`` → Qwen3.
    - ``n_experts>0`` → Mixtral-style sparse MoE.
    - ``embed_scale`` + ``norm_plus_one`` → Gemma.
    - ``parallel_residual`` + ``rope_pct<1`` + layernorm → GPT-NeoX/Pythia.
    - ``norm_position="post"`` + ``qk_norm_full`` → OLMo-2.
    """

    family: str = "llama"
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    head_dim: int = 128
    d_ff: int = 11008
    max_seq_len: int = 4096
    norm_eps: float = 1e-6
    act: str = "silu"  # "silu" | "gelu" (tanh approx) | "gelu_exact" (erf)
    pos: str = "rope"  # "rope" | "learned"
    rope_theta: float = 10000.0
    # rotary applied to the first rope_pct of each head's dims (GPT-NeoX /
    # Pythia rotary_pct; 1.0 = full-dim rotary)
    rope_pct: float = 1.0
    attn_bias: bool = False  # GPT-2 / Qwen2 have qkv biases
    attn_out_bias: bool = False  # GPT-2 / GPT-NeoX bias on the o projection
    mlp_bias: bool = False
    mlp: str = "gated"  # "gated" (gate*up) | "fused" (up->act->down)
    norm: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    norm_plus_one: bool = False  # Gemma rmsnorm: x * rms * (1 + scale)
    qk_norm: bool = False  # Qwen3 per-head-dim RMSNorm on q and k
    # OLMo-2: RMSNorm over the FULL q/k projection dim (not per-head),
    # applied before the head reshape
    qk_norm_full: bool = False
    # "pre" (llama-style input norms) | "post" (OLMo-2: norm applied to the
    # sublayer OUTPUT before the residual add; no input norm)
    norm_position: str = "pre"
    embed_scale: bool = False  # Gemma: embeddings scaled by sqrt(d_model)
    parallel_residual: bool = False  # GPT-NeoX: x + attn(ln1 x) + mlp(ln2 x)
    tie_embeddings: bool = False
    attn_scale: float | None = None  # None → 1/sqrt(head_dim)
    # MoE (Mixtral): 0 experts = dense
    n_experts: int = 0
    n_experts_per_tok: int = 2
    # "dense" runs every token through every expert (exact, small scale);
    # "sparse" is the capacity-factor top-k dispatch (parallel/expert.py) —
    # the worker flips this on when its stage mesh carries an expert axis
    moe_dispatch: str = "dense"
    moe_capacity_factor: float = 2.0
    # sparse dispatch groups tokens so the one-hot scatter einsums scale
    # linearly with sequence length (GShard token grouping)
    moe_group_size: int = 1024
    # sliding-window attention (Mistral); None = full causal
    sliding_window: int | None = None
    dtype: Any = jnp.bfloat16
    # Logit soft-capping (Gemma-style); None = off
    logit_cap: float | None = None
    # Pallas flash-attention for the serving engine's fresh-cache prefill
    # (ops/attention.py): blockwise online softmax, no [T, T] score tensor
    # in HBM. Opt-in; decode and training keep the einsum path.
    flash_attention: bool = False
    # EQuARX-style quantized collectives (parallel/ring.py): sequence-
    # parallel ring attention rotates int8 K/V chunks + per-(position,
    # head) scales over ICI instead of full-precision blocks — half the
    # hop bytes at a bounded, test-pinned divergence. Opt-in
    # (MLConfig.collective_quant applies it at stage load).
    collective_quant: bool = False
    # -- layers of more than one kind (models/latent.py) ------------------
    # ``layer_kinds`` names each layer's attention kind ("full" /
    # "sliding"), ``latent`` the sizes of each kind; () = every layer the
    # one GQA kind above. The first ``n_dense_layers`` keep the dense MLP
    # (``d_ff``); the others route over ``n_experts`` experts of
    # ``moe_d_ff`` beside ``n_shared_experts`` always-on ones.
    layer_kinds: tuple = ()
    latent: tuple = ()  # ((kind, LatentAttn), ...)
    n_dense_layers: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    # "softmax": top-k of the logits, softmax over the k (Mixtral).
    # "sigmoid": sigmoid scores, top-k of score + selection bias, the k
    # scores normalised to sum 1 (``moe_norm_topk``), times ``moe_scale``.
    # "softmax_all": softmax over every published expert, top-k of the
    # scores (no bias), the k scores as weights (normalised only with
    # ``moe_norm_topk``), times ``moe_scale``
    moe_router: str = "softmax"
    moe_norm_topk: bool = True
    # what ``moe_norm_topk`` adds to the sum it divides by
    moe_norm_eps: float = 1e-20
    moe_scale: float = 1.0
    # group-limited routing: the published experts lie in ``moe_n_group``
    # groups of consecutive experts, a group scores as its best expert,
    # and a token picks its experts inside its ``moe_topk_group`` best
    # groups only (0 = no limit)
    moe_n_group: int = 0
    moe_topk_group: int = 0
    # a chip's share of an expert group: the router scores all
    # ``n_experts``, this program holds and computes experts
    # ``experts_first .. experts_first + experts_held - 1`` (0 = all)
    experts_first: int = 0
    experts_held: int = 0
    # MiniCPM's scaling keys (1.0 = none): on the embeddings
    # (``scale_emb``), on what each sublayer adds to the residual stream
    # (``scale_depth / sqrt(published depth)``), and the divisor of the
    # final norm's output before the head (``hidden / dim_model_base``)
    embed_mult: float = 1.0
    residual_mult: float = 1.0
    logit_div: float = 1.0

    @property
    def patterned(self) -> bool:
        return bool(self.layer_kinds)

    @property
    def slot_state(self) -> str | None:
        """What a slot of the engine holds of this model that no page
        chain describes, by the kind of layer that carries it: the
        ``"lightning"`` layers' float32 states, the ``"gqa_window"``
        layers' rings of keys and values, the ``"conv"`` layers' tails,
        the ``"gated_delta"`` layers' states and tails; None: pages alone
        (:attr:`slot_arrays` names the arrays). THE answer to "does a slot hold a state": what
        reuses or moves a slot of such a model restores a snapshot and
        replays, or refuses (engine/continuous.py, parallel/planner.py)."""
        return next((k for k in ("lightning", "gqa_window", CONV_KIND,
                                 GATED_DELTA)
                     if k in self.layer_kinds), None)

    @property
    def slot_arrays(self) -> tuple:
        """The arrays of the engine's cache a slot holds whole beside its
        pages, by field name (engine/latent.py::LatentPagedCache): what a
        snapshot takes and restores TOGETHER (engine/sala.py). A ring is
        not one of them (its snapshot is a window of pages)."""
        return {"lightning": ("state",), CONV_KIND: ("state",),
                GATED_DELTA: ("state", "tail")}.get(self.slot_state, ())

    @property
    def recurrent(self) -> bool:
        """A slot holds arrays (states, tails) that are snapshotted whole."""
        return bool(self.slot_arrays)

    @property
    def ring_window(self) -> int | None:
        """The window of the layers whose keys and values the slot engine
        holds as a ring a slot (kind ``"gqa_window"``); None: no such
        layer. What reuses or moves a slot of such a model restores a
        snapshot of the window, as for a recurrent state."""
        if self.slot_state != "gqa_window":
            return None
        return self.latent_of("gqa_window").window

    def latent_of(self, kind: str) -> LatentAttn:
        return dict(self.latent)[kind]

    @property
    def n_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def moe(self) -> bool:
        return self.n_experts > 0

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    def to_json(self) -> dict:
        """JSON-safe dict (job specs carry the config over the wire — the
        reference ships whole serialized modules instead, torch_node.py:879)."""
        from dataclasses import asdict

        d = asdict(self)
        d["dtype"] = jnp.dtype(self.dtype).name
        return d

    @classmethod
    def from_json(cls, d: dict) -> "ModelConfig":
        d = dict(d)
        if isinstance(d.get("dtype"), str):
            d["dtype"] = jnp.dtype(d["dtype"]).type
        # JSON has no tuples and no dataclasses: a config is a static
        # (hashed) argument of the compiled programs
        if "layer_kinds" in d:
            d["layer_kinds"] = tuple(d["layer_kinds"])
        if "latent" in d:
            d["latent"] = tuple(
                (k, _latent_attn(v) if isinstance(v, dict) else v)
                for k, v in d["latent"]
            )
        return cls(**d)

    def param_count(self) -> int:
        """Analytic parameter count (used by the sharding planner's memory
        estimator — TPU analogue of reference ml/utils.py:36-124)."""
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        if self.patterned:
            # ``total`` counts every published expert; what one chip of an
            # expert group holds is ``held_param_count``
            return self._patterned_count(self.n_experts)
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.moe:
            mlp = self.n_experts * 3 * d * f + d * self.n_experts
        elif self.mlp == "gated":
            mlp = 3 * d * f
        else:
            mlp = 2 * d * f
        norms = 2 * d * (2 if self.norm == "layernorm" else 1)
        emb = v * d + (0 if self.tie_embeddings else v * d)
        pos = self.max_seq_len * d if self.pos == "learned" else 0
        return L * (attn + mlp + norms) + emb + pos + d

    def _patterned_count(self, n_experts: int) -> int:
        d, v = self.d_model, self.vocab_size
        expert = 3 * d * self.moe_d_ff
        bias = self.n_experts if self.moe_router == "sigmoid" else 0
        moe = (
            d * self.n_experts + bias  # router + selection bias
            + (n_experts + self.n_shared_experts) * expert
        )
        # embedding, head (the embedding again where tied), final norm
        n = (1 if self.tie_embeddings else 2) * v * d + d
        for i, kind in enumerate(self.layer_kinds):
            n += self.latent_of(kind).param_count(d) + 2 * d
            # no experts at all: every layer keeps the dense MLP
            dense = i < self.n_dense_layers or not self.n_experts
            n += 3 * d * self.d_ff if dense else moe
        return n

    def held_param_count(self) -> int:
        """Parameters this program holds: ``param_count`` with the
        experts held in place of the experts published."""
        if not self.patterned:
            return self.param_count()
        return self._patterned_count(self.n_held)


def _latent_attn(d: dict):
    """A layer kind's sizes from their JSON form: the class whose fields
    the keys are. JSON has no tuples: the sizes are hashed with the
    config."""
    d = dict(d)
    if d.get("rope_scaling") is not None:
        d["rope_scaling"] = tuple(d["rope_scaling"])
    if "dense_len" in d:
        return SparseAttn(**d)
    if "q_rank" in d:
        return LatentAttn(**d)
    if "key_dim" in d:
        return GatedDelta(**d)
    if "kernel" in d:
        return ShortConv(**d)
    return (GqaAttn if "n_kv_heads" in d else LinearAttn)(**d)


@jax.tree_util.register_dataclass
@dataclass
class KVCache:
    """Per-model decode cache: ``k``/``v`` are ``[L, B, S_max, n_kv, hd]``,
    ``length`` is the number of valid positions per batch row ``[B]``.

    Stored stacked over layers so the decode ``lax.scan`` indexes its layer
    slice, and donated into the decode step so XLA updates it in place.

    **int8 mode** (``quantized=True``): ``k``/``v`` hold int8 with
    per-(layer, row, position, head) scales in ``k_scale``/``v_scale``
    ``[L, B, S, n_kv, 1]`` — halves the per-token cache stream that grows
    with context (the parameter stream is fixed; at 32k context the KV
    read rivals it) and doubles the servable context per HBM byte.
    Attention dequantizes on read; writes quantize each step's keys
    (models/transformer.py::_block).
    """

    k: jax.Array
    v: jax.Array
    length: jax.Array  # int32 [B]
    k_scale: jax.Array | None = None  # f32, present in int8 mode
    v_scale: jax.Array | None = None

    @classmethod
    def init(
        cls,
        cfg: ModelConfig,
        batch: int,
        max_len: int | None = None,
        dtype=None,
        quantized: bool = False,
    ):
        S = max_len or cfg.max_seq_len
        shape = (cfg.n_layers, batch, S, cfg.n_kv_heads, cfg.head_dim)
        if quantized:
            sshape = shape[:-1] + (1,)
            return cls(
                k=jnp.zeros(shape, jnp.int8),
                v=jnp.zeros(shape, jnp.int8),
                length=jnp.zeros((batch,), jnp.int32),
                k_scale=jnp.zeros(sshape, jnp.float32),
                v_scale=jnp.zeros(sshape, jnp.float32),
            )
        dt = dtype or cfg.dtype
        return cls(
            k=jnp.zeros(shape, dt),
            v=jnp.zeros(shape, dt),
            length=jnp.zeros((batch,), jnp.int32),
        )

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


# Wire format support: KV caches cross the P2P boundary when a job migrates
# between workers (reference ships DynamicCache, ml/utils.py:587-603).
serialization.register_struct(
    "tensorlink.KVCache",
    KVCache,
    lambda c: {
        "k": c.k, "v": c.v, "length": c.length,
        **({"k_scale": c.k_scale, "v_scale": c.v_scale} if c.quantized else {}),
    },
    lambda t: KVCache(
        k=jnp.asarray(np.asarray(t["k"])),
        v=jnp.asarray(np.asarray(t["v"])),
        length=jnp.asarray(np.asarray(t["length"])),
        k_scale=(
            jnp.asarray(np.asarray(t["k_scale"])) if "k_scale" in t else None
        ),
        v_scale=(
            jnp.asarray(np.asarray(t["v_scale"])) if "v_scale" in t else None
        ),
    ),
)
