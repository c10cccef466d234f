"""Operations the full grouped-query layers' walk (``gqa_full_attention``,
both passes) must do in a model whose attention layers all have
``num_attention_heads`` query heads of ``head_dim`` (no per-layer list;
Olmo-Hybrid: 30 of 128, ONE query head a kv head): per query row and cached
position ``2 x query heads x (head_dim + head_dim)`` (a head's score
against the key, then the value under the softmax weight; 15,360 at 30
heads of 128), an attention layer. With no group to share a key among, a
continuation step does 1 operation a byte of keys and values and is
bandwidth bound by a factor of 240; a prefill block of 128 rows is not.

Rows, pass by pass, as ``gqa_full_flops`` counts them (its ``by_pass``,
handed this model's one head count as a per-layer list): a lower bound, so
that a share over 100% cannot come from here.
"""

from __future__ import annotations

from benchmarks.bytes_fns import gqa_full_flops


def flops_per_row_position(model: dict) -> float:
    return 2.0 * int(model["num_attention_heads"]) * 2 * int(model["head_dim"])


def by_pass(chunks: list[dict], model: dict) -> list[float]:
    # the accepted function's rows, at one head count for every layer
    heads = [int(model["num_attention_heads"])] * int(
        model["num_hidden_layers"])
    return gqa_full_flops.by_pass(
        chunks, {**model, "num_attention_heads_per_layer": heads})


def gqa_full_flops_g1(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
