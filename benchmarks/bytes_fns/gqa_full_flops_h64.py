"""Operations the full grouped-query layers' walk (``gqa_full_attention``,
both passes) must do in a model whose attention layers all have
``num_attention_heads`` query heads (no per-layer list): per query row and
cached position ``2 x query heads x (head_dim + head_dim)`` (a head's score
against the key, then the value under the softmax weight; 8,192 at 32 heads
of 64), an attention layer. What the walk does beyond that is not counted:
a head of 64 lies beside its values in one row of 128 lanes, and the walk
multiplies the whole row on both sides (twice these operations on the MXU),
which is why a prefill block reads a low share.

Rows, pass by pass, as ``gqa_full_flops`` counts them (its ``by_pass``,
handed this model's one head count as a per-layer list): a continuation step has one query row a
participating slot; the ragged pass one a slot that holds context, plus the
prefill rows the engine granted in that chunk beyond one a slot, each at
the slots' mean context less half a block: a lower bound, so that a share
over 100% cannot come from here.
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


def gqa_full_flops_h64(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
