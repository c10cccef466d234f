"""Operations the full grouped-query layers' walk (``gqa_full_attention``,
both passes) must do: per query row and cached position ``2 x query heads x
(head_dim + head_dim)`` (a head's score against the key, then the value
under the softmax weight; 24,576 at 48 heads of 128), a full layer. The
projections around the walk are plain matmuls outside the kernel and are
not counted; nor is anything computed twice or for a row without a token.

Rows, pass by pass (``latent_full_bytes.passes``): a continuation step has
one query row a participating slot; the ragged pass one a slot that holds
context, plus the prefill rows the engine granted in that chunk beyond one
a slot (``prefill_granted``, which ``readers/trace_roofline_max.py`` copies
from the engine's own record of the chunk; absent = none), each at the
slots' mean context less half a block: a lower bound, so that a share over
100% cannot come from here.
"""

from __future__ import annotations

from benchmarks.bytes_fns.gqa_full_bytes import layers_of
from benchmarks.bytes_fns.latent_full_bytes import passes

HALF_BLOCK = 64  # a prefill row sits up to a block under its slot's context


def full_heads(model: dict) -> int:
    """Query heads of a full layer."""
    kinds = model["layer_types"][: int(model["num_hidden_layers"])]
    return int(model["num_attention_heads_per_layer"][
        kinds.index("full_attention")])


def flops_per_row_position(model: dict) -> float:
    return 2.0 * full_heads(model) * 2 * int(model["head_dim"])


def by_pass(chunks: list[dict], model: dict) -> list[float]:
    per = flops_per_row_position(model) * layers_of(model, "full_attention")
    out = []
    for c in chunks:
        ragged, *steps = passes(c)
        rows = sum(ragged)
        if ragged:
            extra = max(int(c.get("prefill_granted", 0)) - len(ragged), 0)
            rows += extra * max(sum(ragged) / len(ragged) - HALF_BLOCK, 0.0)
        out.append(rows * per)
        out += [sum(ctx) * per for ctx in steps]
    return out


def gqa_full_flops(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
