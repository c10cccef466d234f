"""Operations the full layers' walk (``latent_full_attention``, both
passes) must do: absorbed latent attention, per query row and cached
position ``2 x heads x ((kv_lora_rank + qk_rope_head_dim) + kv_lora_rank)``
(the score against the row, then the row's latent under the softmax
weight; 278.5 k at 128 heads of 576 | 512), every layer a full layer. The
projections around the walk (the keys' up-projection onto the queries, the
values' behind the softmax) are plain matmuls outside the kernel and are
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

from benchmarks.bytes_fns.latent_full_bytes import passes

HALF_BLOCK = 64  # a prefill row sits up to a block under its slot's context


def flops_per_row_position(model: dict) -> float:
    rank = int(model["kv_lora_rank"])
    return 2.0 * int(model["num_attention_heads"]) * (
        rank + int(model["qk_rope_head_dim"]) + rank)


def by_pass(chunks: list[dict], model: dict) -> list[float]:
    per = flops_per_row_position(model) * int(model["num_hidden_layers"])
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


def latent_full_flops(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
