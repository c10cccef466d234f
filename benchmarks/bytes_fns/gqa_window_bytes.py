"""Bytes of cached keys and values that the sliding grouped-query layers'
walk (``gqa_window_attention``, both passes) must read.

**Bandwidth bound**: a pass needs, of each participating slot, the
positions inside the window (``min(context, sliding_window)``: the walk
need not touch a position before it, whatever the ring holds) x keys and
values of ``num_key_value_heads x head_dim`` at 2 bytes a value (4,096 B) x
the sliding layers. A prefill block's rows reach up to a block further
back than the last row's window; that is left out (a lower bound), as are
queries, outputs and tables. Passes and their slots' contexts:
``latent_full_bytes.passes``.
"""

from __future__ import annotations

from benchmarks.bytes_fns.gqa_full_bytes import layers_of, position_bytes
from benchmarks.bytes_fns.latent_full_bytes import passes


def by_pass(chunks: list[dict], model: dict) -> list[float]:
    window = int(model["sliding_window"])
    per = position_bytes(model) * layers_of(model, "sliding_attention")
    return [sum(min(x, window) for x in ctx) * per
            for c in chunks for ctx in passes(c)]


def gqa_window_bytes(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
