"""Bytes of cached keys and values that the full grouped-query layers' walk
(``gqa_full_attention``, both passes) must read.

A position caches ``num_key_value_heads x head_dim`` keys and as many
values a layer, 2 bytes a value (4,096 B at 8 heads of 128); one pass needs
each participating slot's live span ONCE a full layer, however many row
blocks of a prefill walk it again and however many query heads share a kv
head. Queries, outputs and block tables are left out, and so are the
positions past the live span. Sizes come from the configuration's own keys
(``Obs.model``); the full layers are counted from ``layer_types``.

Passes and their slots' contexts: ``latent_full_bytes.passes`` (a chunk's
ragged pass, then each continuation step). :func:`by_pass` gives a number a
pass, in the order ``gqa_full_flops.by_pass`` gives its own:
``readers/trace_roofline_max.py`` takes the larger bound pass by pass.
"""

from __future__ import annotations

from benchmarks.bytes_fns.latent_full_bytes import passes


def position_bytes(model: dict) -> float:
    """Keys and values of one position, one layer."""
    return 2.0 * int(model["num_key_value_heads"]) * int(model["head_dim"]) * 2


def layers_of(model: dict, kind: str) -> int:
    kinds = model["layer_types"][: int(model["num_hidden_layers"])]
    return sum(k == kind for k in kinds)


def by_pass(chunks: list[dict], model: dict) -> list[float]:
    per = position_bytes(model) * layers_of(model, "full_attention")
    return [sum(ctx) * per for c in chunks for ctx in passes(c)]


def gqa_full_bytes(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
