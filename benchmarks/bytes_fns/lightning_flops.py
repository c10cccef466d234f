"""Operations the lightning layers' kernels must do, from the rows
granted: a row with a token costs, a head, the state's update (``k^T v``:
``2 d^2``) and its read (``q S``: ``2 d^2``); what a chunk's rows do among
themselves (``(Q K^T) * D`` and its product with ``V``) is left out, a
lower bound, so that a share over 100% cannot come from here. ``d`` =
``lightning_head_dim``, every lightning layer.

Rows, pass by pass (``latent_full_bytes.passes``): a continuation step has
one row a participating slot; the ragged pass one a slot that holds
context, plus the prefill rows the engine granted in that chunk beyond one
a slot (``prefill_granted``, which ``readers/trace_roofline_max.py`` copies
from the engine's own record of the chunk; absent = none).
"""

from __future__ import annotations

from benchmarks.bytes_fns.latent_full_bytes import passes


def flops_per_row(model: dict) -> float:
    hd = int(model.get("lightning_head_dim", model["head_dim"]))
    layers = sum(m == "lightning-attn" for m in model["mixer_types"])
    return 4.0 * hd * hd * int(model["lightning_nh"]) * layers


def by_pass(chunks: list[dict], model: dict) -> list[float]:
    per = flops_per_row(model)
    out = []
    for c in chunks:
        ragged, *steps = passes(c)
        rows = len(ragged)
        if ragged:
            rows += max(int(c.get("prefill_granted", 0)) - len(ragged), 0)
        out.append(rows * per)
        out += [len(ctx) * per for ctx in steps]
    return out


def lightning_flops(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
