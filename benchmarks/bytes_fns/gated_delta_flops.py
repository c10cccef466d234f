"""Operations the gated delta-rule layers' kernels must do, from the rows
granted: a row with a token costs, a head, what the state predicts for the
key (``S k``: ``2 dk dv``), the rank-one update (``2 dk dv``) and the read
(``S q``: ``2 dk dv``): ``6 x 96 x 192`` at the published sizes, every
gated-delta layer. What a chunk's rows do among themselves (``K K^T``, the
triangular inverse, its products with ``V``) is left out, a lower bound,
so that a share over 100% cannot come from here.

Rows, pass by pass (``latent_full_bytes.passes``): a continuation step has
one row a participating slot; the ragged pass one a slot that holds
context, plus the prefill rows the engine granted in that chunk beyond one
a slot (``prefill_granted``, which ``readers/trace_roofline_max.py`` copies
from the engine's own record of the chunk; absent = none). ``kernel`` as
``gated_delta_state_bytes.by_pass`` takes it.
"""

from __future__ import annotations

from benchmarks.bytes_fns.gated_delta_state_bytes import layers_of, of_kernel
from benchmarks.bytes_fns.latent_full_bytes import passes


def flops_per_row(model: dict) -> float:
    return 6.0 * int(model["linear_key_head_dim"]) * int(
        model["linear_value_head_dim"]) * int(
        model["linear_num_value_heads"]) * layers_of(model)


def by_pass(chunks: list[dict], model: dict, kernel: str | None = None):
    per = flops_per_row(model)
    out = []
    for c in chunks:
        ragged, *steps = passes(c)
        rows = len(ragged)
        if ragged:
            rows += max(int(c.get("prefill_granted", 0)) - len(ragged), 0)
        out.append([rows * per] + [len(ctx) * per for ctx in steps])
    return of_kernel(out, kernel)


def gated_delta_flops(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
