"""Bytes of recurrent state that the lightning layers' kernels
(``lightning_attention_step``, ``lightning_attention_chunk``) must move: a
slot that takes part in a pass reads its state once and writes it once in
each lightning layer, ``lightning_nh x lightning_head_dim^2`` float32 values
(2.1 MB at 32 heads of 128). Queries, keys, values and outputs (a few KB a
row) are left out, and so are the slots that take no part (the kernels'
grid passes over them). Sizes from the configuration's own keys, passes
from the harness's record of contexts (``latent_full_bytes.passes``), in
the order ``lightning_flops.by_pass`` gives its own.
"""

from __future__ import annotations

from benchmarks.bytes_fns.latent_full_bytes import passes


def state_bytes(model: dict) -> float:
    hd = int(model.get("lightning_head_dim", model["head_dim"]))
    layers = sum(m == "lightning-attn" for m in model["mixer_types"])
    return 4.0 * int(model["lightning_nh"]) * hd * hd * layers


def by_pass(chunks: list[dict], model: dict) -> list[float]:
    per_slot = 2.0 * state_bytes(model)  # read and written
    return [len(ctx) * per_slot for c in chunks for ctx in passes(c)]


def lightning_state_bytes(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
