"""Bytes of cached latent rows that the full layers' walk
(``latent_full_attention``, both passes) must read.

One row of ``pool_dim`` values (the latent and the rotated key, in whole
128-lane rows as the pool stores them, 2 bytes a value) serves every query
head and every query row of a slot in a pass, as key and value both: a
pass needs each participating slot's live span ONCE a layer, however many
row blocks of a prefill walk it again. Queries, outputs and block tables
are left out, and so are the positions past the live span. Sizes come from
the configuration's own keys (``Obs.model``); every layer is a full layer.

A pass is one execution of the layers: a chunk's ragged pass (every slot
that holds context when it is packed: a decode row or a prefill block),
then each continuation step (the slots whose context grew over the chunk,
at the mean of their context before and after it). :func:`by_pass` gives a
number a pass, in the order ``latent_full_flops.by_pass`` gives its own:
``readers/trace_roofline_max.py`` takes the larger bound pass by pass.
"""

from __future__ import annotations


def row_bytes(model: dict) -> float:
    width = int(model["kv_lora_rank"]) + int(model["qk_rope_head_dim"])
    return -(-width // 128) * 128 * 2.0


def passes(chunk: dict) -> list[list[float]]:
    """The contexts of the slots that take part in each pass of a chunk:
    the ragged pass, then ``decode_steps - 1`` continuation steps."""
    after = chunk["ctx_after"]
    before = chunk.get("ctx_before") or after
    ragged = [float(a) for a in before if a > 0]
    grew = [(a + b) / 2.0 for a, b in zip(before, after) if b > a > 0]
    return [ragged] + [grew] * max(int(chunk["decode_steps"]) - 1, 0)


def by_pass(chunks: list[dict], model: dict) -> list[float]:
    per_position = row_bytes(model) * int(model["num_hidden_layers"])
    return [sum(ctx) * per_position for c in chunks for ctx in passes(c)]


def latent_full_bytes(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
