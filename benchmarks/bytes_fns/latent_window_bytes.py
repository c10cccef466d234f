"""Bytes of cached latent rows that the sliding layers' continuation-step
kernel (``latent_window_attention``) must read.

**Bandwidth bound**: one row of ``pool_dim`` values serves every query head
of a slot (64 of them), read once as key and value both; queries, outputs
and block tables are left out, and so are the positions outside the window,
which the walk need not touch. Sizes come from the configuration's own keys
(``Obs.model``): the sliding layers' latent rank and rotated-key width, in
whole 128-lane rows as the pool stores them, 2 bytes a value.
"""

from __future__ import annotations


def row_bytes(model: dict) -> float:
    width = int(model["swa_kv_lora_rank"]) + int(model["swa_qk_rope_head_dim"])
    return -(-width // 128) * 128 * 2.0


def sliding_layers(model: dict) -> int:
    kinds = model["layer_types"][: int(model["num_hidden_layers"])]
    return sum(k == "sliding_attention" for k in kinds)


def latent_window_bytes(chunks: list[dict], model: dict) -> float:
    """Over the given chunks: for every continuation step (a chunk's steps
    but its first, the ragged pass, which does not call the kernel), each
    slot's context inside the window x the row's bytes x the sliding
    layers. A slot's context is the mean of its length before and after
    the chunk, as in ``paged_kv_bytes``."""
    window = int(model["sliding_window_size"])
    per_row = row_bytes(model) * sliding_layers(model)
    total = 0.0
    for c in chunks:
        before = c.get("ctx_before") or c["ctx_after"]
        ctx = sum(min((a + b) / 2.0, window)
                  for a, b in zip(before, c["ctx_after"]))
        total += max(int(c["decode_steps"]) - 1, 0) * ctx * per_row
    return total
