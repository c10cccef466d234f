"""Functions that compute what a kernel needs to move, from shapes. Kept
with the benchmark so that no PR that claims a gain can change them. A
``trace_roofline`` layer metric names one by ``bytes_fn``.
"""

from __future__ import annotations

PAGE_ELEM_BYTES = {"none": 2.0, "int8": 1.0, "int4": 0.5}


def paged_kv_bytes(chunks: list[dict], model: dict) -> float:
    """Bytes of live KV that the paged attention kernels must read, per
    device, over the given chunks: for every step executed and every layer,
    each slot's context x local kv heads x 2 (K and V) x (head_dim x
    bytes per element + a 4-byte scale where pages are quantized).

    This is the **bandwidth bound** of decode attention: queries, outputs
    and block tables are left out (small beside the KV), and so are dead
    pages, which a kernel need not touch. A chunk's first step is the
    ragged pass (prefill pieces and decode rows together), the others are
    decode steps; a slot's context is taken as the mean of its length
    before and after the chunk.
    """
    elem = PAGE_ELEM_BYTES[model["kv_quant"]]
    scale = 4.0 if model["kv_quant"] != "none" else 0.0
    per_token = (
        model["kv_heads_local"] * 2 * (model["head_dim"] * elem + scale)
    )
    total = 0.0
    for c in chunks:
        before = c.get("ctx_before") or c["ctx_after"]
        ctx = sum((a + b) / 2.0 for a, b in zip(before, c["ctx_after"]))
        steps = max(int(c["decode_steps"]), 1)
        total += steps * ctx * per_token * model["n_layers"]
    return total


FUNCTIONS = {"paged_kv_bytes": paged_kv_bytes}
