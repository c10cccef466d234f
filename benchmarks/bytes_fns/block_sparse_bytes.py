"""Bytes of cached keys and values that the sparse layers' walk over the
kept blocks (``block_sparse_attention``: one query position a slot, the
ragged pass's first rows and every continuation step) must read.

A slot at context ``c`` (its query at position ``c - 1``) under
``dense_len`` reads every cached position; past it, ``topk`` blocks of
``block_size`` positions of which the last, the query's own, holds ``(c -
1) mod block_size + 1``. A position is ``num_key_value_heads x head_dim``
values of 2 bytes, key and value, in each sparse layer. Queries, outputs,
block tables and the pooled keys the selection reads (another operation,
outside the kernel) are left out. Sizes come from the configuration's own
keys (``Obs.model``: ``sparse_config``, ``mixer_types``), the contexts from
the harness's record (``latent_full_bytes.passes``: the ragged pass, then
each continuation step), never from the program's counters.
"""

from __future__ import annotations

from benchmarks.bytes_fns.latent_full_bytes import passes

SPARSE_DEFAULTS = dict(block_size=64, topk=64, dense_len=8192)


def kept_positions(ctx: float, model: dict) -> float:
    sp = {**SPARSE_DEFAULTS, **(model.get("sparse_config") or {})}
    if ctx <= sp["dense_len"]:
        return float(ctx)
    block = int(sp["block_size"])
    return float((int(sp["topk"]) - 1) * block + (int(ctx) - 1) % block + 1)


def position_bytes(model: dict) -> float:
    layers = sum(m == "minicpm4" for m in model["mixer_types"])
    return (2.0 * int(model["num_key_value_heads"]) * int(model["head_dim"])
            * 2 * layers)


def by_pass(chunks: list[dict], model: dict) -> list[float]:
    per = position_bytes(model)
    return [sum(kept_positions(c, model) for c in ctx) * per
            for ch in chunks for ctx in passes(ch)]


def block_sparse_bytes(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
