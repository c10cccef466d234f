"""Bytes the tensor-parallel step's all-gathers bring to one chip, from
shapes. Kept with the benchmark, beside ``bytes_fns.py``, so that the
number a rate is computed from does not come from the program under test
(whose own ``tp_gather_bytes`` counter is read by ``tp_gather_mb_per_step``).

What the step gathers (docs/SHARDING.md: every matmul weight is sharded by
output column and the activation reassembled by a tiled all-gather): in
each layer the attention heads' outputs (``heads x head_dim`` wide), the
``o_proj`` output (``hidden``), the MLP's hidden activation
(``intermediate``) and its output (``hidden``); after the last layer, where
the head is untied, the logits (``vocab``). A chip receives the other
``tp - 1`` of ``tp`` shards of each, in the activations' type.

Rows: a chunk's first step is the ragged pass, which carries every row of
the packed block (``slots x prefill_chunk``) through the layers and
``slots x spec_width`` verify rows through the head; each further step
carries one row a slot through both.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def sizes_from_config(hf: dict, ml) -> dict:
    """The widths and row counts the function needs, from a configuration
    file's published keys and its deployment's ``MLConfig``."""
    heads = int(hf["num_attention_heads"])
    head_dim = int(hf.get("head_dim") or hf["hidden_size"] // heads)
    return {
        "tp": int(ml.tensor_parallel),
        "n_layers": int(hf["num_hidden_layers"]),
        "layer_width": (heads * head_dim + 2 * int(hf["hidden_size"])
                        + int(hf["intermediate_size"])),
        "head_width": (0 if hf.get("tie_word_embeddings", False)
                       else int(hf["vocab_size"])),
        "elem_bytes": DTYPE_BYTES[hf.get("torch_dtype", "bfloat16")],
        "slots": int(ml.cont_max_slots),
        "chunk": int(ml.prefill_chunk),
        "verify_rows": 1 + (int(ml.spec_draft) if ml.spec_decode else 0),
    }


def tp_gather_bytes(chunks: list[dict], sizes: dict) -> float:
    """Bytes one chip receives over the given chunks."""
    tp = sizes["tp"]
    if tp <= 1:
        return 0.0
    recv = sizes["elem_bytes"] * (tp - 1) / tp
    layer_row = sizes["n_layers"] * sizes["layer_width"] * recv
    head_row = sizes["head_width"] * recv
    S = sizes["slots"]
    total = 0.0
    for c in chunks:
        more = max(int(c["decode_steps"]), 1) - 1  # steps after the pass
        total += layer_row * (S * sizes["chunk"] + more * S)
        total += head_row * S * (sizes["verify_rows"] + more)
    return total


FUNCTIONS = {"tp_gather_bytes": tp_gather_bytes}
