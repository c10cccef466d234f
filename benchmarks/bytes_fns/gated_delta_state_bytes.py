"""Bytes of recurrent state that the gated delta-rule layers' kernels
(``gated_delta_step``, ``gated_delta_chunk``) must move: a slot that takes
part in a pass reads its state once and writes it once in each gated-delta
layer, ``linear_num_value_heads x linear_key_head_dim x
linear_value_head_dim`` float32 values, unpadded (2,211,840 B at 30 heads
of 96 x 192: the state lies ``[96, 30 x 192]`` a slot and layer, whole lane
rows). Queries, keys, values, decays and outputs (tens of KB a row), the
convolution's tail and the slots that take no part are left out. Sizes
from the configuration's own keys, passes from the harness's record of
contexts (``latent_full_bytes.passes``), in the order
``gated_delta_flops.by_pass`` gives its own. ``kernel``: ``"step"`` counts
the continuation steps alone and ``"chunk"`` the ragged passes alone (the
other passes read 0: each kernel has a share of its own).
"""

from __future__ import annotations

from benchmarks.bytes_fns.latent_full_bytes import passes


def layers_of(model: dict) -> int:
    kinds = model["layer_types"][: int(model["num_hidden_layers"])]
    return sum(k == "linear_attention" for k in kinds)


def state_bytes(model: dict) -> float:
    """One slot's state of one layer."""
    return 4.0 * int(model["linear_num_value_heads"]) * int(
        model["linear_key_head_dim"]) * int(model["linear_value_head_dim"])


def of_kernel(per_pass: list[list[float]], kernel: str | None):
    """``per_pass`` (a list a chunk: the ragged pass's number, then each
    continuation step's) flattened, with the passes ``kernel`` does not run
    at 0."""
    keep = {None: (1.0, 1.0), "chunk": (1.0, 0.0), "step": (0.0, 1.0)}[kernel]
    return [x * keep[min(i, 1)] for one in per_pass for i, x in enumerate(one)]


def by_pass(chunks: list[dict], model: dict, kernel: str | None = None):
    per_slot = 2.0 * state_bytes(model) * layers_of(model)  # read and written
    return of_kernel(
        [[len(ctx) * per_slot for ctx in passes(c)] for c in chunks], kernel)


def gated_delta_state_bytes(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
