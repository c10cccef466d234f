"""gated_delta_flops for the gated_delta_step kernel alone: the passes that
kernel does not run read 0 (gated_delta_flops.by_pass, kernel="step")."""

from __future__ import annotations

from benchmarks.bytes_fns import gated_delta_flops


def by_pass(chunks: list[dict], model: dict) -> list[float]:
    return gated_delta_flops.by_pass(chunks, model, kernel="step")


def gated_delta_step_flops(chunks: list[dict], model: dict) -> float:
    return sum(by_pass(chunks, model))
