"""A kernel's share of its roofline where neither bound holds for every
call: the least time the chip could take, pass by pass the LARGER of the
bytes the pass needs at the published HBM bandwidth and the operations it
needs at the published bf16 peak (``bytes_fn`` / ``flops_fn``:
``benchmarks/bytes_fns/<name>.py``, each with ``by_pass(chunks, model)``
giving one number a kernel pass in the same order), summed, over the
kernels' measured device time. No clamp: a reading over 100% means bytes
or operations are counted too high or the time leaves out part of the work.

A pass's rows come from the harness's own record of slot contexts; how many
prompt rows the engine granted a chunk is the one thing that record lacks,
so each traced chunk gets ``prefill_granted`` from the engine's
FlightRecorder record of the same chunk (nearest ``t0``, as
``trace_idle_by_phase`` pairs them). A trace without the kernel (a program
that lacks it) gives nothing."""

import importlib

NEAR_S = 0.005  # a record and its tap record are microseconds apart


def with_grants(chunks: list[dict], records: list[dict]) -> list[dict]:
    """``chunks`` with each one's ``prefill_granted`` where the engine's
    records hold a chunk that started with it."""
    stamped = [r for r in records if "t0" in r and "prefill_granted" in r]
    out = []
    for c in chunks:
        near = min(stamped, key=lambda r: abs(r["t0"] - c["t0"]), default=None)
        if near is not None and abs(near["t0"] - c["t0"]) <= NEAR_S:
            c = {**c, "prefill_granted": near["prefill_granted"]}
        out.append(c)
    return out


def _by_pass(name: str):
    return importlib.import_module(f"benchmarks.bytes_fns.{name}").by_pass


def read(obs, spec):
    if obs.trace is None or not obs.chunks:
        return None
    secs = obs.trace.op_seconds(spec["patterns"])
    if secs <= 0:
        return None
    chunks = with_grants(obs.chunks, obs.recorder)
    need_bytes = _by_pass(spec["bytes_fn"])(chunks, obs.model)
    need_flops = _by_pass(spec["flops_fn"])(chunks, obs.model)
    least = sum(
        max(b / obs.peaks["hbm_bytes_per_s"], f / obs.peaks["bf16_flops"])
        for b, f in zip(need_bytes, need_flops))
    return least / secs * 100.0
