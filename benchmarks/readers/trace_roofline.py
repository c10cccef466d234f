"""A kernel's share of its roofline: the least time the chip could take for
the bytes the call needs (``bytes_fn``, at the published HBM bandwidth) over
the kernels' measured device time. Bandwidth-bound by construction; no
clamp: a reading over 100% means the bytes are counted too high or the time
leaves out part of the work."""

from benchmarks.harness.bytes_fns import FUNCTIONS


def read(obs, spec):
    if obs.trace is None or not obs.chunks:
        return None
    secs = obs.trace.op_seconds(spec["patterns"])
    if secs <= 0:
        return None
    need = FUNCTIONS[spec["bytes_fn"]](obs.chunks, obs.model)
    return need / obs.peaks["hbm_bytes_per_s"] / secs * 100.0
