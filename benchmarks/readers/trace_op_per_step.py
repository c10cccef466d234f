"""Device time of the programs matching ``modules`` (or, with
``patterns``, of operations) per decode step executed in the traced chunks,
in milliseconds."""


def read(obs, spec):
    steps = sum(c["decode_steps"] for c in obs.chunks)
    if obs.trace is None or steps <= 0:
        return None
    if "modules" in spec:
        secs = obs.trace.module_seconds(spec["modules"])
    else:
        secs = obs.trace.op_seconds(spec["patterns"])
    return secs / steps * 1e3 if secs > 0 else None
