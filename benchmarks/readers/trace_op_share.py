"""Device self time of the operations matching ``patterns`` over the
device's busy time, in the traced chunks."""


def read(obs, spec):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    return obs.trace.op_seconds(spec["patterns"]) / obs.trace.busy_s * 100.0
