"""``peak_bytes_in_use`` after the window, the largest over the cell's
devices (leaves out a loaded program's reserved temp space)."""


def read(obs, spec):
    if not obs.memory_peak_bytes:
        return None
    return obs.memory_peak_bytes * float(spec.get("scale", 1e-9))
