"""A counter's growth over the window."""


def read(obs, spec):
    k = spec["key"]
    if k not in obs.stats1:
        return None
    return float(obs.stats1[k] - obs.stats0.get(k, 0))
