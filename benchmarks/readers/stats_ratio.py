"""Ratio of counter deltas over the window: sum of ``num`` over sum of
``den``, times ``scale`` (100 for a share)."""


def read(obs, spec):
    d = lambda k: obs.stats1.get(k, 0) - obs.stats0.get(k, 0)
    den = sum(d(k) for k in spec["den"])
    if den <= 0:
        return None
    return sum(d(k) for k in spec["num"]) / den * float(spec.get("scale", 100))
