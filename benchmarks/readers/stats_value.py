"""A gauge of the engine as the window closes (``key`` of ``cont.stats`` /
``serving_snapshot()``), times ``scale``. A program that does not report
the gauge gives nothing."""


def read(obs, spec):
    v = obs.stats1.get(spec["key"])
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        return None
    return float(v) * float(spec.get("scale", 1.0))
