"""Ratio of sums over the FlightRecorder's per-chunk records that fall in
the window. The engine defines ``host_ms`` as the time from ``step_chunk``'s
entry to the dispatch of the step program (admission, packing) and
``chunk_ms`` as dispatch to the end of the one sync that drains the tokens;
token delivery and eviction after the sync are in neither."""


def read(obs, spec):
    den = sum(r.get(k, 0.0) for r in obs.recorder for k in spec["den"])
    if den <= 0:
        return None
    num = sum(r.get(k, 0.0) for r in obs.recorder for k in spec["num"])
    return num / den * float(spec.get("scale", 100))
