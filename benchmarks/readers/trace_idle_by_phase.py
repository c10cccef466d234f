"""Of the traced window's device idle seconds, the share that lies inside
one of the program's own host phases other than ``exclude`` (``wait``:
while the host waits the device is running, so idle time under it is a
bubble inside the running program, not host work in the device's way).

The phases come from the engine's FlightRecorder records (``t0`` =
``time.monotonic()`` at ``step_chunk``'s entry, then ``<phase>_ms`` in
order, ``between_ms`` before ``t0``). They are put on the trace's axis
through the benchmark's own tap: the tap record in ``obs.chunks`` with the
nearest ``t0`` (the tap wraps ``step_chunk``; both stamps are
``time.monotonic()``) is the i-th traced chunk, whose
``bench:step_chunk`` event is the i-th of the trace. A value near 100
says the program's phases account for the idle time; the rest is bubbles
inside the running program. A program whose records hold no phases gives
nothing."""

from benchmarks.harness import xplane

ORDER = ("admit", "pack", "dispatch", "wait", "drain", "deliver", "post")
NEAR_S = 0.005  # a record and its tap record are microseconds apart


def phase_intervals(records, taps, events, exclude=("wait",)):
    """Intervals on the trace's axis of every phase not in ``exclude``,
    for the records that match a traced chunk."""
    out = []
    if not taps or not events:
        return out
    for r in records:
        if "t0" not in r or "between_ms" not in r:
            continue
        j = min(range(len(taps)), key=lambda i: abs(taps[i]["t0"] - r["t0"]))
        if abs(taps[j]["t0"] - r["t0"]) > NEAR_S or j >= len(events):
            continue
        t = events[j].start + (r["t0"] - taps[j]["t0"])
        if "between" not in exclude:
            out.append((t - r["between_ms"] * 1e-3, t))
        for ph in ORDER:
            dur = r.get(f"{ph}_ms", 0.0) * 1e-3
            if ph not in exclude:
                out.append((t, t + dur))
            t += dur
    return out


def read(obs, spec):
    tr = obs.trace
    if tr is None:
        return None
    dev = next((d for d in tr.devices if d.ops), None)
    if dev is None:
        return None
    events = sorted((h for h in tr.host if h.name == xplane.CHUNK),
                    key=lambda h: h.start)
    spans = phase_intervals(obs.recorder, obs.chunks, events,
                            tuple(spec.get("exclude", ("wait",))))
    gaps = xplane.complement(dev.busy, tr.t0, tr.t1)
    idle = sum(b - a for a, b in gaps)
    if not spans or idle <= 0:
        return None
    merged = xplane.union(spans)
    return sum(xplane.overlap(merged, a, b) for a, b in gaps) / idle * 100.0
