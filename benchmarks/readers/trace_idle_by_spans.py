"""Of the traced window's device idle seconds, the share during which at
least one request was on its way: between the start of its ``from`` span
and the end of its ``to`` span (``api_in`` and ``queue_wait``: inside the
program, and not yet admitted to a slot). Idle time an earlier admission
would fill, as against idle time in which no request existed.

A span's ``t0`` is ``time.monotonic()`` in the process that recorded it,
one clock for every process of the host, and the clock of the benchmark's
own tap (``obs.chunks``: ``t0`` at ``step_chunk``'s entry). So a moment
``t`` lies on the trace's axis at the start of the i-th
``bench:step_chunk`` event + (``t`` - the i-th tap's ``t0``), for the tap
nearest in time: the step ``trace_idle_by_phase`` takes for the chunk
records. A request that lacks either span, or whose two spans name
different hosts, is left out; a program whose spans hold no ``t0`` gives
nothing."""

from benchmarks.harness import xplane


def on_axis(taps, events):
    """``t -> seconds on the trace's axis``, or None with nothing to tie
    the two clocks together."""
    pairs = [(tap["t0"], ev.start) for tap, ev in zip(taps, events)
             if "t0" in tap]
    if not pairs:
        return None

    def place(t: float) -> float:
        t_tap, t_trace = min(pairs, key=lambda p: abs(p[0] - t))
        return t_trace + (t - t_tap)

    return place


def way_intervals(spans_by_rid, first: str, last: str):
    """(start of ``first``, end of ``last``) on the spans' own clock, a
    request each."""
    out = []
    for spans in spans_by_rid.values():
        a = [s for s in spans if s.get("name") == first and "t0" in s]
        b = [s for s in spans if s.get("name") == last and "t0" in s
             and "dur_ms" in s]
        if not a or not b:
            continue
        a = min(a, key=lambda s: s["t0"])
        b = min(b, key=lambda s: s["t0"])  # the first admission
        if a.get("host") != b.get("host"):
            continue
        out.append((a["t0"], b["t0"] + b["dur_ms"] * 1e-3))
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
    place = on_axis(obs.chunks, events)
    ways = way_intervals(obs.spans, spec["from"], spec["to"])
    gaps = xplane.complement(dev.busy, tr.t0, tr.t1)
    idle = sum(b - a for a, b in gaps)
    if place is None or not ways or idle <= 0:
        return None
    merged = xplane.union([(place(a), place(b)) for a, b in ways])
    return sum(xplane.overlap(merged, a, b) for a, b in gaps) / idle * 100.0
