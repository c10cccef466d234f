"""Time in the operations whose OWN name matches ``patterns`` during which
no other operation runs on that device, over the traced window, averaged
over the devices used, in %.

Two things apart from ``trace_exposed_share``, for traces of several
chips. A trace event's name is the operation's whole HLO text, operands
included, so an unanchored pattern also matches every fusion that consumes
a collective's result (``%fusion.556 = ... fusion(... %all-gather.54 ...)``):
here a pattern is matched against the operation's own name only (the text
before `` = ``). And the time covered by other operations is found by
bisection over their merged intervals, not by a scan of all of them for
every collective: a four-chip window of 5 s holds 12,000 collectives and
200,000 other operations a chip, on which the scan takes minutes."""

import bisect
import re

from benchmarks.harness import xplane


def own_name(name: str) -> str:
    return name.split(" = ", 1)[0].lstrip("%")


def covered(starts, ends, cum, a: float, b: float) -> float:
    """Length of ``[a, b]`` inside the merged, sorted intervals."""
    i = bisect.bisect_right(ends, a)  # first interval that ends after a
    j = bisect.bisect_left(starts, b)  # first interval that starts at or after b
    if i >= j:
        return 0.0
    inside = cum[j] - cum[i]
    inside -= max(0.0, a - starts[i])  # the part of the first before a
    inside -= max(0.0, ends[j - 1] - b)  # the part of the last after b
    return inside


def exposed_seconds(trace, patterns: list[str]) -> float:
    rx = [re.compile(p) for p in patterns]
    used = [d for d in trace.devices if d.ops]
    if not used:
        return 0.0
    total = 0.0
    for d in used:
        mine, leaf = [], []
        for o in d.ops:
            name = own_name(o.name)
            if any(r.search(name) for r in rx):
                mine.append(o)
            elif o.self_s > 0 and not xplane._is_container(o.name):
                leaf.append((o.start, o.end))
        other = xplane.union(leaf)
        starts = [x for x, _ in other]
        ends = [y for _, y in other]
        cum = [0.0]
        for x, y in other:
            cum.append(cum[-1] + (y - x))
        for o in mine:
            total += (o.end - o.start) - covered(starts, ends, cum, o.start, o.end)
    return total / len(used)


def read(obs, spec):
    if obs.trace is None or obs.trace.window_s <= 0:
        return None
    return exposed_seconds(obs.trace, spec["patterns"]) / obs.trace.window_s * 100.0
