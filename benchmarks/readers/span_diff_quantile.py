"""A quantile, over the requests due in the window, of one span's duration
minus another's (spans as ``GET /trace/<rid>`` gives them). Both are
monotonic pairs, each on the clock of the process that recorded it, so the
difference needs no clock shared between hosts. A request that lacks
either span is left out."""

from benchmarks.harness.e2e import due_in_window, percentile


def read(obs, spec):
    vals = []
    for r in due_in_window(obs.recs, obs.t0, obs.t1):
        dur = {}
        for s in obs.spans.get(r.rid, []):
            if "dur_ms" in s:
                dur[s.get("name")] = dur.get(s.get("name"), 0.0) + s["dur_ms"]
        if spec["span"] in dur and spec["minus"] in dur:
            vals.append(dur[spec["span"]] - dur[spec["minus"]])
    return percentile(vals, float(spec["q"]))
