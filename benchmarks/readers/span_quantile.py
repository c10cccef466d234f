"""A quantile of one span's duration over the requests due in the window
(spans as ``GET /trace/<rid>`` gives them)."""

from benchmarks.harness.e2e import due_in_window, percentile


def read(obs, spec):
    vals = []
    for r in due_in_window(obs.recs, obs.t0, obs.t1):
        durs = [s["dur_ms"] for s in obs.spans.get(r.rid, [])
                if s.get("name") == spec["span"] and "dur_ms" in s]
        if durs:
            vals.append(sum(durs))
    return percentile(vals, float(spec["q"]))
