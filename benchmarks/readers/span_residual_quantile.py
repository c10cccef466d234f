"""A quantile, over the requests due in the window, of one span's duration
minus the SUM of the spans named in ``minus`` (spans as ``GET /trace/<rid>``
gives them): what of the outer span the inner ones, which lie end to end
inside it, leave unnamed. Each duration is a monotonic pair on the clock
of the process that recorded it. A request that lacks the outer span or
any of the inner ones is left out, so a program that records none of
them gives nothing."""

from benchmarks.harness.e2e import due_in_window, percentile


def read(obs, spec):
    vals = []
    for r in due_in_window(obs.recs, obs.t0, obs.t1):
        dur = {}
        for s in obs.spans.get(r.rid, []):
            if "dur_ms" in s:
                dur[s.get("name")] = dur.get(s.get("name"), 0.0) + s["dur_ms"]
        if spec["span"] in dur and all(n in dur for n in spec["minus"]):
            vals.append(dur[spec["span"]] - sum(dur[n] for n in spec["minus"]))
    return percentile(vals, float(spec["q"]))
