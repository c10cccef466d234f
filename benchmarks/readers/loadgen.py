"""How late the generator ran: a quantile of sent minus due over the
requests due in the window, in milliseconds."""

from benchmarks.harness.e2e import due_in_window, percentile


def read(obs, spec):
    late = [(r.sent - r.due) * 1e3
            for r in due_in_window(obs.recs, obs.t0, obs.t1)
            if r.sent is not None]
    return percentile(late, float(spec["q"]))
