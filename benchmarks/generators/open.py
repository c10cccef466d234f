"""Open loop at a fixed rate: independent users.

Traffic file::

    {"kind": "open", "prompt_tokens": [lo, hi], "output_tokens": [lo, hi],
     "spacing": "log", "jitter": 0.5, "lead_in_s": 6, "plan_seed": 11}

The rate is the cell's own number (``cells/<cell>.json``: ``rate_rps``).
The window holds ``floor(rate x seconds)`` arrivals, one in each slot of
``1 / rate`` seconds, moved inside its slot by the seeded jitter. Their
sizes are the mid-quantiles of the two ranges, paired once by the file's
``pair_seed``. Their order and the jitter come from ``plan_seed`` where the
file has one, and from ``--seed`` where it has none; the token contents
always come from ``--seed``. (On the chip the order alone moved the 90th
percentile of 36 first-token times by +-7% between seeds, while two runs of
one order agreed within 1.3%: PERF.md, Findings, PR 23. So the mixes of this
benchmark fix the order, and every run of a cell offers the same requests
at the same moments.) ``lead_in_s`` seconds of arrivals of the same kind come before the
window opens, so that it opens on a system in its steady state; they are
not counted.
"""

from __future__ import annotations

import math

from benchmarks.harness.plan import Plan, Req, content_seed, quantiles, rng_for


def _arrivals(n, rate, t0, seed, layout_seed, stream, traffic, first_idx):
    spacing = traffic.get("spacing", "log")
    prompts = quantiles(*traffic["prompt_tokens"], n, spacing)
    outputs = quantiles(*traffic["output_tokens"], n, spacing)
    # the pairing belongs to the file, the order to the seed
    pair = rng_for(int(traffic.get("pair_seed", 0)), stream).permutation(n)
    pairs = [(prompts[i], outputs[j]) for i, j in enumerate(pair)]
    rng = rng_for(layout_seed, stream)
    order = rng.permutation(n)
    prompts = [pairs[i][0] for i in order]
    outputs = [pairs[i][1] for i in order]
    jitter = float(traffic.get("jitter", 0.5))
    u = rng.uniform(-1.0, 1.0, size=n)
    return [
        Req(
            idx=first_idx + i, prompt_tokens=prompts[i],
            output_tokens=outputs[i],
            content_seed=content_seed(seed, stream, i),
            count_template=bool(traffic.get("count_template", False)),
            due_s=t0 + (i + 0.5 + jitter * float(u[i])) / rate,
        )
        for i in range(n)
    ]


def plan(traffic: dict, params: dict, seed: int, seconds: float,
         deployment: dict) -> Plan:
    rate = float(params["rate_rps"])
    lead = float(traffic.get("lead_in_s", 0.0))
    n_lead = int(math.floor(rate * lead))
    n = int(math.floor(rate * seconds))
    layout = int(traffic.get("plan_seed", seed))
    sched = _arrivals(n_lead, rate, -n_lead / rate, seed, layout, 2, traffic, 0)
    sched += _arrivals(n, rate, 0.0, seed, layout, 3, traffic, n_lead)
    sched.sort(key=lambda r: r.due_s)
    return Plan(mode="open", schedule=sched, lead_in_s=n_lead / rate)
