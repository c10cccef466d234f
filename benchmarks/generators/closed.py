"""Closed loop: ``clients`` callers, each sending its next request when the
stream of its last one ends. Who: offline and reasoning callers.

Traffic file::

    {"kind": "closed", "clients": "per_slot" | <n>,
     "request_set": [[prompt_tokens, output_tokens], ...],
     "count_template": true, "stagger": true, "rounds": 64}

``request_set`` is the fixed multiset; it is repeated to fill
``clients x rounds`` places and ``plan_seed`` (or ``--seed`` where the file
has none) deals it out; the token contents always come from ``--seed``. With ``stagger`` the
first request of client ``i`` asks for ``(i + 1) / clients`` of its output,
so the clients finish evenly spread whatever the system's speed.
"""

from __future__ import annotations

from benchmarks.harness.plan import Plan, Req, content_seed, rng_for


def n_clients(traffic: dict, deployment: dict) -> int:
    c = traffic.get("clients", "per_slot")
    if c == "per_slot":
        return int(deployment.get("ml", {}).get("cont_max_slots", 8))
    return int(c)


def plan(traffic: dict, params: dict, seed: int, seconds: float,
         deployment: dict) -> Plan:
    clients = n_clients(traffic, deployment)
    rounds = int(traffic.get("rounds", 64))
    base = [tuple(p) for p in traffic["request_set"]]
    places = clients * rounds
    sizes = (base * (places // len(base) + 1))[:places]
    order = rng_for(int(traffic.get("plan_seed", seed)), 1).permutation(places)
    out: list[list[Req]] = []
    for c in range(clients):
        reqs = []
        for r in range(rounds):
            i = c * rounds + r
            p, o = sizes[order[i]]
            if r == 0 and traffic.get("stagger", True):
                o = max(1, round(o * (c + 1) / clients))
            reqs.append(Req(
                idx=i, prompt_tokens=int(p), output_tokens=int(o),
                content_seed=content_seed(seed, 1, i),
                count_template=bool(traffic.get("count_template", False)),
                client=c,
            ))
        out.append(reqs)
    return Plan(mode="closed", clients=out)
