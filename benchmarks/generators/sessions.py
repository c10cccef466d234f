"""Closed loop of multi-turn sessions under one shared system prompt: an
assistant or agent product. No think time.

Traffic file::

    {"kind": "sessions", "clients": 8, "turns": 4, "system_tokens": 768,
     "user_tokens": [lo, hi], "answer_tokens": [lo, hi],
     "spacing": "linear", "cycles": 16}

The fixed multiset is ``clients x turns`` (user, answer) pairs, the
mid-quantiles of the two ranges paired once by the file's ``pair_seed``,
which ``plan_seed`` (or ``--seed`` where the file has none) deals out to the
places (client, turn); the token contents always come from ``--seed``. A client whose session ends starts a new one with the same
sizes and new contents. Each turn's prompt is the system prompt plus the
session's whole history, so the page cache's prefix trie can serve all but
the last answer and the new message.
"""

from __future__ import annotations

from benchmarks.harness.plan import Plan, Req, content_seed, quantiles, rng_for


def plan(traffic: dict, params: dict, seed: int, seconds: float,
         deployment: dict) -> Plan:
    clients = int(traffic["clients"])
    turns = int(traffic["turns"])
    cycles = int(traffic.get("cycles", 16))
    spacing = traffic.get("spacing", "linear")
    n = clients * turns
    rng = rng_for(int(traffic.get("plan_seed", seed)), 4)
    users = quantiles(*traffic["user_tokens"], n, spacing)
    answers = quantiles(*traffic["answer_tokens"], n, spacing)
    # the pairing belongs to the file, the dealing to the seed
    pair = rng_for(int(traffic.get("pair_seed", 0)), 4).permutation(n)
    deal = rng.permutation(n)
    users, answers = ([users[i] for i in deal],
                      [answers[pair[i]] for i in deal])
    out: list[list[Req]] = []
    for c in range(clients):
        reqs = []
        for cyc in range(cycles):
            session = cyc * clients + c
            for t in range(turns):
                place = c * turns + t
                reqs.append(Req(
                    idx=(cyc * clients + c) * turns + t,
                    prompt_tokens=users[place], output_tokens=answers[place],
                    content_seed=content_seed(seed, 4, session, t),
                    client=c, session=session, turn=t,
                ))
        out.append(reqs)
    sys_seed = content_seed(seed, 5)
    # one request in set-up makes the system prompt's pages resident
    setup = [Req(idx=-1, prompt_tokens=16, output_tokens=8,
                 content_seed=content_seed(seed, 6), session=-1, turn=0)]
    return Plan(mode="closed", clients=out,
                system_tokens=int(traffic["system_tokens"]),
                system_seed=sys_seed, setup=setup)
