"""Device time of one phase of the step program per execution (or, with
``per: "cont_step"``, per decode-continuation step), in milliseconds.

Within each execution of the programs matching ``modules`` the top-level
``while`` operations (those inside no other), in start order, are the
phases the metric's file lists under ``phases``: the program gives each of
its three loops a name of its own and its test pins their order
(``tests/test_step_scopes.py``), which is what ties first, second and third
to ``ragged_pass``, ``verify_emit`` and ``decode_cont`` while a trace's
events carry operation names only. A phase's time is its loop's whole
span (container and body). An execution with another count of top-level
loops returns nothing and says so on the run's output: the compiler then
laid the program out differently, and no order can be trusted."""

import re


def is_loop(name: str) -> bool:
    """``%while.45 = (...) while(...)``: the operation's own name."""
    return name.lstrip("%").split(".")[0].split(" ")[0] == "while"


def top_level_loops(loops, start: float, end: float) -> list:
    """Of ``loops`` (``while`` events), those of one execution that lie in
    no other, in start order."""
    eps = 1e-9
    inside = sorted(
        (o for o in loops if o.start >= start - eps and o.end <= end + eps),
        key=lambda o: (o.start, -(o.end - o.start)))
    out = []
    for o in inside:
        if not out or o.start >= out[-1].end - eps:
            out.append(o)
    return out


def phase_seconds(trace, modules: list[str], phases: list[str]):
    """(seconds of each phase summed over the executions and averaged over
    the devices used, executions per device), or None with the reason."""
    rx = [re.compile(p) for p in modules]
    used = [d for d in trace.devices if d.modules and d.ops]
    total = dict.fromkeys(phases, 0.0)
    runs = 0
    for d in used:
        whiles = [o for o in d.ops if is_loop(o.name)]
        for m in d.modules:
            if not any(r.search(m.name) for r in rx):
                continue
            loops = top_level_loops(whiles, m.start, m.end)
            if len(loops) != len(phases):
                return None, (f"an execution of {m.name[:40]} holds "
                              f"{len(loops)} top-level loops, not {len(phases)}")
            runs += 1
            for name, o in zip(phases, loops):
                total[name] += o.end - o.start
    if not runs:
        return None, "no execution of the step program in the trace"
    return ({k: v / len(used) for k, v in total.items()}, runs / len(used)), None


def read(obs, spec):
    if obs.trace is None:
        return None
    got, why = phase_seconds(obs.trace, spec["modules"], spec["phases"])
    if got is None:
        print(f"trace_phase_ms({spec['phase']}): nothing to read: {why}",
              flush=True)
        return None
    secs, runs = got
    if spec.get("per") == "cont_step":
        # a prefill-only chunk runs no continuation step
        per = sum(max(c["decode_steps"] - 1, 0) for c in obs.chunks)
    else:
        per = runs
    return secs[spec["phase"]] / per * 1e3 if per > 0 else None
