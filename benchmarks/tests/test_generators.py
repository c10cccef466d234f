"""The generators are pure functions of (file, seed): the seed orders, fills
and jitters a fixed request set and never resamples it."""

import json
from collections import Counter

import pytest

from benchmarks.harness import spec
from benchmarks.harness.plan import quantiles

DEPLOY = {"ml": {}}
CASES = [
    ("decode-closed", {}),
    ("chat-steady", {"rate_rps": 0.7}),
    ("prefix-sessions", {}),
]


def make(name, params, seed, seconds=51):
    traffic = spec.load_traffic(name)
    return spec.generator(traffic["kind"]).plan(traffic, params, seed, seconds,
                                                DEPLOY)


def flat(plan):
    if plan.mode == "open":
        return [r for r in plan.schedule if r.due_s >= 0]
    return [r for c in plan.clients for r in c]


@pytest.mark.parametrize("name,params", CASES)
def test_same_seed_same_plan(name, params):
    assert make(name, params, 2147483700) == make(name, params, 2147483700)


def sized(plan):
    return [(r.prompt_tokens, r.output_tokens, r.due_s) for r in flat(plan)]


@pytest.mark.parametrize("name,params", CASES)
def test_two_seeds_offer_the_same_work_with_other_contents(name, params):
    a, b = make(name, params, 1), make(name, params, 2**31 + 5)
    assert a.sizes() == b.sizes()
    assert len(flat(a)) == len(flat(b))
    assert [r.content_seed for r in flat(a)] != [r.content_seed for r in flat(b)]
    # the files of this benchmark fix order and arrival moments (plan_seed)
    assert sized(a) == sized(b)


@pytest.mark.parametrize("name,params", CASES[1:])
def test_without_plan_seed_the_seed_orders_a_fixed_multiset(name, params):
    traffic = {k: v for k, v in spec.load_traffic(name).items()
               if k != "plan_seed"}
    gen = spec.generator(traffic["kind"])
    a = gen.plan(traffic, params, 1, 51, DEPLOY)
    b = gen.plan(traffic, params, 2**31 + 5, 51, DEPLOY)
    assert a.sizes() == b.sizes() and sized(a) != sized(b)


def test_open_loop_offers_a_fixed_count_one_arrival_per_slot():
    rate = 0.7
    for seed in (0, 7, 2**31 + 99):
        p = make("chat-steady", {"rate_rps": rate}, seed, seconds=51)
        due = [r.due_s for r in p.schedule if r.due_s >= 0]
        assert len(due) == int(rate * 51)
        for i, d in enumerate(sorted(due)):
            assert i / rate <= d < (i + 1) / rate
        lead = [r.due_s for r in p.schedule if r.due_s < 0]
        assert len(lead) == int(rate * 6) and min(lead) >= -p.lead_in_s


def test_open_loop_sizes_are_the_quantiles_of_the_file():
    t = spec.load_traffic("chat-steady")
    p = make("chat-steady", {"rate_rps": 1.0}, 3, seconds=40)
    reqs = [r for r in p.schedule if r.due_s >= 0]
    assert sorted(r.prompt_tokens for r in reqs) == quantiles(*t["prompt_tokens"], 40)
    assert sorted(r.output_tokens for r in reqs) == quantiles(*t["output_tokens"], 40)
    assert min(r.prompt_tokens for r in reqs) >= 32
    assert max(r.prompt_tokens for r in reqs) <= 512


ANSWERS = [163, 190, 221, 244, 269, 291, 318, 352]


def unstaggered(seed, **without):
    """decode-closed's plan before the first round is scaled."""
    traffic = {k: v for k, v in spec.load_traffic("decode-closed").items()
               if k not in without} | {"stagger": False}
    return spec.generator("closed").plan(traffic, {}, seed, 51, DEPLOY)


def test_closed_loop_deals_eight_answer_lengths_the_same_way_at_every_seed():
    t = spec.load_traffic("decode-closed")
    assert [o for _, o in t["request_set"]] == ANSWERS
    assert sum(ANSWERS) == 8 * 256  # the mean answer PR 23's file asked
    # no two clients' answers take the same number of 8-step chunks
    assert len({-(-a // 8) for a in ANSWERS}) == 8
    plans = [make("decode-closed", {}, seed) for seed in (5, 7, 2**31 + 99)]
    for p in plans:
        assert len(p.clients) == 8 and all(len(c) == 64 for c in p.clients)
        assert {r.prompt_tokens for c in p.clients for r in c} == {128}
        assert all(r.count_template for c in p.clients for r in c)
    assert sized(plans[0]) == sized(plans[1]) == sized(plans[2])
    # without plan_seed the seed deals the same multiset another way
    a, b = (unstaggered(s, plan_seed=None) for s in (1, 2**31 + 5))
    assert a.sizes() == b.sizes() and sized(a) != sized(b)
    # the multiset before the stagger: each length 64 times in 512 places
    whole = unstaggered(5)
    assert Counter(r.output_tokens for r in flat(whole)) == Counter(
        {a: 64 for a in ANSWERS})
    # no client is dealt long answers only: every client's first dozen
    # requests (a window's worth) hold at least four different lengths
    for c in whole.clients:
        assert len({r.output_tokens for r in c[:12]}) >= 4


def test_closed_loop_staggers_the_first_round_only():
    p = make("decode-closed", {}, 5)
    whole = unstaggered(5)
    for i, (c, w) in enumerate(zip(p.clients, whole.clients)):
        assert c[0].output_tokens == round(w[0].output_tokens * (i + 1) / 8)
        assert [r.output_tokens for r in c[1:]] == [
            r.output_tokens for r in w[1:]]


def test_sessions_deal_one_fixed_set_of_turns():
    p = make("prefix-sessions", {}, 9)
    t = spec.load_traffic("prefix-sessions")
    first_cycle = [r for c in p.clients for r in c[: t["turns"]]]
    assert Counter(r.prompt_tokens for r in first_cycle) == Counter(
        quantiles(*t["user_tokens"], 32, "linear"))
    assert Counter(r.output_tokens for r in first_cycle) == Counter(
        quantiles(*t["answer_tokens"], 32, "linear"))
    # a client's next session repeats its sizes with new contents
    c0 = p.clients[0]
    assert [(r.prompt_tokens, r.output_tokens) for r in c0[:4]] == [
        (r.prompt_tokens, r.output_tokens) for r in c0[4:8]]
    assert c0[0].content_seed != c0[4].content_seed
    assert c0[0].session != c0[4].session and c0[3].turn == 3
    assert p.system_tokens == 768 and len(p.setup) == 1


def test_traffic_files_are_data_only():
    for name, _ in CASES:
        with open(spec.BENCH_DIR / "traffic" / f"{name}.json") as f:
            assert "kind" in json.load(f)
