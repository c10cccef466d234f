"""The step program's sampling epilogue does what the slots ask and no
more (ROADMAP S1): ``_sample_rows`` chooses greedy or sampled once over
the slots, ``_verify_emit`` walks as many rows as the longest emitting
draft has. Both are held here to the code they replaced, bit for bit:
the per-row ``sampling.sample`` (and the old ``vmap`` of it), and the
full ``W``-row scan of the walk, which this file keeps as the reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine import paged
from tensorlink_tpu.engine.continuous import (
    ContinuousEngine, _row_keys, _sample_rows,
)
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.sampling import SamplingParams, sample
from tensorlink_tpu.models import ModelConfig, init_params

S, V = 6, 160


# ---- (a) _sample_rows against sampling.sample, row by row ----------------

def _knobs(mix: str, penalties: bool, filters: bool, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    temp = {
        "greedy": np.zeros(S),
        "sampled": rng.uniform(0.3, 1.4, S),
        # rows 0, 3 sample; the rest are greedy beside them
        "mixed": np.where(np.arange(S) % 3 == 0, rng.uniform(0.3, 1.4, S), 0),
    }[mix]
    return dict(
        logits=rng.normal(0, 3, (S, V)).astype(np.float32),
        keys=_row_keys(jnp.asarray(rng.integers(0, 2**31 - 1, S), jnp.int32),
                       jnp.asarray(rng.integers(0, 500, S), jnp.int32)),
        temp=temp.astype(np.float32),
        top_k=(rng.integers(0, 12, S) if filters
               else np.zeros(S)).astype(np.int32),
        top_p=(rng.uniform(0.5, 1.0, S) if filters
               else np.ones(S)).astype(np.float32),
        pres=(rng.uniform(0, 1.5, S) if penalties
              else np.zeros(S)).astype(np.float32),
        freq=(rng.uniform(0, 0.8, S) if penalties
              else np.zeros(S)).astype(np.float32),
        # a context histogram that hits the top logits, so a penalty can
        # change an argmax
        counts=rng.integers(0, 4, (S, V)).astype(np.int32),
    )


def _per_row_reference(k: dict) -> np.ndarray:
    """One ``sample`` call a row with that row's own scalar knobs: the
    legacy engine's path, which never saw a ``vmap``."""
    out = []
    for s in range(S):
        sp = SamplingParams(
            temperature=jnp.float32(k["temp"][s]), top_k=jnp.int32(k["top_k"][s]),
            top_p=jnp.float32(k["top_p"][s]),
            presence_penalty=jnp.float32(k["pres"][s]),
            frequency_penalty=jnp.float32(k["freq"][s]),
        )
        out.append(int(sample(
            jnp.asarray(k["logits"][s][None]), k["keys"][s], sp,
            jnp.asarray(k["counts"][s][None]),
        )[0]))
    return np.asarray(out, np.int32)


@jax.jit
def _vmapped_reference(logits, keys, temp, top_k, top_p, pres, freq, counts):
    """``_sample_rows`` as it was before the choice left the ``vmap``."""
    def one(lg, key, t, k, p, pp, fp, cnt):
        sp = SamplingParams(temperature=t, top_k=k, top_p=p,
                            presence_penalty=pp, frequency_penalty=fp)
        return sample(lg[None], key, sp, cnt[None])[0]

    return jax.vmap(one)(logits, keys, temp, top_k, top_p, pres, freq, counts)


@pytest.mark.parametrize("filters", [False, True], ids=["nofilter", "topk_topp"])
@pytest.mark.parametrize("penalties", [False, True], ids=["plain", "penalised"])
@pytest.mark.parametrize("mix", ["greedy", "sampled", "mixed"])
def test_sample_rows_tokens_are_the_per_row_samples(mix, penalties, filters):
    k = _knobs(mix, penalties, filters, seed=11)
    args = [jnp.asarray(k[n]) for n in (
        "logits", "keys", "temp", "top_k", "top_p", "pres", "freq", "counts")]
    got = np.asarray(_sample_rows(*args))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, np.asarray(_vmapped_reference(*args)))
    np.testing.assert_array_equal(got, _per_row_reference(k))
    if mix != "sampled":  # a greedy row is its penalised logits' argmax
        cf = k["counts"].astype(np.float32)
        pen = (k["logits"] - k["pres"][:, None] * (cf > 0)
               - k["freq"][:, None] * cf)
        greedy = k["temp"] == 0
        np.testing.assert_array_equal(got[greedy], pen.argmax(-1)[greedy])


# ---- (c) one cond, a scalar predicate, the sort in one branch ------------

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_sample_rows_holds_one_branch_with_the_sort_on_one_side():
    k = _knobs("mixed", True, True, seed=3)
    args = [jnp.asarray(k[n]) for n in (
        "logits", "keys", "temp", "top_k", "top_p", "pres", "freq", "counts")]
    jaxpr = jax.make_jaxpr(_sample_rows)(*args).jaxpr
    conds = [e for e in _eqns(jaxpr) if e.primitive.name == "cond"]
    # sample's own cond is under the vmap: batched, it is a select there
    assert len(conds) == 1
    (cond,) = conds
    assert cond.invars[0].aval.shape == ()  # a real branch, not a select
    sorts = [
        sum(e.primitive.name == "sort" for e in _eqns(br.jaxpr))
        for br in cond.params["branches"]
    ]
    assert sorted(sorts) == [0, 1], sorts
    # ... and nowhere else in the function
    assert sum(e.primitive.name == "sort" for e in _eqns(jaxpr)) == 1
    # and lowered: one case, the sort inside it
    text = _sample_rows.lower(*args).as_text()
    assert text.count("stablehlo.case") == 1
    assert text.count("stablehlo.sort") == 1
    assert text.index("stablehlo.case") < text.index("stablehlo.sort")


# ---- (b) the bounded walk against the full W-row walk --------------------

def _full_walk(blk, logits_v, base, n_spec, emit, seeds, steps, temp,
               top_k, top_p, pres, freq, counts, remaining, eos):
    """``_verify_emit`` as it was: a scan over all ``W`` rows."""
    S_, W, _V = logits_v.shape
    rows = jnp.arange(S_)
    j_idx = jnp.arange(W)[None, :]
    nxt_rows = jnp.clip(base[:, None] + j_idx + 1, 0, blk.shape[1] - 1)
    draft_next = jnp.take_along_axis(blk, nxt_rows, axis=1)
    has_draft = j_idx < n_spec[:, None]

    def vstep(carry, xs):
        counts, steps, remaining, stopped, ended, last, m = carry
        lg, dnext, hd = xs
        keys = _row_keys(seeds, steps)
        t = _vmapped_reference(lg, keys, temp, top_k, top_p, pres, freq,
                               counts)
        live = emit & ~stopped
        liv32 = live.astype(jnp.int32)
        t = jnp.where(live, t, 0)
        counts = counts.at[rows, t].add(liv32)
        steps = steps + liv32
        remaining = remaining - liv32
        end_now = live & ((t[:, None] == eos).any(-1) | (remaining <= 0))
        accept = live & hd & (dnext == t) & ~end_now
        last = jnp.where(live, t, last)
        m = m + liv32
        ended = ended | end_now
        stopped = stopped | (live & ~accept)
        return (counts, steps, remaining, stopped, ended, last, m), t

    init = (counts, steps, remaining, ~emit, jnp.zeros_like(emit),
            jnp.zeros(S_, jnp.int32), jnp.zeros(S_, jnp.int32))
    (counts, steps, remaining, _st, ended, last, m), toks = jax.lax.scan(
        vstep, init,
        (logits_v.transpose(1, 0, 2), draft_next.T, has_draft.T))
    return toks.T, last, m, ended, counts, steps, remaining


def _walk_case(seed: int, W: int, *, any_emit: bool = True,
               sampled: bool = False):
    """A packed block with drafts of random length whose first tokens are
    what the rows' greedy draws will be (so walks accept, then reject),
    budgets that run out inside the pass and an EOS id that some draw
    hits."""
    rng = np.random.default_rng(seed)
    C = 16
    n_spec = rng.integers(0, W, S).astype(np.int32)
    n_spec[rng.random(S) < 0.3] = 0  # plain decodes among them
    emit = (rng.random(S) < 0.75) if any_emit else np.zeros(S, bool)
    if any_emit:  # slot 0: the longest draft, every token of it right
        n_spec[0], emit[0] = W - 1, True
    n_valid = (1 + n_spec).astype(np.int32)
    base = np.maximum(n_valid - 1 - n_spec, 0).astype(np.int32)
    logits_v = rng.normal(0, 3, (S, W, V)).astype(np.float32)
    blk = rng.integers(1, V, (S, C)).astype(np.int32)
    for s in range(S):
        # drafts that will match a greedy draw
        good = n_spec[s] if s == 0 else rng.integers(0, n_spec[s] + 1)
        for j in range(good):
            blk[s, base[s] + j + 1] = logits_v[s, j].argmax()
    temp = (np.where(rng.random(S) < 0.5, 0.8, 0.0) if sampled
            else np.zeros(S)).astype(np.float32)
    # budgets: some end inside the pass
    remaining = rng.integers(1, W + 3, S).astype(np.int32)
    remaining[0] = W + 1
    # EOS: the second row's greedy draw of one slot, nothing of the rest
    eos = np.full((S, 2), -1, np.int32)
    hit = int(rng.integers(1, S))
    eos[hit, 0] = logits_v[hit, min(1, W - 1)].argmax()
    return tuple(jnp.asarray(a) for a in (
        blk, logits_v, base, n_spec, emit,
        rng.integers(0, 2**31 - 1, S).astype(np.int32),   # seeds
        rng.integers(0, 40, S).astype(np.int32),          # steps
        temp, np.zeros(S, np.int32), np.ones(S, np.float32),
        np.zeros(S, np.float32), np.zeros(S, np.float32),
        rng.integers(0, 3, (S, V)).astype(np.int32),      # counts
        remaining, eos,
    ))


WALK_OUTPUTS = ("tokens", "last", "m", "ended", "counts", "steps",
                "remaining")


@pytest.mark.parametrize("case", [
    dict(seed=0, W=9), dict(seed=1, W=9), dict(seed=2, W=9),
    dict(seed=3, W=5), dict(seed=4, W=9, sampled=True),
    dict(seed=5, W=5, sampled=True), dict(seed=6, W=1),
    dict(seed=7, W=9, any_emit=False),
], ids=lambda c: "-".join(f"{k}{v}" for k, v in c.items()))
def test_bounded_walk_equals_the_full_walk(case):
    ops = _walk_case(**case)
    want = jax.jit(_full_walk)(*ops)
    got = jax.jit(paged._verify_emit)(*ops)
    for name, g, w in zip(WALK_OUTPUTS, got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), name)
    n_spec, emit = np.asarray(ops[3]), np.asarray(ops[4])
    m = np.asarray(got[2])
    # no slot emits past its own draft + 1, none without emit
    assert (m <= np.where(emit, n_spec + 1, 0)).all()
    if case.get("any_emit", True) and not case.get("sampled"):
        assert m[0] == case["W"]  # a whole draft accepted, and the bonus


def test_walk_is_a_loop_with_a_traced_bound():
    """The walk's trip count is data (the longest emitting draft + 1), so
    no compiler pass can unroll or inline it: it stays the program's
    second loop at any ``spec_width``."""
    ops = _walk_case(seed=0, W=9)
    text = jax.jit(paged._verify_emit).lower(*ops).compile().as_text()
    whiles = [ln for ln in text[text.index("ENTRY"):].splitlines()
              if " while(" in ln]
    assert len(whiles) == 1
    assert "known_trip_count" not in whiles[0]


# ---- (e) the counters, on the tiny engine --------------------------------

@pytest.fixture(scope="module")
def tiny_engine():
    cfg = ModelConfig(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=64,
        dtype=jnp.float32, tie_embeddings=False,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    return GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,), max_seq_len=64
    )


COUNTERS = ("sampler_calls", "sampler_calls_sampled", "verify_rows_walked",
            "verify_rows_capacity")
REP = (3, 9) * 6


def _delta(ce, before: dict) -> dict:
    return {k: ce.stats[k] - before[k] for k in COUNTERS}


def test_epilogue_counters_follow_what_the_slots_ask(tiny_engine):
    """Per dispatched chunk, from what the host packed: a chunk of plain
    greedy decodes walks one verify row of ``spec_width`` and calls the
    sampler once a step, none of them sampled; one sampled slot makes
    every call of its chunks a sampled call; a drafting slot lengthens
    the walk to its draft + 1."""
    W, steps = 5, 3
    ce = ContinuousEngine(
        tiny_engine, max_slots=3, page_size=8, chunk_steps=steps,
        spec_decode=True, spec_draft=W - 1,
    )
    keys = tuple(ce.stats)
    at = keys.index("chunk_us_between")  # they precede the chunk_us_* family
    assert keys[at - 4:at] == COUNTERS
    # plain greedy: two chunks of three steps
    s0 = dict(ce.stats)
    a = ce.submit([1, 2, 3], max_new_tokens=6, seed=1)
    ce.run_until_idle()
    assert a.finished and len(a.tokens) == 6
    d = _delta(ce, s0)
    chunks = (ce.stats["verify_rows_capacity"]
              - s0["verify_rows_capacity"]) // W
    assert chunks == 2
    assert d == {"sampler_calls": 2 * steps, "sampler_calls_sampled": 0,
                 "verify_rows_walked": 2, "verify_rows_capacity": 2 * W}
    # a chunk that dispatches nothing counts nothing
    s1 = dict(ce.stats)
    ce.step_chunk(admit_only=True)
    assert _delta(ce, s1) == dict.fromkeys(COUNTERS, 0)
    # one sampled slot beside a greedy one: every call of the chunk sorts
    b = ce.submit([4, 5], max_new_tokens=3, seed=2,
                  sampling=SamplingParams.make(temperature=0.8, top_k=5))
    c = ce.submit([6, 7], max_new_tokens=3, seed=3)
    ce.run_until_idle()
    assert b.finished and c.finished
    d = _delta(ce, s1)
    assert d["sampler_calls"] == steps == d["sampler_calls_sampled"]
    assert d["verify_rows_walked"] == 1
    # ... and the slot's release puts its temperature back: plain again
    s2 = dict(ce.stats)
    ce.submit([8, 9], max_new_tokens=3, seed=4)
    ce.run_until_idle()
    assert _delta(ce, s2)["sampler_calls_sampled"] == 0
    # a drafting slot: the walk is as long as the draft it was granted
    s3 = dict(ce.stats)
    r = ce.submit(list(REP), max_new_tokens=12, seed=5, speculative=True)
    drafted = []
    while ce.step_chunk():
        drafted.append(ce.recorder.records()[-1]["spec_drafted"])
    drafted.append(ce.recorder.records()[-1]["spec_drafted"])
    assert r.finished and max(drafted) >= 1, drafted
    d = _delta(ce, s3)
    n_chunks = d["verify_rows_capacity"] // W
    recs = ce.recorder.records()[-n_chunks:]
    assert d["verify_rows_walked"] == sum(
        rec["spec_drafted"] + 1 for rec in recs)
    assert d["verify_rows_walked"] > n_chunks
    assert d["sampler_calls"] == d["verify_rows_walked"] + sum(
        rec["decode_steps"] - 1 for rec in recs)
    assert d["sampler_calls_sampled"] == 0
    snap = ce.serving_snapshot()
    for k in COUNTERS:
        assert snap[k] == ce.stats[k]
    text = ce.metrics.render({"model": "tiny"})
    for k in COUNTERS:
        assert f"tlink_engine_{k}_total" in text
    ce.check_page_conservation()
    ce.close()
