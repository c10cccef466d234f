"""SLO-aware request scheduling (engine/scheduler.py + its engine wiring).

The subsystem under test is the POLICY layer over PR 2/3's mechanisms:
priority classes with starvation-free aging, cache-backed preemption, and
bounded queues with backpressure. The hard contracts pinned here:

- a preempted-then-resumed request's stream is BIT-identical to an
  uninterrupted run (solo and co-batched — preemption rides the exact
  crash-recovery re-prefill semantics);
- page conservation holds mid-preemption and after a failed
  re-admission;
- an aged ``best_effort`` request completes under sustained
  ``interactive`` load (no starvation);
- preemption/re-admission add ZERO compiled programs (the jit-cache
  guard extends over scheduler churn);
- past the class queue cap, submission fails fast with the 429-shaped
  rejection record instead of queueing forever.
"""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from tensorlink_tpu.engine.continuous import ContinuousEngine
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.sampling import SamplingParams
from tensorlink_tpu.engine.scheduler import (
    PRIORITY_RANK,
    RequestScheduler,
    SchedulerOverloaded,
    normalize_priority,
)
from tensorlink_tpu.models import ModelConfig, init_params


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


def _cont(eng, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("chunk_steps", 4)
    return ContinuousEngine(eng, **kw)


def _solo(eng, prompt, n, *, sampling=None, seed=0):
    ce = _cont(eng)
    req = ce.submit(prompt, max_new_tokens=n, sampling=sampling, seed=seed)
    ce.run_until_idle()
    return req.tokens


class _Req:
    """Bare queued-entry stand-in for the pure-policy unit tests."""

    def __init__(self, priority="interactive"):
        self.priority = priority
        self.sched_seq = 0
        self.enqueue_tick = 0
        self.enqueue_t = 0.0
        self.admit_rank = -1


# ---------------------------------------------------------------------------
# policy unit tests (no engine, no device)
# ---------------------------------------------------------------------------
def test_class_ordering_fifo_within_class():
    s = RequestScheduler(max_slots=2)
    batch1 = _Req("batch")
    inter1 = _Req("interactive")
    inter2 = _Req("interactive")
    best = _Req("best_effort")
    for r in (batch1, inter1, best, inter2):
        s.push(r)
    # interactive beats batch beats best_effort; FIFO within a class
    order = []
    while len(s):
        r = s.select()
        order.append(r)
        s.remove(r)
    assert order == [inter1, inter2, batch1, best]


def test_normalize_priority_clamps_unknown():
    assert normalize_priority("BATCH") == "batch"
    assert normalize_priority(None) == "interactive"
    assert normalize_priority("turbo") == "interactive"


def test_aging_promotes_queued_rank():
    s = RequestScheduler(max_slots=1, aging_ticks=4)
    old_best = _Req("best_effort")
    s.push(old_best)
    for _ in range(8):  # 8 ticks / 4 per rank = rank 2 -> 0
        s.tick()
    new_inter = _Req("interactive")
    s.push(new_inter)
    assert s.effective_rank(old_best) == 0
    # equal effective rank -> FIFO: the aged best_effort wins the slot
    assert s.select() is old_best


def test_fcfs_policy_is_strict_arrival_order():
    s = RequestScheduler(max_slots=2, policy="fcfs")
    best = _Req("best_effort")
    inter = _Req("interactive")
    s.push(best)
    s.push(inter)
    assert s.select() is best  # arrival order, classes ignored
    # and fcfs never preempts
    best.admit_rank = PRIORITY_RANK["best_effort"]
    assert s.victim([best], inter) is None


def test_victim_selection_rank_then_recency():
    s = RequestScheduler(max_slots=4)
    running = []
    for i, cls in enumerate(
        ("interactive", "batch", "best_effort", "best_effort")
    ):
        r = _Req(cls)
        s.push(r)
        s.remove(r)
        r.admit_rank = PRIORITY_RANK[cls]
        running.append(r)
    cand = _Req("interactive")
    s.push(cand)
    # worst class first; within best_effort, the most recently admitted
    # (highest seq = least sunk decode work)
    assert s.victim(running, cand) is running[3]
    # a candidate that outranks nobody gets no victim
    lowly = _Req("best_effort")
    s.push(lowly)
    assert s.victim(running, lowly) is None
    # an aged-into-its-slot request (admit_rank 0) is shielded even from
    # interactive candidates — aging is a guarantee, not a treadmill
    for r in running:
        r.admit_rank = 0
    assert s.victim(running, cand) is None


def test_preempting_long_running_victim_is_not_futile():
    """A victim that RAN long enough to have aged (had it been queued)
    must not win the freed slot back from the candidate it was preempted
    for: requeue restarts the aging clock, so ticks spent running never
    count as waiting."""
    s = RequestScheduler(max_slots=1, aging_ticks=4)
    b = _Req("batch")
    s.push(b)
    s.remove(b)
    s.note_admitted(b)
    for _ in range(8):  # b RUNS for 8 ticks (2 aging periods)
        s.tick()
    cand = _Req("interactive")
    s.push(cand)
    assert s.victim([b], cand) is b  # admit_rank 1 > 0: eligible
    s.requeue(b)
    # the whole point of the preemption: the candidate gets the slot
    assert s.select() is cand
    # and b still ages from here — parked forever it is not
    for _ in range(4):
        s.tick()
    assert s.effective_rank(b) == 0


def test_victim_recency_is_admission_order_not_arrival_order():
    """'Most recently admitted' means least sunk decode work SINCE the
    latest (re)admission — an early arrival that just re-admitted is the
    cheaper victim than a later arrival that has decoded for ages."""
    s = RequestScheduler(max_slots=2)
    early, late = _Req("best_effort"), _Req("best_effort")
    s.push(early)
    s.push(late)
    for r in (late, early):  # late admitted FIRST, early re-admits after
        s.remove(r)
        s.note_admitted(r)
    assert early.sched_seq < late.sched_seq
    assert early.admit_seq > late.admit_seq
    cand = _Req("interactive")
    s.push(cand)
    # arrival order would pick `late` (newest seq, most sunk work);
    # admission order correctly picks `early`
    assert s.victim([early, late], cand) is early


def test_requeue_preserves_arrival_order_and_skips_cap():
    s = RequestScheduler(max_slots=1, queue_cap=2)
    a, b = _Req("batch"), _Req("batch")
    s.push(a)
    s.push(b)
    s.remove(a)  # a admitted
    a.admit_rank = PRIORITY_RANK["batch"]
    s.requeue(a)  # a preempted: cap is full but requeue never rejects
    assert s.depth("batch") == 2
    assert s.by_class["batch"].preempted == 1
    # original seq preserved -> a re-admits ahead of b
    assert s.select() is a


def test_queue_cap_rejects_with_429_record():
    s = RequestScheduler(max_slots=1, queue_cap=2)
    s.push(_Req("batch"))
    s.push(_Req("batch"))
    with pytest.raises(SchedulerOverloaded) as ei:
        s.push(_Req("batch"))
    e = ei.value
    assert e.priority == "batch" and e.queue_depth == 2 and e.cap == 2
    assert e.retry_after >= 0.0
    # other classes keep their own headroom
    s.push(_Req("interactive"))
    # admission_check mirrors the same bounds without mutating the queue
    rej = s.admission_check("batch")
    assert rej is not None and rej["cap"] == 2
    assert rej["retry_after"] >= 1.0
    assert s.admission_check("best_effort") is None


def test_drain_fence_rejects_all_classes():
    """Live-migration admission fence: a draining scheduler takes no new
    work — push fails fast, admission_check rejects with the draining
    marker — and lowering the fence restores normal admission."""
    s = RequestScheduler(max_slots=2, queue_cap=8)
    s.push(_Req("interactive"))  # pre-drain work stays queued
    s.set_draining(True)
    for cls in ("interactive", "batch", "best_effort"):
        with pytest.raises(SchedulerOverloaded):
            s.push(_Req(cls))
        rej = s.admission_check(cls)
        assert rej is not None and rej.get("draining") is True
    assert len(s) == 1  # the fence admitted nothing
    s.set_draining(False)
    s.push(_Req("batch"))
    assert s.admission_check("batch") is None
    assert len(s) == 2


def test_estimated_wait_backpressure():
    s = RequestScheduler(max_slots=1, queue_cap=64, max_wait_s=2.0)
    # teach the estimator: ~1s per request on the single slot
    for _ in range(4):
        s.note_finished(_Req(), 1.0)
    for _ in range(3):
        s.push(_Req("interactive"))
    # 3 queued ahead x ~1s on 1 slot > 2s bar -> reject with a finite hint
    rej = s.admission_check("interactive")
    assert rej is not None
    assert 1.0 <= rej["retry_after"] <= 600.0
    # a best_effort arrival is judged against MORE of the queue, never less
    assert s.estimate_wait("best_effort") >= s.estimate_wait("interactive")


# ---------------------------------------------------------------------------
# preemption correctness on the real engine
# ---------------------------------------------------------------------------
def test_preempt_resume_stream_bit_identical_co_batched(tiny_engine):
    """THE preemption pin: low-class residents preempted by interactive
    arrivals (slots full) re-queue, re-admit through the prefix cache,
    and every stream — preempted and preemptor, greedy and sampled — is
    bit-identical to its uninterrupted solo run."""
    eng = tiny_engine
    ce = _cont(eng, sched_aging_ticks=1000)  # isolate preemption from aging
    mixes_low = [
        ([1, 2, 3], 14, SamplingParams.make(temperature=0.9, top_k=5), 1),
        ([4, 5], 14, SamplingParams.make(), 2),
        ([9, 8, 7], 14, SamplingParams.make(temperature=0.7, top_p=0.9), 3),
        ([6, 6], 14, SamplingParams.make(), 4),
    ]
    low = [
        ce.submit(p, max_new_tokens=n, sampling=sp, seed=seed,
                  priority="best_effort")
        for p, n, sp, seed in mixes_low
    ]
    ce.step_chunk()  # all four slots taken by best_effort work
    assert ce.live_slots == 4
    mixes_hi = [
        ([11, 12], 6, SamplingParams.make(temperature=0.8), 21),
        ([13], 6, SamplingParams.make(), 22),
    ]
    hi = [
        ce.submit(p, max_new_tokens=n, sampling=sp, seed=seed,
                  priority="interactive")
        for p, n, sp, seed in mixes_hi
    ]
    ce.run_until_idle()
    assert ce.stats["preemptions"] >= 2
    snap = ce.serving_snapshot()
    assert snap["sched_classes"]["best_effort"]["preempted"] >= 2
    for req, (p, n, sp, seed) in zip(low + hi, mixes_low + mixes_hi):
        assert req.finished
        assert req.tokens == _solo(eng, p, n, sampling=sp, seed=seed), (
            req.priority, p
        )
    ce.close()


def test_preempted_request_tokens_stream_exactly_once(tiny_engine):
    """Tokens emitted before a preemption are never re-delivered: the
    stream callback sees each position exactly once, in order, across
    the preempt -> resume boundary."""
    eng = tiny_engine
    ce = _cont(eng, sched_aging_ticks=1000)
    seen: list[int] = []
    victim = ce.submit(
        [2, 4, 6], max_new_tokens=16, seed=5, priority="best_effort",
        stream_cb=lambda t: seen.append(t) and False,
    )
    fillers = [
        ce.submit([i + 1], max_new_tokens=16, seed=i, priority="best_effort")
        for i in range(3)
    ]
    ce.step_chunk()
    assert victim.tokens and not seen  # decoding; its tokens wait to leave
    pre = ce.submit([9, 9], max_new_tokens=4, seed=30,
                    priority="interactive")
    ce.run_until_idle()
    assert ce.stats["preemptions"] >= 1
    assert all(r.finished for r in [victim, pre, *fillers])
    assert seen == victim.tokens  # no dupes, no gaps, order preserved
    assert victim.tokens == _solo(eng, [2, 4, 6], 16, seed=5)
    ce.close()


def test_page_conservation_through_preemption_churn(tiny_engine):
    """free + slot-owned + cache-resident == total at EVERY chunk
    boundary while preemption churns slots, and at teardown."""
    eng = tiny_engine
    ce = _cont(eng, sched_aging_ticks=1000)
    for i in range(4):
        ce.submit([i + 1, i + 2], max_new_tokens=12, seed=i,
                  priority="best_effort")
    ce.step_chunk()
    for i in range(3):
        ce.submit([20 + i], max_new_tokens=4, seed=40 + i,
                  priority="interactive")
    while ce.has_work():
        ce.step_chunk()
        ce.check_page_conservation()
    assert ce.stats["preemptions"] >= 1
    ce.close()


def test_failed_readmission_keeps_conservation_and_resumes(tiny_engine):
    """A preempted request whose re-admission finds the allocator dry
    stays QUEUED (head-of-line, like PR 3's page-wait) with conservation
    intact, then resumes bit-identically once pages free up."""
    eng = tiny_engine
    ce = _cont(eng, max_slots=2, sched_aging_ticks=1000)
    victim = ce.submit([3, 1, 4], max_new_tokens=12, seed=7,
                       priority="best_effort")
    ce.step_chunk()
    emitted_before = len(victim.tokens)
    assert emitted_before > 0
    # tighten the pool so the victim's re-admission cannot fit, then
    # trigger the preemption with an interactive arrival. (The held pages
    # are outside the engine's ownership sets, so mid-churn we assert
    # disjointness + the exact held-adjusted total; the FULL invariant is
    # re-checked the moment they're returned.)
    held = ce.alloc.alloc(ce.alloc.n_free)

    def conserved_with_held():
        acc = ce.page_accounting()
        free, cached, slots = acc["free"], acc["cached"], acc["slots"]
        assert len(slots) == len(set(slots))
        assert not (free & cached) and not (set(slots) & (free | cached))
        assert not (set(held) & (free | cached | set(slots)))
        assert (
            len(free) + len(cached) + len(slots) + len(held)
            == ce.cache.n_pages - 1
        )

    pre = ce.submit([8, 8], max_new_tokens=2, seed=9,
                    priority="interactive")
    ce.step_chunk()
    assert ce.stats["preemptions"] >= 1
    assert not victim.finished and victim.slot == -1  # parked, not lost
    conserved_with_held()
    for _ in range(3):  # churn while parked: still conserved
        ce.step_chunk()
        conserved_with_held()
    ce.alloc.free(held)
    ce.check_page_conservation()
    ce.run_until_idle()
    assert victim.finished and pre.finished
    assert victim.tokens == _solo(eng, [3, 1, 4], 12, seed=7)
    ce.close()


def test_preemption_mid_prefill_is_safe(tiny_engine):
    """Preempting a slot that is still CHUNK-PREFILLING (no token out
    yet) unwinds to a clean re-queue: the stream still matches solo."""
    eng = tiny_engine
    ce = _cont(eng, max_slots=1, prefill_chunk=8, sched_aging_ticks=1000)
    long_prompt = list(range(1, 33))  # 32 tokens -> 4 prefill ticks
    victim = ce.submit(long_prompt, max_new_tokens=6, seed=3,
                       priority="best_effort")
    ce.step_chunk(admit_only=True)
    ce.step_chunk()  # one 8-token grant lands: partially prefilled,
    # zero tokens emitted (the prompt needs 4 grants)
    assert 0 < victim.prefill_pos < len(long_prompt)
    pre = ce.submit([5], max_new_tokens=3, seed=4, priority="interactive")
    ce.run_until_idle()
    assert ce.stats["preemptions"] >= 1
    assert victim.finished and pre.finished
    ce.check_page_conservation()
    assert victim.tokens == _solo(eng, long_prompt, 6, seed=3)
    ce.close()


def test_no_starvation_best_effort_completes_under_load(tiny_engine):
    """The aging guarantee: a best_effort request queued behind sustained
    interactive pressure on a full slot set still completes — and once
    aged into its slot it is NOT re-preempted by newer interactive
    arrivals (admit_rank shield)."""
    eng = tiny_engine
    ce = _cont(eng, max_slots=2, sched_aging_ticks=2)
    lowly = ce.submit([7, 7, 7], max_new_tokens=4, seed=50,
                      priority="best_effort")
    seq = 0
    live: list = []
    for _ in range(40):  # sustained interactive load, slots contested
        while len([r for r in live if not r.finished]) < 3:
            seq += 1
            live.append(
                ce.submit([seq % 30 + 1], max_new_tokens=4, seed=seq,
                          priority="interactive")
            )
        ce.step_chunk()
        if lowly.finished:
            break
    assert lowly.finished, "best_effort starved under interactive load"
    assert lowly.tokens == _solo(eng, [7, 7, 7], 4, seed=50)
    ce.run_until_idle()
    ce.close()


def test_jit_cache_fixed_across_preemption_and_readmission(tiny_engine):
    """The PR 2/3 compile-set guard EXTENDED over the scheduler: once the
    feature programs have fired, preemption, re-queue and cache-walking
    re-admission are all DATA — zero new compiled programs."""
    eng = tiny_engine
    ce = _cont(eng, sched_aging_ticks=1000)
    pre = ce.jit_cache_sizes()
    # warm every program preemption can touch: the step program AND the
    # COW page copy — a preempted request's re-admission walks the cache
    # like any admission, so a partial-page hit may fire copy_page (it
    # is warmed ONCE here; churn below must add nothing)
    ce.submit(list(range(1, 25)), max_new_tokens=3, seed=0)  # 3 full pages
    ce.run_until_idle()
    # diverges at position 22, mid-cached-page 3 -> fires the COW copy
    ce.submit(list(range(1, 23)) + [99, 98], max_new_tokens=3, seed=0)
    ce.run_until_idle()
    base = ce.jit_cache_sizes()
    # the COW copy really ran (warm); its compile-count is a DELTA, not
    # an absolute — jit caches are process-global and an earlier module
    # serving a different engine shape leaves its own copy_page program
    # resident (tlint TL006's order-dependence class)
    assert ce.prefix.stats["cow_copies"] >= 1
    assert 0 <= base["copy_page"] - pre["copy_page"] <= 1
    for i in range(4):
        ce.submit([i + 1, i + 2], max_new_tokens=10, seed=i,
                  priority="best_effort")
    ce.step_chunk()
    for i in range(3):
        ce.submit([40 + i], max_new_tokens=4, seed=60 + i,
                  priority="interactive")
    ce.run_until_idle()
    assert ce.stats["preemptions"] >= 1
    assert ce.jit_cache_sizes() == base, (base, ce.jit_cache_sizes())
    ce.close()


# ---------------------------------------------------------------------------
# backpressure + telemetry on the engine and batcher
# ---------------------------------------------------------------------------
def test_engine_queue_cap_fails_fast(tiny_engine):
    """Past the class cap, submit() fails the request immediately with
    SchedulerOverloaded on req.error — the engine-side 429 backstop."""
    ce = _cont(tiny_engine, max_slots=1, sched_queue_cap=2)
    ok = [
        ce.submit([i + 1], max_new_tokens=2, seed=i, priority="batch")
        for i in range(2)
    ]
    rej = ce.submit([9], max_new_tokens=2, seed=9, priority="batch")
    assert rej.done.is_set() and isinstance(rej.error, SchedulerOverloaded)
    assert rej.error.queue_depth == 2 and rej.error.cap == 2
    # other classes still admit (per-class caps)
    other = ce.submit([8], max_new_tokens=2, seed=8, priority="interactive")
    ce.run_until_idle()
    assert all(r.finished for r in [*ok, other])
    snap = ce.serving_snapshot()
    assert snap["sched_rejected"] >= 1
    assert snap["sched_classes"]["batch"]["rejected"] >= 1
    ce.close()


def test_serving_snapshot_carries_scheduler_telemetry(tiny_engine):
    """The /stats contract: per-class queue depth, queue-wait and TTFT
    percentiles, admissions/preemptions/rejections all ride
    serving_snapshot() (and from there ContinuousBatcher.stats() and the
    validator's /stats, like the prefix-cache counters)."""
    ce = _cont(tiny_engine)
    ce.submit([1, 2], max_new_tokens=3, seed=1, priority="interactive")
    ce.submit([3], max_new_tokens=3, seed=2, priority="batch")
    ce.run_until_idle()
    snap = ce.serving_snapshot()
    assert snap["sched_policy"] == "slo"
    assert snap["sched_queue_depth"] == 0
    for cls in ("interactive", "batch", "best_effort"):
        sub = snap["sched_classes"][cls]
        for key in (
            "queue_depth", "admitted", "rejected", "preempted",
            "queue_wait_ms_p50", "queue_wait_ms_p95",
            "ttft_ms_p50", "ttft_ms_p95",
        ):
            assert key in sub, (cls, key)
    assert snap["sched_classes"]["interactive"]["admitted"] == 1
    assert snap["sched_classes"]["batch"]["admitted"] == 1
    assert snap["sched_classes"]["interactive"]["ttft_ms_p50"] > 0
    ce.close()


def test_batcher_priority_passthrough_and_admission_check(tiny_engine):
    """ContinuousBatcher forwards the request's class to the engine
    scheduler and exposes admission_check for the API's 429 gate."""
    from tensorlink_tpu.ml.batching import ContinuousBatcher

    b = ContinuousBatcher(
        engine=tiny_engine, eos_ids=[], max_slots=4, page_size=8,
        chunk_steps=4, sched_queue_cap=3,
    )
    assert b.admission_check("interactive") is None
    out: dict = {}

    def run(i, pr):
        out[i] = b.generate(
            [i + 1], max_new_tokens=3, priority=pr
        )

    ts = [
        threading.Thread(target=run, args=(0, "interactive")),
        threading.Thread(target=run, args=(1, "batch")),
        threading.Thread(target=run, args=(2, "best_effort")),
    ]
    for t in ts:
        t.start()
        time.sleep(0.01)
    for t in ts:
        t.join(30)
    assert sorted(out) == [0, 1, 2]
    st = b.stats()
    cls = st["engine"]["sched_classes"]
    assert cls["interactive"]["admitted"] == 1
    assert cls["batch"]["admitted"] == 1
    assert cls["best_effort"]["admitted"] == 1
    b.close()


def test_fcfs_engine_policy_never_preempts(tiny_engine):
    """MLConfig.sched_policy="fcfs" reproduces the PR 2 behavior: strict
    arrival order, zero preemptions, streams still exact."""
    eng = tiny_engine
    ce = _cont(eng, sched_policy="fcfs")
    low = [
        ce.submit([i + 1], max_new_tokens=8, seed=i, priority="best_effort")
        for i in range(4)
    ]
    ce.step_chunk()
    hi = ce.submit([9, 9], max_new_tokens=4, seed=9, priority="interactive")
    ce.run_until_idle()
    assert ce.stats["preemptions"] == 0
    assert all(r.finished for r in [*low, hi])
    assert hi.tokens == _solo(eng, [9, 9], 4, seed=9)
    ce.close()


def test_preempt_then_crash_then_recover_stream_exact(tiny_engine):
    """Preemption composed with the chaos-suite crash shape: a request is
    preempted mid-flight, resumes, then its worker "dies" (fresh engine,
    fresh allocator — the recovery path's replacement) and the request
    re-submits prompt + delivered with start_step. The final stream is
    bit-identical to the uninterrupted solo run: preemption and crash
    recovery ride the same re-prefill + fold_in(seed, n) contract, so
    they compose."""
    eng = tiny_engine
    sp = SamplingParams.make(temperature=0.9, top_k=5)
    want = _solo(eng, [2, 4, 6], 14, sampling=sp, seed=77)

    ce = _cont(eng, sched_aging_ticks=1000)
    victim = ce.submit([2, 4, 6], max_new_tokens=14, sampling=sp, seed=77,
                       priority="best_effort")
    for i in range(3):
        ce.submit([i + 1], max_new_tokens=14, seed=i,
                  priority="best_effort")
    ce.step_chunk()
    ce.submit([9, 9], max_new_tokens=6, seed=30, priority="interactive")
    # drive until the victim has been preempted AND re-admitted and
    # emitted a few post-resume tokens — then "crash"
    for _ in range(60):
        ce.step_chunk()
        if ce.stats["preemptions"] >= 1 and not victim.finished \
                and victim.slot >= 0 and len(victim.tokens) >= 4:
            break
    assert ce.stats["preemptions"] >= 1
    delivered = list(victim.tokens)
    ce.close()  # the worker dies with its slots

    # the replacement worker: fresh engine state, recovery re-submission
    ce2 = _cont(eng, sched_aging_ticks=1000)
    resumed = ce2.submit(
        [2, 4, 6] + delivered, max_new_tokens=14 - len(delivered),
        sampling=sp, seed=77, start_step=len(delivered),
        priority="best_effort",
    )
    ce2.run_until_idle()
    assert delivered + resumed.tokens == want
    ce2.close()
