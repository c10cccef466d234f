"""Continuous batching over the paged KV cache (engine/paged.py,
engine/continuous.py, ml/batching.py::ContinuousBatcher).

The determinism contract under test: a request decodes token-for-token
identically whether it runs alone, co-resident with any neighbor mix,
admitted mid-flight, or resumed after a crash — per-slot stateless RNG
(fold_in(seed, n)) plus slot-local attention make this exact, not
approximate. Plus the compile-set bound: the slot-batched decode is ONE
program a width of the packed block (``block_widths``: at most two)
regardless of request mix."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine.continuous import ContinuousEngine
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.sampling import SamplingParams
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


# ---------------------------------------------------------------------------
# parity: co-batched == solo, token for token
# ---------------------------------------------------------------------------
def test_continuous_parity_with_mid_flight_admission(tiny_engine):
    """Each request's stream is bit-identical to its solo decode — greedy
    and sampled rows mixed, one request admitted WHILE the others are
    mid-flight (the acceptance criterion's exact shape)."""
    eng = tiny_engine
    mixes = [
        ([1, 2, 3], 12, SamplingParams.make(temperature=0.9, top_k=5), 1),
        ([4, 5], 6, SamplingParams.make(), 2),
        ([9, 8, 7, 6], 10, SamplingParams.make(temperature=0.7, top_p=0.9), 3),
    ]
    ce = _cont(eng)
    r0 = ce.submit(mixes[0][0], max_new_tokens=mixes[0][1],
                   sampling=mixes[0][2], seed=mixes[0][3])
    r1 = ce.submit(mixes[1][0], max_new_tokens=mixes[1][1],
                   sampling=mixes[1][2], seed=mixes[1][3])
    ce.step_chunk()  # r0/r1 are now mid-flight
    assert ce.live_slots >= 1
    r2 = ce.submit(mixes[2][0], max_new_tokens=mixes[2][1],
                   sampling=mixes[2][2], seed=mixes[2][3])
    ce.run_until_idle()
    for req, (prompt, n, sp, seed) in zip((r0, r1, r2), mixes):
        assert req.finished
        assert req.tokens == _solo(eng, prompt, n, sampling=sp, seed=seed)


def test_continuous_greedy_matches_dense_compiled(tiny_engine):
    """Greedy through the paged slot path emits exactly the dense compiled
    loop's tokens — the paged attention + scatter write is the same math
    as the contiguous cache, not an approximation of it."""
    eng = tiny_engine
    prompt = [3, 1, 4, 1, 5]
    ref = eng.generate_compiled([prompt], max_new_tokens=16).sequences[0]
    assert _solo(eng, prompt, 16) == ref


def test_continuous_recovery_resume_is_exact(tiny_engine):
    """The PR-1 re-prefill recovery shape: resubmitting prompt + emitted
    with start_step=len(emitted) continues the stream bit-identically
    (per-token keys are stateless in the step index)."""
    eng = tiny_engine
    sp = SamplingParams.make(temperature=1.0, top_p=0.9)
    full = _solo(eng, [5, 6, 7], 10, sampling=sp, seed=9)
    cut = 4
    ce = _cont(eng)
    resumed = ce.submit(
        [5, 6, 7] + full[:cut], max_new_tokens=10 - cut, sampling=sp,
        seed=9, start_step=cut,
    )
    ce.run_until_idle()
    assert full[:cut] + resumed.tokens == full


# ---------------------------------------------------------------------------
# bounded compile set
# ---------------------------------------------------------------------------
def test_slot_batched_decode_program_count_is_fixed(tiny_engine):
    """The compiled decode/sampling program count must not depend on the
    request mix — ragged lengths, admissions, evictions and knob mixes are
    all DATA to the one slot-batched program."""
    eng = tiny_engine
    # jit caches are PROCESS-global (module-level jitted functions in
    # engine/paged.py) — any earlier test module that served a different
    # model config leaves its programs in the same cache, so an absolute
    # `ragged_step == 1` would be order-dependent (tlint TL006's leak
    # class). Count THIS engine's contribution as a delta from the
    # process state at test start.
    ce = _cont(eng)
    pre = ce.jit_cache_sizes()  # before this engine compiled anything
    # one chunk of each width of the ladder: a prompt longer than a page
    # packs the wide block, the decode chunk after it the narrow one
    ce.submit([100] * 9, max_new_tokens=6)
    ce.run_until_idle()
    assert {r["block_rows"] for r in ce.recorder.records()} == set(
        ce.block_widths)
    base = ce.jit_cache_sizes()
    # churn: different lengths, budgets, knobs, staggered admission
    reqs = [
        ce.submit(list(range(1, 2 + i)), max_new_tokens=2 + 3 * i,
                  sampling=SamplingParams.make(temperature=0.3 * i),
                  seed=i)
        for i in range(3)
    ]
    ce.step_chunk()
    late = ce.submit([7] * 9, max_new_tokens=5, seed=99)
    ce.run_until_idle()
    assert all(r.finished for r in [*reqs, late])
    after = ce.jit_cache_sizes()
    assert after == base, (base, after)
    # at most ONE step-program compile A WIDTH OF THE LADDER (two) across
    # this whole test — zero when an earlier test already compiled the
    # same-shaped programs (same process-global cache, same tiny config:
    # even this module's own earlier tests do), two when this test ran
    # first. The teeth are the delta bound + `after == base` above:
    # request-mix churn never adds a program (delta, not absolute — the
    # order-dependence note)
    assert 0 <= after["ragged_step"] - pre["ragged_step"] <= len(
        ce.block_widths)
    # the prefix cache must not add per-mix compiles either: once every
    # feature program has fired ONCE (the step program at base, COW copy
    # on the first divergent hit), multi-chunk prompts, cache hits
    # (full-page and COW-partial), misses and evictions are all DATA —
    # the compiled set stays frozen across any further mix
    long = [5, 9] * 12
    ce.submit(long, max_new_tokens=3, seed=7)  # miss -> promoted
    ce.run_until_idle()
    ce.submit(long[:20] + [2, 2, 2, 2], max_new_tokens=3, seed=8)  # COW
    ce.run_until_idle()
    warm = ce.jit_cache_sizes()
    assert warm["ragged_step"] == after["ragged_step"]  # no growth yet
    ce.submit(long + [3], max_new_tokens=3, seed=9)  # full-page + COW hit
    ce.submit(long[:-1] + [2, 2], max_new_tokens=4, seed=10)
    ce.submit([6] * 31, max_new_tokens=2, seed=11)  # different miss shape
    ce.run_until_idle()
    assert ce.jit_cache_sizes() == warm, (warm, ce.jit_cache_sizes())


# ---------------------------------------------------------------------------
# unified ragged prefill+decode step (the only serving path — the legacy
# two-program fallback completed its one-release window and was retired)
# ---------------------------------------------------------------------------
def test_legacy_path_is_retired(tiny_engine):
    """The PR-6 fallback window closed: the monolithic dense-prefill
    admission (prefill_chunk=0) refuses loudly, the unified_step flag is
    gone from the engine API, and the compile-set keys no longer carry
    the legacy two-program pair."""
    with pytest.raises(ValueError, match="prefill_chunk"):
        _cont(tiny_engine, prefill_chunk=0)
    with pytest.raises(TypeError):
        _cont(tiny_engine, unified_step=True)
    sizes = _cont(tiny_engine).jit_cache_sizes()
    assert "decode_chunk" not in sizes and "prefill_chunk" not in sizes
    assert "ragged_step" in sizes and "copy_page" in sizes


def test_unified_step_is_one_program(tiny_engine):
    """The PR-6 acceptance bar, still standing after the legacy path's
    retirement: the ENTIRE serving hot loop is one compiled step program
    a width of the ladder (plus the COW ``copy_page``) — admission, mixed prefill/decode
    churn, preemption and recovery-shaped resume add ZERO compiles.
    Deltas, not absolutes: jit caches are process-global (the TL006
    order-dependence note on the guard above)."""
    eng = tiny_engine
    ce = _cont(eng, sched_aging_ticks=1000)
    pre = ce.jit_cache_sizes()
    # warm: a multi-chunk miss (promoted at eviction), then a mid-page
    # divergence so the COW copy fires once
    long = [5, 9] * 12
    ce.submit(long, max_new_tokens=3, seed=7)
    ce.run_until_idle()
    ce.submit(long[:20] + [2, 2, 2, 2], max_new_tokens=3, seed=8)
    ce.run_until_idle()
    base = ce.jit_cache_sizes()
    assert 0 <= base["ragged_step"] - pre["ragged_step"] <= len(
        ce.block_widths)  # one program a width of the ladder
    assert 0 <= base["copy_page"] - pre["copy_page"] <= 1
    # churn: staggered mixed admissions (prefill riding decode chunks),
    # deterministic preemption (batch residents, interactive arrival),
    # and a recovery-shaped resume — all DATA to the one program
    holders = [
        ce.submit([3 + i] * 9, max_new_tokens=30, seed=i, priority="batch")
        for i in range(ce.max_slots)
    ]
    ce.step_chunk()
    vip = ce.submit(long + [3], max_new_tokens=4, seed=9,
                    priority="interactive")
    ce.run_until_idle()
    assert vip.finished and all(r.finished for r in holders)
    assert ce.stats["preemptions"] >= 1
    sp = SamplingParams.make(temperature=1.0, top_p=0.9)
    full = ce.submit([5, 6, 7], max_new_tokens=10, sampling=sp, seed=9)
    ce.run_until_idle()
    resumed = ce.submit(
        [5, 6, 7] + full.tokens[:4], max_new_tokens=6, sampling=sp,
        seed=9, start_step=4,
    )
    ce.run_until_idle()
    assert full.tokens[:4] + resumed.tokens == full.tokens
    after = ce.jit_cache_sizes()
    assert after == base, (base, after)
    ce.check_page_conservation()


def test_pack_prefill_budgets_unit():
    """The host-side token-budget assembly in isolation: full-chunk
    grants with no budget, exact round-robin fairness under one, and the
    degenerate inputs the engine can hand it."""
    from tensorlink_tpu.engine.continuous import pack_prefill_budgets

    # no budget: every slot gets min(chunk, remaining)
    assert pack_prefill_budgets([100, 3, 8], 8) == [8, 3, 8]
    # budget below demand: round-robin one token at a time, slot order
    assert pack_prefill_budgets([8, 8], 8, budget=10) == [5, 5]
    assert pack_prefill_budgets([8, 2, 8], 8, budget=9) == [4, 2, 3]
    # budget above demand: the cap never inflates a grant
    assert pack_prefill_budgets([4, 4], 8, budget=100) == [4, 4]
    # degenerate: nothing to prefill / nothing allowed
    assert pack_prefill_budgets([], 8) == []
    assert pack_prefill_budgets([5, 0], 8, budget=0) == [0, 0]
    # determinism: a pure function of its inputs
    assert pack_prefill_budgets([7, 7, 7], 4, budget=5) == \
        pack_prefill_budgets([7, 7, 7], 4, budget=5) == [2, 2, 1]
    # phase rotation: a budget smaller than the slot count rotates who
    # gets this step's tokens — across consecutive phases every slot
    # makes progress (no tail-slot starvation)
    assert pack_prefill_budgets([8, 8, 8], 8, budget=2, phase=0) == [1, 1, 0]
    assert pack_prefill_budgets([8, 8, 8], 8, budget=2, phase=1) == [0, 1, 1]
    assert pack_prefill_budgets([8, 8, 8], 8, budget=2, phase=2) == [1, 0, 1]
    total = [0, 0, 0]
    for ph in range(3):
        for i, g in enumerate(
            pack_prefill_budgets([8, 8, 8], 8, budget=2, phase=ph)
        ):
            total[i] += g
    assert min(total) >= 1


@pytest.mark.slow  # two full budgeted traces — tier-1 wall-time; CI's
# engine job runs this file unfiltered on every push
def test_unified_prefill_budget_throttles_admission_not_streams(tiny_engine):
    """A total per-step prefill budget slows admission (more steps to
    cover a prompt) but never moves a token: streams are bit-identical
    to the unbudgeted engine's, and co-resident decodes keep emitting
    every step while the budgeted prefill trickles in."""
    eng = tiny_engine
    sp = SamplingParams.make(temperature=0.8)

    def run(budget):
        ce = _cont(eng, prefill_budget=budget)
        bg = ce.submit([1, 2], max_new_tokens=20, seed=0)
        ce.step_chunk()
        long_req = ce.submit(list(range(1, 41)), max_new_tokens=4,
                             sampling=sp, seed=1)
        stalls = 0
        while not long_req.finished:
            before = len(bg.tokens)
            ce.step_chunk()
            if not bg.finished and len(bg.tokens) == before:
                stalls += 1
        ce.run_until_idle()
        assert bg.finished and long_req.finished
        return bg.tokens, long_req.tokens, stalls

    bg0, long0, _ = run(0)
    bg1, long1, stalls = run(7)  # 40-token prompt -> ≥6 budgeted steps
    assert (bg1, long1) == (bg0, long0)
    assert stalls == 0, "a budgeted prefill step starved the running decode"


# ---------------------------------------------------------------------------
# pages: lifecycle + isolation
# ---------------------------------------------------------------------------
def test_eviction_returns_pages_and_isolates_slots(tiny_engine):
    """Finished slots return their pages to the free-list at the step
    boundary; live block tables never share a physical page (the
    no-cross-session-contamination invariant), and the scratch page 0 is
    never allocated."""
    eng = tiny_engine
    ce = _cont(eng)
    free0 = ce.alloc.n_free
    reqs = [
        ce.submit([i + 1, i + 2], max_new_tokens=4 + i, seed=i)
        for i in range(4)
    ]
    seen_tables = []
    while ce.has_work():
        ce.step_chunk()
        bt = np.asarray(ce.cache.block_tables)
        live = [s for s in range(ce.max_slots) if ce._active[s]]
        pages = [p for s in live for p in bt[s] if p > 0]
        assert len(pages) == len(set(pages)), "live slots share a page"
        assert 0 not in [p for s in live for p in bt[s][: 1]], \
            "live slot bound to the scratch page"
        seen_tables.append(len(pages))
    assert all(r.finished for r in reqs)
    assert ce.alloc.n_free == free0  # every page came back
    # all slots cleared: on the host's table at once, and a row the device
    # still holds is marked to go with the next chunk's control buffer
    assert ce._bt_host.sum() == 0 and ce._len0.sum() == 0
    stale = np.asarray(ce.cache.lengths) > 0
    assert stale.any() and ce._bind[stale].all() and ce._reset[stale].all()
    # ... which the next dispatched chunk's program applies before its pass
    ce.submit([9, 8], max_new_tokens=40, seed=9)
    ce.step_chunk()
    (live,) = [s for s in range(ce.max_slots) if ce._slots[s] is not None]
    lengths = np.asarray(ce.cache.lengths)
    bt = np.asarray(ce.cache.block_tables)
    assert lengths[live] > 0 and lengths.sum() == lengths[live]
    assert bt[live].any() and bt.sum() == bt[live].sum()
    assert not ce._bind.any() and not ce._reset.any()
    ce.run_until_idle()


def test_admission_queues_when_slots_exhausted(tiny_engine):
    """All-or-nothing admission: a request that can't get a slot (and all
    the pages it could need) stays queued FIFO until evictions free
    capacity — it is never admitted half-resident. (Slot shape matches the
    other tests so the suite reuses the one compiled step program.)"""
    eng = tiny_engine
    ce = _cont(eng)  # max_slots=4
    rs = [ce.submit([i + 1], max_new_tokens=3, seed=i) for i in range(6)]
    ce.step_chunk(admit_only=True)
    assert ce.live_slots == 4  # four admitted, two queued
    ce.run_until_idle()
    assert all(r.finished for r in rs)
    assert ce.stats["admitted"] == 6


# ---------------------------------------------------------------------------
# scheduler: admission latency + batcher front-end
# ---------------------------------------------------------------------------
def test_new_request_joins_within_one_chunk(tiny_engine):
    """A request submitted while a long decode is in flight starts
    emitting within one decode chunk — not after the running batch
    drains (the static batcher's convoy failure)."""
    eng = tiny_engine
    ce = _cont(eng, chunk_steps=4)
    long_req = ce.submit([1, 2], max_new_tokens=40, seed=0)
    ce.step_chunk()  # long request mid-flight
    emitted_before_late = len(long_req.tokens)
    late_first_at = {}

    def late_cb(tok):
        late_first_at.setdefault("long_progress", len(long_req.tokens))
        return False

    late = ce.submit([9, 9], max_new_tokens=4, seed=1, stream_cb=late_cb)
    ce.step_chunk()
    assert late.tokens, "late request not admitted"
    assert not late_first_at  # settled; it leaves behind the next dispatch
    ce.step_chunk()
    # the late request's first token left while the long one was still
    # well short of done, within one chunk of its submission
    assert late_first_at["long_progress"] <= emitted_before_late + ce.chunk_steps
    assert not long_req.finished
    ce.run_until_idle()
    assert long_req.finished


def test_continuous_batcher_local_engine(tiny_engine):
    """ContinuousBatcher over a local engine: GenBatcher's client contract
    (blocking generate, per-request stream demux, budget trim, close
    drains) with continuous scheduling underneath."""
    from tensorlink_tpu.ml.batching import ContinuousBatcher

    b = ContinuousBatcher(
        engine=tiny_engine, eos_ids=[], max_slots=4, page_size=8,
        chunk_steps=4,
    )
    results: dict[int, list[int]] = {}
    streams: dict[int, list[int]] = {i: [] for i in range(3)}

    def req(i, n, temp):
        results[i] = b.generate(
            [i + 1, i + 2], max_new_tokens=n, temperature=temp,
            stream_cb=lambda ts, i=i: streams[i].extend(ts),
        )

    threads = [
        threading.Thread(target=req, args=(0, 4, 0.0)),
        threading.Thread(target=req, args=(1, 2, 0.8)),
        threading.Thread(target=req, args=(2, 6, 0.0)),
    ]
    for t in threads:
        t.start()
        time.sleep(0.01)
    for t in threads:
        t.join(30)
    assert sorted(results) == [0, 1, 2]
    assert [len(results[i]) for i in range(3)] == [4, 2, 6]
    assert streams == {i: results[i] for i in range(3)}
    st = b.stats()
    assert st["requests"] == 3 and st["continuous"]
    b.close()
    with pytest.raises(RuntimeError):
        b.generate([1], max_new_tokens=1)


# ---------------------------------------------------------------------------
# automatic prefix caching + chunked prefill
# ---------------------------------------------------------------------------
# tlint: disable=TL006(read-only shared-prompt fixture data)
SYS = [7, 3, 9, 11, 2, 5, 8, 1, 4, 6, 10, 12, 7, 9, 3, 5, 2, 8, 11, 1]


def _run_set(eng, mixes, *, prefix_cache, prefill_chunk=128, stagger=False,
             warm=None):
    """Decode a request mix on a fresh engine; returns per-request token
    streams (and the engine, for stats/conservation asserts). ``warm``
    runs (and finishes) one request FIRST — on a cache-on engine its
    promoted pages are what the mix can hit; run on the cache-off engine
    too so the two sides stay symmetric."""
    ce = _cont(
        eng, prefix_cache=prefix_cache, prefill_chunk=prefill_chunk
    )
    if warm is not None:
        w = ce.submit(warm, max_new_tokens=2, seed=1234)
        ce.run_until_idle()
        assert w.finished
    reqs = []
    for i, (prompt, n, sp, seed) in enumerate(mixes):
        reqs.append(
            ce.submit(prompt, max_new_tokens=n, sampling=sp, seed=seed)
        )
        if stagger:
            ce.step_chunk()  # later requests join mid-flight
    ce.run_until_idle()
    assert all(r.finished for r in reqs)
    return [r.tokens for r in reqs], ce


def test_prefix_cache_streams_bit_identical_on_off(tiny_engine):
    """THE acceptance pin: with a shared page-spanning system prompt, the
    cache-on engine skips prefill compute for the hit region yet every
    stream — greedy and sampled, co-batched and mid-flight admitted — is
    BIT-identical to the cache-off engine's (cached KV is bitwise the KV
    the slot would have computed)."""
    eng = tiny_engine
    mixes = [
        (SYS + [21], 8, SamplingParams.make(), 1),
        (SYS + [22, 23], 8, SamplingParams.make(temperature=0.9, top_k=5), 2),
        (SYS + [24], 6, SamplingParams.make(temperature=0.7, top_p=0.9), 3),
        (SYS + [21], 8, SamplingParams.make(), 4),  # same prompt, new seed
    ]
    off, _ = _run_set(
        eng, mixes, prefix_cache=False, stagger=True, warm=SYS + [99]
    )
    on, ce = _run_set(
        eng, mixes, prefix_cache=True, stagger=True, warm=SYS + [99]
    )
    assert on == off
    snap = ce.serving_snapshot()
    # the shared prefix really was reused, not recomputed: SYS spans two
    # full 8-token pages resident from the warm request, and every mix
    # member hits them
    assert snap["prefix_hit_tokens"] >= 4 * 16
    assert snap["prefill_tokens_skipped"] == snap["prefix_hit_tokens"]
    ce.check_page_conservation()
    # solo == co-batched with the cache on, too
    for (prompt, n, sp, seed), toks in zip(mixes, on):
        solo, ce2 = _run_set(
            eng, [(prompt, n, sp, seed)], prefix_cache=True
        )
        assert solo[0] == toks
        ce2.check_page_conservation()


@pytest.mark.slow  # compiles three extra chunk shapes — tier-1
# wall-time; the CI engine job runs this file unfiltered
def test_prefill_chunk_size_never_moves_a_token(tiny_engine):
    """Greedy parity across prefill chunk sizes: the chunk width is
    schedule, never math (the framing-invariance contract at the engine
    level — the bitwise KV pin lives in tests/test_ops.py)."""
    eng = tiny_engine
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3], SYS + [30], [8] * 17]
    mixes = [(p, 10, SamplingParams.make(), i) for i, p in enumerate(prompts)]
    ref, _ = _run_set(eng, mixes, prefix_cache=False, prefill_chunk=128)
    for chunk in (4, 8, 64):
        got, _ = _run_set(
            eng, mixes, prefix_cache=False, prefill_chunk=chunk
        )
        assert got == ref, chunk


def test_prefix_cache_cow_divergent_page(tiny_engine):
    """A prompt diverging MID-page from a cached chain copy-on-writes the
    divergent page: the matched positions skip prefill, the cached
    original is never written (later hits of the original chain still
    see its exact KV), and streams stay bit-identical to cache-off."""
    eng = tiny_engine
    base = SYS + [21, 22, 23, 24]  # 24 tokens = 3 full 8-token pages
    fork = SYS + [21, 22, 99, 98]  # diverges at position 22, mid-page 3
    mixes = [
        (fork, 6, SamplingParams.make(temperature=0.8), 2),
        (base, 6, SamplingParams.make(), 3),  # original chain re-hit
    ]
    off, _ = _run_set(eng, mixes, prefix_cache=False, warm=base)
    on, ce = _run_set(eng, mixes, prefix_cache=True, warm=base)
    assert on == off
    snap = ce.serving_snapshot()
    assert snap["prefix_cow_copies"] >= 1
    # the fork's hit = 2 full pages + 2 COW-matched positions
    ce.check_page_conservation()


def test_prefix_cache_recovery_readmission_near_free(tiny_engine):
    """Crash recovery re-admits through the cache: resubmitting prompt +
    delivered with start_step resumes the stream bit-identically AND
    skips the resident prefix's prefill (near-free re-prefill — the
    tentpole's recovery dividend)."""
    eng = tiny_engine
    sp = SamplingParams.make(temperature=1.0, top_p=0.9)
    ce = _cont(eng, prefix_cache=True)
    full = ce.submit(SYS, max_new_tokens=10, sampling=sp, seed=9)
    ce.run_until_idle()
    cut = 4
    # the dead worker's replacement: same engine state (the cache SURVIVES
    # the session — pages were promoted at the original's eviction)
    resumed = ce.submit(
        SYS + full.tokens[:cut], max_new_tokens=10 - cut, sampling=sp,
        seed=9, start_step=cut,
    )
    skipped0 = ce.stats["prefill_tokens_skipped"]
    ce.run_until_idle()
    assert full.tokens[:cut] + resumed.tokens == full.tokens
    # the re-admission hit the resident prefix: SYS spans 2 full pages
    assert ce.stats["prefill_tokens_skipped"] - skipped0 >= 16
    ce.check_page_conservation()
    # and the recovered stream equals the cache-OFF recovered stream
    ce_off = _cont(eng, prefix_cache=False)
    r_off = ce_off.submit(
        SYS + full.tokens[:cut], max_new_tokens=10 - cut, sampling=sp,
        seed=9, start_step=cut,
    )
    ce_off.run_until_idle()
    assert r_off.tokens == resumed.tokens


def test_shared_prefix_mid_flight_eviction(tiny_engine):
    """A slot set sharing cached prefix pages: evicting one member
    mid-flight (downstream cancel) releases only ITS references — the
    co-resident followers keep decoding on the shared pages and emit
    exactly their solo streams; page conservation holds throughout."""
    eng = tiny_engine
    ce = _cont(eng, prefix_cache=True)
    seed_req = ce.submit(SYS + [40], max_new_tokens=2, seed=0)
    ce.run_until_idle()  # leaves SYS's full pages resident
    assert seed_req.finished

    cancel_after = 2
    seen: list[int] = []

    def cancel_cb(tok: int) -> bool:
        seen.append(tok)
        return len(seen) >= cancel_after  # confirmed stop -> cancel row

    victim = ce.submit(
        SYS + [41], max_new_tokens=12, seed=1, stream_cb=cancel_cb
    )
    keep_a = ce.submit(SYS + [42], max_new_tokens=10, seed=2)
    keep_b = ce.submit(
        SYS + [43], max_new_tokens=10,
        sampling=SamplingParams.make(temperature=0.8), seed=3,
    )
    while ce.has_work():
        ce.step_chunk()
        ce.check_page_conservation()  # invariant holds mid-flight too
    assert victim.finished and len(victim.tokens) <= cancel_after + ce.chunk_steps
    for req, (prompt, n, sp, seed) in (
        (keep_a, (SYS + [42], 10, None, 2)),
        (keep_b, (SYS + [43], 10, SamplingParams.make(temperature=0.8), 3)),
    ):
        assert req.tokens == _solo(eng, prompt, n, sampling=sp, seed=seed)
    # eviction released the victim's refs: teardown finds no leak
    ce.close()


@pytest.mark.slow  # needs a small-chunk program shape (C=8) the rest of
# the tier-1 file never compiles — the CI engine job runs it unfiltered
def test_chunked_prefill_never_stalls_running_decodes(tiny_engine):
    """The chunked-prefill TTFT guarantee: while a LONG prompt is being
    admitted chunk by chunk, a co-resident request keeps emitting every
    step — admission compute interleaves instead of convoying."""
    eng = tiny_engine
    ce = _cont(eng, prefix_cache=True, prefill_chunk=8)
    bg = ce.submit([1, 2], max_new_tokens=30, seed=0)
    ce.step_chunk()
    assert len(bg.tokens) > 0
    long_req = ce.submit(list(range(1, 49)), max_new_tokens=4, seed=1)
    # 48 prompt tokens / 8-token chunks = 6 prefill ticks
    stalls = 0
    while long_req.slot < 0 or long_req.prefill_pos < 48:
        before = len(bg.tokens)
        ce.step_chunk()
        if not bg.finished and len(bg.tokens) == before:
            stalls += 1
        if bg.finished:
            break
    assert stalls == 0, "a prefill tick starved the running decode"
    ce.run_until_idle()
    assert long_req.finished and bg.finished


def test_alloc_pressure_skips_futile_cache_wipe(tiny_engine):
    """Eviction-on-demand fires only when it can actually cover the
    allocation's deficit: an oversized ask against a tight pool stays
    queued WITHOUT destroying the resident prefixes every follower is
    hitting (wipe-then-fail would turn them all into full misses)."""
    ce = _cont(tiny_engine, prefix_cache=True)
    held = ce.alloc.alloc(ce.alloc.n_free - 1)  # tighten the pool
    page = ce.alloc.alloc(1)[0]  # -> 0 free
    ce.prefix.insert(None, (1,) * ce.page_size, page)
    # deficit 3, evictable 1: refuse, and leave the cache alone
    assert ce._alloc_pages(3) is None
    assert ce.prefix.n_resident == 1
    assert ce.prefix.stats["evictions"] == 0
    # deficit 1, evictable 1: evict exactly the deficit and fit
    ce.alloc.free(held[:2])
    got = ce._alloc_pages(3)
    assert got is not None and len(got) == 3
    assert ce.prefix.n_resident == 0


def test_failed_admission_unwinds_pages_and_refs(tiny_engine, monkeypatch):
    """A device failure mid-admission — after private pages are allocated
    and prefix refs pinned — must unwind cleanly: pages back on the
    free-list, refcounts dropped, so close()'s conservation check holds
    on the error-cleanup path and the engine can keep serving."""
    import tensorlink_tpu.engine.continuous as cont_mod

    def boom(*a, **k):
        raise RuntimeError("synthetic device failure")

    eng = tiny_engine
    ce = _cont(eng, prefix_cache=True)
    base = SYS + [21, 22, 23, 24]  # 3 full pages resident after this
    ce.submit(base, max_new_tokens=2, seed=0)
    ce.run_until_idle()
    # fail at the COW copy: the deepest unwind point — hit-chain refs AND
    # the COW source ref are pinned, private pages already off the list
    monkeypatch.setattr(cont_mod, "copy_page", boom)
    fork = ce.submit(SYS + [21, 22, 99, 98], max_new_tokens=4, seed=1)
    with pytest.raises(RuntimeError, match="synthetic"):
        ce.run_until_idle()
    monkeypatch.undo()
    ce.check_page_conservation()  # nothing leaked by the failed admission
    ce.run_until_idle()  # the request stayed queued: re-admits cleanly
    assert fork.finished
    ce.check_page_conservation()
    # at idle every slot has been evicted — a ref leaked by the failed
    # admission would show as a permanently pinned resident node
    assert all(n.refs == 0 for n in ce.prefix._by_page.values())


def test_page_conservation_asserted_at_teardown(tiny_engine):
    """close() itself asserts free + slot-owned + cache-resident == total
    (the hardened free-list invariant) — including when requests are
    failed mid-flight by the teardown."""
    eng = tiny_engine
    ce = _cont(eng, prefix_cache=True)
    ce.submit(SYS + [50], max_new_tokens=4, seed=1)
    ce.run_until_idle()
    r = ce.submit(SYS + [51], max_new_tokens=30, seed=2)
    ce.step_chunk()  # leave it mid-flight
    assert not r.finished
    ce.close()  # evicts mid-flight slots, then checks conservation
    assert r.error is not None
    acc = ce.page_accounting()
    assert not acc["slots"]  # nothing owned after teardown
    assert len(acc["free"]) + len(acc["cached"]) == ce.cache.n_pages - 1


# ---------------------------------------------------------------------------
# quantized paged KV cache (kv_quant="int8"): the lifecycle pins
# ---------------------------------------------------------------------------
@pytest.mark.slow  # compiles the int8 step-program shape — tier-1
# wall-time; CI's engine job runs this file unfiltered on every push
def test_kv_quant_streams_bit_identical_across_lifecycle(tiny_engine):
    """THE quantized acceptance pin: with ``kv_quant="int8"`` every
    existing stream-identity contract holds AMONG quantized streams —
    solo == co-batched == mid-flight-admitted == recovery-resumed, with
    the prefix cache on or off. (int8 streams may differ from fp
    streams; that divergence is bounded in tests/test_ops.py — the
    engine contract is that quantization never breaks determinism.)"""
    eng = tiny_engine

    def solo_q(prompt, n, sp, seed, prefix_cache=True):
        ce = _cont(eng, kv_quant="int8", prefix_cache=prefix_cache)
        req = ce.submit(prompt, max_new_tokens=n, sampling=sp, seed=seed)
        ce.run_until_idle()
        assert req.finished
        ce.check_page_conservation()
        return req.tokens

    mixes = [
        (SYS + [21], 8, SamplingParams.make(temperature=0.9, top_k=5), 1),
        ([4, 5], 6, SamplingParams.make(), 2),
        (SYS + [22, 23], 8,
         SamplingParams.make(temperature=0.7, top_p=0.9), 3),
    ]
    # co-batched + mid-flight admission, cache on
    ce = _cont(eng, kv_quant="int8")
    reqs = []
    for prompt, n, sp, seed in mixes:
        reqs.append(ce.submit(prompt, max_new_tokens=n, sampling=sp,
                              seed=seed))
        ce.step_chunk()  # later requests join mid-flight
    ce.run_until_idle()
    assert all(r.finished for r in reqs)
    ce.check_page_conservation()
    for req, (prompt, n, sp, seed) in zip(reqs, mixes):
        assert req.tokens == solo_q(prompt, n, sp, seed), (prompt, seed)
        # cache off == cache on (quantized hit pages are byte-exactly
        # what a cold quantized prefill writes)
        assert req.tokens == solo_q(prompt, n, sp, seed,
                                    prefix_cache=False)
    # recovery resume: the crash-recovery re-prefill shape continues the
    # quantized stream bit-identically
    sp = SamplingParams.make(temperature=1.0, top_p=0.9)
    full = solo_q([5, 6, 7], 10, sp, 9)
    cut = 4
    ce2 = _cont(eng, kv_quant="int8")
    resumed = ce2.submit(
        [5, 6, 7] + full[:cut], max_new_tokens=10 - cut, sampling=sp,
        seed=9, start_step=cut,
    )
    ce2.run_until_idle()
    assert full[:cut] + resumed.tokens == full
    ce2.close()


@pytest.mark.slow  # int8 COW/preemption churn on top of the module's
# compile set — tier-1 wall-time; CI's engine job runs this unfiltered
def test_kv_quant_page_lifecycle_byte_exact(tiny_engine):
    """Quantized pages round-trip BYTE-exactly through the page
    lifecycle: a COW copy reproduces the source page's int8 payload AND
    scale rows bit for bit, promoted (cache-resident) pages are never
    mutated by the admissions that hit them, and preemption + resume
    emits the uninterrupted quantized stream."""
    import jax.numpy as jnp
    from tensorlink_tpu.engine.paged import PagedKVCache, copy_page

    # -- copy_page: the COW primitive moves payload + scales together --
    cfg = tiny_engine.cfg
    cache = PagedKVCache.init(cfg, 2, page_size=8, max_len=64,
                              quantized=True)
    rng = np.random.default_rng(3)
    cache = type(cache)(
        k=jnp.asarray(rng.integers(-127, 128, cache.k.shape, np.int8)),
        v=jnp.asarray(rng.integers(-127, 128, cache.v.shape, np.int8)),
        block_tables=cache.block_tables,
        lengths=cache.lengths,
        k_scale=jnp.asarray(
            rng.random(cache.k_scale.shape).astype(np.float32)
        ),
        v_scale=jnp.asarray(
            rng.random(cache.v_scale.shape).astype(np.float32)
        ),
    )
    src_k = np.asarray(cache.k[:, 3])
    src_ks = np.asarray(cache.k_scale[:, 3])
    src_vs = np.asarray(cache.v_scale[:, 3])
    cache = copy_page(cache, jnp.int32(3), jnp.int32(7))
    assert np.array_equal(np.asarray(cache.k[:, 7]), src_k)
    assert np.array_equal(np.asarray(cache.k_scale[:, 7]), src_ks)
    assert np.array_equal(np.asarray(cache.v_scale[:, 7]), src_vs)

    # -- engine level: promotion -> hit -> COW never mutates a resident
    # quantized page (followers of the original chain still see its
    # exact bytes: their streams equal their solo runs) --
    eng = tiny_engine
    base = SYS + [21, 22, 23, 24]
    fork = SYS + [21, 22, 99, 98]  # diverges mid-page: COW fires
    ce = _cont(eng, kv_quant="int8")
    w = ce.submit(base, max_new_tokens=2, seed=0)
    ce.run_until_idle()
    assert w.finished  # base chain promoted + resident
    resident0 = {
        p: (np.asarray(ce.cache.k[:, p]), np.asarray(ce.cache.k_scale[:, p]))
        for p in sorted(ce.prefix.resident_pages)
    }
    f = ce.submit(fork, max_new_tokens=6,
                  sampling=SamplingParams.make(temperature=0.8), seed=2)
    b = ce.submit(base, max_new_tokens=6, sampling=SamplingParams.make(),
                  seed=3)
    ce.run_until_idle()
    assert f.finished and b.finished
    assert ce.prefix.stats["cow_copies"] >= 1
    for p, (k0, ks0) in resident0.items():
        if p in ce.prefix.resident_pages:  # still resident: byte-exact
            assert np.array_equal(np.asarray(ce.cache.k[:, p]), k0), p
            assert np.array_equal(
                np.asarray(ce.cache.k_scale[:, p]), ks0
            ), p
    ce.check_page_conservation()

    # -- preemption: the quantized victim resumes bit-identically --
    ce3 = _cont(eng, kv_quant="int8", max_slots=1, sched_aging_ticks=1000)
    victim = ce3.submit([3, 1, 4], max_new_tokens=8, seed=7,
                        priority="best_effort")
    ce3.step_chunk()
    pre = ce3.submit([8, 8], max_new_tokens=2, seed=9,
                     priority="interactive")
    ce3.run_until_idle()
    assert ce3.stats["preemptions"] >= 1
    assert victim.finished and pre.finished
    solo = _cont(eng, kv_quant="int8")
    sr = solo.submit([3, 1, 4], max_new_tokens=8, seed=7)
    solo.run_until_idle()
    assert victim.tokens == sr.tokens
    ce3.close()
    solo.close()


@pytest.mark.slow  # drives a second (int8) step-program shape through
# admission/churn — tier-1 wall-time; CI's engine job runs it unfiltered
def test_kv_quant_is_one_program(tiny_engine):
    """The compile-set bar extends to quantization: the int8 engine is
    ONE ragged_step program (+ copy_page) of its own — storage dtype is
    a trace-time constant, and admission, mixed churn, hits, COW and
    eviction with quant on add ZERO compiles beyond it."""
    eng = tiny_engine
    ce = _cont(eng, kv_quant="int8")
    pre = ce.jit_cache_sizes()
    long = [5, 9] * 12
    ce.submit(long, max_new_tokens=3, seed=7)  # miss -> promoted
    ce.run_until_idle()
    ce.submit(long[:20] + [2, 2, 2, 2], max_new_tokens=3, seed=8)  # COW
    ce.run_until_idle()
    base = ce.jit_cache_sizes()
    assert 0 <= base["ragged_step"] - pre["ragged_step"] <= len(
        ce.block_widths)  # one program a width of the ladder
    assert 0 <= base["copy_page"] - pre["copy_page"] <= 1
    reqs = [
        ce.submit([3 + i] * (2 + i), max_new_tokens=3 + i, seed=i)
        for i in range(4)
    ]
    ce.step_chunk()
    late = ce.submit(long + [3], max_new_tokens=3, seed=30)  # cache hit
    ce.submit([6] * 31, max_new_tokens=2, seed=31)  # different miss
    ce.run_until_idle()
    assert all(r.finished for r in [*reqs, late])
    assert ce.jit_cache_sizes() == base, (base, ce.jit_cache_sizes())
    ce.check_page_conservation()
    ce.close()


# ---------------------------------------------------------------------------
# live slot migration (KV-page shipping between engines + drain fence)
# ---------------------------------------------------------------------------
def _drive_until(ce, req, n):
    """Step until ``req`` has emitted at least ``n`` tokens (mid-decode
    freeze point)."""
    while len(req.tokens) < n and not req.finished:
        ce.step_chunk()
    assert not req.finished, "budget too small to freeze mid-decode"


def _migrate(src, dst, req, mig_id, *, probe=True, roundtrip=True):
    """The full engine-level migration protocol: freeze at the chunk
    boundary, probe the destination's resident prefix, export, TLTS
    round-trip (the real wire encoding), stage, commit, resume-with-adopt
    — returning the destination request."""
    from tensorlink_tpu.core import serialization as ser

    slot = req.slot
    src.freeze_slot(slot)
    src.check_page_conservation()  # frozen pages count in transit
    chain, limit = src.migration_chain(slot)
    n_skip = dst.resident_prefix_pages(chain, limit) if probe else 0
    blob = src.export_slot(slot, n_skip=n_skip)
    if roundtrip:
        blob = ser.decode(ser.encode(blob), copy=True)
    assert dst.stage_migration(mig_id, blob)
    dst.check_page_conservation()  # staged pages count in transit
    moved = src.commit_migration(slot)
    src.check_page_conservation()
    return dst.submit(
        moved.prompt + moved.tokens,
        max_new_tokens=moved.budget - len(moved.tokens),
        sampling=moved.sampling,
        eos_ids=sorted(moved.eos),
        seed=moved.seed,
        start_step=moved.start_step + len(moved.tokens),
        priority=moved.priority,
        adopt=mig_id,
    ), moved


@pytest.mark.slow  # drives full decode traces on two engines — tier-1
# wall-time; CI's engine job runs this file unfiltered on every push
def test_migrated_stream_bit_identical_solo_and_cobatched(tiny_engine):
    """THE migration acceptance pin: a stream migrated between two live
    engines mid-decode (pages shipped byte-exact, resume draw at
    fold_in(seed, start_step + emitted)) is bit-identical to the same
    stream run uninterrupted — greedy and sampled, with co-resident
    neighbors live on BOTH engines throughout, and page conservation
    holding on both sides at every stage."""
    eng = tiny_engine
    mixes = [
        ([5, 6, 7], 14, SamplingParams.make(temperature=0.9, top_k=5), 9),
        ([1, 2, 3, 4], 12, SamplingParams.make(), 3),
    ]
    solos = [
        _solo(eng, p, n, sampling=sp, seed=s) for p, n, sp, s in mixes
    ]
    src, dst = _cont(eng), _cont(eng)
    # neighbors: one decoding on each engine while the migration happens
    nb_src = src.submit([9, 9, 1], max_new_tokens=20, seed=41)
    nb_dst = dst.submit([8, 8, 2], max_new_tokens=20, seed=42)
    reqs = [
        src.submit(p, max_new_tokens=n, sampling=sp, seed=s)
        for p, n, sp, s in mixes
    ]
    for r in reqs:
        _drive_until(src, r, 5)
    outs = []
    for i, r in enumerate(reqs):
        dst.step_chunk()  # the destination keeps serving mid-migration
        r2, moved = _migrate(src, dst, r, f"mig{i}")
        outs.append((moved, r2))
    src.run_until_idle()
    dst.run_until_idle()
    for (moved, r2), solo in zip(outs, solos):
        assert r2.finished
        assert moved.tokens + r2.tokens == solo
    # the neighbors never noticed (row-local contract)
    assert nb_src.tokens == _solo(eng, [9, 9, 1], 20, seed=41)
    assert nb_dst.tokens == _solo(eng, [8, 8, 2], 20, seed=42)
    assert src.stats["migrations_completed"] == 2
    assert dst.stats["migrations_adopted"] == 2
    assert src.serving_snapshot()["pages_in_transit"] == 0
    src.close()
    dst.close()


@pytest.mark.slow  # see above — CI engine job coverage
def test_migration_prefix_short_circuit_ships_fewer_pages(tiny_engine):
    """Destination-resident prefix pages short-circuit the transfer (the
    PR-3 trie digest): the exporter skips them, the adopted slot maps the
    resident chain — and the stream is still bit-identical, because a
    cache hit is bitwise the prefill the source ran."""
    eng = tiny_engine
    prompt = SYS + [40, 41]
    base = _solo(eng, prompt, 10, seed=7)
    src, dst = _cont(eng), _cont(eng)
    warm = dst.submit(prompt, max_new_tokens=2, seed=1)
    dst.run_until_idle()
    assert warm.finished  # prompt pages promoted into dst's trie
    r = src.submit(prompt, max_new_tokens=10, seed=7)
    _drive_until(src, r, 4)
    slot = r.slot
    src.freeze_slot(slot)
    chain, limit = src.migration_chain(slot)
    n_skip = dst.resident_prefix_pages(chain, limit)
    assert n_skip >= 2  # the warmed prompt really is resident
    full_blob = src.export_slot(slot, n_skip=0)
    blob = src.export_slot(slot, n_skip=n_skip)
    assert blob["k"].shape[0] == full_blob["k"].shape[0] - n_skip
    assert dst.stage_migration("m", blob)
    moved = src.commit_migration(slot)
    r2 = dst.submit(
        moved.prompt + moved.tokens,
        max_new_tokens=moved.budget - len(moved.tokens),
        seed=7, start_step=len(moved.tokens), adopt="m",
    )
    dst.run_until_idle()
    assert moved.tokens + r2.tokens == base
    src.close()
    dst.close()


@pytest.mark.slow  # see above — CI engine job coverage
def test_migration_failure_falls_back_to_re_prefill(tiny_engine):
    """The fallback ladder: when staging fails (refused blob / stale
    ticket), the stream resumes via the crash-recovery re-prefill rung —
    still bit-identical, with conservation holding on BOTH engines and
    the failure counted. A corrupted transfer (bad digest) is refused the
    same way."""
    eng = tiny_engine
    prompt = [3, 1, 4, 1, 5]
    base = _solo(eng, prompt, 12, seed=5)
    src, dst = _cont(eng), _cont(eng)
    r = src.submit(prompt, max_new_tokens=12, seed=5)
    _drive_until(src, r, 5)
    slot = r.slot
    src.freeze_slot(slot)
    blob = src.export_slot(slot)
    # storage-mode mismatch refuses staging...
    assert not dst.stage_migration("m", dict(blob, kv_quant="int8"))
    # ...and so does a corrupted payload (integrity digest)
    bad = dict(blob, digest="0" * 64)
    assert not dst.stage_migration("m", bad)
    dst.check_page_conservation()  # refusals leak nothing
    moved = src.commit_migration(slot, fell_back=True)
    src.check_page_conservation()
    assert src.stats["migrations_failed"] == 1
    assert src.stats["migrations_fell_back"] == 1
    # the resume carries a ticket id that was never staged: admission
    # quietly takes the re-prefill rung
    r2 = dst.submit(
        moved.prompt + moved.tokens,
        max_new_tokens=moved.budget - len(moved.tokens),
        seed=5, start_step=len(moved.tokens), adopt="m",
    )
    dst.run_until_idle()
    assert moved.tokens + r2.tokens == base
    assert dst.stats["migrations_adopted"] == 0
    src.close()
    dst.close()


@pytest.mark.slow  # see above — CI engine job coverage
def test_migration_abort_resumes_locally_bit_identical(tiny_engine):
    """abort_migration un-freezes (export is read-only): the slot resumes
    decoding HERE exactly where it stopped."""
    eng = tiny_engine
    prompt = [2, 7, 1, 8]
    base = _solo(eng, prompt, 12, seed=6)
    ce = _cont(eng)
    r = ce.submit(prompt, max_new_tokens=12, seed=6)
    _drive_until(ce, r, 4)
    ce.freeze_slot(r.slot)
    ce.export_slot(r.slot)  # gathered bytes, then the handoff dies
    ce.abort_migration(r.slot)
    ce.run_until_idle()
    assert r.finished and r.tokens == base
    assert ce.stats["migrations_failed"] == 1
    ce.close()


@pytest.mark.slow  # see above — CI engine job coverage
def test_migrated_stream_composed_with_preemption(tiny_engine):
    """Migration composes with the scheduler lifecycle: an adopted slot
    preempted on the DESTINATION resumes through the normal cache-backed
    preemption contract — the full stream (source tokens + destination
    tokens across the preemption) is still bit-identical."""
    eng = tiny_engine
    prompt = [6, 5, 4]
    base = _solo(eng, prompt, 14, seed=8)
    src = _cont(eng)
    dst = _cont(eng, max_slots=1)  # one slot: the flood must preempt
    r = src.submit(
        prompt, max_new_tokens=14, seed=8,
        priority="best_effort",  # preemptable at the destination
    )
    _drive_until(src, r, 5)
    r2, moved = _migrate(src, dst, r, "mp")
    dst.step_chunk()  # adopted + decoding on the destination
    assert len(r2.tokens) > 0 and not r2.finished
    hi = dst.submit([1, 1], max_new_tokens=3, seed=1, priority="interactive")
    dst.run_until_idle()
    assert hi.finished and r2.finished
    assert dst.stats["preemptions"] >= 1  # the adopted slot was preempted
    assert moved.tokens + r2.tokens == base
    src.close()
    dst.close()


@pytest.mark.slow  # exercises the migration device paths' compile keys —
# referenced by CI's compile-count-guard step
def test_migration_adds_zero_new_programs(tiny_engine):
    """Compile-set guard: a full migration (freeze/export/stage/adopt/
    resume) adds ZERO compiled programs beyond the explicit gather/scatter
    page keys it registers in jit_cache_sizes — the serving step set
    (ragged_step, copy_page) stays exactly where it was."""
    eng = tiny_engine
    src, dst = _cont(eng), _cont(eng)
    r = src.submit([4, 2, 4, 2], max_new_tokens=12, seed=2)
    _drive_until(src, r, 4)
    base = src.jit_cache_sizes()
    r2, moved = _migrate(src, dst, r, "mz")
    src.run_until_idle()
    dst.run_until_idle()
    assert r2.finished
    after = src.jit_cache_sizes()
    for key in ("ragged_step", "copy_page", "decode_step"):
        assert after[key] == base[key], (key, base, after)
    for key in ("gather_page", "scatter_page"):
        # the page-mover keys exist and stay bounded: ONE program per
        # engine storage mode, no matter how many pages moved
        assert after[key] - base[key] <= 1, (key, base, after)
    src.close()
    dst.close()


def test_drain_fence_sheds_queue_and_refuses_new_work(tiny_engine):
    """begin_drain is an admission fence: submit fails fast, the
    backpressure probe rejects with the draining marker, shed_queued
    hands back the queued requests unfinished (for redirection), and a
    queued request with nowhere to go fails loudly. Zero compiles — no
    chunk ever runs."""
    eng = tiny_engine
    ce = _cont(eng)
    q1 = ce.submit([1, 2], max_new_tokens=4, seed=1)
    q2 = ce.submit([3, 4], max_new_tokens=4, seed=2)
    ce.begin_drain()
    assert ce.drain_state == "draining"
    rej = ce.admission_check()
    assert rej is not None and rej.get("draining") is True
    late = ce.submit([5, 6], max_new_tokens=4, seed=3)
    assert late.error is not None  # failed fast at the fence
    # a REJECTED resume expires its staged-adoption ticket (submit may run
    # on a client thread, so the pages are freed by the DRIVER's next GC
    # sweep, not inline) — they must not stay pinned for the full TTL
    pages = ce.alloc.alloc(2)
    ce._migrations["tk"] = {"pages": pages, "nodes": [], "t": 0.0}
    free_before = ce.alloc.n_free
    rejected = ce.submit([7, 8], max_new_tokens=4, seed=4, adopt="tk")
    assert rejected.error is not None
    assert ce._migrations["tk"]["t"] == float("-inf")  # expired in place
    ce._gc_staged_migrations()  # the driver's sweep frees it immediately
    assert "tk" not in ce._migrations
    assert ce.alloc.n_free == free_before + 2
    shed = ce.shed_queued()
    assert {r.rid for r in shed} == {q1.rid, q2.rid}
    assert not q1.done.is_set()  # shed ≠ finished: the stream redirects
    assert ce.stats["migrations_fell_back"] == 2
    ce.fail_queued(q1, RuntimeError("no transport context"))
    assert q1.done.is_set() and q1.error is not None
    ce.fail_queued(q2, RuntimeError("no transport context"))
    # a draining engine refuses to adopt inbound migrations too
    assert not ce.stage_migration("m", {"kv_quant": "none", "page_size": 8})
    ce.close()


# ---------------------------------------------------------------------------
# speculative decoding (draft/verify as ragged slots, docs/SERVING.md)
# ---------------------------------------------------------------------------
# a repetitive prompt: prompt-lookup can draft from it, so the spec path
# really exercises multi-token acceptance (the bit-identity contract
# holds for ANY prompt; this one makes the accepted>=1 asserts real)
# tlint: disable=TL006(read-only repetitive-prompt fixture data)
REP = [5, 9, 5, 9, 5, 9, 5, 9]


def _spec_cont(eng, **kw):
    kw.setdefault("spec_decode", True)
    kw.setdefault("spec_draft", 4)
    return _cont(eng, **kw)


def test_spec_controller_kill_switch_units():
    """The shared policy machine (engine/spec.py) in isolation — zero
    compiles: prescan arms only on repetitive history, a miss run
    disarms, a recurring pair re-arms, and the acceptance-rate kill
    switch fires after the probe window and NEVER re-probes (note_pair
    cannot resurrect a dead controller)."""
    from tensorlink_tpu.engine.spec import (
        ACC_PROBE, MISS_OFF, SpecController, lookup_draft,
    )

    # prescan: zero recurring adjacent pairs -> off; repetition -> on
    assert not SpecController().prescan([1, 2, 3, 4])
    assert SpecController().prescan([1, 2, 1, 2])
    # draft misses disarm after MISS_OFF consecutive misses
    c = SpecController(n_draft=4)
    c.prescan([1, 2, 1, 2])
    for _ in range(MISS_OFF):
        assert c.draft([1, 2, 3, 4, 5, 6, 7, 8]) == []  # no recurrence
    assert not c.on and not c.dead
    # a recurring pair re-arms a disarmed (but not killed) controller
    c.note_pair(7, 8)
    c.note_pair(7, 8)
    assert c.on
    # real drafting delegates to lookup_draft (one implementation)
    hist = [3, 4, 5, 3, 4]
    assert c.draft(hist, cap=2) == lookup_draft(hist, 2)
    # acceptance kill: ACC_PROBE passes at 1 token/pass -> dead, and the
    # accounting matches (accepted = per_pass - 1 each pass)
    c2 = SpecController()
    c2.prescan([1, 2, 1, 2])
    fired = [c2.note_verify(1) for _ in range(ACC_PROBE)]
    assert fired == [False] * (ACC_PROBE - 1) + [True]
    assert c2.dead and not c2.active
    assert c2.tokens_per_pass == 1.0
    # dead is PERMANENT: recurring pairs never re-arm it
    c2.note_pair(1, 2)
    c2.note_pair(1, 2)
    assert c2.dead and not c2.on
    assert c2.draft([1, 2, 1, 2, 1, 2]) == []
    # a high-acceptance controller survives the probe window
    c3 = SpecController()
    c3.prescan([1, 2, 1, 2])
    for _ in range(ACC_PROBE + 2):
        assert not c3.note_verify(5)
    assert c3.active and c3.tokens_per_pass == 5.0


def test_spec_engine_knobs_zero_compile(tiny_engine):
    """Construction-level contracts, no chunk ever runs: spec_width is
    1 + spec_draft capped by the block row; the per-request flag is
    gated on the ENGINE knob (a speculative submit on a plain engine
    decodes vanilla); the snapshot carries the enablement + amortization
    keys the /metrics//healthz surfaces read."""
    ce = _cont(tiny_engine)  # spec off (default)
    assert ce.spec_width == 1 and ce.spec_decode is False
    r = ce.submit(REP, max_new_tokens=2, speculative=True)
    assert r.speculative is False  # gated: engine knob off
    snap = ce.serving_snapshot()
    assert snap["spec_decode"] is False
    assert snap["spec_tokens_per_pass"] == 0.0
    for k in ("spec_drafted", "spec_accepted", "spec_verify_passes",
              "spec_killed"):
        assert snap[k] == 0, k
    on = _spec_cont(tiny_engine)
    assert on.spec_width == 5 and on.spec_decode is True
    assert on.submit(REP, max_new_tokens=2, speculative=True).speculative
    # a non-opted request on a spec engine stays vanilla
    assert not on.submit(REP, max_new_tokens=2).speculative
    # the block row caps the draft width (drafts are extra columns)
    capped = _cont(tiny_engine, spec_decode=True, spec_draft=64,
                   prefill_chunk=8)
    assert capped.spec_width == 8  # 1 + (prefill_chunk - 1)


@pytest.mark.slow  # compiles the spec-width step program shape — tier-1
# wall-time; CI's engine job runs this file unfiltered on every push
def test_spec_streams_bit_identical_across_lifecycle(tiny_engine):
    """THE speculative acceptance pin: with spec_decode on, every stream
    — greedy and sampled, solo, co-batched with plain neighbors,
    admitted mid-flight, preempted + resumed, and crash-recovery
    resumed — is BIT-IDENTICAL to the plain engine's (acceptance folds
    into the same fold_in(seed, step) chain; rejected draft KV is
    unwound by length truncation before any mask can see it). Real
    multi-token acceptance is asserted, not assumed."""
    eng = tiny_engine
    mixes = [
        (REP + [21], 14, SamplingParams.make(), 1),
        (REP, 16, SamplingParams.make(temperature=0.9, top_k=5), 2),
        ([4, 5], 8, SamplingParams.make(temperature=0.7, top_p=0.9), 3),
    ]
    plain = [
        _solo(eng, p, n, sampling=sp, seed=s) for p, n, sp, s in mixes
    ]
    # co-batched + mid-flight admission, every request opted in
    ce = _spec_cont(eng)
    reqs = []
    for prompt, n, sp, seed in mixes:
        reqs.append(ce.submit(prompt, max_new_tokens=n, sampling=sp,
                              seed=seed, speculative=True))
        ce.step_chunk()  # later requests join mid-flight
    ce.run_until_idle()
    snap = ce.serving_snapshot()
    for req, ref in zip(reqs, plain):
        assert req.finished and req.tokens == ref
    assert snap["spec_verify_passes"] >= 1
    assert snap["spec_accepted"] >= 1  # speculation actually accepted
    ce.check_page_conservation()
    ce.close()
    # solo spec == solo plain (and speculating alone compiles nothing new
    # beyond the engine's own step program — guarded in the compile test)
    for (prompt, n, sp, seed), ref in zip(mixes, plain):
        ce2 = _spec_cont(eng)
        r = ce2.submit(prompt, max_new_tokens=n, sampling=sp, seed=seed,
                       speculative=True)
        ce2.run_until_idle()
        assert r.tokens == ref
        ce2.close()
    # preemption: a speculating victim resumes bit-identically (the
    # controller — including any kill — survives the requeue)
    ce3 = _spec_cont(eng, max_slots=1, sched_aging_ticks=1000)
    victim = ce3.submit(REP, max_new_tokens=12, seed=2,
                        sampling=SamplingParams.make(temperature=0.9,
                                                     top_k=5),
                        speculative=True, priority="best_effort")
    ce3.step_chunk()
    hi = ce3.submit([8, 8], max_new_tokens=2, seed=9,
                    priority="interactive")
    ce3.run_until_idle()
    assert ce3.stats["preemptions"] >= 1
    assert victim.finished and hi.finished
    assert victim.tokens == plain[1][:12]
    ce3.close()
    # crash-recovery resume: prompt + delivered with start_step continues
    # the SPECULATIVE stream bit-identically
    cut = 5
    ce4 = _spec_cont(eng)
    resumed = ce4.submit(
        REP + plain[1][:cut], max_new_tokens=16 - cut,
        sampling=SamplingParams.make(temperature=0.9, top_k=5),
        seed=2, start_step=cut, speculative=True,
    )
    ce4.run_until_idle()
    assert plain[1][:cut] + resumed.tokens == plain[1]
    ce4.close()


@pytest.mark.slow  # drives two engines through the migration protocol —
# tier-1 wall-time; CI's engine job runs this file unfiltered
def test_spec_stream_migrated_bit_identical(tiny_engine):
    """A SPECULATING stream migrated mid-decode is bit-identical to the
    uninterrupted plain stream: the shipped KV never contains rejected
    draft rows (export bounds itself by the slot's truncated length),
    and the drafting state deliberately does NOT migrate — the
    destination re-probes fresh (documented in docs/SERVING.md), which
    can only change speed, never tokens."""
    eng = tiny_engine
    prompt = REP + [40]
    base = _solo(eng, prompt, 14, seed=7)
    src = _spec_cont(eng)
    dst = _spec_cont(eng)
    r = src.submit(prompt, max_new_tokens=14, seed=7, speculative=True)
    _drive_until(src, r, 5)
    slot = r.slot
    src.freeze_slot(slot)
    src.check_page_conservation()
    chain, limit = src.migration_chain(slot)
    blob = src.export_slot(slot, n_skip=dst.resident_prefix_pages(chain,
                                                                  limit))
    assert dst.stage_migration("sm", blob)
    moved = src.commit_migration(slot)
    r2 = dst.submit(
        moved.prompt + moved.tokens,
        max_new_tokens=moved.budget - len(moved.tokens),
        seed=7, start_step=len(moved.tokens), adopt="sm",
        speculative=True,  # the destination speculates afresh
    )
    dst.run_until_idle()
    assert moved.tokens + r2.tokens == base
    assert r2.spec_state is not moved.spec_state  # re-probed, not shipped
    src.check_page_conservation()
    dst.check_page_conservation()
    src.close()
    dst.close()


@pytest.mark.slow  # engine-level kill-switch trace — tier-1 wall-time;
# CI's engine job runs this file unfiltered on every push
def test_spec_kill_switch_fires_and_never_reprobes(tiny_engine, monkeypatch):
    """Adversarial drafts (hit every pass, never match the model) must
    trip the acceptance-rate kill switch after the probe window, fall
    the request back to 1-token decode PERMANENTLY, and still emit the
    bit-identical stream. After the kill no further drafts pack — and a
    preemption + resume does not re-probe (the controller rides the
    request through the requeue)."""
    import tensorlink_tpu.engine.spec as spec_mod
    from tensorlink_tpu.engine.spec import ACC_PROBE

    eng = tiny_engine
    plain = _solo(eng, REP, 24, seed=4,
                  sampling=SamplingParams.make(temperature=0.9, top_k=5))

    def bad_draft(history, n_draft, **kw):
        # always-hitting, never-matching drafts: token 1 is never what
        # the sampled stream emits for this seed (asserted below)
        return [1] * int(n_draft)

    monkeypatch.setattr(spec_mod, "lookup_draft", bad_draft)
    ce = _spec_cont(eng, max_slots=1, chunk_steps=1,
                    sched_aging_ticks=1000)
    r = ce.submit(REP, max_new_tokens=24, seed=4,
                  sampling=SamplingParams.make(temperature=0.9, top_k=5),
                  speculative=True, priority="best_effort")
    # drive until the kill fires, then preempt the victim mid-stream
    while ce.stats["spec_killed"] == 0 and not r.finished:
        ce.step_chunk()
    assert ce.stats["spec_killed"] == 1
    assert r.spec_state is not None and r.spec_state.dead
    assert ce.stats["spec_verify_passes"] == ACC_PROBE
    drafted_at_kill = ce.stats["spec_drafted"]
    assert not r.finished, "budget too small to observe the post-kill tail"
    hi = ce.submit([8, 8], max_new_tokens=2, seed=9,
                   priority="interactive")
    ce.run_until_idle()
    assert ce.stats["preemptions"] >= 1 and hi.finished
    assert r.finished
    # never re-probes: the resumed request packed ZERO further drafts
    assert ce.stats["spec_drafted"] == drafted_at_kill
    assert ce.stats["spec_verify_passes"] == ACC_PROBE
    # and the stream never moved a token (1 was indeed never emitted —
    # the premise of "never matching" held)
    assert r.tokens == plain
    assert 1 not in plain
    ce.close()


@pytest.mark.slow  # drives the spec-width program through churn — in
# CI's compile-count-guard step; tier-1 wall-time protected
def test_spec_decode_is_one_program(tiny_engine):
    """The compile-set bar extends to speculation: a spec_decode engine
    is ONE ragged_step program a width of its own (spec_width is a
    trace-time constant; per-slot draft lengths are DATA) — spec/non-spec mixed
    churn, draft hits and misses, acceptance and rejection, preemption
    and recovery-shaped resume add ZERO compiles. Deltas, not absolutes
    (process-global jit caches — the TL006 order-dependence note)."""
    eng = tiny_engine
    ce = _spec_cont(eng, sched_aging_ticks=1000)
    pre = ce.jit_cache_sizes()
    w = ce.submit(REP, max_new_tokens=6, seed=1, speculative=True)
    ce.run_until_idle()
    assert w.finished
    # warm the COW program too: REP's page is resident now, so a
    # mid-page divergence fires copy_page once (its one allowed compile)
    ce.submit(REP[:4] + [2, 2, 2, 2], max_new_tokens=2, seed=90)
    ce.run_until_idle()
    # and the wide block: REP fits a page, so only the narrow width of
    # the ladder has run so far
    ce.submit([11] * 9, max_new_tokens=2, seed=91)
    ce.run_until_idle()
    assert {r["block_rows"] for r in ce.recorder.records()} == set(
        ce.block_widths)
    base = ce.jit_cache_sizes()
    assert 0 <= base["ragged_step"] - pre["ragged_step"] <= len(
        ce.block_widths)  # one program a width of the ladder
    assert 0 <= base["copy_page"] - pre["copy_page"] <= 1
    # churn: spec and non-spec co-batched, different knobs/lengths,
    # mid-flight admission, preemption, recovery-shaped resume
    reqs = [
        ce.submit(REP + [20 + i], max_new_tokens=6 + i, seed=i,
                  speculative=bool(i % 2),
                  priority="batch" if i else "best_effort")
        for i in range(3)
    ]
    ce.step_chunk()
    vip = ce.submit([7] * 9, max_new_tokens=4, seed=99,
                    priority="interactive")
    ce.run_until_idle()
    assert vip.finished and all(x.finished for x in reqs)
    full = ce.submit(REP, max_new_tokens=10, seed=5, speculative=True)
    ce.run_until_idle()
    resumed = ce.submit(REP + full.tokens[:4], max_new_tokens=6, seed=5,
                        start_step=4, speculative=True)
    ce.run_until_idle()
    assert full.tokens[:4] + resumed.tokens == full.tokens
    assert ce.jit_cache_sizes() == base, (base, ce.jit_cache_sizes())
    ce.check_page_conservation()
    ce.close()


def test_continuous_refuses_unsupported_cache_modes(tiny_engine):
    """Sliding windows stay on the static batcher: the engine refuses
    loudly (the worker catches this and falls back). int8 KV is NOT
    refused anymore — kv_quant serves it natively on the paged path
    (routing regression pinned in tests/test_quant.py)."""
    cfg = tiny_engine.cfg.with_(sliding_window=8)
    eng = GenerationEngine(
        cfg, tiny_engine.params, seq_buckets=(8, 32), batch_buckets=(1,),
        max_seq_len=64,
    )
    with pytest.raises(ValueError, match="sliding-window"):
        ContinuousEngine(eng)
    # int4 joined int8 as a native page mode; only unknown strings refuse
    with pytest.raises(ValueError, match="kv_quant"):
        ContinuousEngine(tiny_engine, kv_quant="nf4")


# ---------------------------------------------------------------------------
# packed int4 KV pages (kv_quant="int4"): lifecycle + compile-set pins
# ---------------------------------------------------------------------------
@pytest.mark.slow  # compiles the int4 step-program shape — tier-1
# wall-time; CI's engine job runs this file unfiltered on every push
def test_int4_streams_bit_identical_across_lifecycle(tiny_engine):
    """THE int4 acceptance pin (the int8 lifecycle contract at double
    density): with ``kv_quant="int4"`` every stream-identity contract
    holds AMONG int4 streams — solo == co-batched == mid-flight-admitted
    == recovery-resumed == preempted == MIGRATED, cache on or off. (int4
    streams may differ from fp/int8 streams; that divergence is bounded
    in tests/test_ops.py.)"""
    eng = tiny_engine

    def solo4(prompt, n, sp, seed, prefix_cache=True):
        ce = _cont(eng, kv_quant="int4", prefix_cache=prefix_cache)
        req = ce.submit(prompt, max_new_tokens=n, sampling=sp, seed=seed)
        ce.run_until_idle()
        assert req.finished
        ce.check_page_conservation()
        return req.tokens

    mixes = [
        (SYS + [21], 8, SamplingParams.make(temperature=0.9, top_k=5), 1),
        ([4, 5], 6, SamplingParams.make(), 2),
        (SYS + [22, 23], 8,
         SamplingParams.make(temperature=0.7, top_p=0.9), 3),
    ]
    # co-batched + mid-flight admission, cache on == solo == cache off
    ce = _cont(eng, kv_quant="int4")
    reqs = []
    for prompt, n, sp, seed in mixes:
        reqs.append(ce.submit(prompt, max_new_tokens=n, sampling=sp,
                              seed=seed))
        ce.step_chunk()  # later requests join mid-flight
    ce.run_until_idle()
    assert all(r.finished for r in reqs)
    ce.check_page_conservation()
    for req, (prompt, n, sp, seed) in zip(reqs, mixes):
        assert req.tokens == solo4(prompt, n, sp, seed), (prompt, seed)
        assert req.tokens == solo4(prompt, n, sp, seed, prefix_cache=False)
    ce.close()
    # recovery resume: the crash-recovery re-prefill shape continues the
    # int4 stream bit-identically
    sp = SamplingParams.make(temperature=1.0, top_p=0.9)
    full = solo4([5, 6, 7], 10, sp, 9)
    cut = 4
    ce2 = _cont(eng, kv_quant="int4")
    resumed = ce2.submit(
        [5, 6, 7] + full[:cut], max_new_tokens=10 - cut, sampling=sp,
        seed=9, start_step=cut,
    )
    ce2.run_until_idle()
    assert full[:cut] + resumed.tokens == full
    ce2.close()
    # preemption: the int4 victim resumes bit-identically
    ce3 = _cont(eng, kv_quant="int4", max_slots=1, sched_aging_ticks=1000)
    victim = ce3.submit([3, 1, 4], max_new_tokens=8, seed=7,
                        priority="best_effort")
    ce3.step_chunk()
    pre = ce3.submit([8, 8], max_new_tokens=2, seed=9,
                     priority="interactive")
    ce3.run_until_idle()
    assert ce3.stats["preemptions"] >= 1
    assert victim.finished and pre.finished
    assert victim.tokens == solo4([3, 1, 4], 8, None, 7)
    ce3.close()
    # migration: int4 pages ship byte-exact between two int4 engines and
    # the migrated stream equals the uninterrupted one
    base = solo4([5, 6, 7], 14,
                 SamplingParams.make(temperature=0.9, top_k=5), 9)
    src = _cont(eng, kv_quant="int4")
    dst = _cont(eng, kv_quant="int4")
    r = src.submit([5, 6, 7], max_new_tokens=14,
                   sampling=SamplingParams.make(temperature=0.9, top_k=5),
                   seed=9)
    _drive_until(src, r, 5)
    r2, moved = _migrate(src, dst, r, "mig4")
    src.run_until_idle()
    dst.run_until_idle()
    assert r2.finished and moved.tokens + r2.tokens == base
    src.check_page_conservation()
    dst.check_page_conservation()
    src.close()
    dst.close()


@pytest.mark.slow  # drives the int4 step-program shape through churn —
# tier-1 wall-time; CI's compile-count-guard step runs it on every push
def test_int4_is_one_program(tiny_engine):
    """The compile-set bar per kv_quant mode, int4 edition: the packed
    engine is ONE ragged_step program (+ copy_page) of its own — the
    nibble packing is a trace-time constant, and admission, mixed churn,
    hits, COW and eviction add ZERO compiles beyond it."""
    eng = tiny_engine
    ce = _cont(eng, kv_quant="int4")
    pre = ce.jit_cache_sizes()
    long = [5, 9] * 12
    ce.submit(long, max_new_tokens=3, seed=7)  # miss -> promoted
    ce.run_until_idle()
    ce.submit(long[:20] + [2, 2, 2, 2], max_new_tokens=3, seed=8)  # COW
    ce.run_until_idle()
    base = ce.jit_cache_sizes()
    assert 0 <= base["ragged_step"] - pre["ragged_step"] <= len(
        ce.block_widths)  # one program a width of the ladder
    assert 0 <= base["copy_page"] - pre["copy_page"] <= 1
    reqs = [
        ce.submit([3 + i] * (2 + i), max_new_tokens=3 + i, seed=i)
        for i in range(4)
    ]
    ce.step_chunk()
    late = ce.submit(long + [3], max_new_tokens=3, seed=30)  # cache hit
    ce.submit([6] * 31, max_new_tokens=2, seed=31)  # different miss
    ce.run_until_idle()
    assert all(r.finished for r in [*reqs, late])
    assert ce.jit_cache_sizes() == base, (base, ce.jit_cache_sizes())
    ce.check_page_conservation()
    ce.close()


def test_migration_refuses_kv_mode_triple_mismatch(tiny_engine):
    """The storage-mode gate is the FULL (kv_quant, page_size, dtype)
    triple: int4 and int8 pools share the int8 byte dtype, so an
    int4<->int8 drain must refuse on kv_quant — loudly — and a page-size
    mismatch refuses the same way (regression for the two-dtype
    assumption the old check baked in). Zero-compile: the refusal fires
    before any device work."""
    ce8 = _cont(tiny_engine, kv_quant="int8")
    ce4 = _cont(tiny_engine, kv_quant="int4")
    assert ce8.migration_mode() == ("int8", 8, "int8")
    assert ce4.migration_mode() == ("int4", 8, "int8")  # same byte dtype!
    blob = {
        "blob_v": 2, "chain": np.asarray([1, 2, 3], np.int32), "length": 2,
        "last_tok": 3, "prefill_target": 3, "n_skip": 0,
        "page_size": 8, "kv_quant": "int8", "dtype": "int8",
        "k": np.zeros(0, np.int8), "v": np.zeros(0, np.int8),
    }
    # int8 blob into an int4 engine: kv_quant differs, dtype alone would
    # NOT have caught it
    assert not ce4.stage_migration("m1", blob)
    assert "m1" not in ce4._migrations
    # page-size mismatch refuses through the same triple
    blob2 = dict(blob, page_size=16)
    assert not ce8.stage_migration("m2", blob2)
    # the matching triple passes the mode gate (fails later on page-count
    # sanity instead of silently staging: length 2 needs 1 page, 0 shipped)
    assert not ce8.stage_migration("m3", blob)
    ce4.close()
    ce8.close()


@pytest.mark.slow  # two engines + full decode traces — CI engine job
def test_int4_to_int8_drain_falls_back_to_re_prefill(tiny_engine):
    """An int4 source draining onto an int8 destination cannot page-ship
    (mode triple mismatch, refused loudly at staging) — the stream takes
    the re-prefill rung instead: resumed at the destination from prompt +
    emitted, exactly-once, with the failure counted and conservation
    holding on both sides."""
    eng = tiny_engine
    src = _cont(eng, kv_quant="int4")
    dst = _cont(eng, kv_quant="int8")
    r = src.submit([5, 6, 7], max_new_tokens=12, seed=9)
    _drive_until(src, r, 5)
    slot = r.slot
    src.freeze_slot(slot)
    chain, limit = src.migration_chain(slot)
    blob = src.export_slot(slot, n_skip=0)
    assert not dst.stage_migration("x1", blob)  # refused: int4 != int8
    dst.check_page_conservation()  # nothing staged, nothing leaked
    moved = src.commit_migration(slot, fell_back=True)
    assert src.stats["migrations_fell_back"] == 1
    src.check_page_conservation()
    # the re-prefill rung: resume WITHOUT a ticket — adopt never set
    r2 = dst.submit(
        moved.prompt + moved.tokens,
        max_new_tokens=moved.budget - len(moved.tokens),
        seed=9, start_step=len(moved.tokens),
    )
    dst.run_until_idle()
    assert r2.finished and len(moved.tokens) + len(r2.tokens) == 12
    dst.check_page_conservation()
    src.close()
    dst.close()


# ---------------------------------------------------------------------------
# multi-tenant co-hosting: one page pool, per-model quotas, cross-model
# preemption (engine/paged.py::SharedPagePool)
# ---------------------------------------------------------------------------
@pytest.mark.slow  # two tenant engines churning on one pool — tier-1
# wall-time; CI's engine job runs this file unfiltered on every push
def test_shared_pool_cross_tenant_preemption_and_conservation(tiny_engine):
    """Two models on ONE page pool: streams bit-identical to private-pool
    runs, per-tenant conservation holding mid-churn, and an interactive
    candidate of tenant A preempting tenant B's best_effort slot when the
    SHARED free list runs dry (the PR 4 rank rules applied across
    models) — with B's victim resuming bit-identically afterwards."""
    from tensorlink_tpu.engine.paged import SharedPagePool

    eng = tiny_engine

    def solo4(prompt, n, seed, priority="interactive"):
        ce = _cont(eng, kv_quant="int4")
        req = ce.submit(prompt, max_new_tokens=n, seed=seed,
                        priority=priority)
        ce.run_until_idle()
        ce.close()
        return req.tokens

    pool = SharedPagePool(eng.cfg, 10, page_size=8, kv_quant="int4")
    a = _cont(eng, kv_quant="int4", pool=pool, model_id="a", page_quota=10)
    b = _cont(eng, kv_quant="int4", pool=pool, model_id="b", page_quota=10)

    # B decodes a best_effort stream holding 3 of the 10 shared pages
    rb = b.submit([3, 1, 4], max_new_tokens=20, seed=7,
                  priority="best_effort")
    _drive_until(b, rb, 3)
    pool.check_page_conservation()
    held_b = b.alloc.used
    assert held_b >= 3

    # A's interactive request needs 8 pages — more than the pool has
    # free — so admission preempts B's strictly-lower-ranked slot
    # THROUGH B's engine (teardown + requeue + bit-identical resume)
    ra = a.submit([40] * 44, max_new_tokens=16, seed=5,
                  priority="interactive")
    a.step_chunk(admit_only=True)
    assert ra.slot >= 0, "candidate should have preempted cross-tenant"
    assert pool.cross_preemptions >= 1
    assert b.stats["preempted_cross_tenant"] >= 1
    pool.check_page_conservation()

    # drive both tenants to quiescence from ONE thread (the pool's
    # single-driver contract), conservation checked every boundary
    while a.step_chunk() | b.step_chunk():
        pool.check_page_conservation()
        assert a.alloc.used <= a.alloc.quota
        assert b.alloc.used <= b.alloc.quota
    assert ra.finished and rb.finished

    # pooled streams == private-pool streams, preempted victim included
    assert ra.tokens == solo4([40] * 44, 16, 5)
    assert rb.tokens == solo4([3, 1, 4], 20, 7, priority="best_effort")

    # per-model telemetry: each tenant's snapshot carries its own quota
    # view and the shared pool totals
    snap_a, snap_b = a.serving_snapshot(), b.serving_snapshot()
    assert snap_a["pool_pages_total"] == snap_b["pool_pages_total"] == 10
    assert snap_b["preempted_cross_tenant"] >= 1
    assert snap_a["preempted_cross_tenant"] == 0
    a.close()
    b.close()
    assert pool.alloc.n_free == 10  # everything returned at teardown


# ---------------------------------------------------------------------------
# disaggregated prefill/decode pools: handoff at the prefill boundary
# (docs/SERVING.md "Disaggregated prefill/decode")
# ---------------------------------------------------------------------------
def _prefill_cont(eng, **kw):
    kw.setdefault("handoff_after_prefill", True)
    kw.setdefault("worker_role", "prefill")
    return _cont(eng, **kw)


def _drive_to_handoff(src, max_chunks=50):
    """Step the prefill-pool engine until at least one slot freezes at
    its prefill→decode boundary; returns the popped manifest."""
    for _ in range(max_chunks):
        src.step_chunk()
        manifest = src.handoff_manifest()
        if manifest:
            return manifest
    raise AssertionError("no handoff produced")


def _handoff(src, dst, slot, mig_id, *, probe=True):
    """The full prefill→decode handoff: probe, export, stage, commit,
    resume-with-adopt at the decode engine. The moved request has emitted
    ZERO tokens (its prefill stopped one short of the prompt), so the
    resume is a plain first submission whose first draw happens at the
    destination."""
    chain, limit = src.migration_chain(slot)
    n_skip = dst.resident_prefix_pages(chain, limit) if probe else 0
    blob = src.export_slot(slot, n_skip=n_skip)
    assert dst.stage_migration(mig_id, blob)
    moved = src.commit_handoff(slot)
    assert moved is not None and moved.tokens == []
    r2 = dst.submit(
        moved.prompt,
        max_new_tokens=moved.budget,
        sampling=moved.sampling,
        eos_ids=sorted(moved.eos),
        seed=moved.seed,
        start_step=moved.start_step,
        priority=moved.priority,
        adopt=mig_id,
    )
    return r2, moved


def test_handoff_flags_and_snapshot_zero_compile(tiny_engine):
    """Fast, zero-compile shape checks: the handoff mark needs BOTH the
    armed engine and the per-request opt-in, 1-token prompts are exempt,
    and the role + handoff counter families ride the serving snapshot
    (→ /stats → /metrics → /healthz serving_modes)."""
    eng = tiny_engine
    ce = _prefill_cont(eng)
    r = ce.submit([1, 2, 3], max_new_tokens=4, seed=1, handoff=True)
    assert r.handoff is True
    r1 = ce.submit([9], max_new_tokens=4, seed=1, handoff=True)
    assert r1.handoff is False  # nothing to prefill ahead of the draw
    r2 = ce.submit([1, 2, 3], max_new_tokens=4, seed=1)
    assert r2.handoff is False  # per-request opt-in
    snap = ce.serving_snapshot()
    assert snap["worker_role"] == "prefill"
    for key in ("handoffs_started", "handoffs_completed",
                "handoffs_fell_back", "kv_pages_slots"):
        assert key in snap, key
    ce.close()
    plain = _cont(eng)
    r3 = plain.submit([1, 2, 3], max_new_tokens=4, seed=1, handoff=True)
    assert r3.handoff is False  # unarmed engine never freezes prefills
    assert plain.serving_snapshot()["worker_role"] == "mixed"
    plain.close()


def test_mlconfig_worker_role_and_spec_decode_defaults():
    """Config pins: worker_role defaults to the single-pool "mixed", and
    MLConfig.spec_decode's one-release opt-in window has elapsed — the
    default is ON (requests still opt in per-call), with False kept as
    the explicit opt-out."""
    from tensorlink_tpu.core.config import MLConfig

    assert MLConfig().worker_role == "mixed"
    assert MLConfig().spec_decode is True
    assert MLConfig(spec_decode=False).spec_decode is False


def test_placement_reserves_decode_pool_only_for_pageable_models():
    """Role-aware placement (ml/validator.py::_plan_and_create) reserves
    decode-role workers as handoff destinations ONLY for jobs that can
    actually hand off — a model the paged engine refuses (sliding-window
    attention) serves through the windowed batcher, which has no
    prefill→decode boundary, so excluding decode workers from its
    placement would just shrink the plannable pool. Driven through the
    real planner with a faked stats/create_job bridge."""
    import logging
    from types import SimpleNamespace

    from tensorlink_tpu.core.config import MLConfig
    from tensorlink_tpu.ml.validator import DistributedValidator

    stats = [
        {"id": "w-pre", "addr": ["127.0.0.1", 1], "serving_role": "prefill",
         "free_bytes": 8e9, "n_devices": 1},
        {"id": "w-dec", "addr": ["127.0.0.1", 2], "serving_role": "decode",
         "free_bytes": 8e9, "n_devices": 1},
    ]
    created = {}

    def _request(kind, payload=None, timeout=None):
        if kind == "stats_workers":
            return stats
        assert kind == "create_job"
        created["job"] = payload["job"]
        return {"accepted": list(payload["job"]["stage_bytes"]),
                "job_id": "j"}

    fake = SimpleNamespace(
        bridge=SimpleNamespace(request=_request),
        node=SimpleNamespace(config=SimpleNamespace(ml=MLConfig())),
        log=logging.getLogger("test-placement"),
    )
    tiny = dict(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=64,
        dtype=jnp.float32, tie_embeddings=False,
    )

    # pageable model: decode worker reserved, stages land on the prefill
    # worker, and the recruit push names its decode pool
    DistributedValidator._plan_and_create(
        fake, {"name": "m"}, ModelConfig(**tiny), seq_len=64,
    )
    job = created["job"]
    assert set(job["stage_bytes"]) == {"w-pre"}, job["stage_bytes"]
    assert job["handoff_push"] == {
        "w-pre": [{"id": "w-dec", "addr": ["127.0.0.1", 2]}]
    }

    # unpageable model (sliding-window attention → windowed batcher, no
    # handoff boundary): the decode worker stays plannable and no pool
    # is pushed
    DistributedValidator._plan_and_create(
        fake, {"name": "m"}, ModelConfig(**tiny, sliding_window=16),
        seq_len=64,
    )
    job = created["job"]
    assert "handoff_push" not in job
    # both workers offered to the planner (whichever it picked, the
    # decode worker was not excluded)
    assert set(job["stage_bytes"]) <= {"w-pre", "w-dec"}

    # continuous batching off: same single-pool placement even for a
    # pageable model
    fake.node.config.ml = MLConfig(continuous_batching=False)
    DistributedValidator._plan_and_create(
        fake, {"name": "m"}, ModelConfig(**tiny), seq_len=64,
    )
    assert "handoff_push" not in created["job"]

    # capacity fallback: when the prefill/mixed subset alone can't fit
    # the model, placement retries single-pool over the FULL pool (the
    # reserved decode worker's capacity is what makes the job fit) —
    # disaggregation must never decline a job the cluster can serve
    fake.node.config.ml = MLConfig()
    stats[0]["free_bytes"] = 1e4  # prefill worker alone: far too small
    DistributedValidator._plan_and_create(
        fake, {"name": "m"}, ModelConfig(**tiny), seq_len=64,
    )
    job = created["job"]
    assert "handoff_push" not in job
    assert "w-dec" in job["stage_bytes"], job["stage_bytes"]
    stats[0]["free_bytes"] = 8e9


@pytest.mark.slow  # drives full decode traces on two engines — tier-1
# wall-time; CI's engine job runs this file unfiltered on every push
def test_handoff_stream_bit_identical_across_pools(tiny_engine):
    """THE disaggregation acceptance pin: a stream admitted on a
    prefill-pool engine and handed to a decode-pool engine at its
    prefill→decode boundary is bit-identical to the single-pool run —
    greedy and sampled, prefix-cache hit and miss on the destination,
    and composed with preemption at the destination. The source emits
    ZERO tokens: the destination recomputes position T-1 as its first
    decode row (bitwise, by ragged framing invariance) and makes the
    fold_in(seed, 0) first draw itself."""
    eng = tiny_engine
    mixes = [
        (SYS + [40, 41], 12, SamplingParams.make(), 7),
        ([5, 6, 7, 8, 9, 10, 11, 12, 13], 10,
         SamplingParams.make(temperature=0.9, top_k=5), 9),
    ]
    solos = [
        _solo(eng, p, n, sampling=sp, seed=s) for p, n, sp, s in mixes
    ]
    # -- miss: a cold decode engine adopts every shipped page ------------
    src, dst = _prefill_cont(eng), _cont(eng)
    reqs = [
        src.submit(p, max_new_tokens=n, sampling=sp, seed=s, handoff=True)
        for p, n, sp, s in mixes
    ]
    shipped = []
    for _ in range(50):
        src.step_chunk()
        for i, (slot, req) in enumerate(src.handoff_manifest()):
            dst.step_chunk()  # the decode pool keeps serving mid-handoff
            mid = f"h{len(shipped)}"
            shipped.append((req, *_handoff(src, dst, slot, mid)))
        if len(shipped) == len(mixes):
            break
    assert len(shipped) == len(mixes)
    dst.run_until_idle()
    by_req = {id(req): r2 for req, r2, _ in shipped}
    for req, solo in zip(reqs, solos):
        r2 = by_req[id(req)]
        assert r2.finished and req.tokens == []
        assert r2.tokens == solo, (r2.tokens, solo)
    assert src.stats["handoffs_started"] == 2
    assert src.stats["handoffs_completed"] == 2
    assert src.serving_snapshot()["pages_in_transit"] == 0
    assert dst.stats["migrations_adopted"] == 2
    src.close()
    dst.close()

    # -- hit: destination-resident prefix short-circuits the ship --------
    src, dst = _prefill_cont(eng), _cont(eng)
    warm = dst.submit(SYS + [40, 41], max_new_tokens=2, seed=1)
    dst.run_until_idle()
    assert warm.finished  # prompt pages promoted into dst's trie
    r = src.submit(SYS + [40, 41], max_new_tokens=12, seed=7, handoff=True)
    (slot, _req), = _drive_to_handoff(src)
    chain, limit = src.migration_chain(slot)
    assert chain == SYS + [40, 41] and limit == len(chain) - 1
    n_skip = dst.resident_prefix_pages(chain, limit)
    assert n_skip >= 2  # the warmed prompt really is resident
    full_pages = src.export_slot(slot, n_skip=0)["k"].shape[0]
    r2, _moved = _handoff(src, dst, slot, "hh")
    dst.run_until_idle()
    assert r2.tokens == solos[0]
    # fewer pages crossed the "wire" than the slot holds
    assert full_pages > full_pages - n_skip >= 0
    src.close()
    dst.close()

    # -- composed with preemption at the destination ---------------------
    src = _prefill_cont(eng)
    dst = _cont(eng, max_slots=1)  # one slot: the flood must preempt
    r = src.submit(
        SYS + [40, 41], max_new_tokens=12, seed=7,
        priority="best_effort", handoff=True,
    )
    (slot, _req), = _drive_to_handoff(src)
    r2, _moved = _handoff(src, dst, slot, "hp")
    dst.step_chunk()  # adopted + decoding on the destination
    assert len(r2.tokens) > 0 and not r2.finished
    hi = dst.submit([1, 1], max_new_tokens=3, seed=1, priority="interactive")
    dst.run_until_idle()
    assert hi.finished and r2.finished
    assert dst.stats["preemptions"] >= 1  # the adopted slot was preempted
    assert r2.tokens == solos[0]
    src.close()
    dst.close()


@pytest.mark.slow  # see above — CI engine job coverage
def test_handoff_fallback_ladder_re_prefill_and_local_resume(tiny_engine):
    """The handoff fallback ladder, both rungs below page-ship: a failed
    transfer redirects the stream for a fresh prefill at the destination
    (commit_handoff(fell_back=True) — the never-staged ticket quietly
    takes the re-prefill rung at admission), and with no destination at
    all the slot resumes locally (abort_handoff): the final prompt token
    simply prefills here and the stream decodes as on a mixed worker.
    Both rungs bit-identical; started == completed + fell_back."""
    eng = tiny_engine
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    base = _solo(eng, prompt, 12, seed=5)

    # rung: re-prefill redirect at the destination
    src, dst = _prefill_cont(eng), _cont(eng)
    r = src.submit(prompt, max_new_tokens=12, seed=5, handoff=True)
    (slot, _req), = _drive_to_handoff(src)
    src.export_slot(slot)  # gathered, then the wire "fails"
    moved = src.commit_handoff(slot, fell_back=True)
    src.check_page_conservation()
    r2 = dst.submit(
        moved.prompt, max_new_tokens=moved.budget, seed=5,
        start_step=0, adopt="never-staged",
    )
    dst.run_until_idle()
    assert r2.finished and r2.tokens == base
    assert dst.stats["migrations_adopted"] == 0  # re-prefill rung
    assert src.stats["handoffs_started"] == 1
    assert src.stats["handoffs_fell_back"] == 1
    assert src.stats["handoffs_completed"] == 0
    src.close()
    dst.close()

    # rung: resume locally (no usable destination)
    ce = _prefill_cont(eng)
    r = ce.submit(prompt, max_new_tokens=12, seed=5, handoff=True)
    (slot, req), = _drive_to_handoff(ce)
    ce.abort_handoff(slot)
    assert req.handoff is False  # degraded to mixed serving for good
    ce.run_until_idle()
    assert r.finished and r.tokens == base
    s = ce.stats
    assert s["handoffs_started"] == s["handoffs_completed"] \
        + s["handoffs_fell_back"] == 1
    ce.close()


@pytest.mark.slow  # see above — CI engine job coverage
def test_handoff_freeze_does_not_fence_admissions(tiny_engine):
    """The drain fence generalized into steady-state handoff: while a
    slot sits frozen at its prefill→decode boundary, the engine keeps
    ADMITTING and SERVING — submit succeeds (no SchedulerOverloaded, no
    draining rejection), the new admission prefills and decodes to
    completion, and page conservation (frozen pages in transit) holds on
    both engines mid-flight throughout."""
    eng = tiny_engine
    prompt = SYS + [40, 41]
    src, dst = _prefill_cont(eng), _cont(eng)
    r = src.submit(prompt, max_new_tokens=12, seed=7, handoff=True)
    (slot, _req), = _drive_to_handoff(src)
    # slot is frozen, nothing resolved yet: the fence must NOT exist
    assert src.drain_state == "serving"
    assert src.admission_check() is None
    nb = src.submit([8, 8, 2], max_new_tokens=6, seed=42)
    assert nb.error is None
    while not nb.finished:
        src.step_chunk()
        src.check_page_conservation()  # frozen slot counted in transit
    assert nb.tokens == _solo(eng, [8, 8, 2], 6, seed=42)
    # now resolve the parked handoff; the stream is unharmed
    chain, limit = src.migration_chain(slot)
    blob = src.export_slot(slot, n_skip=0)
    assert dst.stage_migration("hf", blob)
    dst.check_page_conservation()  # staged ticket counted in transit
    moved = src.commit_handoff(slot)
    r2 = dst.submit(
        moved.prompt, max_new_tokens=moved.budget, seed=7, adopt="hf",
    )
    dst.run_until_idle()
    assert r2.tokens == _solo(eng, prompt, 12, seed=7)
    src.close()
    dst.close()


@pytest.mark.slow  # exercises the handoff device paths' compile keys —
# referenced by CI's compile-count-guard step
def test_handoff_adds_zero_new_programs(tiny_engine):
    """Compile-set guard over the steady-state data path: a full
    prefill→decode handoff (freeze at the boundary / export / stage /
    adopt / first draw at the destination) adds ZERO compiled programs
    beyond the gather/scatter page movers migration already registered —
    the serving step set (ragged_step, copy_page) stays exactly where
    it was on BOTH sides."""
    eng = tiny_engine
    src, dst = _prefill_cont(eng), _cont(eng)
    # warm every program class once (incl. the page movers)
    w = src.submit([4, 2, 4, 2, 1, 1, 3], max_new_tokens=4, seed=2,
                   handoff=True)
    (slot, _req), = _drive_to_handoff(src)
    r2, _ = _handoff(src, dst, slot, "w")
    dst.run_until_idle()
    assert r2.finished and w.tokens == []
    base = src.jit_cache_sizes()
    # steady state: more handoffs, mixed with live decode on both sides
    nb = dst.submit([9, 9, 1], max_new_tokens=16, seed=41)
    reqs = [
        src.submit([4, 2, 4, 2, 1, 1, 3 + i], max_new_tokens=6, seed=2 + i,
                   handoff=True)
        for i in range(2)
    ]
    done = []
    for _ in range(50):
        src.step_chunk()
        dst.step_chunk()
        for slot, _req in src.handoff_manifest():
            done.append(_handoff(src, dst, slot, f"z{len(done)}")[0])
        if len(done) == len(reqs):
            break
    dst.run_until_idle()
    assert len(done) == len(reqs) and all(r.finished for r in done)
    assert nb.finished
    after = src.jit_cache_sizes()
    assert after == base, (base, after)
    src.close()
    dst.close()
