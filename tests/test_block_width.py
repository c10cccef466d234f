"""The width ladder of the packed block (engine/continuous.py
``block_widths``, ROADMAP S5): a chunk whose longest grant fits the narrow
width (a whole number of pages that holds the verify rows) packs a block
that wide, every other chunk ``prefill_chunk``. The step function is
compiled once a width, at most twice; which width ran is data of the
chunk (``block_rows`` in its record, ``ragged_blocks_narrow`` of
``ragged_blocks``), and the streams are the ones a single-width engine
serves, bit for bit."""

import threading

import jax
import jax.numpy as jnp
import pytest

from tensorlink_tpu.engine.continuous import ContinuousEngine
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.sampling import SamplingParams
from tensorlink_tpu.models import ModelConfig, init_params

SLOTS, PAGE, CHUNK, MAX_LEN = 4, 8, 32, 96
REP = [5, 9] * 4  # what prompt lookup drafts from


@pytest.fixture(autouse=True)
def _an_idle_engine_waits_for_its_build(monkeypatch):
    """These tests compile on the CPU, seconds a program: an idle engine
    waits for its build thread as long as that takes (a server's waits
    ``BUILD_JOIN_MAX_S`` at a time: ``tests/test_flat_rung.py`` has that
    case)."""
    from tensorlink_tpu.engine import continuous

    monkeypatch.setattr(continuous, "BUILD_JOIN_MAX_S", 120.0)


def _cfg(**kw):
    # widths of its own: the jit caches are process-global, and the
    # compile counts below are of THIS module's programs
    base = dict(
        family="llama", vocab_size=160, d_model=32, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=48, max_seq_len=MAX_LEN,
        dtype=jnp.float32, tie_embeddings=False,
    )
    return ModelConfig(**(base | kw))


@pytest.fixture(scope="module")
def tiny():
    cfg = _cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _cont(tiny, **kw):
    cfg, params = tiny
    eng = GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,),
        max_seq_len=MAX_LEN,
    )
    kw = dict(max_slots=SLOTS, page_size=PAGE, chunk_steps=4,
              prefill_chunk=CHUNK, spec_decode=True, spec_draft=4) | kw
    return ContinuousEngine(eng, **kw)


def _widths(ce) -> list[int]:
    return [r["block_rows"] for r in ce.recorder.records()]


# ---------------------------------------------------------------------------
# the ladder itself
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "kw,want",
    [
        (dict(), (8, 32)),  # 5 verify rows: one page of 8
        (dict(spec_draft=8), (16, 32)),  # 9 verify rows: two pages
        (dict(spec_decode=False), (8, 32)),  # one row still packs a page
        (dict(page_size=16, prefill_chunk=16), (16,)),  # nothing narrower
        (dict(prefill_chunk=8), (8,)),
        (dict(spec_draft=31), (32,)),  # the verify rows fill the chunk
    ],
    ids=["spec4", "spec8", "no-spec", "page-is-chunk", "tiny-chunk",
         "drafts-fill-chunk"],
)
def test_the_ladder_is_derived_from_page_and_verify_rows(tiny, kw, want):
    ce = _cont(tiny, **kw)
    assert ce.block_widths == want
    assert ce.block_widths[-1] == ce.prefill_chunk
    assert ce.block_widths[0] >= ce.spec_width
    with pytest.raises(ValueError, match="width"):
        ce.lower_step(ce.prefill_chunk + 1)
    ce.close()


def _decoding(ce, speculative=False):
    """Two slots past their prefill, decoding."""
    for seed, prompt in enumerate(([1, 2, 3], REP)):
        ce.submit(prompt, max_new_tokens=40, seed=seed,
                  speculative=speculative)
    ce.step_chunk()
    assert not ce._prefilling and ce._active.sum() == 2


def _all_decode(ce):
    _decoding(ce)
    return 8, 1


def _decode_and_drafts(ce):
    _decoding(ce, speculative=True)
    for _ in range(8):  # until the stream repeats itself: a full draft
        if ce._pack_ragged()[3].max() == ce.spec_draft:
            return 8, 1 + ce.spec_draft
        ce.step_chunk()
    raise AssertionError("no slot drafted spec_draft rows")


def _tail(n):
    def case(ce):
        # CHUNK prompt tokens ride the first (wide) chunk, n are left
        ce.submit(list(range(1, CHUNK + n + 1)), max_new_tokens=4)
        ce.step_chunk()
        assert list(ce._prefilling) == [0]
        return (8 if n <= 8 else CHUNK), n
    return case


def _mid_prefill(ce):
    _decoding(ce)
    ce.submit(list(range(1, 2 * CHUNK)), max_new_tokens=4, seed=3)
    return CHUNK, CHUNK  # a whole grant beside two single rows


@pytest.mark.parametrize(
    "case",
    [_all_decode, _decode_and_drafts, _tail(8), _tail(9), _tail(1),
     _mid_prefill],
    ids=["all-decode", "decode+drafts", "tail-of-the-narrow-width",
         "tail-one-more", "tail-of-one", "a-slot-mid-prefill"],
)
def test_pack_picks_the_narrowest_width_the_longest_grant_fits(tiny, case):
    ce = _cont(tiny)
    assert ce.block_widths == (8, CHUNK)
    width, longest = case(ce)
    ce._admit()
    blk, _starts, n_valid, *_ = ce._pack_ragged()
    assert int(n_valid.max()) == longest
    assert blk.shape == (SLOTS, width) and blk.flags["C_CONTIGUOUS"]
    # every granted row is in the block that goes out
    assert all(n <= width for n in n_valid)
    ce.run_until_idle()
    ce.close()


def test_an_idle_engine_packs_nothing(tiny):
    ce = _cont(tiny)
    assert ce._pack_ragged() is None
    assert ce.step_chunk() is False
    assert ce.stats["ragged_blocks"] == 0 and not ce.recorder.records()
    ce.close()


# ---------------------------------------------------------------------------
# same work: the streams of a single-width engine, bit for bit
# ---------------------------------------------------------------------------
def _serve(ce):
    """A prefix hit, sampled beside greedy rows, a mid-flight admission and
    a preemption; returns every stream."""
    sp = SamplingParams.make(temperature=0.9, top_k=7)
    long = [(7 * i) % 150 + 1 for i in range(44)]
    warm = ce.submit(long, max_new_tokens=6, seed=1)  # promoted at teardown
    ce.run_until_idle()
    holders = [
        ce.submit([3 + i] * (3 + 4 * i), max_new_tokens=30, seed=10 + i,
                  sampling=sp if i % 2 else None, priority="batch",
                  speculative=not i % 2)
        for i in range(ce.max_slots)
    ]
    ce.step_chunk()
    ce.step_chunk()
    hit = ce.submit(long[:40] + [2, 2, 2], max_new_tokens=9, seed=2,
                    sampling=sp, priority="interactive")
    rep = ce.submit(REP * 2, max_new_tokens=12, seed=3, speculative=True)
    ce.run_until_idle()
    reqs = [warm, *holders, hit, rep]
    assert all(r.finished for r in reqs)
    assert ce.stats["preemptions"] >= 1
    assert ce.serving_snapshot()["prefix_hit_tokens"] >= 32
    ce.check_page_conservation()
    return [r.tokens for r in reqs]


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_streams_are_the_single_width_engines(tiny, spec):
    """The wide engine has no narrow width by construction: its pages are
    as long as its chunk, so every block is ``prefill_chunk`` wide."""
    kw = dict(spec_decode=spec, sched_aging_ticks=1000)
    wide = _cont(tiny, page_size=CHUNK, **kw)
    assert wide.block_widths == (CHUNK,)
    ref = _serve(wide)
    assert set(_widths(wide)) == {CHUNK}
    assert wide.stats["ragged_blocks_narrow"] == 0
    ladder = _cont(tiny, **kw)
    assert ladder.block_widths == (8, CHUNK)
    assert _serve(ladder) == ref
    assert set(_widths(ladder)) == {8, CHUNK}
    # and it computed fewer rows for the same tokens
    assert ladder.stats["ragged_rows_valid"] > 0
    assert (ladder.stats["ragged_rows_computed"]
            < SLOTS * CHUNK * ladder.stats["ragged_blocks"])
    wide.close()
    ladder.close()


def test_same_pages_the_same_streams_at_one_forced_width(tiny):
    """The ladder against the same engine (pages of 8 too) held to its
    wide program: what differs is the block's width and nothing else."""
    kw = dict(sched_aging_ticks=1000)
    wide = _cont(tiny, **kw)
    wide.block_widths = wide.block_widths[-1:]
    ref = _serve(wide)
    assert set(_widths(wide)) == {CHUNK}
    ladder = _cont(tiny, **kw)
    assert _serve(ladder) == ref
    assert ladder.stats["prefill_tokens"] == wide.stats["prefill_tokens"]
    assert ladder.stats["decode_steps"] == wide.stats["decode_steps"]
    wide.close()
    ladder.close()


# ---------------------------------------------------------------------------
# one program a width, at most two; the counters and the record
# ---------------------------------------------------------------------------
def test_a_churn_of_mixes_compiles_one_program_a_width():
    # a config of its own: what this test compiles nobody compiled before
    tiny = (_cfg(d_ff=56), init_params(_cfg(d_ff=56), jax.random.PRNGKey(1)))
    ce = _cont(tiny, sched_aging_ticks=1000)
    pre = ce.jit_cache_sizes()
    # one chunk of each width: a prompt longer than a page, then decode
    ce.submit([100] * 9, max_new_tokens=6)
    ce.run_until_idle()
    assert _widths(ce) == [CHUNK, 8]
    base = ce.jit_cache_sizes()
    assert base["ragged_step"] - pre["ragged_step"] == 2 == len(
        ce.block_widths)
    _serve(ce)
    for n in (1, 7, 8, 9, 31, 33, 60):  # tails on both sides of the page
        ce.submit([n] * n, max_new_tokens=3, seed=n)
    ce.run_until_idle()
    after = ce.jit_cache_sizes()
    assert after["ragged_step"] == base["ragged_step"], (base, after)
    assert after["decode_step"] == after["sample_rows"] == 0
    ce.close()


def test_counters_and_records_say_which_width_ran(tiny):
    ce = _cont(tiny)
    _serve(ce)
    recs, s = ce.recorder.records(), ce.stats
    widths = [r["block_rows"] for r in recs]
    assert set(widths) == {8, CHUNK}
    assert s["ragged_blocks"] == len(recs)
    assert s["ragged_blocks_narrow"] == widths.count(8) > 0
    assert s["ragged_rows_computed"] == SLOTS * sum(widths)
    # a narrow chunk granted no slot more than its width, a wide one did
    for r in recs:
        if r["block_rows"] == 8:
            assert r["prefill_granted"] <= r["prefilling"] * 8
        else:  # some slot's grant did not fit a page
            assert r["prefill_granted"] > 8
    snap = ce.serving_snapshot()
    assert snap["ragged_blocks_narrow"] == s["ragged_blocks_narrow"]
    ce.close()


def test_lower_step_lowers_each_width(tiny):
    ce = _cont(tiny)
    texts = {w: ce.lower_step(w).as_text() for w in ce.block_widths}
    for w, text in texts.items():
        assert f"tensor<{SLOTS}x{w}xi32>" in text
    assert ce.lower_step().as_text() == texts[CHUNK]  # the widest by default
    ce.close()


# ---------------------------------------------------------------------------
# the narrow program is built behind the first requests
# ---------------------------------------------------------------------------
@pytest.fixture
def built():
    """The names of the functions whose programs the backend compiled
    while the test ran."""
    from jax._src import monitoring

    names: list = []

    def listen(event, _secs, **kw):
        if event.endswith("backend_compile_duration"):
            names.append(kw.get("fun_name"))

    monitoring.register_event_duration_secs_listener(listen)
    yield names
    monitoring.unregister_event_duration_listener(listen)


@pytest.mark.parametrize("tp", [1, 2])
def test_build_steps_returns_with_the_widest_and_serving_builds_none(
        tiny, tp, built):
    """What a server calls before traffic (``ml/worker.py::_ensure_cont``):
    the widest program is built when it returns, the narrow one when the
    engine first runs out of work, and the chunks that follow find both
    (no second ``backend_compile`` of the step at either width)."""
    if len(jax.devices()) < tp:
        pytest.skip("needs two devices")
    # a config of its own, so that nothing here was built before
    cfg = _cfg(d_ff=72 + 8 * tp)
    ce = _cont((cfg, init_params(cfg, jax.random.PRNGKey(2))),
               tensor_parallel=tp)
    name = "jit(tp_ragged_step)" if tp > 1 else "jit(paged_ragged_step)"
    ce.build_steps()
    assert built.count(name) >= 1 and ce._build is not None
    assert not ce.recorder.records() and ce.stats["ragged_blocks"] == 0
    ce.build_steps()  # a second call starts no second build
    ce.submit([100] * 9, max_new_tokens=6)
    ce.run_until_idle()
    assert ce._build is None  # joined when the work ran out, at the latest
    assert built.count(name) == len(ce.block_widths) == 2, built
    first = _widths(ce)
    assert first[0] == CHUNK  # a request begins wide; the rest as built
    assert ce.stats["ragged_blocks_narrow_unbuilt"] == first[1:].count(CHUNK)
    ce.submit([101] * 9, max_new_tokens=6, seed=1)
    ce.run_until_idle()
    assert _widths(ce)[len(first):] == [CHUNK, 8]
    assert built.count(name) == 2, built  # the calls found them built
    key = "tp_ragged_step" if tp > 1 else "ragged_step"
    before = ce.jit_cache_sizes()[key]
    _serve(ce)
    assert ce.jit_cache_sizes()[key] == before
    assert built.count(name) == 2, built
    ce.check_page_conservation()
    ce.close()


class _Gated:
    """A lowered step whose ``compile`` waits for ``gate`` and then fails
    with ``error`` or compiles: the thread of ``build_steps`` held where a
    test wants it."""

    def __init__(self, lowered, gate, error):
        self.lowered, self.gate, self.error = lowered, gate, error
        self.compiled = threading.Event()

    def compile(self):
        assert self.gate.wait(60)
        if self.error is not None:
            raise self.error
        out = self.lowered.compile()
        self.compiled.set()
        return out


def _gate_the_narrow_build(ce, error=None):
    """``ce.build_steps()`` with the narrow program's compile held at a
    gate; returns (gate, the gated program)."""
    gate, real, held = threading.Event(), ce.lower_step, []

    def lower_step(width=None):
        low = real(width)
        if width != ce.block_widths[0]:
            return low
        held.append(_Gated(low, gate, error))
        return held[-1]

    ce.lower_step = lower_step
    ce.build_steps()  # returns with the gate shut: it waited for the widest
    assert ce._build is not None and not ce._build.done()
    return gate, held[0]


def test_a_chunk_that_fits_narrow_runs_wide_until_its_program_is_built(tiny):
    ref = _cont(tiny)
    want = ref.submit([100] * 9, max_new_tokens=30, seed=4)
    ref.run_until_idle()
    ce = _cont(tiny)
    gate, narrow = _gate_the_narrow_build(ce)
    req = ce.submit([100] * 9, max_new_tokens=30, seed=4)
    for _ in range(3):  # a prefill piece, then decode chunks: all wide
        assert ce.step_chunk()
    assert _widths(ce) == [CHUNK] * 3 and not narrow.compiled.is_set()
    assert ce.stats["ragged_blocks_narrow_unbuilt"] == 2
    assert ce.stats["ragged_blocks_narrow"] == 0
    assert ce.stats["ragged_rows_computed"] == 3 * SLOTS * CHUNK
    gate.set()
    ce._build.result(timeout=120)  # the thread is through; nothing joined
    assert ce.step_chunk()
    assert ce._build is None and _widths(ce)[-1] == 8
    ce.run_until_idle()
    assert ce.stats["ragged_blocks_narrow_unbuilt"] == 2
    assert req.finished and req.tokens == want.tokens  # the same stream
    ref.close()
    ce.close()


def test_the_build_is_joined_when_the_engine_first_has_no_work(tiny):
    ce = _cont(tiny)
    gate, narrow = _gate_the_narrow_build(ce)
    answered = []

    def on_finish(req):
        # the answer left with the gate still shut: it waited for nothing
        answered.append(narrow.compiled.is_set())
        threading.Timer(0.1, gate.set).start()

    req = ce.submit([100] * 9, max_new_tokens=10, on_finish=on_finish)
    ce.run_until_idle()  # returns with the build joined
    assert answered == [False] and req.finished and len(req.tokens) == 10
    assert narrow.compiled.is_set() and ce._build is None
    assert set(_widths(ce)) == {CHUNK}
    assert ce.stats["ragged_blocks_narrow_unbuilt"] == len(_widths(ce)) - 1
    n = len(_widths(ce))
    ce.submit([101] * 9, max_new_tokens=6, seed=1)
    ce.run_until_idle()
    assert _widths(ce)[n:] == [CHUNK, 8]
    assert ce.stats["ragged_blocks_narrow_unbuilt"] == n - 1
    ce.close()


@pytest.mark.parametrize("live", [False, True], ids=["at-idle", "mid-flight"])
def test_a_failing_build_is_raised_on_the_serving_path(tiny, live):
    ce = _cont(tiny)
    gate, _narrow = _gate_the_narrow_build(ce, RuntimeError("no such rung"))
    # at idle: the thread fails once the answer has left
    req = ce.submit([100] * 9, max_new_tokens=10,
                    on_finish=None if live else lambda _req: gate.set())
    assert ce.step_chunk()
    if live:
        gate.set()
        assert ce._build.exception(timeout=60) is not None
    with pytest.raises(RuntimeError, match="no such rung"):
        ce.run_until_idle()
    assert ce._build is None  # raised once
    assert req.finished != live  # at idle the wide program had served it
    ce.close()


def test_close_drops_a_build_under_way(tiny):
    ce = _cont(tiny)
    gate, narrow = _gate_the_narrow_build(ce)
    build = ce._build
    ce.close()
    assert ce._build is None
    gate.set()
    # dropped if the thread had not taken it up, else it ends on its own
    assert build.cancelled() or narrow.compiled.wait(120)


# ---------------------------------------------------------------------------
# the build is counted where it happens (``step_build_ms``,
# ``step_build_waited_ms`` of ``serving_snapshot``)
# ---------------------------------------------------------------------------
def _build_gauges(ce) -> tuple[float, float]:
    snap = ce.serving_snapshot()
    return snap["step_build_ms"], snap["step_build_waited_ms"]


@pytest.mark.parametrize(
    "d_ff,kw,ahead",
    [
        (104, dict(), True),  # what a server does: build_steps, then the join
        (112, dict(prefill_chunk=8), False),  # one width: its first chunk
        (120, dict(), False),  # two widths nobody built ahead: a call each
    ],
    ids=["build-steps-and-join", "one-width-first-chunk", "a-first-call-each"],
)
def test_the_build_is_counted_where_it_happens_and_nowhere_else(
        d_ff, kw, ahead):
    """``step_build_ms`` holds the seconds of tracing, lowering and
    compiling every step program this engine built, on whatever thread;
    ``step_build_waited_ms`` what of it a serving path spent in the work
    or waiting for it: the ``build_steps`` call and the join, or the
    first call at a width where nothing was built ahead. Both are written
    there only: no chunk after that moves either."""
    cfg = _cfg(d_ff=d_ff)  # of its own: nothing here was built before
    ce = _cont((cfg, init_params(cfg, jax.random.PRNGKey(3))), **kw)
    assert _build_gauges(ce) == (0.0, 0.0)
    if ahead:
        ce.build_steps()
        built, waited = _build_gauges(ce)
        # both lowerings and the widest's compile, against the call itself
        assert built >= waited > 0 and ce._unbuilt == set()
    else:
        built = waited = 0.0
    ce.submit([100] * 9, max_new_tokens=6)
    ce.run_until_idle()  # a wide chunk, narrow ones, and the join
    assert ce._build is None and not ce._unbuilt
    after = _build_gauges(ce)
    if ahead:
        # the narrow program's own seconds came in where it was joined
        assert after[0] > built and after[1] >= waited
    assert after[0] >= after[1] > 0
    if not ahead:
        # the calls that built a program, and nothing of the other chunks
        assert sorted(set(_widths(ce))) == list(ce.block_widths)
        recs = ce.recorder.records()
        first = {r["block_rows"]: r["dispatch_ms"] for r in reversed(recs)}
        assert after[0] == after[1] == pytest.approx(
            sum(first.values()), abs=0.01 * len(first))
    _serve(ce)  # a churn of every mix: neither gauge moves again
    ce.submit([101] * 40, max_new_tokens=5, seed=1)
    ce.run_until_idle()
    assert _build_gauges(ce) == after
    ce.close()


def test_a_program_built_before_the_engine_is_not_counted(tiny):
    """The jit cache is the process's: an engine whose calls find their
    programs there (another engine of the same shapes built them) built
    nothing, and says so."""
    first = _cont(tiny)
    first.submit([100] * 9, max_new_tokens=6)
    first.run_until_idle()
    ce = _cont(tiny)
    ce.submit([100] * 9, max_new_tokens=6)
    ce.run_until_idle()
    assert sorted(set(_widths(ce))) == list(ce.block_widths)
    assert _build_gauges(ce) == (0.0, 0.0) and not ce._unbuilt
    first.close()
    ce.close()


# ---------------------------------------------------------------------------
# the stream stage's order
# ---------------------------------------------------------------------------
def test_first_tokens_leave_first_and_ending_entries_last(tiny):
    ce = _cont(tiny, spec_decode=False)
    log: list = []

    def taps(name):
        return dict(stream_cb=lambda tok: log.append((name, tok)) and None,
                    on_finish=lambda req: log.append((name, "end")))

    mid = ce.submit([1, 2, 3], max_new_tokens=40, **taps("mid"))
    end = ce.submit([4, 5, 6], max_new_tokens=6, seed=1, **taps("end"))
    ce.step_chunk()  # four tokens each (chunk_steps)
    new = ce.submit([7, 8, 9], max_new_tokens=40, seed=2, **taps("new"))
    ce.step_chunk()  # `end` ends, `new` gets its first token, `mid` goes on
    assert [r.slot for r in (mid, new)] == [0, 2] and len(end.tokens) == 6
    assert list(ce._unstreamed) == [mid.rid, end.rid, new.rid]  # slot order
    del log[:]
    ce.flush_stream()
    assert end.finished and not ce._unstreamed
    # the first token alone, every other stream's next token, what goes
    # on in slot order, what is left of an ending entry and its end last
    assert [name for name, _ in log] == (
        ["new", "mid", "end"] + ["mid"] * 3 + ["new"] * 3 + ["end"] * 2)
    # a request's own tokens in their order, the end behind its last
    assert log[2] == ("end", end.tokens[4])
    assert log[-2:] == [("end", end.tokens[5]), ("end", "end")]
    assert [t for name, t in log if name == "new"] == new.tokens
    assert [t for name, t in log if name == "mid"] == mid.tokens[4:]
    ce.run_until_idle()
    ce.close()


def test_every_stream_s_next_token_leaves_before_anyone_s_second(tiny):
    """Behind the first tokens every stream that goes on hands on ONE
    token, in slot order, and only then the rest of its chunk: no reader's
    stall lasts while another reader's whole chunk goes out ahead of it.
    What is left of an entry that ends a request leaves last, with its
    end; a stop on a token sent ahead takes the rest of its entry
    along."""
    ce = _cont(tiny, spec_decode=False)
    log: list = []
    stop_at: dict = {}

    def taps(name):
        def cb(tok):
            log.append((name, tok))
            return stop_at.get(name) == sum(1 for n, _ in log if n == name)
        return dict(stream_cb=cb,
                    on_finish=lambda req: log.append((name, "end")))

    a = ce.submit([1, 2, 3], max_new_tokens=40, **taps("a"))
    b = ce.submit([4, 5, 6], max_new_tokens=40, seed=1, **taps("b"))
    end = ce.submit([7, 8, 9], max_new_tokens=6, seed=2, **taps("end"))
    ce.step_chunk()  # four tokens each (chunk_steps)
    new = ce.submit([2, 4, 6], max_new_tokens=40, seed=3, **taps("new"))
    ce.step_chunk()  # `end` ends, `new` gets its first token, a and b go on
    assert list(ce._unstreamed) == [a.rid, b.rid, end.rid, new.rid]
    del log[:]
    ce.flush_stream()
    assert [name for name, _ in log] == (
        ["new"] + ["a", "b", "end"] + ["a"] * 3 + ["b"] * 3 + ["new"] * 3
        + ["end"] * 2)
    assert log[-1] == ("end", "end") and end.finished
    for name, req, since in (("a", a, 4), ("b", b, 4), ("new", new, 0)):
        assert [t for n, t in log if n == name] == req.tokens[since:]
    # a reader that stops at the token sent ahead loses the rest of the
    # chunk with it, and nobody else's
    ce.step_chunk()
    del log[:]
    stop_at["a"] = 1
    ce.flush_stream()
    assert a.cancelled and log[:2] == [("a", a.tokens[-1]), ("a", "end")]
    assert [n for n, _ in log].count("a") == 2
    assert a.rid not in ce._unstreamed
    assert [t for n, t in log if n == "b"] == b.tokens[-4:]
    ce.run_until_idle()
    assert b.finished and new.finished and len(b.tokens) == 40
    ce.check_page_conservation()
    ce.close()


@pytest.mark.parametrize("how", ["stops", "raises"])
def test_an_ending_entry_s_token_sent_ahead_can_end_it_once(tiny, how):
    """The entry that ends a request hands its next token on with the
    others' and the rest last. A reader that stops (or raises) at that
    token is answered once, there; the rest of the entry goes with the
    cut, and the slot, which another request holds by then, is left
    alone."""
    ce = _cont(tiny, spec_decode=False)
    got, ended = [], []

    def cb(tok):
        got.append(tok)
        if len(got) == 5:  # the second chunk's token sent ahead
            if how == "raises":
                raise RuntimeError("reader gone")
            return True

    other = ce.submit([1, 2, 3], max_new_tokens=12)
    req = ce.submit([4, 5, 6], max_new_tokens=7, seed=1, stream_cb=cb,
                    on_finish=lambda r: ended.append(r.rid))
    ce.step_chunk()
    ce.step_chunk()  # req's last three tokens are settled, its slot freed
    assert len(got) == 4 and ce._unstreamed[req.rid][1:4] == (3, False, True)
    nxt = ce.submit([7, 8, 9], max_new_tokens=12, seed=2)
    if how == "raises":
        with pytest.raises(RuntimeError, match="reader gone"):
            ce.step_chunk(admit_only=True)
        assert not req.finished and isinstance(req.error, RuntimeError)
    else:
        ce.step_chunk(admit_only=True)  # admits nxt, then streams
        assert req.finished and req.tokens == got
    assert nxt.slot == 1 and ce._slots[1] is nxt  # req's old slot
    assert ended == [req.rid] and req.done.is_set() and len(got) == 5
    assert req.rid not in ce._unstreamed
    ce.run_until_idle()
    assert other.finished and nxt.finished and len(nxt.tokens) == 12
    assert ended == [req.rid]
    ce.check_page_conservation()
    ce.close()


@pytest.mark.parametrize("how", ["stops", "raises"])
def test_a_first_token_that_ends_its_stream_takes_the_rest_along(tiny, how):
    """The first token leaves ahead of the tokens that came with it: a
    callback that stops or raises there leaves none of them pending."""
    ce = _cont(tiny, spec_decode=False)
    got: list = []

    def cb(tok):
        got.append(tok)
        if how == "raises" and len(got) == 1:
            raise RuntimeError("reader gone")
        return how == "stops"

    other = ce.submit([1, 2, 3], max_new_tokens=12)
    req = ce.submit([4, 5, 6], max_new_tokens=12, seed=1, stream_cb=cb)
    ce.step_chunk()  # four tokens each, pending
    assert ce._unstreamed[req.rid][1:4] == (4, True, False)
    if how == "raises":
        with pytest.raises(RuntimeError, match="reader gone"):
            ce.flush_stream()
    else:
        ce.flush_stream()
        assert req.cancelled and req.tokens == got
    assert req.rid not in ce._unstreamed and len(got) == 1
    ce.run_until_idle()
    assert other.finished and len(other.tokens) == 12
    if how == "raises":  # the chunks after it streamed as ever
        assert req.finished and got[1:] == req.tokens[4:]
    ce.check_page_conservation()
    ce.close()


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs two devices")
def test_tp2_serves_the_same_streams_from_two_programs(tiny):
    one = _cont(tiny, sched_aging_ticks=1000)
    ref = _serve(one)
    tp = _cont(tiny, sched_aging_ticks=1000, tensor_parallel=2)
    assert tp.block_widths == one.block_widths == (8, CHUNK)
    assert _serve(tp) == ref
    assert _widths(tp) == _widths(one)
    assert tp.jit_cache_sizes()["tp_ragged_step"] == 2
    # the gathers follow the width that ran (a chunk in which nothing
    # emits runs the pass alone)
    rows, head, _calls = tp._tp_gather
    want = 0
    for r in tp.recorder.records():
        n_exec = max(r["decode_steps"], 1)
        want += int(rows * SLOTS * (r["block_rows"] + n_exec - 1)
                    + head * SLOTS * (tp.spec_width + n_exec - 1))
    assert tp.stats["tp_gather_bytes"] == want > 0
    assert one.stats["tp_gather_bytes"] == 0
    one.close()
    tp.close()


@pytest.mark.parametrize("n_new", [1, 3])
def test_a_request_that_ends_with_its_first_chunk_is_finished_once(tiny, n_new):
    """An answer that ends with its first chunk leaves whole with the
    first tokens, and is ended once, behind its last token."""
    ce = _cont(tiny, spec_decode=False)
    log: list = []
    other = ce.submit([1, 2, 3], max_new_tokens=12,
                      stream_cb=lambda tok: log.append(("other", tok)) and None)
    req = ce.submit(
        [4, 5, 6], max_new_tokens=n_new, seed=1,
        stream_cb=lambda tok: log.append(("req", tok)) and None,
        on_finish=lambda r: log.append(("req", "end")),
    )
    ce.step_chunk()
    assert ce._unstreamed[req.rid][1:4] == (n_new, True, True)
    ce.flush_stream()
    names = [name for name, _ in log]
    assert [t for name, t in log if name == "req"] == req.tokens + ["end"]
    # the other's first token, the whole answer and its end, the rest
    assert names == ["other"] + ["req"] * (n_new + 1) + ["other"] * 3
    assert req.finished and req.done.is_set() and not ce._unstreamed
    ce.run_until_idle()
    assert other.finished
    ce.close()


def test_an_end_that_raises_leaves_the_rest_for_the_next_stage(tiny):
    ce = _cont(tiny, spec_decode=False)
    heard: list = []

    def bad(req):
        raise RuntimeError("requester gone")

    a = ce.submit([1, 2, 3], max_new_tokens=3, on_finish=bad)
    b = ce.submit([4, 5, 6], max_new_tokens=3, seed=1,
                  on_finish=lambda r: heard.append(r.rid))
    on = ce.submit([7, 8, 9], max_new_tokens=12, seed=2)  # a step follows
    ce.step_chunk()
    with pytest.raises(RuntimeError, match="requester gone"):
        ce.flush_stream()
    # the stage stopped at the end that raised: what was behind it stays
    # pending and leaves with the next stage
    assert a.done.is_set() and a.finished and not b.done.is_set()
    assert list(ce._unstreamed) == [b.rid, on.rid]
    ce.run_until_idle()
    assert b.finished and heard == [b.rid] and not ce._unstreamed
    assert on.finished and len(on.tokens) == 12
    ce.check_page_conservation()
    ce.close()
