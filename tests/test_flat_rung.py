"""The flat rung of the packed block's ladder (engine/paged.py
``FlatRows`` / ``flat_rung_rows``, engine/continuous.py ``_block_width`` /
``rungs``, ROADMAP S5): a ``prefill_chunk``-wide block whose live rows fit
``flat_rows`` has the ragged pass compute that many rows, as one
token-major list, and goes to ``[S, C, ...]`` only for the page write and
the attention call. Same kernels, same write plan, same pages: the chunk's
tokens, pages, lengths and histograms are the full program's, for every
family; which rung a chunk takes follows from its ``n_valid`` alone; one
program a rung; an unbuilt rung packs full and is counted."""

import threading
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine import paged
from tensorlink_tpu.engine.continuous import ContinuousEngine
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.models import ModelConfig, init_params
from tensorlink_tpu.models.registry import config_from_hf

SLOTS, PAGE, CHUNK, MAX_LEN = 6, 8, 32, 96
FLAT = 128  # flat_rung_rows(6, 32, w) for every spec width used here
REP = [5, 9] * 4  # what prompt lookup drafts from


@pytest.fixture(autouse=True)
def _an_idle_engine_waits_for_its_build(monkeypatch):
    """These tests compile on the CPU, seconds a program: an idle engine
    waits for its build thread as long as that takes (a server's waits
    ``BUILD_JOIN_MAX_S`` at a time: ``tests/test_flat_rung.py`` has that
    case)."""
    from tensorlink_tpu.engine import continuous

    monkeypatch.setattr(continuous, "BUILD_JOIN_MAX_S", 120.0)


def _dense_cfg(**kw):
    base = dict(
        family="llama", vocab_size=160, d_model=32, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=40, max_seq_len=MAX_LEN,
        dtype=jnp.float32, tie_embeddings=False,
    )
    return ModelConfig(**(base | kw))


def _hf(module: str, name: str = "TINY"):
    import importlib

    hf = getattr(importlib.import_module(module), name)
    return config_from_hf(hf, dtype=jnp.float32)


# family -> (config, engine knobs): tiny float32 presets, every kind of
# layer the ragged pass runs
# tlint: disable=TL006(read-only table)
FAMILIES = {
    "dense": (_dense_cfg, dict(spec_decode=True, spec_draft=4)),
    "dense-int8": (_dense_cfg, dict(kv_quant="int8", spec_decode=True,
                                    spec_draft=4)),
    "tp2": (_dense_cfg, dict(tensor_parallel=2, spec_decode=True,
                             spec_draft=4)),
    "dots3": (lambda: _hf("test_latent"), dict(page_size=4)),
    "deepseek": (lambda: _hf("test_latent", "TINY_DS"),
                 dict(page_size=4, spec_decode=True, spec_draft=3)),
    "laguna": (lambda: _hf("test_laguna"),
               dict(page_size=4, state_snapshot_stride=32)),
    "sala": (lambda: _hf("test_sala"),
             dict(page_size=4, state_snapshot_stride=32)),
    # a conv layer's rows leave the flat list for the block and come back
    "lfm2": (lambda: _hf("test_lfm2"),
             dict(page_size=4, state_snapshot_stride=32)),
}


def _engine(cfg, params, **kw):
    max_len = min(MAX_LEN, cfg.max_seq_len)
    eng = GenerationEngine(cfg, params, seq_buckets=(8, 32),
                           batch_buckets=(1,), max_seq_len=max_len)
    kw = dict(max_slots=SLOTS, page_size=PAGE, chunk_steps=4,
              prefill_chunk=CHUNK) | kw
    return ContinuousEngine(eng, **kw)


def _with_flat_rows(ce, rows: int):
    """``ce`` with a flat rung of ``rows`` (0: held to the programs that
    compute rows where they lie). An engine builds the rung for a model
    whose pass holds one layer body; the step program serves it for every
    kind, which is what the cases below hold."""
    ce.flat_rows = rows
    ce._unbuilt = set(ce.rungs)
    return ce


def _leaves(cache) -> dict:
    """What a chunk leaves in the cache, by field; a page pool without its
    scratch page (padding rows land there, and differ by design)."""
    out = {}
    for name in cache.__dataclass_fields__:
        a = getattr(cache, name)
        if a is None:
            continue
        a = np.asarray(a)
        paged_pool = name in ("k", "v", "k_scale", "v_scale", "wk", "wv",
                              "full", "index", "slide", "ksum")
        out[name] = a[:, 1:] if paged_pool else a
    return out


def _same(a, b, name: str) -> None:
    """Control state bit for bit; a float row to the last ulps (the same
    dot products over another number of rows are fused another way), and
    an int8 page's value to the one step such an ulp can move it."""
    if a.dtype == np.int8:
        off = np.abs(a.astype(np.int32) - b.astype(np.int32))
        assert off.max() <= 1 and (off > 0).mean() < 0.01, name
    elif np.issubdtype(a.dtype, np.floating):
        # a state is a sum over every position seen: ulps of its largest
        atol = 1e-5 * max(1.0, float(np.abs(b).max()))
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=atol, err_msg=name)
    else:
        np.testing.assert_array_equal(a, b, err_msg=name)


def _traffic(ce, vocab: int):
    """Decoders (one drafting where the engine drafts), a prompt of three
    grants (mid-prefill, then completing), a prompt tail beside it, idle
    slots; a second wave joins mid-flight."""
    n = min(MAX_LEN, ce.max_seq_len)
    long = [(7 * i) % (vocab - 3) + 1 for i in range(2 * CHUNK + 9)]
    reqs = [
        ce.submit([1, 2, 3], max_new_tokens=14, seed=0),
        ce.submit(REP, max_new_tokens=n - 12, seed=1, speculative=True),
    ]
    yield reqs
    reqs += [
        ce.submit(long[: n - 8], max_new_tokens=6, seed=2),
        ce.submit(long[3 : CHUNK + 8], max_new_tokens=5, seed=3),
    ]
    yield reqs
    yield reqs
    reqs.append(ce.submit(long[5 : CHUNK + 2], max_new_tokens=4, seed=4))
    while True:
        yield reqs


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_flat_chunk_leaves_what_the_full_program_leaves(family):
    make, kw = FAMILIES[family]
    if kw.get("tensor_parallel", 1) > len(jax.devices()):
        pytest.skip("needs two devices")
    cfg = make()
    params = init_params(cfg, jax.random.PRNGKey(0))
    flat = _with_flat_rows(_engine(cfg, params, **kw), FLAT)
    full = _with_flat_rows(_engine(cfg, params, **kw), 0)
    assert (CHUNK, FLAT) in flat.rungs and (CHUNK, FLAT) not in full.rungs
    waves = [_traffic(ce, cfg.vocab_size) for ce in (flat, full)]
    mixes = set()
    for _ in range(40):
        reqs = [next(w) for w in waves]
        more = [ce.step_chunk() for ce in (flat, full)]
        assert more[0] == more[1]
        rec = flat.recorder.records()[-1]
        if rec["rows_computed"] == FLAT:
            decoding = rec["live_slots"] - rec["prefilling"]
            mixes.add((rec["prefilling"] > 0, decoding > 0,
                       rec["spec_drafted"] > 0, rec["live_slots"] < SLOTS))
        # the chunk's pages, lengths, counts and histograms
        a, b = _leaves(flat.cache), _leaves(full.cache)
        assert a.keys() == b.keys()
        for name in a:
            _same(a[name], b[name], name)
        np.testing.assert_array_equal(
            np.asarray(flat._counts), np.asarray(full._counts))
        if not more[0]:
            break
    assert not flat.has_work()
    for ra, rb in zip(*reqs):
        assert ra.finished and ra.tokens == rb.tokens
    # prefilling beside decoding (idle slots beside both), decode alone
    assert (True, True, False, True) in mixes or (True, True, True, True) in mixes
    if flat.spec_width > 1:
        assert any(m[2] for m in mixes), mixes
    assert flat.stats["ragged_blocks_flat"] > 0
    assert full.stats["ragged_blocks_flat"] == 0
    assert (flat.stats["ragged_rows_computed"]
            < full.stats["ragged_rows_computed"])
    for ce in (flat, full):
        ce.check_page_conservation()
        ce.close()


# ---------------------------------------------------------------------------
# the row map
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "n_valid",
    [[1, 0, 5, 32, 1, 0], [0, 0, 0, 0, 0, 0], [32, 32, 32, 32, 0, 0],
     [1, 1, 1, 1, 1, 1], [0, 0, 0, 0, 0, 7]],
    ids=["mixed", "idle", "fills-the-rung", "decode", "last-slot"],
)
def test_the_row_map_is_each_slots_rows_back_to_back(n_valid):
    nv = np.asarray(n_valid, np.int32)
    rows = paged.FlatRows.of(jnp.asarray(nv), CHUNK, FLAT)
    to_flat, slot, col, live = (np.asarray(a) for a in rows[:4])
    want = [(s, j) for s in range(SLOTS) for j in range(nv[s])]
    assert live.sum() == len(want) and live[: len(want)].all()
    assert list(zip(slot[live], col[live])) == want
    for r, (s, j) in enumerate(want):
        assert to_flat[s, j] == r
    dead = np.ones((SLOTS, CHUNK), bool)
    for s, j in want:
        dead[s, j] = False
    assert (to_flat[dead] == FLAT).all()  # no row: expand reads zeros
    # there and back: a live block row keeps its value, the others are 0
    blk = jnp.arange(1, SLOTS * CHUNK + 1, dtype=jnp.float32).reshape(
        SLOTS, CHUNK, 1)
    back = np.asarray(rows.expand(rows.collect(blk)))
    np.testing.assert_array_equal(
        back[..., 0], np.where(dead, 0, np.asarray(blk)[..., 0]))


@pytest.mark.parametrize(
    "slots,chunk,spec,want",
    [(8, 128, 1, 256), (8, 128, 9, 256), (16, 128, 1, 512), (4, 32, 5, 0),
     (6, 32, 5, 128), (8, 128, 17, 384), (3, 8, 1, 0)],
)
def test_the_rung_is_a_function_of_the_shapes(slots, chunk, spec, want):
    assert paged.flat_rung_rows(slots, chunk, spec) == want


# ---------------------------------------------------------------------------
# which rung a chunk takes
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    cfg = _dense_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize(
    "n_valid,want",
    [
        ([1, 1, 1, 1, 1, 1], (8, 0)),  # nobody's grant is over a page
        ([8, 1, 0, 0, 0, 0], (8, 0)),  # longest == the narrow width
        ([9, 1, 0, 0, 0, 0], (CHUNK, FLAT)),
        ([32, 32, 32, 32, 0, 0], (CHUNK, FLAT)),  # sum == flat_rows
        ([32, 32, 32, 32, 1, 0], (CHUNK, 0)),  # one row more: full
        ([32, 32, 32, 32, 32, 32], (CHUNK, 0)),
        ([0, 0, 0, 0, 0, 32], (CHUNK, FLAT)),
    ],
    ids=["decode", "a-page", "a-page-and-a-row", "fills-the-rung",
         "a-row-over", "every-slot-prefills", "one-grant"],
)
def test_block_width_picks_the_rung_from_n_valid_alone(tiny, n_valid, want):
    ce = _engine(*tiny)
    assert ce.rungs == ((8, 0), (CHUNK, FLAT), (CHUNK, 0))
    assert ce._block_width(np.asarray(n_valid, np.int32)) == want
    ce.close()


def test_an_engine_whose_block_is_no_larger_has_no_flat_rung(tiny):
    ce = _engine(*tiny, max_slots=4)
    assert ce.flat_rows == 0 and ce.rungs == ((8, 0), (CHUNK, 0))
    assert ce._block_width(np.asarray([9, 1, 0, 0], np.int32)) == (CHUNK, 0)
    with pytest.raises(ValueError, match="flat"):
        ce.lower_step(flat=True)
    ce.close()


# ---------------------------------------------------------------------------
# one program a rung; the counters and the record
# ---------------------------------------------------------------------------
def _churn(ce):
    sp_long = [(5 * i) % 150 + 1 for i in range(70)]
    for i in range(ce.max_slots + 2):
        ce.submit([3 + i] * (3 + 6 * i), max_new_tokens=9 + i, seed=i,
                  speculative=not i % 2)
    ce.step_chunk()
    ce.submit(sp_long, max_new_tokens=5, seed=20)
    ce.run_until_idle()
    # every slot prefills a whole grant: over the rung
    for i in range(ce.max_slots):
        ce.submit(sp_long[i : i + 40], max_new_tokens=3, seed=30 + i)
    ce.run_until_idle()


@pytest.mark.parametrize("tp", [1, 2])
def test_a_churn_of_mixes_compiles_one_program_a_rung(tp):
    if len(jax.devices()) < tp:
        pytest.skip("needs two devices")
    # a config of its own: what this test compiles nobody compiled before
    cfg = _dense_cfg(d_ff=56 + 8 * tp)
    ce = _engine(cfg, init_params(cfg, jax.random.PRNGKey(1)),
                 tensor_parallel=tp, spec_decode=True, spec_draft=4)
    key = "tp_ragged_step" if tp > 1 else "ragged_step"
    pre = ce.jit_cache_sizes()[key]
    _churn(ce)
    ran = {(r["block_rows"], r["rows_computed"])
           for r in ce.recorder.records()}
    assert ran == {(8, SLOTS * 8), (CHUNK, FLAT), (CHUNK, SLOTS * CHUNK)}
    base = ce.jit_cache_sizes()
    assert base[key] - pre == 3 == len(ce.rungs)
    _churn(ce)
    for n in (1, 7, 8, 9, 31, 33, 60):
        ce.submit([n] * n, max_new_tokens=3, seed=n)
    ce.run_until_idle()
    after = ce.jit_cache_sizes()
    assert after[key] == base[key], (base, after)
    ce.check_page_conservation()
    ce.close()


def test_counters_and_records_say_which_rung_ran(tiny):
    ce = _engine(*tiny, spec_decode=True, spec_draft=4)
    _churn(ce)
    recs, s = ce.recorder.records(), ce.stats
    flat = [r for r in recs if r["rows_computed"] == FLAT]
    narrow = [r for r in recs if r["block_rows"] == 8]
    full = [r for r in recs if r["rows_computed"] == SLOTS * CHUNK]
    assert len(flat) + len(narrow) + len(full) == len(recs) == s["ragged_blocks"]
    assert s["ragged_blocks_flat"] == len(flat) > 0
    assert s["ragged_blocks_narrow"] == len(narrow) > 0 and full
    assert s["ragged_blocks_flat_unbuilt"] == 0
    # what the pass computed position-wise, and what carried a token
    assert s["ragged_rows_computed"] == sum(r["rows_computed"] for r in recs)
    assert s["ragged_rows_computed"] == (
        FLAT * len(flat) + SLOTS * 8 * len(narrow) + SLOTS * CHUNK * len(full))
    assert s["ragged_rows_valid"] <= s["ragged_rows_computed"]
    snap = ce.serving_snapshot()
    assert snap["ragged_blocks_flat"] == s["ragged_blocks_flat"]
    ce.close()


def test_the_gathers_follow_the_rows_computed(tiny):
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    tp = _engine(*tiny, tensor_parallel=2)
    _churn(tp)
    rows, head, _calls = tp._tp_gather
    want = 0
    for r in tp.recorder.records():
        n_exec = max(r["decode_steps"], 1)
        want += int(rows * (r["rows_computed"] + (n_exec - 1) * SLOTS)
                    + head * SLOTS * (tp.spec_width + n_exec - 1))
    assert tp.stats["tp_gather_bytes"] == want > 0
    tp.close()


def test_lower_step_lowers_each_rung(tiny):
    ce = _engine(*tiny)
    texts = {rung: ce.lower_step(rung[0], flat=rung[1] > 0).as_text()
             for rung in ce.rungs}
    assert len(set(texts.values())) == 3
    for (w, _flat), text in texts.items():
        assert f"tensor<{SLOTS}x{w}xi32>" in text
    # the flat rung's residual stream is the row list, the full one's the block
    assert f"tensor<1x{FLAT}x32xf32>" in texts[(CHUNK, FLAT)]
    assert f"tensor<1x{FLAT}x32xf32>" not in texts[(CHUNK, 0)]
    assert ce.lower_step().as_text() == texts[(CHUNK, 0)]  # full by default
    ce.close()


# ---------------------------------------------------------------------------
# the rung is built behind the first requests
# ---------------------------------------------------------------------------
class _Gated:
    """A lowered step whose ``compile`` waits for ``gate``."""

    def __init__(self, lowered, gate):
        self.lowered, self.gate = lowered, gate
        self.compiled = threading.Event()

    def compile(self):
        assert self.gate.wait(60)
        out = self.lowered.compile()
        self.compiled.set()
        return out


def _gate_the_flat_build(ce):
    gate, real, held = threading.Event(), ce.lower_step, []

    def lower_step(width=None, *, flat=False):
        low = real(width, flat=flat)
        if not flat:
            return low
        held.append(_Gated(low, gate))
        return held[-1]

    ce.lower_step = lower_step
    ce.build_steps()  # returns with the gate shut: it waited for the full one
    assert ce._build is not None and not ce._build.done()
    return gate, held[0]


def test_an_unbuilt_flat_rung_packs_full_and_counts_it(tiny):
    ref = _engine(*tiny)
    want = ref.submit([100] * 20, max_new_tokens=30, seed=4)
    ref.run_until_idle()
    assert ref.recorder.records()[0]["rows_computed"] == FLAT
    ce = _engine(*tiny)
    gate, rung = _gate_the_flat_build(ce)
    req = ce.submit([100] * 20, max_new_tokens=30, seed=4)
    for _ in range(3):  # a prefill piece, then decode chunks: all full
        assert ce.step_chunk()
    recs = ce.recorder.records()
    assert [r["rows_computed"] for r in recs] == [SLOTS * CHUNK] * 3
    assert not rung.compiled.is_set()
    assert ce.stats["ragged_blocks_flat_unbuilt"] == 1
    assert ce.stats["ragged_blocks_narrow_unbuilt"] == 2
    assert ce.stats["ragged_blocks_flat"] == 0
    gate.set()
    ce._build.result(timeout=120)  # the thread is through; nothing joined
    ce.submit([101] * 20, max_new_tokens=4, seed=5)
    assert ce.step_chunk()
    assert ce._build is None
    assert ce.recorder.records()[-1]["rows_computed"] == FLAT
    ce.run_until_idle()
    assert ce.stats["ragged_blocks_flat_unbuilt"] == 1
    assert ce.stats["ragged_blocks_flat"] == 1
    assert req.finished and req.tokens == want.tokens  # the same stream
    ref.close()
    ce.close()


def test_an_idle_engine_waits_for_its_build_a_bounded_time(tiny, monkeypatch):
    """A request that arrives while an idle engine waits for its build
    thread waits behind it, and a server ends a stream after 30 s without
    an event: the wait is ``BUILD_JOIN_MAX_S`` at a time, the full program
    serves meanwhile, and a later idle moment joins."""
    from tensorlink_tpu.engine import continuous

    monkeypatch.setattr(continuous, "BUILD_JOIN_MAX_S", 0.05)
    ce = _engine(*tiny)
    gate, rung = _gate_the_flat_build(ce)
    waited0 = ce.serving_snapshot()["step_build_waited_ms"]
    req = ce.submit([100] * 20, max_new_tokens=6)
    ce.run_until_idle()  # returns with the gate shut: it gave up waiting
    assert req.finished and ce._build is not None
    assert not rung.compiled.is_set()
    waited1 = ce.serving_snapshot()["step_build_waited_ms"]
    assert 50 <= waited1 - waited0 < 5000
    again = ce.submit([101] * 20, max_new_tokens=6, seed=1)
    ce.run_until_idle()
    assert again.finished and ce._build is not None
    assert {r["rows_computed"] for r in ce.recorder.records()} == {
        SLOTS * CHUNK}
    gate.set()
    ce._build.result(timeout=120)
    last = ce.submit([102] * 20, max_new_tokens=6, seed=2)
    ce.run_until_idle()
    assert last.finished and ce._build is None
    assert ce.recorder.records()[-1]["block_rows"] == 8
    ce.close()


def test_build_steps_builds_every_rung_and_serving_builds_none():
    from jax._src import monitoring

    names: list = []

    def listen(event, _secs, **kw):
        if event.endswith("backend_compile_duration"):
            names.append(kw.get("fun_name"))

    cfg = _dense_cfg(d_ff=88)  # of its own: nothing here was built before
    ce = _engine(cfg, init_params(cfg, jax.random.PRNGKey(2)))
    monitoring.register_event_duration_secs_listener(listen)
    try:
        ce.build_steps()
        assert names.count("jit(paged_ragged_step)") >= 1
        assert ce._build is not None and not ce._unbuilt
        ce.submit([100] * 20, max_new_tokens=6)
        ce.run_until_idle()  # joined when the work ran out, at the latest
        assert ce._build is None
        assert names.count("jit(paged_ragged_step)") == 3 == len(ce.rungs)
        _churn(ce)
        assert names.count("jit(paged_ragged_step)") == 3, names
    finally:
        monitoring.unregister_event_duration_listener(listen)
    ran = {(r["block_rows"], r["rows_computed"])
           for r in ce.recorder.records()}
    assert ran == {(8, SLOTS * 8), (CHUNK, FLAT), (CHUNK, SLOTS * CHUNK)}
    snap = ce.serving_snapshot()
    assert snap["step_build_ms"] >= snap["step_build_waited_ms"] > 0
    ce.close()


TILE = paged.row_tile(SLOTS, CHUNK)  # 48 rows a trip
TILED = paged.tiled_rows(SLOTS, CHUNK)  # the whole block: 192
# tlint: disable=TL006(read-only table)
WIDE_PASS = {  # what an engine reads off the model's layer kinds
    "static": (FLAT, ((CHUNK, FLAT), (CHUNK, 0))),
    "tiled": (TILED, ((CHUNK, TILED),)),
    "full": (0, ((CHUNK, 0),)),
}


@pytest.mark.parametrize(
    "module,name,shape",
    [("test_latent", "TINY_DS", "static"), ("test_latent", "TINY", "tiled"),
     ("test_laguna", "TINY", "tiled"), ("test_sala", "TINY", "full"),
     ("test_lfm2", "TINY", "tiled")],
    ids=["deepseek", "dots3", "laguna", "sala", "lfm2"],
)
def test_an_engine_builds_the_rung_for_a_pass_of_one_layer_body(
        module, name, shape):
    """The wide pass has three shapes (engine/continuous.py, where
    ``flat_rows`` is set): a pass of one body (dense, DeepSeek-V2) has the
    static rung, built by ``build_steps`` beside the full program; a
    patterned model whose bodies are engine/latent.py's own has ONE wide
    program, the tiled pass, and reports the rows its tiles computed; a
    model of sparse / lightning layers keeps the full program."""
    cfg = _hf(module, name)
    ce = _engine(cfg, init_params(cfg, jax.random.PRNGKey(0)), page_size=4)
    assert ce.block_widths == (CHUNK,)
    assert (len(set(cfg.layer_kinds)) == 1) == (shape == "static")
    assert (ce.flat_rows, ce.rungs) == WIDE_PASS[shape]
    assert ce.tiled == (shape == "tiled")
    ce.build_steps()
    assert (ce._build is not None) == (shape == "static")
    ce.submit([1, 2, 3] * 5, max_new_tokens=8)
    ce.run_until_idle()
    ce.submit([3, 2, 1] * 5, max_new_tokens=8, seed=1)
    ce.run_until_idle()
    ran = {r["rows_computed"] for r in ce.recorder.records()}
    if shape == "tiled":  # 15 prompt rows or a decode row: one tile
        assert ran == {TILE}
        assert ce.stats["ragged_blocks_flat"] == ce.stats["ragged_blocks"]
        # one program, built by the first call: nobody waited beside it
        snap = ce.serving_snapshot()
        assert snap["step_build_ms"] == snap["step_build_waited_ms"]
        # ``lower_step`` lowers what serves: the row list is the stream
        d = cfg.d_model
        assert f"tensor<1x{TILED}x{d}xf32>" in ce.lower_step().as_text()
        assert f"tensor<1x{TILED}x{d}xf32>" not in ce.lower_step(
            flat=False).as_text()
    else:
        # the first request ran the full program while the rung was built
        assert ran - {SLOTS * CHUNK} == (
            {FLAT} if shape == "static" else set())
    assert ce._build is None and not ce._unbuilt
    ce.check_page_conservation()
    ce.close()


# ---------------------------------------------------------------------------
# the tiled pass: position-wise work over row tiles, a live-row bound
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "slots,chunk,tile,rows",
    [(16, 128, 512, 2048), (8, 128, 256, 1024), (32, 128, 512, 4096),
     (6, 32, 48, 192), (4, 32, 32, 128), (3, 8, 8, 24), (1, 8, 8, 8),
     (5, 24, 24, 120)],
)
def test_the_tile_is_a_function_of_the_shapes(slots, chunk, tile, rows):
    assert paged.row_tile(slots, chunk) == tile
    assert paged.tiled_rows(slots, chunk) == rows >= slots * chunk
    assert rows % tile == 0 and tile <= paged.ROW_TILE


@pytest.mark.parametrize("n_live", [0, 1, TILE - 1, TILE, TILE + 1, TILED])
def test_by_tile_computes_the_tiles_that_hold_a_live_row(n_live):
    nv = jnp.asarray(_n_valid(n_live))
    rows = paged.FlatRows.of(nv, CHUNK, TILED, TILE)
    a = jnp.arange(1, 2 * TILED + 1, dtype=jnp.float32).reshape(1, TILED, 2)
    b = jnp.arange(TILED, dtype=jnp.int32)[None]
    got = jax.jit(lambda a, b: rows.by_tile(
        lambda a, b: {"sum": a.sum(-1) + b, "twice": 2 * a}, a, b))(a, b)
    done = -(-n_live // TILE) * TILE
    assert int(rows.trips) * TILE == done
    mask = (np.arange(TILED) < done)[None]
    np.testing.assert_array_equal(
        got["sum"], np.where(mask, np.asarray(a.sum(-1) + b), 0))
    np.testing.assert_array_equal(
        got["twice"], np.where(mask[..., None], 2 * np.asarray(a), 0))
    # rows along the first axis (the expert layer's ``[N, d]`` lists)
    got0 = jax.jit(lambda a: rows.by_tile(lambda a: a + 1, a, axis=0))(a[0])
    np.testing.assert_array_equal(
        got0, np.where(mask[0][:, None], np.asarray(a[0]) + 1, 0))
    # without a tile the list is computed whole
    whole = paged.FlatRows.of(nv, CHUNK, FLAT)
    assert whole.tile == 0 and whole.trips is None
    np.testing.assert_array_equal(
        whole.by_tile(lambda a: 2 * a, a), 2 * np.asarray(a))


def _n_valid(n_live: int) -> np.ndarray:
    """``n_live`` rows over the slots: a decode row a slot from the last
    slot on, the rest as grants from slot 0 on."""
    nv = np.zeros(SLOTS, np.int32)
    for s in range(SLOTS - 1, -1, -1):
        if nv.sum() < n_live:
            nv[s] = 1
    for s in range(SLOTS):
        nv[s] += min(CHUNK - nv[s], n_live - nv.sum())
    assert nv.sum() == n_live
    return nv


TILED_FAMILIES = ("dots3", "laguna", "lfm2")
_PASS = jax.jit(paged._ragged_pass, static_argnames=(
    "cfg", "spec_width", "kernel", "flat_rows"))


def _tiled_cfg(family: str, **kw):
    return replace(FAMILIES[family][0](), **kw)


def _a_block_both_ways(family: str, n_live: int):
    """One block of ``n_live`` rows (decode rows and grants, each slot
    16 positions into its sequence) through the ragged pass tiled and
    untiled, on one cache: what each leaves, and the rows' logits."""
    from tensorlink_tpu.engine.latent import LatentPagedCache

    cfg = _tiled_cfg(family)
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = LatentPagedCache.init(cfg, SLOTS, page_size=4, max_len=64,
                                  prefill_chunk=CHUNK)
    n_pp = cache.pages_per_slot
    perm = np.random.default_rng(0).permutation(np.arange(1, cache.n_pages))
    cache = paged._with_kv(
        cache, paged._cache_kv(cache),
        block_tables=jnp.asarray(perm[: SLOTS * n_pp].reshape(SLOTS, n_pp)))
    rng = np.random.default_rng(n_live)
    zero = jnp.zeros(SLOTS, jnp.int32)

    def block(cache, starts, nv, flat_rows):
        blk = rng.integers(1, cfg.vocab_size - 1, (SLOTS, CHUNK))
        blk = np.where(np.arange(CHUNK)[None] < nv[:, None], blk, 0)
        return _PASS(params, jnp.asarray(blk, jnp.int32), cache,
                     jnp.asarray(starts), jnp.asarray(nv), zero, cfg=cfg,
                     spec_width=1, kernel=False, flat_rows=flat_rows)

    # every slot holds 16 positions: pages, rings and tails to read
    first = np.full(SLOTS, 16, np.int32)
    _lv, _base, kv = block(cache, np.zeros(SLOTS, np.int32), first, 0)
    cache = paged._with_kv(cache, kv, lengths=jnp.asarray(first))
    nv = _n_valid(n_live)
    state = rng.bit_generator.state
    out = []
    for flat_rows in (TILED, 0):
        rng.bit_generator.state = state  # the same tokens both ways
        lv, _base, kv = block(cache, np.where(nv > 0, first, 0), nv,
                              flat_rows)
        left = paged._with_kv(cache, kv)
        out.append((_leaves(left), np.asarray(lv)[nv > 0]))
    return out


def _churn_both_ways(family: str):
    """A churn of mixes through a tiled engine and one held to the
    untiled program; the tiled one's chunks as packed (``n_valid``)."""
    cfg = _tiled_cfg(family, norm_eps=3e-6)  # programs nobody built before
    params = init_params(cfg, jax.random.PRNGKey(1))
    kw = FAMILIES[family][1]
    tiled, full = _engine(cfg, params, **kw), _with_flat_rows(
        _engine(cfg, params, **kw), 0)
    packed, pack = [], tiled._pack_ragged

    def watch():
        out = pack()
        if out is not None:
            packed.append(out[2].copy())
        return out

    tiled._pack_ragged = watch
    return tiled, full, packed


@pytest.mark.parametrize("family", TILED_FAMILIES)
@pytest.mark.parametrize(
    "case", [0, 1, TILE - 1, TILE, TILE + 1, TILED - TILE, TILED - TILE + 1,
             TILED, "churn", "counters"],
    ids=lambda c: c if isinstance(c, str) else f"rows-{c}")
def test_the_tiled_pass_is_the_untiled_program_over_fewer_rows(family, case):
    """A patterned model's one wide program (``flat_rows`` = the whole
    block, ``paged.tiled_rows``): at every live-row count around a tile's
    edge a chunk leaves the pages, rings, conv tails, counts and logits
    that the untiled program leaves (from ``TILED - TILE + 1`` rows on
    every tile holds a live row and the program takes its untiled body);
    a churn of mixes runs ONE program; the counters are the tiles
    computed."""
    if isinstance(case, int):
        (a, lv_a), (b, lv_b) = _a_block_both_ways(family, case)
        assert a.keys() == b.keys()
        for name in a:
            _same(a[name], b[name], name)
        if case:  # (no row, no logits)
            _same(lv_a, lv_b, "logits")
        return
    tiled, full, packed = _churn_both_ways(family)
    pre = tiled.jit_cache_sizes()["ragged_step"]
    assert tiled.tiled and tiled.rungs == ((CHUNK, TILED),)
    if case == "churn":
        reqs = [_submit_churn(ce) for ce in (tiled, full)]
        base = tiled.jit_cache_sizes()["ragged_step"]
        # the tiled engine's one program and the other engine's full one
        assert base - pre == 2
        for ce in (tiled, full):
            for n in (1, 7, 8, 9, 31, 33, 60):
                ce.submit([n] * n, max_new_tokens=3, seed=n)
            ce.run_until_idle()
        assert tiled.jit_cache_sizes()["ragged_step"] == base
        for ra, rb in zip(*reqs):  # the same streams
            assert ra.finished and ra.tokens == rb.tokens
        trips = {r["rows_computed"] // TILE for r in tiled.recorder.records()}
        assert {1, 2, 4} <= trips, trips  # decode alone .. every row live
    else:
        _submit_churn(tiled)
        recs, s = tiled.recorder.records(), tiled.stats
        assert len(packed) == len(recs) == s["ragged_blocks"]
        assert s["ragged_blocks_flat"] == len(recs)
        assert s["ragged_blocks_narrow"] == 0 == s["ragged_blocks_flat_unbuilt"]
        by_hand = [-(-int(nv.sum()) // TILE) * TILE for nv in packed]
        assert [r["rows_computed"] for r in recs] == by_hand
        assert s["ragged_rows_computed"] == sum(by_hand)
        assert s["ragged_rows_valid"] == sum(int(nv.sum()) for nv in packed)
        assert max(by_hand) == TILED and min(by_hand) == TILE
    for ce in (tiled, full):
        ce.check_page_conservation()
        ce.close()


def _submit_churn(ce) -> list:
    """:func:`_churn` within these families' 64 positions."""
    reqs = [ce.submit([3 + i] * (3 + 5 * i), max_new_tokens=5 + i, seed=i)
            for i in range(ce.max_slots + 2)]
    ce.step_chunk()
    reqs.append(ce.submit([(5 * i) % 90 + 1 for i in range(50)],
                          max_new_tokens=5, seed=20))
    ce.run_until_idle()
    # every slot prefills a whole grant: every row of the block is live
    reqs += [ce.submit([(7 * j + i) % 90 + 1 for j in range(40)],
                       max_new_tokens=3, seed=30 + i)
             for i in range(ce.max_slots)]
    ce.run_until_idle()
    return reqs
