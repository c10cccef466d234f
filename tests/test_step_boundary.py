"""The host-device boundary of a chunk (engine/paged.py ``pack_control`` /
``unpack_control`` / ``pack_results`` / ``unpack_results``,
engine/continuous.py ``_step_operands`` / ``step_chunk``): a chunk's
control rows cross to the device as ONE ``int32`` array and what the host
reads of it comes back as ONE. Held here: the two packers are inverses
bit for bit, the packed program returns what the step's own phases return
when they are handed the rows one by one (the seventeen-operand spelling,
kept in this file), a chunk in the steady state places one array and
fetches one, and ``wait`` still ends at the sync with ``drain`` behind it.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine import continuous, paged
from tensorlink_tpu.engine.continuous import ContinuousEngine
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.paged import (
    CTL_COLS, EOS_WIDTH, Control, pack_control, pack_results,
    unpack_control, unpack_results,
)
from tensorlink_tpu.engine.sampling import SamplingParams
from tensorlink_tpu.models import ModelConfig, init_params
from tensorlink_tpu.models.registry import config_from_hf

from test_latent import TINY, TINY_DS

SLOTS, PAGE, CHUNK, MAX_LEN = 4, 8, 32, 96


# ---------------------------------------------------------------------------
# (a) the packers are inverses
# ---------------------------------------------------------------------------
def _random_rows(rng, S: int, C: int, eos_width: int = EOS_WIDTH,
                 n_pp: int = 0) -> Control:
    """Every field at values that would show a wrong column or a lossy
    float: random float32 BITS (NaNs left out: they compare unequal to
    themselves, not to their bits), negative seeds, mixed ``emit``, an
    EOS row of -1s, table rows ``n_pp`` pages wide under ``bind`` and
    ``reset`` flags that differ from ``emit`` and from each other."""
    bits = rng.integers(-2**31, 2**31, (4, S), dtype=np.int64).astype(np.int32)
    floats = bits.view(np.float32)
    floats = np.where(np.isnan(floats), np.float32(-0.0), floats)
    eos = rng.integers(0, 2**31 - 1, (S, eos_width)).astype(np.int32)
    eos[0] = -1
    i32 = lambda lo, hi: rng.integers(lo, hi, S).astype(np.int32)  # noqa: E731
    return Control(
        blk=rng.integers(0, 2**31 - 1, (S, C)).astype(np.int32),
        starts=i32(0, 4096), n_valid=i32(0, C + 1), n_spec=i32(0, 9),
        emit=np.arange(S) % 2 == 0, seeds=i32(-2**31, 2**31 - 1),
        steps=i32(0, 2**20), temp=floats[0], top_k=i32(0, 200),
        top_p=floats[1], pres=floats[2], freq=floats[3],
        remaining=i32(-3, 4096), eos=eos,
        bind=np.arange(S) % 3 == 0, bind_len=i32(0, 2**20),
        reset=np.arange(S) % 3 == 1,
        bind_rows=rng.integers(0, 2**31 - 1, (S, n_pp)).astype(np.int32),
    )


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("S,C,n_pp", [
    (8, 16, 256), (8, 128, 256), (16, 128, 1024), (3, 5, 0), (3, 5, 7)])
def test_unpack_is_the_inverse_of_pack_for_every_field(S, C, n_pp, jitted):
    rows = _random_rows(np.random.default_rng(S * 1000 + C), S, C, n_pp=n_pp)
    ctl = pack_control(*rows)
    assert ctl.dtype == np.int32 and ctl.shape == (S, C + CTL_COLS + n_pp)
    unpack = (jax.jit(unpack_control, static_argnums=1) if jitted
              else unpack_control)
    got = unpack(jnp.asarray(ctl), n_pp)
    for name, want, have in zip(Control._fields, rows, got):
        assert have.dtype == want.dtype, name
        np.testing.assert_array_equal(_bits(have), _bits(want), err_msg=name)
    # and back: the buffer is a function of the rows alone
    np.testing.assert_array_equal(
        pack_control(*(np.asarray(x) for x in got)), ctl)


def test_a_narrow_eos_table_is_padded_and_a_wide_one_refused():
    rows = _random_rows(np.random.default_rng(1), 4, 8, eos_width=2)
    got = unpack_control(jnp.asarray(pack_control(*rows)))
    assert got.eos.shape == (4, EOS_WIDTH)
    np.testing.assert_array_equal(got.eos[:, :2], rows.eos)
    assert (np.asarray(got.eos[:, 2:]) == -1).all()
    wide = rows._replace(eos=np.zeros((4, EOS_WIDTH + 1), np.int32))
    with pytest.raises(ValueError, match="EOS"):
        pack_control(*wide)


@pytest.mark.parametrize("n_stats", [0, 9])
@pytest.mark.parametrize("n_steps,W", [(8, 9), (4, 1), (1, 1)])
def test_results_round_trip(n_steps, W, n_stats):
    rng = np.random.default_rng(n_steps + W)
    S, T = 5, n_steps + W - 1
    tokens = rng.integers(0, 2**31 - 1, (S, T)).astype(np.int32)
    n_tok, spec_m = (rng.integers(0, T + 1, S).astype(np.int32)
                     for _ in range(2))
    stats = rng.integers(0, 2**31 - 1, n_stats).astype(np.int32)
    out = np.asarray(jax.jit(pack_results)(
        tokens, n_tok, spec_m, jnp.int32(n_steps),
        jnp.asarray(stats) if n_stats else None,
    ))
    assert out.dtype == np.int32 and out.shape == (S, T + 3 + n_stats)
    got = unpack_results(out, n_steps, W)
    for want, have in zip((tokens, n_tok, spec_m, n_steps, stats), got):
        np.testing.assert_array_equal(have, want)
    assert type(got[3]) is int


# ---------------------------------------------------------------------------
# (c) the packed program against the step's phases, operand by operand
# ---------------------------------------------------------------------------
def _dense_cfg(**kw):
    # widths of its own: the jit caches are process-global
    base = dict(
        family="llama", vocab_size=144, d_model=32, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=40, max_seq_len=MAX_LEN,
        dtype=jnp.float32, tie_embeddings=False,
    )
    return ModelConfig(**(base | kw))


@pytest.fixture(scope="module")
def tiny():
    cfg = _dense_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _seventeen_operand_step(params, blk, cache, starts, n_valid, n_spec,
                            emit, seeds, steps, temp, top_k, top_p, pres,
                            freq, counts, remaining, eos, *, cfg, n_steps,
                            spec_width):
    """The step as it was spelled before its operands were packed: the
    program's own three phases, handed every row as an array of its own,
    returning the nine results of old."""
    W = spec_width
    logits_v, base, kv_new = paged._ragged_pass(
        params, blk, cache, starts, n_valid, n_spec, cfg, W, False)
    toks0, nxt, spec_m, ended, counts, steps, remaining = paged._verify_emit(
        blk, logits_v, base, n_spec, emit, seeds, steps, temp, top_k, top_p,
        pres, freq, counts, remaining, eos)
    done = ~emit | ended
    adv = jnp.where((n_spec > 0) & emit, spec_m, n_valid)
    cache = paged._with_kv(
        cache, kv_new,
        lengths=jnp.where(n_valid > 0, starts + adv, cache.lengths))
    tokens = jnp.zeros(
        (blk.shape[0], n_steps + W - 1), jnp.int32).at[:, :W].set(toks0)
    body = paged._decode_loop_body(
        params, seeds, temp, top_k, top_p, pres, freq, eos, cfg, False)
    n_exec, _tok, cache, done, steps, counts, remaining, n_tok, tokens = (
        jax.lax.while_loop(
            lambda st: (st[0] < n_steps) & ~st[3].all(), body,
            (jnp.int32(1), nxt, cache, done, steps, counts, remaining,
             spec_m, tokens)))
    return (tokens, n_tok, spec_m, n_exec, cache, done, steps, counts,
            remaining)


def _block(cfg, mode: str):
    """Four slots over a cache with history: a decode slot, a fresh
    prefill that completes, a slot with three drafts (``drafting``:
    random tokens, so the walk stops at a correction wherever the model
    disagrees), an idle slot."""
    rng = np.random.default_rng(11)
    S, C, n_pp = SLOTS, 8, 4
    cache = paged.PagedKVCache.init(cfg, S, page_size=PAGE, max_len=n_pp * PAGE)
    kv = tuple(jnp.asarray(rng.uniform(0.01, 1.0, a.shape), a.dtype)
               for a in paged._cache_kv(cache))
    starts = np.asarray([5, 0, 11, 0], np.int32)
    bt = 1 + rng.permutation(S * n_pp).reshape(S, n_pp).astype(np.int32)
    cache = paged._with_kv(
        cache, kv, block_tables=jnp.asarray(bt), lengths=jnp.asarray(starts))
    n_spec = np.asarray([0, 0, 3 if mode == "drafting" else 0, 0], np.int32)
    sampled = mode == "sampled"
    f32 = lambda *v: np.asarray(v, np.float32)  # noqa: E731
    i32 = lambda *v: np.asarray(v, np.int32)  # noqa: E731
    rows = Control(
        blk=rng.integers(1, cfg.vocab_size, (S, C)).astype(np.int32),
        starts=starts, n_valid=i32(1, C, 1, 0) + n_spec, n_spec=n_spec,
        emit=np.asarray([True, True, True, False]),
        seeds=i32(3, -4, 5, 6), steps=i32(0, 0, 2, 0),
        temp=f32(0.7, 0.9, 0, 0) if sampled else f32(0, 0, 0, 0),
        top_k=i32(0, 5, 0, 0) if sampled else i32(0, 0, 0, 0),
        top_p=f32(0.8, 0.95, 1, 1) if sampled else f32(1, 1, 1, 1),
        pres=f32(0.3, 0, 0.2, 0) if sampled else f32(0, 0, 0, 0),
        freq=f32(0, 0.1, 0.2, 0) if sampled else f32(0, 0, 0, 0),
        remaining=i32(9, 2, 9, 0),
        eos=np.full((S, EOS_WIDTH), -1, np.int32),
    )
    counts = rng.integers(0, 3, (S, cfg.vocab_size)).astype(np.int32)
    return rows, cache, counts


@pytest.mark.parametrize("mode", ["greedy", "sampled", "drafting"])
def test_packed_program_returns_what_the_phases_return(tiny, mode):
    """Tokens, counts, the verify pass's emitted counts, the steps
    executed and the WHOLE cache, bit for bit, between the program on
    one packed buffer and the phases on seventeen arrays."""
    cfg, params = tiny
    n_steps, W = 4, 4
    rows, cache, counts = _block(cfg, mode)
    want = jax.jit(
        _seventeen_operand_step,
        static_argnames=("cfg", "n_steps", "spec_width"),
    )(params, *(jnp.asarray(x) for x in rows[:1]), cache,
      *(jnp.asarray(x) for x in rows[1:12]), jnp.asarray(counts),
      *(jnp.asarray(x) for x in rows[12:14]), cfg=cfg, n_steps=n_steps,
      spec_width=W)
    tokens, n_tok, spec_m, n_exec, cache_w, _d, _s, counts_w, _r = want
    # on operands of its own: the program donates cache and counts
    rows, cache, counts = _block(cfg, mode)
    out, cache_g, counts_g = paged.paged_ragged_step(
        params,
        pack_control(*rows[:14], pages_per_slot=cache.pages_per_slot),
        cache, jnp.asarray(counts), cfg, n_steps, W, False)
    got = unpack_results(np.asarray(out), n_steps, W)
    for have, expect in zip(got[:4], (tokens, n_tok, spec_m, n_exec)):
        np.testing.assert_array_equal(have, np.asarray(expect))
    assert got[4].size == 0  # a dense model packs no counts of its own
    np.testing.assert_array_equal(np.asarray(counts_g), np.asarray(counts_w))
    for g, w in zip(jax.tree.leaves(cache_g), jax.tree.leaves(cache_w)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the block did something to compare
    n_tok = np.asarray(n_tok)
    assert (n_tok[:3] >= 1).all() and n_tok[3] == 0
    if mode == "sampled":
        assert n_tok[1] == 2  # its budget
    if mode == "drafting":
        assert 1 <= int(np.asarray(spec_m)[2]) <= 4


def test_a_patterned_model_s_counts_ride_the_result():
    """``cache.stats`` of a patterned model leaves in the packed result's
    last columns, the same in every row, and equals the cache's own."""
    from tensorlink_tpu.engine.latent import LatentPagedCache
    from tensorlink_tpu.models.latent import STEP_STATS

    cfg = config_from_hf(TINY, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    S, C, n_steps = 3, 8, 2
    cache = LatentPagedCache.init(cfg, S, page_size=4, max_len=64)
    n_pp = cache.pages_per_slot
    bt = 1 + np.arange(S * n_pp, dtype=np.int32).reshape(S, n_pp)
    cache = paged._with_kv(
        cache, paged._cache_kv(cache), block_tables=jnp.asarray(bt))
    z, zf = np.zeros(S, np.int32), np.zeros(S, np.float32)
    blk = np.random.default_rng(0).integers(0, 64, (S, C)).astype(np.int32)
    ctl = pack_control(
        blk, z, np.asarray([C, 3, 0], np.int32), z,
        np.asarray([True, False, False]), z, z, zf, z, zf + 1, zf, zf,
        z + 5, np.full((S, 1), -1, np.int32), pages_per_slot=n_pp)
    out, cache, _counts = paged.paged_ragged_step(
        params, ctl, cache, jnp.zeros((S, cfg.vocab_size), jnp.int32), cfg,
        n_steps, 1, False)
    out = np.asarray(out)
    assert out.shape == (S, n_steps + 3 + len(STEP_STATS))
    *_rest, n_exec, stats = unpack_results(out, n_steps, 1)
    assert n_exec == n_steps
    np.testing.assert_array_equal(stats, np.asarray(cache.stats))
    assert (out[:, -len(STEP_STATS):] == stats).all()
    assert dict(zip(STEP_STATS, stats))["moe_rows_valid"] > 0


# ---------------------------------------------------------------------------
# (b) one array in, one array out, a dispatched chunk
# ---------------------------------------------------------------------------
class _Counting:
    """A module with some of its functions counted: stands in for ``np`` /
    ``jnp`` inside engine/continuous.py while a chunk runs."""

    def __init__(self, module, names, when=lambda *a, **k: True):
        self._module, self._names, self._when = module, set(names), when
        self.calls = []  # (name, start, end) on time.monotonic()

    def __getattr__(self, name):
        fn = getattr(self._module, name)
        if name not in self._names:
            return fn

        def counted(*a, **k):
            t0 = time.monotonic()
            out = fn(*a, **k)
            if self._when(*a, **k):
                self.calls.append((name, t0, time.monotonic()))
            return out

        return counted


def _watch(monkeypatch):
    """Count, inside the engine's module, every fetch of a device array
    (``np.asarray`` / ``np.array`` of a ``jax.Array``) and every placement
    (``jnp.asarray`` / ``jnp.array`` of anything, ``jax.device_put``
    anywhere): ``(fetches, placements)``."""
    on_device = lambda x, *a, **k: isinstance(x, jax.Array)  # noqa: E731
    fetches = _Counting(np, ("asarray", "array"), on_device)
    places = _Counting(jnp, ("asarray", "array"))
    monkeypatch.setattr(continuous, "np", fetches)
    monkeypatch.setattr(continuous, "jnp", places)
    put = jax.device_put

    def device_put(*a, **k):
        places.calls.append(("device_put", 0.0, 0.0))
        return put(*a, **k)

    monkeypatch.setattr(jax, "device_put", device_put)
    return fetches, places


def _engine(kind: str, tp: int) -> ContinuousEngine:
    if kind == "dense":
        cfg = _dense_cfg()
        kw = dict(max_slots=SLOTS, page_size=PAGE, chunk_steps=4,
                  prefill_chunk=CHUNK, spec_decode=True, spec_draft=4)
        if tp > 1:
            kw["tensor_parallel"] = tp
    else:
        cfg = config_from_hf(
            TINY if kind == "dots3" else TINY_DS, dtype=jnp.float32)
        kw = dict(max_slots=3, page_size=4, chunk_steps=4, prefill_chunk=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,), max_seq_len=64)
    return ContinuousEngine(eng, **kw)


def _decode_steadily(ce) -> None:
    """Step until every admitted request decodes and a chunk of pure
    decode has run (its width's program is built)."""
    while ce._prefilling or not ce._active.any():
        ce.step_chunk()
    ce.step_chunk()


@pytest.mark.parametrize(
    "kind,tp", [("dense", 1), ("dense", 2), ("dots3", 1), ("deepseek", 1)],
    ids=["dense", "dense-tp2", "dots3", "deepseek"],
)
def test_a_chunk_places_one_array_and_fetches_one(monkeypatch, kind, tp):
    if tp > len(jax.devices()):
        pytest.skip("needs 2 (virtual) devices")
    ce = _engine(kind, tp)
    rng = np.random.default_rng(4)
    vocab = ce.cfg.vocab_size
    reqs = [
        ce.submit(rng.integers(1, vocab, n).tolist(), max_new_tokens=40,
                  seed=i, sampling=sp)
        for i, (n, sp) in enumerate([
            (11, None), (5, SamplingParams.make(temperature=0.8, top_k=7))])
    ]
    # every chunk, admission and prefill included, counts two
    _decode_steadily(ce)
    assert ce.stats["chunk_host_arrays"] == 2 * ce.stats["ragged_blocks"] > 0
    ops = ce._step_operands(*ce._pack_ragged()[:7])
    assert len(ops) == 4 and ops[3] is ce._counts
    assert isinstance(ops[1], np.ndarray) and ops[1].dtype == np.int32
    assert ops[1].shape == (  # pure decode: narrow; the table rides too
        ce.max_slots,
        ce.block_widths[0] + CTL_COLS + ce.cache.pages_per_slot)
    # the steady state, watched: nothing is placed by hand, the call takes
    # the one host buffer, and one device array is read back
    fetches, places = _watch(monkeypatch)
    before = ce.stats["chunk_host_arrays"], ce.stats["ragged_blocks"]
    programs = ce._step_programs()
    for _ in range(3):
        assert ce.step_chunk()
    assert ce.stats["ragged_blocks"] - before[1] == 3
    assert ce.stats["chunk_host_arrays"] - before[0] == 6
    assert len(fetches.calls) == 3, fetches.calls
    assert places.calls == []
    assert ce._step_programs() == programs  # ... and no program was built
    monkeypatch.undo()
    ce.run_until_idle()
    assert all(len(r.tokens) == 40 for r in reqs)
    assert ce.stats["chunk_host_arrays"] == 2 * ce.stats["ragged_blocks"]
    if kind != "dense":
        assert ce.stats["moe_rows_valid"] > 0  # the counts still arrive
    ce.check_page_conservation()
    ce.close()


def test_the_counter_is_exported():
    names = {c[0]: c[1] for c in continuous._ENGINE_COUNTERS}
    assert names["chunk_host_arrays"] == "tlink_engine_chunk_host_arrays_total"


# ---------------------------------------------------------------------------
# (d) wait ends at the fetch, drain is the split behind it
# ---------------------------------------------------------------------------
def test_wait_holds_the_one_fetch_and_drain_lies_after_it(monkeypatch):
    ce = _engine("dense", 1)
    ce.submit([1, 2, 3, 4, 5], max_new_tokens=30, seed=1)
    _decode_steadily(ce)
    fetches, _places = _watch(monkeypatch)
    split = []
    unpack = continuous.unpack_results

    def watched(out, *a):
        assert isinstance(out, np.ndarray)  # the sync lies behind
        split.append(time.monotonic())
        return unpack(out, *a)

    monkeypatch.setattr(continuous, "unpack_results", watched)
    n0 = len(ce.recorder)
    for _ in range(3):
        ce.step_chunk()
    recs = ce.recorder.records()[n0:]
    assert len(recs) == len(fetches.calls) == len(split) == 3
    for r, (_name, f0, f1), at in zip(recs, fetches.calls, split):
        assert r["chunk_ms"] == pytest.approx(
            r["dispatch_ms"] + r["wait_ms"] + r["drain_ms"], abs=1e-6)
        # the record's phases lie end to end from t0 (a few us between)
        wait0 = r["t0"] + (
            r["admit_ms"] + r["pack_ms"] + r["dispatch_ms"]) / 1e3
        wait1 = wait0 + r["wait_ms"] / 1e3
        slack = 2e-3
        assert wait0 - slack <= f0 <= f1 <= wait1 + slack
        assert f1 <= at  # tlink:drain's work starts behind the sync
        assert wait1 - slack <= at <= wait1 + r["drain_ms"] / 1e3 + slack
    ce.close()
