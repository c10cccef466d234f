"""An admission and a retirement make no device call of their own
(engine/continuous.py ``_bind_slot`` / ``_arm_slot`` / ``_teardown_slot``,
engine/paged.py ``Control.bind`` / ``bind_len`` / ``reset`` /
``bind_rows``): a slot's table row, its start length and its histogram
reset ride the next dispatched chunk's control buffer, and the step
program applies them before its ragged pass.

Held here: every stream is bit for bit what an engine makes that writes
each bind, clear and reset to the device at once, the way the engine did
before (``_CallsTheDevice``, kept in this file), over a prefix hit with a
copy-on-write page, slots retired and re-admitted in consecutive chunks,
a preempted request's resume, penalties beside plain requests, a drafting
slot, two tensor-parallel shards, both latent families and the stateful
one, a migrated stream and a shared pool's tenants; the packers round-trip
the new columns; the program applies them before it reads the table; and
the counters say what still calls the device."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.engine import continuous, paged
from tensorlink_tpu.engine.continuous import ContinuousEngine
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.paged import (
    CTL_COLS, PagedKVCache, SharedPagePool, pack_control, unpack_control,
    unpack_results,
)
from tensorlink_tpu.engine.sampling import SamplingParams
from tensorlink_tpu.models import ModelConfig, init_params
from tensorlink_tpu.models.registry import config_from_hf

import test_latent
import test_sala


class _CallsTheDevice(ContinuousEngine):
    """The engine as it bound and cleared slots before: a device write at
    the admission and at the retirement itself, nothing left to ride."""

    def _bind_slot(self, slot, bt_row, length):
        super()._bind_slot(slot, bt_row, length)
        c = self.cache
        self.cache = replace(
            c,
            block_tables=c.block_tables.at[slot].set(
                jnp.asarray(self._bt_host[slot])),
            lengths=c.lengths.at[slot].set(int(length)),
        )
        self._bind[slot] = False

    def _zero_now(self, slot):
        if self._reset[slot]:
            self._counts = self._counts.at[slot].set(0)
            self._reset[slot] = False

    def _arm_slot(self, req, slot, ctx=None):
        super()._arm_slot(req, slot, ctx)
        self._zero_now(slot)

    def _teardown_slot(self, slot):
        req = super()._teardown_slot(slot)
        self._zero_now(slot)
        return req


def _dense_cfg(**kw):
    # widths of its own: the jit caches are the process's
    base = dict(
        family="llama", vocab_size=136, d_model=32, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=8, d_ff=48, max_seq_len=96,
        dtype=jnp.float32, tie_embeddings=False,
    )
    return ModelConfig(**(base | kw))


@pytest.fixture(scope="module")
def dense():
    cfg = _dense_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,), max_seq_len=96)


def _dense_engine(cls, eng, **kw):
    kw = dict(max_slots=4, page_size=8, chunk_steps=4, prefill_chunk=32) | kw
    return cls(eng, **kw)


def _finish(ce, reqs):
    ce.run_until_idle()
    assert all(r.finished for r in reqs)
    ce.check_page_conservation()
    out = [list(r.tokens) for r in reqs]
    stats = dict(ce.stats)
    ce.close()
    return out, stats


# ---------------------------------------------------------------------------
# the scenarios: each returns (token streams, the engine's counters)
# ---------------------------------------------------------------------------
def _cow(cls, eng):
    """A prompt that shares two full pages and half of the third with a
    resident chain: a prefix hit, then a copy-on-write page."""
    ce = _dense_engine(cls, eng)
    rng = np.random.default_rng(1)
    first = rng.integers(1, 136, 24).tolist()
    r0 = ce.submit(first, max_new_tokens=6, seed=1)
    ce.run_until_idle()  # its three prompt pages join the trie
    second = first[:20] + rng.integers(1, 136, 9).tolist()
    r1 = ce.submit(second, max_new_tokens=9, seed=2)
    r2 = ce.submit(first[:16] + [7, 7], max_new_tokens=5, seed=3)
    out, stats = _finish(ce, [r0, r1, r2])
    assert ce.prefix.stats["cow_copies"] >= 1
    assert stats["prefill_tokens_skipped"] >= 16 + 4 + 16
    return out, stats


def _churn(cls, eng, **kw):
    """Two slots, seven requests of unlike lengths: a slot retires at one
    chunk's end and is bound again at the next one's start, and the last
    retirements find no chunk to ride."""
    ce = _dense_engine(cls, eng, max_slots=2, **kw)
    rng = np.random.default_rng(2)
    reqs = [
        ce.submit(rng.integers(1, 136, 3 + 5 * i).tolist(),
                  max_new_tokens=3 + 2 * i, seed=i,
                  sampling=SamplingParams.make(temperature=0.8, top_k=9)
                  if i % 3 == 1 else None)
        for i in range(7)
    ]
    return _finish(ce, reqs)


def _preempt(cls, eng):
    """Interactive arrivals take the slots of best-effort residents, which
    resume through the prefix cache."""
    ce = _dense_engine(cls, eng, sched_aging_ticks=1000)
    low = [
        ce.submit([1 + i, 2, 3 + i], max_new_tokens=14, seed=i,
                  priority="best_effort",
                  sampling=SamplingParams.make(temperature=0.9, top_k=5)
                  if i % 2 else None)
        for i in range(4)
    ]
    ce.step_chunk()
    assert ce.live_slots == 4
    hi = [ce.submit([11 + i, 12], max_new_tokens=6, seed=20 + i,
                    priority="interactive") for i in range(2)]
    out, stats = _finish(ce, low + hi)
    assert stats["preemptions"] >= 2
    return out, stats


def _penalties(cls, eng):
    """Requests with a presence and a frequency penalty beside plain ones,
    two slots: a plain request takes the slot a penalised one left (its
    histogram has to start at zero) and the other way round."""
    ce = _dense_engine(cls, eng, max_slots=2)
    pen = SamplingParams.make(presence_penalty=0.8, frequency_penalty=0.6)
    hot = SamplingParams.make(temperature=0.9, top_k=12,
                              presence_penalty=1.2, frequency_penalty=0.3)
    rng = np.random.default_rng(3)
    reqs = [
        ce.submit(rng.integers(1, 40, 9).tolist(), max_new_tokens=n,
                  seed=i, sampling=sp)
        for i, (n, sp) in enumerate(
            [(7, pen), (12, None), (9, None), (8, hot), (6, pen), (5, None)])
    ]
    out, stats = _finish(ce, reqs)
    assert stats["admit_device_calls"] >= 3  # the three histograms
    return out, stats


def _drafting(cls, eng):
    """A slot that drafts from its own repetitive history beside one that
    does not, and a late arrival."""
    ce = _dense_engine(cls, eng, spec_decode=True, spec_draft=4)
    rep = [5, 6, 7, 8] * 5
    reqs = [ce.submit(rep, max_new_tokens=24, seed=1, speculative=True),
            ce.submit([9, 3, 4], max_new_tokens=10, seed=2)]
    ce.step_chunk()
    reqs.append(ce.submit(rep[:9], max_new_tokens=12, seed=3,
                          speculative=True))
    out, stats = _finish(ce, reqs)
    assert stats["spec_drafted"] > 0
    return out, stats


def _pool(cls, eng):
    """Two tenants of one shared page pool, stepped in turn."""
    pool = SharedPagePool(eng.cfg, 40, page_size=8)
    tenants = [
        cls(eng, max_slots=2, page_size=8, chunk_steps=4, prefill_chunk=32,
            pool=pool, model_id=f"m{i}", page_quota=24)
        for i in range(2)
    ]
    rng = np.random.default_rng(4)
    reqs = [[ce.submit(rng.integers(1, 136, 5 + 3 * j).tolist(),
                       max_new_tokens=5 + j, seed=10 * i + j)
             for j in range(4)] for i, ce in enumerate(tenants)]
    while any(ce.has_work() for ce in tenants):
        for ce in tenants:
            ce.step_chunk()
    outs, stats = [], {}
    for ce, rs in zip(tenants, reqs):
        out, stats = _finish(ce, rs)
        outs += out
    return outs, stats


# tlint: disable=TL006(read-only table)
SCENARIOS = {"cow": _cow, "churn": _churn, "preempt": _preempt,
             "penalties": _penalties, "drafting": _drafting, "pool": _pool}


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_streams_are_what_device_writes_at_once_make(dense, name):
    got, stats = SCENARIOS[name](ContinuousEngine, dense)
    want, _ = SCENARIOS[name](_CallsTheDevice, dense)
    assert got == want
    assert all(got)  # every request spoke
    # a chunk still crosses the boundary as one array each way
    assert stats["chunk_host_arrays"] == 2 * stats["ragged_blocks"] > 0


def test_two_tensor_parallel_shards(dense):
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    got, stats = _churn(ContinuousEngine, dense, tensor_parallel=2)
    want, _ = _churn(_CallsTheDevice, dense, tensor_parallel=2)
    one, _ = _churn(ContinuousEngine, dense)
    assert got == want == one
    assert stats["admit_device_calls"] == 0
    assert stats["chunk_host_arrays"] == 2 * stats["ragged_blocks"]


def test_a_tensor_parallel_chunk_places_no_table_again(dense, monkeypatch):
    """The step's own results come back under the spelling ``_canon``
    wants: with nothing bound or cleared by a program of its own, no
    chunk places ``block_tables``, ``lengths`` or ``counts`` again, the
    chunk after an admission and the one after a retirement included."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 (virtual) devices")
    ce = _dense_engine(ContinuousEngine, dense, max_slots=2,
                       tensor_parallel=2)
    first = ce.submit([1, 2, 3], max_new_tokens=30, seed=1)
    ce.step_chunk()
    put, placed = jax.device_put, []
    monkeypatch.setattr(
        jax, "device_put", lambda x, *a, **k: (placed.append(
            getattr(x, "shape", None)), put(x, *a, **k))[1])
    reqs = [ce.submit([4 + i, 5], max_new_tokens=4, seed=2 + i)
            for i in range(3)]  # admitted beside, retired, replaced
    ce.run_until_idle()
    assert all(r.finished for r in reqs + [first])
    assert placed == []
    ce.close()


@pytest.mark.parametrize("family", ["dots3", "deepseek_v2"])
def test_a_tiny_latent_engine(family):
    hf = test_latent.TINY if family == "dots3" else test_latent.TINY_DS
    cfg = config_from_hf(hf, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,), max_seq_len=64)

    def run(cls):
        ce = cls(eng, max_slots=2, page_size=4, chunk_steps=4,
                 prefill_chunk=8)
        rng = np.random.default_rng(5)
        shared = rng.integers(1, cfg.vocab_size, 14).tolist()
        r0 = ce.submit(shared, max_new_tokens=4, seed=0)
        ce.run_until_idle()
        reqs = [r0] + [
            ce.submit(shared[:10 + i] + rng.integers(
                1, cfg.vocab_size, 3 + i).tolist(),
                max_new_tokens=4 + i, seed=1 + i)
            for i in range(4)
        ]
        out, stats = _finish(ce, reqs)
        assert stats["prefill_tokens_skipped"] > 0
        return out, stats

    got, stats = run(ContinuousEngine)
    want, _ = run(_CallsTheDevice)
    assert got == want and all(got)
    assert stats["slot_binds_packed"] == (
        stats["admitted"] + stats["evicted"])


def test_a_tiny_stateful_engine():
    """Block-sparse and lightning layers: an admission restores a
    snapshot or zeroes the state by a call (one an admission) and binds
    its pages through the control buffer."""
    cfg = config_from_hf(test_sala.TINY, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))

    def run(cls):
        eng = GenerationEngine(cfg, params, seq_buckets=(8, 32),
                               batch_buckets=(1,), max_seq_len=256)
        ce = cls(eng, max_slots=2, page_size=4, chunk_steps=4,
                 prefill_chunk=8, state_snapshot_stride=32)
        rng = np.random.default_rng(6)
        doc = rng.integers(1, 97, 70).tolist()
        r0 = ce.submit(doc, max_new_tokens=4, seed=0)
        ce.run_until_idle()
        reqs = [r0] + [
            ce.submit(doc[:66] + rng.integers(1, 97, 5 + i).tolist(),
                      max_new_tokens=5 + i, seed=1 + i)
            for i in range(3)
        ]
        out, stats = _finish(ce, reqs)
        assert stats["state_snapshots_restored"] >= 3
        return out, stats

    got, stats = run(ContinuousEngine)
    want, _ = run(_CallsTheDevice)
    assert got == want and all(got)
    # the state's restore or zero, and nothing else, an admission
    assert stats["admit_device_calls"] == stats["state_admissions"] == 4


def test_a_tiny_ring_engine():
    """Grouped-query layers with a ring a slot: an admission under a
    prefix hit restores ONE window snapshot by a call, one that hits
    nothing calls nothing (a ring needs no zero), and the pages bind
    through the control buffer."""
    import test_laguna

    cfg = config_from_hf(test_laguna.TINY, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))

    def run(cls):
        eng = GenerationEngine(cfg, params, seq_buckets=(8, 32),
                               batch_buckets=(1,), max_seq_len=256)
        ce = cls(eng, max_slots=2, page_size=4, chunk_steps=4,
                 prefill_chunk=8, state_snapshot_stride=32)
        rng = np.random.default_rng(6)
        doc = rng.integers(1, 97, 70).tolist()
        r0 = ce.submit(doc, max_new_tokens=4, seed=0)
        ce.run_until_idle()
        reqs = [r0] + [
            ce.submit(doc[:66] + rng.integers(1, 97, 5 + i).tolist(),
                      max_new_tokens=5 + i, seed=1 + i)
            for i in range(3)
        ]
        out, stats = _finish(ce, reqs)
        return out, stats

    got, stats = run(ContinuousEngine)
    want, _ = run(_CallsTheDevice)
    assert got == want and all(got)
    assert stats["window_admissions"] == 4
    assert stats["admit_device_calls"] == (
        stats["window_snapshots_restored"]) == 3
    assert stats["slot_binds_packed"] == stats["admitted"] + stats["evicted"]


def test_a_tiny_tail_engine():
    """Short-convolution layers with a tail a slot: an admission under a
    prefix hit restores ONE tail snapshot by a call, one that hits nothing
    calls nothing (the ragged pass reads zeros before position 0), and
    the pages bind through the control buffer."""
    import test_lfm2

    cfg = config_from_hf(test_lfm2.TINY, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))

    def run(cls):
        eng = GenerationEngine(cfg, params, seq_buckets=(8, 32),
                               batch_buckets=(1,), max_seq_len=256)
        ce = cls(eng, max_slots=2, page_size=4, chunk_steps=4,
                 prefill_chunk=8, state_snapshot_stride=32)
        rng = np.random.default_rng(6)
        doc = rng.integers(1, 97, 70).tolist()
        r0 = ce.submit(doc, max_new_tokens=4, seed=0)
        ce.run_until_idle()
        reqs = [r0] + [
            ce.submit(doc[:66] + rng.integers(1, 97, 5 + i).tolist(),
                      max_new_tokens=5 + i, seed=1 + i)
            for i in range(3)
        ]
        out, stats = _finish(ce, reqs)
        return out, stats

    got, stats = run(ContinuousEngine)
    want, _ = run(_CallsTheDevice)
    assert got == want and all(got)
    assert stats["conv_admissions"] == 4
    assert stats["admit_device_calls"] == (
        stats["conv_snapshots_restored"]) == 3
    assert stats["slot_binds_packed"] == stats["admitted"] + stats["evicted"]


def test_a_migrated_stream_adopts_through_the_control_buffer(dense):
    """The destination binds the shipped pages on its host table; frozen
    again before any chunk ran there, the slot's length is still the
    shipped one (``_slot_length``), and the stream ends as it would have
    at home."""
    from test_continuous import _drive_until, _migrate

    def solo():
        ce = _dense_engine(ContinuousEngine, dense)
        r = ce.submit([5, 6, 7], max_new_tokens=14, seed=9)
        return _finish(ce, [r])[0][0]

    def run(cls):
        src, dst = _dense_engine(cls, dense), _dense_engine(cls, dense)
        r = src.submit([5, 6, 7], max_new_tokens=14, seed=9)
        _drive_until(src, r, 5)
        r2, moved = _migrate(src, dst, r, "m0")
        dst.step_chunk(admit_only=True)  # adopted, nothing dispatched yet
        assert r2.slot >= 0 and dst._active[r2.slot]
        length = len(moved.prompt) + len(moved.tokens) - 1
        assert dst._slot_length(r2.slot) == length
        # and on again: what it exports from there is the same chain
        dst.freeze_slot(r2.slot)
        assert dst.migration_chain(r2.slot)[0] == moved.prompt + moved.tokens
        blob = dst.export_slot(r2.slot)
        assert blob["length"] == length
        dst.abort_migration(r2.slot)
        dst.run_until_idle()
        src.run_until_idle()
        out = moved.tokens + r2.tokens
        src.close()
        dst.close()
        return out

    assert run(ContinuousEngine) == run(_CallsTheDevice) == solo()


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------
def test_plain_requests_call_the_device_for_nothing_but_their_chunks():
    """Over a run of plain requests: no device call of the admission or
    retirement path, one packed bind an admission and one a retirement,
    two arrays a chunk, and the step programs of the widths that ran."""
    cfg = _dense_cfg(d_ff=56)  # programs of its own to count
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,), max_seq_len=96)
    ce = ContinuousEngine(eng, max_slots=2, page_size=8, chunk_steps=4,
                          prefill_chunk=32, prefix_cache=False)
    programs = ce._step_programs()
    rng = np.random.default_rng(7)
    reqs = [ce.submit(rng.integers(1, 136, 4 + 6 * i).tolist(),
                      max_new_tokens=4 + i, seed=i) for i in range(6)]
    ce.run_until_idle()
    stats = ce.stats
    assert all(r.finished for r in reqs)
    assert stats["admit_device_calls"] == 0
    assert stats["admitted"] == stats["evicted"] == 6
    assert stats["slot_binds_packed"] == 12
    assert stats["chunk_host_arrays"] == 2 * stats["ragged_blocks"] > 0
    widths = {r["block_rows"] for r in ce.recorder.records()}
    assert widths == set(ce.block_widths)
    assert ce._step_programs() - programs == len(ce.block_widths)
    sizes = ce.jit_cache_sizes()
    assert "bind_slot" not in sizes and "clear_slot" not in sizes
    assert not hasattr(paged, "bind_slot") and not hasattr(paged, "clear_slot")
    ce.close()


def test_the_counters_are_exported():
    names = {c[0]: c[1] for c in continuous._ENGINE_COUNTERS}
    assert names["slot_binds_packed"] == "tlink_engine_slot_binds_packed_total"
    assert names["admit_device_calls"] == (
        "tlink_engine_admit_device_calls_total")


def test_an_admission_places_nothing_and_calls_no_program(dense, monkeypatch):
    """Watched from inside the engine's module, as a steady chunk is in
    tests/test_step_boundary.py: an admission, the chunk that carries it,
    a retirement and the re-admission behind it place nothing by hand
    (``jnp.asarray`` / ``jnp.int32`` / ``jnp.zeros`` / ``jax.device_put``),
    call no jitted program but the step, and read nothing back from the
    device but each chunk's one result (a request's sampling knobs are
    host scalars: ``SamplingParams.make``)."""
    ce = _dense_engine(ContinuousEngine, dense, max_slots=2,
                       prefix_cache=False)
    warm = ce.submit([1, 2, 3], max_new_tokens=3, seed=0)
    ce.run_until_idle()
    assert warm.finished

    from test_step_boundary import _Counting

    fetches = _Counting(np, ("asarray", "array"),
                        lambda x, *a, **k: isinstance(x, jax.Array))
    places = _Counting(jnp, ("asarray", "array", "int32", "zeros"))
    monkeypatch.setattr(continuous, "np", fetches)
    monkeypatch.setattr(continuous, "jnp", places)
    put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda *a, **k: (
        places.calls.append(("device_put", 0.0, 0.0)), put(*a, **k))[1])
    for name in ("copy_page", "scatter_page", "gather_page",
                 "set_counts_row"):
        monkeypatch.setattr(continuous, name, lambda *a, _n=name, **k: (
            pytest.fail(f"{_n} called")))
    blocks = ce.stats["ragged_blocks"]
    reqs = [ce.submit([4 + i, 5, 6], max_new_tokens=3 + i, seed=i,
                      sampling=SamplingParams.make(temperature=0.7, top_k=9)
                      if i % 2 else None)
            for i in range(5)]
    ce.run_until_idle()
    assert all(r.finished for r in reqs)
    assert places.calls == []
    assert len(fetches.calls) == ce.stats["ragged_blocks"] - blocks > 0
    monkeypatch.undo()
    ce.close()


def test_pending_binds_wait_for_the_next_dispatch(dense):
    """An ``admit_only`` round binds on the host and dispatches nothing:
    the device's table is as it was, the marks stand, and the next
    dispatched chunk's program takes them."""
    ce = _dense_engine(ContinuousEngine, dense, max_slots=2)
    r = ce.submit([3, 1, 4, 1, 5], max_new_tokens=6, seed=1)
    assert ce.step_chunk(admit_only=True)
    assert r.slot >= 0 and ce._bind[r.slot] and ce._reset[r.slot]
    assert ce._bt_host[r.slot].any()
    assert not np.asarray(ce.cache.block_tables).any()
    assert ce.stats["ragged_blocks"] == 0
    ce.step_chunk()
    assert not ce._bind.any() and not ce._reset.any()
    np.testing.assert_array_equal(
        np.asarray(ce.cache.block_tables), ce._bt_host)
    want = _finish(ce, [r])[0]
    solo = _dense_engine(ContinuousEngine, dense, max_slots=2)
    assert want == _finish(
        solo, [solo.submit([3, 1, 4, 1, 5], max_new_tokens=6, seed=1)])[0]


# ---------------------------------------------------------------------------
# the packers and the program
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_pp", [0, 12, 256])
def test_the_new_columns_round_trip(n_pp):
    rng = np.random.default_rng(n_pp)
    S, C = 5, 16
    z, zf = np.zeros(S, np.int32), np.zeros(S, np.float32)
    bind = np.asarray([True, False, True, False, False])
    reset = np.asarray([False, True, True, False, False])
    bind_len = rng.integers(0, 2**20, S).astype(np.int32)
    rows = rng.integers(0, 2**31 - 1, (S, n_pp)).astype(np.int32)
    blk = rng.integers(0, 999, (S, C)).astype(np.int32)
    eos = np.full((S, 2), -1, np.int32)
    ctl = pack_control(blk, z + 3, z + 1, z, bind, z, z, zf, z, zf, zf, zf,
                       z, eos, bind, bind_len, reset, rows)
    assert ctl.shape == (S, C + CTL_COLS + n_pp)
    got = jax.jit(unpack_control, static_argnums=1)(jnp.asarray(ctl), n_pp)
    np.testing.assert_array_equal(got.blk, blk)
    np.testing.assert_array_equal(got.bind, bind)
    np.testing.assert_array_equal(got.reset, reset)
    np.testing.assert_array_equal(got.bind_len, bind_len)
    np.testing.assert_array_equal(got.bind_rows, rows)
    assert got.bind.dtype == got.reset.dtype == jnp.bool_
    # left out, nothing is bound and the table's width is named
    bare = pack_control(blk, z + 3, z + 1, z, bind, z, z, zf, z, zf, zf, zf,
                        z, eos, pages_per_slot=n_pp)
    assert bare.shape == ctl.shape
    none = unpack_control(jnp.asarray(bare), n_pp)
    assert not np.asarray(none.bind).any()
    assert not np.asarray(none.reset).any()
    assert not np.asarray(none.bind_rows).any()


def test_the_program_binds_and_resets_before_its_pass():
    """A stale table, a stale length and a stale histogram under ``bind``
    and ``reset`` give what a cache bound beforehand gives, bit for bit;
    a slot whose flags are down keeps what the device holds."""
    cfg = _dense_cfg(d_ff=40)
    params = init_params(cfg, jax.random.PRNGKey(1))
    S, C, page = 3, 8, 8
    rng = np.random.default_rng(8)

    def fresh():
        cache = PagedKVCache.init(cfg, S, page_size=page, max_len=64)
        n_pp = cache.pages_per_slot
        bt = np.zeros((S, n_pp), np.int32)
        bt[0, :3], bt[1, :3] = [1, 2, 3], [4, 5, 6]
        return cache, bt, n_pp

    blk = rng.integers(1, 136, (S, C)).astype(np.int32)
    z, zf = np.zeros(S, np.int32), np.zeros(S, np.float32)
    n_valid = np.asarray([C, 5, 0], np.int32)
    emit = np.asarray([True, True, False])
    pres = np.asarray([0.5, 0.0, 0.0], np.float32)
    rows = (blk, z, n_valid, z, emit, z + 3, z, zf, z, zf + 1, pres, zf,
            z + 4, np.full((S, 1), -1, np.int32))
    counts0 = rng.integers(0, 5, (S, cfg.vocab_size)).astype(np.int32)

    cache, bt, n_pp = fresh()
    want = paged.paged_ragged_step(
        params, pack_control(*rows, pages_per_slot=n_pp),
        replace(cache, block_tables=jnp.asarray(bt)),
        jnp.asarray(counts0).at[0].set(0), cfg, 3, 1, False)
    cache, bt, n_pp = fresh()
    stale = np.zeros_like(bt)
    stale[0, :3], stale[1, :3], stale[2, :2] = [7, 7, 7], [4, 5, 6], [8, 9]
    got = paged.paged_ragged_step(
        params,
        pack_control(*rows, np.asarray([True, False, True]), z,
                     np.asarray([True, False, False]), bt),
        replace(cache, block_tables=jnp.asarray(stale),
                lengths=jnp.asarray([9, 0, 11], jnp.int32)),
        jnp.asarray(counts0), cfg, 3, 1, False)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    tokens, n_tok, *_ = unpack_results(np.asarray(got[0]), 3, 1)
    assert (n_tok[:2] == 3).all() and n_tok[2] == 0
    # slot 2 was cleared (row to scratch, length 0), slot 1 kept its own
    np.testing.assert_array_equal(np.asarray(got[1].block_tables), bt)
    assert int(got[1].lengths[2]) == 0
    np.testing.assert_array_equal(np.asarray(got[2])[2], counts0[2])
