"""End-to-end request tracing + the engine flight recorder
(core/trace.py and its engine wiring).

Hard contracts pinned here:

- the tracer's span store is bounded (traces AND spans per trace), ingest
  dedups wire-echoed spans, and ``collect`` returns ts-ordered copies;
- a traced request's engine spans decompose its TTFT contiguously:
  queue_wait + prefill + first_decode == first_token (to float rounding);
- tracing is OBSERVATION ONLY: a traced stream is bit-identical to the
  same request untraced, and the compiled-program set does not grow
  (the compile guard extends over tracing);
- a migration's spans stitch under ONE trace id across both engines
  (freeze/export/commit on the source site, stage/adopt on the
  destination site);
- the flight recorder ring is bounded, appends one record per chunk, and
  dumps on engine error (``recorder.last_dump`` carries the final steps);
- a chunk's record holds its host phases (between, admit, pack,
  dispatch, wait, drain, deliver, post), which add up to the wall time
  from one chunk's entry to the next; the same phases are
  ``tlink:<phase>`` annotations inside one ``tlink:chunk`` on a profiler
  trace's host line, joined to the record by ``chunk`` == ``step``; and
  a request's spans name the chunks it rode in.
"""

import jax
import jax.numpy as jnp
import pytest

from tensorlink_tpu.core.trace import (
    FlightRecorder,
    Tracer,
    current_trace,
    get_tracer,
    mint_trace_id,
)
from tensorlink_tpu.engine.continuous import CHUNK_PHASES, ContinuousEngine
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


# ---------------------------------------------------------------------------
# tracer primitives
# ---------------------------------------------------------------------------


def test_tracer_bounds_and_ingest_dedup():
    t = Tracer(max_traces=3, max_spans=4)
    for i in range(5):
        t.record(f"t{i}", "s")
    # LRU bound: only the newest 3 traces survive
    assert not t.known("t0") and not t.known("t1")
    assert t.known("t4")
    for i in range(10):
        t.record("t4", f"s{i}")
    assert len(t.collect("t4")) == 4  # span cap per trace

    # ingest dedups on sid: a span seen locally AND echoed over the wire
    # lands once
    t2 = Tracer()
    t2.record("x", "a", site="w1", dur_s=0.5)
    spans = t2.collect("x")
    assert t2.ingest("x", spans) == 0  # identical sids -> nothing added
    t3 = Tracer()
    assert t3.ingest("x", spans) == 1  # fresh store -> merged
    assert t3.collect("x")[0]["site"] == "w1"
    assert t3.collect("x")[0]["dur_ms"] == pytest.approx(500.0)


def test_mint_and_contextvar():
    a, b = mint_trace_id(), mint_trace_id()
    assert a != b and len(a) == 16
    assert current_trace.get() == ""
    tok = current_trace.set(a)
    try:
        assert current_trace.get() == a
    finally:
        current_trace.reset(tok)
    assert current_trace.get() == ""


def test_json_log_mode_carries_trace_id(capsys):
    import json as _json
    import logging

    from tensorlink_tpu.core.logging import (
        _TagFormatter,
        set_json_logs,
    )

    fmt = _TagFormatter(color=False)
    rec = logging.LogRecord(
        "tensorlink_tpu.test", logging.INFO, __file__, 1, "hello %s",
        ("x",), None,
    )
    rec.tag = "test"
    set_json_logs(True)
    try:
        tok = current_trace.set("tid123")
        try:
            line = fmt.format(rec)
        finally:
            current_trace.reset(tok)
        obj = _json.loads(line)
        assert obj["msg"] == "hello x"
        assert obj["tag"] == "test"
        assert obj["level"] == "INFO"
        assert obj["trace_id"] == "tid123"
        assert isinstance(obj["ts"], float)
        # no active span -> no trace_id key
        obj2 = _json.loads(fmt.format(rec))
        assert "trace_id" not in obj2
    finally:
        set_json_logs(False)
    # plain mode unaffected after reset
    assert fmt.format(rec).startswith("[")


# ---------------------------------------------------------------------------
# engine spans
# ---------------------------------------------------------------------------


def test_traced_request_spans_decompose_ttft(tiny_engine):
    ce = _cont(tiny_engine, trace_site="wA")
    tid = mint_trace_id()
    r = ce.submit([1, 2, 3], max_new_tokens=5, seed=1, trace_id=tid)
    ce.run_until_idle()
    assert r.finished
    spans = {s["name"]: s for s in get_tracer().collect(tid)}
    for name in ("queue_wait", "admission", "prefill_chunk", "prefill",
                 "first_decode", "first_token", "decode"):
        assert name in spans, name
    assert all(s["site"] == "wA" for s in spans.values())
    # contiguous decomposition: the three parts sum to the TTFT span
    total = (
        spans["queue_wait"]["dur_ms"]
        + spans["prefill"]["dur_ms"]
        + spans["first_decode"]["dur_ms"]
    )
    assert total == pytest.approx(spans["first_token"]["dur_ms"], abs=0.1)
    assert spans["decode"]["tokens"] == 5
    ce.close()


def test_untraced_request_records_nothing(tiny_engine):
    before = len(get_tracer().collect(""))
    ce = _cont(tiny_engine)
    r = ce.submit([4, 5], max_new_tokens=4, seed=2)
    ce.run_until_idle()
    assert r.finished
    assert len(get_tracer().collect("")) == before  # "" never stores
    ce.close()


def test_traced_stream_bit_identical_and_zero_new_programs(tiny_engine):
    """Tracing is observation only: same tokens, same compiled-program
    set — the compile guard extended over the observability layer."""
    prompt, n, seed = [7, 3, 2], 10, 5
    sp = SamplingParams.make(temperature=0.8, top_k=7)
    ce = _cont(tiny_engine)
    base = ce.submit(prompt, max_new_tokens=n, sampling=sp, seed=seed)
    ce.run_until_idle()
    sizes_untraced = ce.jit_cache_sizes()
    ce.close()

    ce2 = _cont(tiny_engine, trace_site="wB")
    traced = ce2.submit(
        prompt, max_new_tokens=n, sampling=sp, seed=seed,
        trace_id=mint_trace_id(),
    )
    ce2.run_until_idle()
    sizes_traced = ce2.jit_cache_sizes()
    ce2.close()

    assert traced.tokens == base.tokens  # bit-identity with tracing on
    assert sizes_traced == sizes_untraced  # zero new compiled programs


def test_rejected_submission_records_rejection_span(tiny_engine):
    ce = _cont(tiny_engine, sched_queue_cap=1, max_slots=1, chunk_steps=2)
    # fill the slot and the queue
    ce.submit([1], max_new_tokens=30, seed=1)
    ce.step_chunk()
    ce.submit([2], max_new_tokens=2, seed=2)
    tid = mint_trace_id()
    rej = ce.submit([3], max_new_tokens=2, seed=3, trace_id=tid)
    assert rej.error is not None
    spans = [s["name"] for s in get_tracer().collect(tid)]
    assert "rejected" in spans
    ce.close()


# ---------------------------------------------------------------------------
# migration spans stitch across engines under one trace id
# ---------------------------------------------------------------------------


def test_migration_spans_stitch_across_sites(tiny_engine):
    src = _cont(tiny_engine, trace_site="workerA")
    dst = _cont(tiny_engine, trace_site="workerB")
    tid = mint_trace_id()
    r = src.submit([5, 6, 7], max_new_tokens=12, seed=9, trace_id=tid)
    while len(r.tokens) < 4:
        src.step_chunk()
    src.freeze_slot(r.slot)
    blob = src.export_slot(r.slot)
    assert blob["trace"] == tid  # rides the MIGRATE wire frame
    assert dst.stage_migration("m1", blob)
    moved = src.commit_migration(r.slot)
    r2 = dst.submit(
        moved.prompt + moved.tokens,
        max_new_tokens=moved.budget - len(moved.tokens),
        seed=moved.seed,
        start_step=moved.start_step + len(moved.tokens),
        adopt="m1",
        trace_id=tid,
    )
    dst.run_until_idle()
    assert r2.finished
    spans = get_tracer().collect(tid)
    by_site = {}
    for s in spans:
        by_site.setdefault(s["site"], set()).add(s["name"])
    # source half: admission through freeze/export/commit
    for name in ("queue_wait", "prefill", "first_token", "freeze",
                 "export", "migrate_commit"):
        assert name in by_site["workerA"], (name, by_site)
    # destination half: staging + adoption + the resumed decode
    for name in ("stage", "adopt", "decode"):
        assert name in by_site["workerB"], (name, by_site)
    src.close()
    dst.close()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_bounds_and_dump():
    fr = FlightRecorder(capacity=5)
    for i in range(12):
        fr.record(pages_free=i)
    recs = fr.records()
    assert len(recs) == 5  # bounded ring
    assert [r["step"] for r in recs] == [8, 9, 10, 11, 12]  # newest kept
    dump = fr.dump(RuntimeError("boom"))
    assert dump["error"] == "RuntimeError: boom"
    assert dump["n_records"] == 5
    assert fr.last_dump is dump


def test_engine_records_one_entry_per_chunk_and_dumps_on_error(tiny_engine):
    ce = _cont(tiny_engine, chunk_steps=2)
    r = ce.submit([1, 2, 3], max_new_tokens=6, seed=3)
    n0 = len(ce.recorder)
    ce.step_chunk()
    assert len(ce.recorder) == n0 + 1
    rec = ce.recorder.records()[-1]
    for key in ("step", "live_slots", "prefilling", "decode_steps",
                "prefill_granted", "tokens_emitted", "pages_free",
                "pages_in_transit", "preemptions", "chunk_ms"):
        assert key in rec, key
    assert rec["live_slots"] >= 1
    # error teardown dumps the ring for the postmortem
    err = RuntimeError("chaos")
    ce.close(err)
    assert r.error is err
    dump = ce.recorder.last_dump
    assert dump is not None and dump["error"] == "RuntimeError: chaos"
    assert dump["records"]  # the per-step state survived the crash path
    # clean close() must NOT dump (no error, no postmortem)
    ce2 = _cont(tiny_engine)
    ce2.submit([4], max_new_tokens=2, seed=1)
    ce2.run_until_idle()
    ce2.close()
    assert ce2.recorder.last_dump is None


# ---------------------------------------------------------------------------
# the anatomy of a chunk: host phases in the record, on the profiler's
# host line, and in the request's spans
# ---------------------------------------------------------------------------

PHASES = ("admit", "pack", "dispatch", "wait", "drain", "deliver", "post")


def test_chunk_record_holds_every_phase_and_they_add_up(tiny_engine):
    assert CHUNK_PHASES == PHASES  # the names are part of /stats and the docs
    ce = _cont(tiny_engine, chunk_steps=2)
    ce.submit([1, 2, 3], max_new_tokens=12, seed=3)
    ce.run_until_idle()
    recs = ce.recorder.records()
    assert len(recs) >= 3
    for r in recs:
        for key in ("t0", "between_ms") + tuple(f"{p}_ms" for p in PHASES):
            assert isinstance(r[key], float) and r[key] >= 0.0, key
        # the two older fields keep their meaning
        assert r["host_ms"] == pytest.approx(
            r["admit_ms"] + r["pack_ms"], abs=1e-6)
        assert r["chunk_ms"] == pytest.approx(
            r["dispatch_ms"] + r["wait_ms"] + r["drain_ms"], abs=1e-6)
    # the engine had no work before the first chunk: nothing lay between
    assert recs[0]["between_ms"] == 0.0
    # entry to entry: a chunk's seven phases and the next one's "between"
    for a, b in zip(recs, recs[1:]):
        wall_ms = (b["t0"] - a["t0"]) * 1e3
        parts = sum(a[f"{p}_ms"] for p in PHASES) + b["between_ms"]
        assert parts == pytest.approx(wall_ms, abs=1.0)
        assert b["between_ms"] > 0.0
    ce.close()


def test_between_is_not_counted_across_an_idle_engine(tiny_engine):
    """``between`` is host time the device waits behind: after the engine
    ran out of work, the wait for the next request is not part of it."""
    import time

    ce = _cont(tiny_engine, chunk_steps=2)
    ce.submit([1, 2, 3], max_new_tokens=2, seed=3)
    ce.run_until_idle()
    time.sleep(0.05)
    ce.submit([4, 5], max_new_tokens=2, seed=3)
    ce.run_until_idle()
    recs = ce.recorder.records()
    first_of_second = next(r for r in recs if r["t0"] > recs[0]["t0"] + 0.05)
    assert first_of_second["between_ms"] == 0.0
    ce.close()


def _tlink_events(trace_dir):
    """(name, start_ns, end_ns, stats) of the ``tlink:`` annotations."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("tlink:"):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda ev: ev[1])


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_profiler_trace_holds_chunks_joined_to_records_by_id(
        tiny_engine, tmp_path):
    """Taken through the program alone: each ``tlink:chunk`` annotation
    carries the ``step`` of the chunk's flight-recorder record, and the
    seven phases lie inside it, in order."""
    ce = _cont(tiny_engine, chunk_steps=2)
    ce.submit([1, 2, 3], max_new_tokens=2, seed=3)
    ce.run_until_idle()  # the step program is built before the trace
    n0 = len(ce.recorder)
    ce.submit([1, 2, 3], max_new_tokens=4, seed=3)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        ce.step_chunk()
        ce.step_chunk()
    finally:
        jax.profiler.stop_trace()
    recs = ce.recorder.records()[n0:]
    events = _tlink_events(tmp_path)
    chunks = [e for e in events if e[0] == "tlink:chunk"]
    assert [c[3]["chunk"] for c in chunks] == [r["step"] for r in recs]
    assert len(chunks) == 2
    for _name, a, b, _stats in chunks:
        inside = [e for e in events
                  if e[0] != "tlink:chunk" and a <= e[1] and e[2] <= b]
        streams = [e for e in inside if e[0] == "tlink:stream"]
        inside = [e for e in inside if e[0] != "tlink:stream"]
        assert [e[0] for e in inside] == [f"tlink:{p}" for p in PHASES]
        assert all(x[2] <= y[1] for x, y in zip(inside, inside[1:]))
        # the stream stage is a sub-span: of wait while a step is in
        # flight, of deliver when none follows
        for _n, sa, sb, _s in streams:
            assert any(e[0] in ("tlink:wait", "tlink:deliver")
                       and e[1] <= sa and sb <= e[2] for e in inside)
    # chunk 2's wait holds chunk 1's stream, its deliver its own (no work left)
    assert len([e for e in events if e[0] == "tlink:stream"]) == 2
    ce.close()


class _Writer:
    def __init__(self):
        self.sent = []

    def write(self, data):
        self.sent.append(data)

    async def drain(self):
        pass


def test_streamed_request_spans_name_its_chunks(tiny_engine):
    """One trace id holds the API's ``http_first_byte`` (handler entry to
    the first delta written, on the API's clock) and the engine's
    ``first_token`` (submit to first emit, on the engine's): the
    difference is the way in and out. ``prefill_chunk``, ``prefill`` and
    ``first_token`` carry the flight-recorder step of their chunk."""
    import asyncio
    from concurrent.futures import ThreadPoolExecutor

    from tensorlink_tpu.api.formatter import ResponseFormatter
    from tensorlink_tpu.api.schemas import GenerationRequest
    from tensorlink_tpu.api.server import TensorlinkAPI

    # prompt 20 > prefill_chunk 8: three prefill chunks before the token
    ce = _cont(tiny_engine, chunk_steps=2, prefill_chunk=8)

    class _Exec:
        def generate_api(self, gen, on_delta, trace_id, meta_cb):
            req = ce.submit(
                list(range(1, 21)), max_new_tokens=5, seed=1,
                trace_id=trace_id,
                stream_cb=lambda tok: on_delta(f"{tok} ") or False,
            )
            ce.run_until_idle()
            return {"prompt_tokens": 20, "finish_reason": "length",
                    "completion_tokens": len(req.tokens)}

    api = TensorlinkAPI.__new__(TensorlinkAPI)
    api.executor = _Exec()
    api._pool = ThreadPoolExecutor(1)
    api._req_ids = {}
    rid = mint_trace_id()
    gen = GenerationRequest.parse({"hf_name": "m", "stream": True})
    writer = _Writer()
    try:
        asyncio.run(api._stream_generate(
            gen, ResponseFormatter("m", "simple"), writer, rid))
    finally:
        api._pool.shutdown()
    assert writer.sent[-1] == b"data: [DONE]\n\n"
    spans = get_tracer().collect(rid)
    by = {}
    for sp in spans:
        by.setdefault(sp["name"], []).append(sp)
    (first_byte,), (first_token,) = by["http_first_byte"], by["first_token"]
    assert first_byte["site"] == "api"
    assert first_byte["dur_ms"] >= first_token["dur_ms"] > 0
    steps = {r["step"] for r in ce.recorder.records()}
    rode = [sp["chunk"] for sp in by["prefill_chunk"]]
    assert len(rode) == 3 and rode == sorted(rode) and set(rode) <= steps
    assert by["prefill"][0]["chunk"] == rode[-1] == first_token["chunk"]
    ce.close()
