"""End-to-end request tracing + the engine flight recorder
(core/trace.py and its engine wiring).

Hard contracts pinned here:

- the tracer's span store is bounded (traces AND spans per trace), ingest
  dedups wire-echoed spans, and ``collect`` returns copies in start
  order: by ``t0`` where the spans' ``host`` agrees, by ``ts`` across;
- every span holds ``t0`` (its start on ``time.monotonic()``), ``host``
  (whose clock that is) and ``parent``; a start stamped in another
  process gives a duration only where the stamp's host is the
  recorder's own;
- a streamed request through a validator + worker cluster leaves a span
  at every boundary from the API's handler to the first delta
  (``PATH_SPANS``), which lie end to end on one clock and add up to
  ``http_first_byte``; a request that arrives while ``step_chunk`` runs
  waits for it in ``work_wait``; only a stream's FIRST ``send_token``
  carries a stamp; an untraced request and a frame from a peer without
  the new keys are served as before;
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
    HOST,
    PATH_SPANS,
    FlightRecorder,
    Tracer,
    current_trace,
    first_token_stamp,
    get_tracer,
    mint_trace_id,
    stamp,
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


def test_span_holds_start_host_and_parent():
    import time

    t = Tracer()
    before = time.monotonic()
    root = t.new_sid()  # named ahead: a span is recorded at its end
    child = t.record("x", "child", dur_s=0.25, parent=root)
    assert t.record("x", "root", t0=before, dur_s=0.5, sid=root) == root
    assert t.record("", "nothing") == ""  # no id: nothing stored, no sid
    by = {s["name"]: s for s in t.collect("x")}
    assert by["child"]["parent"] == root and by["child"]["sid"] == child
    assert by["root"]["parent"] == "" and by["root"]["t0"] == before
    # no start given: the span ends now
    assert by["child"]["t0"] == pytest.approx(
        time.monotonic() - 0.25, abs=0.05)
    assert by["child"]["host"] == by["root"]["host"] == HOST
    assert len(HOST) >= 8 and stamp()["host"] == HOST
    assert not hasattr(t, "span")  # the context manager nobody called


def test_collect_orders_by_start_where_the_hosts_agree():
    """Two processes of one host may read the wall clock in the wrong
    order within a millisecond: their spans are ordered by ``t0``, one
    clock for both; across hosts only ``ts`` can order."""
    t = Tracer()
    mine = [
        {"sid": "a:1", "name": "second", "ts": 100.0001, "t0": 7.002,
         "host": "h1"},
        {"sid": "b:1", "name": "first", "ts": 100.0002, "t0": 7.001,
         "host": "h1"},
        {"sid": "c:1", "name": "elsewhere", "ts": 100.00015, "t0": 99999.0,
         "host": "h2"},
        {"sid": "d:1", "name": "old_peer", "ts": 99.0},  # no t0, no host
        {"sid": "e:1", "name": "inside_first", "ts": 100.0003, "t0": 7.001,
         "host": "h1", "dur_ms": 1.0},
        {"sid": "f:1", "name": "holds_it", "ts": 100.0004, "t0": 7.001,
         "host": "h1", "dur_ms": 5.0},
    ]
    assert t.ingest("x", mine) == 6
    assert [s["name"] for s in t.collect("x")] == [
        "old_peer", "holds_it", "elsewhere", "inside_first", "first",
        "second",
    ]


def test_a_foreign_hosts_stamp_gives_a_span_without_a_duration():
    t = Tracer()
    far = {"t": 12.5, "host": "another-boot", "parent": "z:9"}
    sid = t.record_since("x", "hop_in", far, site="w")
    (sp,) = t.collect("x")
    assert sp["sid"] == sid and "dur_ms" not in sp
    assert (sp["t0"], sp["host"], sp["parent"]) == (12.5, "another-boot", "z:9")
    # this host's own stamp: a duration, between two stamps or up to now
    a = stamp()
    b = {"t": a["t"] + 0.004, "host": HOST}
    t.record_since("y", "hop_in", a, end=b)
    t.record_since("y", "work_wait", b, end=b["t"] + 0.1, parent="p:1")
    hop, wait = t.collect("y")
    assert hop["dur_ms"] == pytest.approx(4.0, abs=1e-3)
    assert wait["dur_ms"] == pytest.approx(100.0, abs=1e-3)
    assert wait["t0"] == b["t"] and wait["parent"] == "p:1"
    # a peer that sends no stamp (or half of one): nothing is recorded
    assert t.record_since("z", "hop_in", None) == ""
    assert t.record_since("z", "hop_in", {"host": HOST}) == ""
    assert not t.known("z")


def _child_stamp(q):
    from tensorlink_tpu.core.trace import stamp as child_stamp

    q.put(child_stamp())


def test_a_stamp_taken_in_a_child_process_is_ordered_with_the_parents():
    """``time.monotonic()`` is one clock for every process of a host, and
    ``host`` says so: what the spans across the bridge rest on."""
    import multiprocessing as mp

    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    before = stamp()
    proc = ctx.Process(target=_child_stamp, args=(q,))
    proc.start()
    theirs = q.get(timeout=60)
    proc.join(timeout=60)
    after = stamp()
    assert theirs["host"] == before["host"] == HOST
    assert before["t"] < theirs["t"] < after["t"]


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
        fetches = [e for e in inside if e[0] == "tlink:fetch"]
        inside = [e for e in inside
                  if e[0] not in ("tlink:stream", "tlink:fetch")]
        assert [e[0] for e in inside] == [f"tlink:{p}" for p in PHASES]
        assert all(x[2] <= y[1] for x, y in zip(inside, inside[1:]))
        # the fetch is wait's last sub-span, behind the stream stage
        (wait,) = [e for e in inside if e[0] == "tlink:wait"]
        ((_n, fa, fb, _s),) = fetches
        assert wait[1] <= fa and fb <= wait[2]
        assert all(sb <= fa for _n, sa, sb, _s in streams if sa >= wait[1]
                   and sb <= wait[2])
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


def test_an_engine_in_the_apis_own_process_has_its_way_out_too(tiny_engine):
    """No worker, no frame: the engine leaves the first token's stamp on
    the thread that runs the stream callback, the API's delta callback
    finds it there, and ``token_out`` starts where ``first_token`` ends."""
    import asyncio
    from concurrent.futures import ThreadPoolExecutor

    from tensorlink_tpu.api.formatter import ResponseFormatter
    from tensorlink_tpu.api.schemas import GenerationRequest
    from tensorlink_tpu.api.server import TensorlinkAPI

    ce = _cont(tiny_engine, chunk_steps=2)

    class _Exec:
        def generate_api(self, gen, on_delta, trace_id, meta_cb):
            ce.submit([1, 2, 3], max_new_tokens=4, seed=1, trace_id=trace_id,
                      stream_cb=lambda tok: on_delta(f"{tok} ") or False)
            ce.run_until_idle()
            return {"prompt_tokens": 3, "finish_reason": "length",
                    "completion_tokens": 4}

    api = TensorlinkAPI.__new__(TensorlinkAPI)
    api.executor = _Exec()
    api._pool = ThreadPoolExecutor(1)
    api._req_ids = {}
    gen = GenerationRequest.parse({"hf_name": "m", "stream": True})
    rid = mint_trace_id()
    try:
        asyncio.run(api._stream_generate(
            gen, ResponseFormatter("m", "simple"), _Writer(), rid))
        # and with no id nothing is looked up or stored
        asyncio.run(api._stream_generate(
            gen, ResponseFormatter("m", "simple"), _Writer(), ""))
    finally:
        api._pool.shutdown()
    by = {sp["name"]: sp for sp in get_tracer().collect(rid)}
    whole, first, out = (
        by[n] for n in ("http_first_byte", "first_token", "token_out"))
    assert by["api_in"]["parent"] == whole["sid"]
    assert out["parent"] == first["sid"] and out["site"] == "api"
    assert out["t0"] == pytest.approx(
        first["t0"] + first["dur_ms"] / 1e3, abs=1e-6)
    assert out["t0"] + out["dur_ms"] / 1e3 == pytest.approx(
        whole["t0"] + whole["dur_ms"] / 1e3, abs=1e-6)
    ce.close()


# ---------------------------------------------------------------------------
# the request path, hop by hop: the worker's side without nodes
# ---------------------------------------------------------------------------


def _fake_worker(ce):
    """A ``DistributedWorker`` over a recording bridge (as
    tests/test_stream_stage.py builds one), hosting ``ce`` as job "j"."""
    import logging
    import queue
    import types

    from tensorlink_tpu.ml.worker import DistributedWorker

    sent: list = []  # every send_token payload, in order

    class _Bridge:
        q = types.SimpleNamespace(work=queue.Queue())

        def notify(self, verb, p):
            if verb == "send_token":
                sent.append(p)

        def request(self, verb, p, timeout=None):
            if verb == "send_token":
                sent.append(p)
            return [] if verb == "poll_cancel" else True

    w = DistributedWorker.__new__(DistributedWorker)
    w.bridge = _Bridge()
    w.node = types.SimpleNamespace(
        node_id="f" * 64,
        config=types.SimpleNamespace(ml=types.SimpleNamespace()),
    )
    w.log = logging.getLogger("test.trace")
    w.draining = None
    w._handoff_pools = {}
    w._respond = lambda *a, **kw: None
    rt = types.SimpleNamespace(
        job_id="j", jstreams={}, orphans={}, cont_scheduled=False,
        engine=ce.engine, cont=ce)
    w.jobs = {"j": rt}
    return w, rt, sent


def _frame(tid, **extra):
    return {
        "job_id": "j", "prompts": [[1, 2, 3]], "max_new_tokens": 6,
        "continuous": True, "seed": 1, "peer": "p0", "rid": "r0",
        "stream": "s0", "trace": tid, **extra,
    }


def test_only_a_streams_first_send_token_carries_a_stamp(tiny_engine):
    """One stamp a stream, none a token: the first frame holds the moment
    the engine handed the first token on (where ``first_token`` ends),
    and the engine leaves nothing behind on its thread."""
    ce = _cont(tiny_engine, trace_site="wS")
    w, rt, sent = _fake_worker(ce)
    tid = mint_trace_id()
    w._generate(_frame(tid, stamp=stamp()))
    ce.run_until_idle()
    toks = [p for p in sent if p["tokens"]]
    assert len(toks) == 6
    assert "stamp" in toks[0] and not any("stamp" in p for p in toks[1:])
    first = {s["name"]: s for s in get_tracer().collect(tid)}["first_token"]
    st = toks[0]["stamp"]
    assert st["host"] == HOST and st["parent"] == first["sid"]
    assert st["t"] == pytest.approx(
        first["t0"] + first["dur_ms"] / 1e3, abs=1e-6)
    assert first_token_stamp.get() is None
    ce.close()


def test_an_untraced_stream_is_stamped_and_recorded_nowhere(tiny_engine):
    """No trace id: the frame carries no stamp, no message does, nothing
    is stored, and ``NetBridge.post_work`` leaves the item as it came."""
    from tensorlink_tpu.nodes.ipc import BridgeQueues, NetBridge

    ce = _cont(tiny_engine)
    w, rt, sent = _fake_worker(ce)
    n_before = int(get_tracer().new_sid().split(":")[1])
    frame = _frame("")
    nb = NetBridge(BridgeQueues())
    nb.post_work("generate", frame)
    kind, item = nb.q.work.get(timeout=10)
    assert "stamp_q" not in item and "stamp" not in item
    w._generate(item)
    ce.run_until_idle()
    assert len([p for p in sent if p["tokens"]]) == 6
    assert not any("stamp" in p for p in sent)
    assert "_way_in" not in item
    # no span id was minted on the way: the next one follows the last
    assert int(get_tracer().new_sid().split(":")[1]) == n_before + 1
    ce.close()


def test_a_frame_from_a_peer_without_the_new_keys_is_served_as_before(
        tiny_engine):
    """An old validator sends ``trace`` and no ``stamp``: the request is
    served, the engine's spans are there, and the way in has none."""
    ce = _cont(tiny_engine, trace_site="wO")
    w, rt, sent = _fake_worker(ce)
    tid = mint_trace_id()
    w._generate(_frame(tid))
    ce.run_until_idle()
    assert len([p for p in sent if p["tokens"]]) == 6
    names = {s["name"] for s in get_tracer().collect(tid)}
    assert {"queue_wait", "prefill", "first_token"} <= names
    assert not names & {"hop_in", "work_wait", "submit"}
    ce.close()


def test_the_work_queue_stamps_a_traced_frame_and_the_worker_reads_it(
        tiny_engine):
    """``hop_in`` ends and ``work_wait`` starts where ``post_work`` puts
    the item on the queue; ``submit`` runs from the handler's start to
    the engine's own stamp, where ``first_token`` starts."""
    import time

    from tensorlink_tpu.nodes.ipc import BridgeQueues, NetBridge

    ce = _cont(tiny_engine, trace_site="wQ")
    w, rt, sent = _fake_worker(ce)
    tid = mint_trace_id()
    nb = NetBridge(BridgeQueues())
    nb.post_work("generate", _frame(tid, stamp={**stamp(), "parent": "v:7"}))
    time.sleep(0.05)  # the loop was busy: the item waits in the queue
    kind, item = nb.q.work.get(timeout=10)
    assert item["stamp_q"]["host"] == HOST
    w._generate(item)
    ce.run_until_idle()
    by = {s["name"]: s for s in get_tracer().collect(tid)}
    hop, wait, sub, first = (
        by[n] for n in ("hop_in", "work_wait", "submit", "first_token"))
    assert hop["parent"] == "v:7" and wait["parent"] == hop["sid"]
    assert sub["parent"] == wait["sid"] and first["parent"] == sub["sid"]
    assert wait["dur_ms"] >= 50.0 and hop["dur_ms"] < 50.0
    end = lambda sp: sp["t0"] + sp["dur_ms"] / 1e3  # noqa: E731
    assert end(hop) == pytest.approx(wait["t0"], abs=1e-6)
    assert end(wait) == pytest.approx(sub["t0"], abs=1e-6)
    assert end(sub) == pytest.approx(first["t0"], abs=1e-6)
    assert "slot_engine_built" not in sub  # the engine was there
    assert wait["chunk"] == ce.recorder.records()[0]["step"] - 1
    ce.close()


# ---------------------------------------------------------------------------
# the request path through a validator + worker cluster (the API's
# handler, the validator, the bridge, TCP, the worker's work queue, the
# engine, and back)
# ---------------------------------------------------------------------------

PATH_MODEL = "tiny-path"


@pytest.fixture(scope="module")
def path_cluster(tmp_path_factory):
    import time

    from test_api_e2e import _req, tiny_cfg_json

    from tensorlink_tpu.core.config import ValidatorConfig, WorkerConfig
    from tensorlink_tpu.nodes.runners import ValidatorNode, WorkerNode

    tmp = tmp_path_factory.mktemp("path_cluster")
    common = dict(
        local_test=True, key_dir=str(tmp / "keys"),
        log_dir=str(tmp / "logs"), env_file=str(tmp / ".env"),
    )
    validator = ValidatorNode(
        ValidatorConfig(endpoint=True, endpoint_port=0, **common)
    ).start()
    worker = WorkerNode(
        WorkerConfig(seed_validators=[["127.0.0.1", validator.port]], **common)
    ).start()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline and not validator.status()["peers"]:
        time.sleep(0.2)
    status, body = _req(
        validator.api, "POST", "/request-model",
        {"hf_name": PATH_MODEL, "config": tiny_cfg_json(), "seq_len": 256},
    )
    assert status == 200 and body["status"] == "ready", body
    # the first request builds the slot engine and its programs
    _stream(validator.api, mint_trace_id(), "warm the engine up")
    validator.test_worker = worker
    yield validator
    worker.stop()
    validator.stop()


def _stream(api, rid, message, new_tokens=6, stream=True):
    """POST /v1/generate under ``X-Request-Id: rid``; the raw reply."""
    import json
    import socket

    payload = json.dumps({
        "hf_name": PATH_MODEL, "message": message, "stream": stream,
        "max_new_tokens": new_tokens, "do_sample": False,
    }).encode()
    s = socket.create_connection(("127.0.0.1", api.port), timeout=200)
    s.sendall(
        f"POST /v1/generate HTTP/1.1\r\nHost: x\r\nX-Request-Id: {rid}\r\n"
        "Content-Type: application/json\r\n"
        f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
    )
    buf = b""
    while True:
        chunk = s.recv(65536)
        if not chunk:
            break
        buf += chunk
    s.close()
    return buf


def _trace_of(api, rid):
    from test_api_e2e import _req

    status, body = _req(api, "GET", f"/trace/{rid}")
    assert status == 200, body
    return body["spans"]


WAY = ("api_in", "prepare", "hop_in", "work_wait", "submit", "first_token",
       "token_out")


@pytest.mark.e2e
def test_a_streamed_request_leaves_a_span_at_every_boundary(path_cluster):
    rid = mint_trace_id()
    assert b"[DONE]" in _stream(path_cluster.api, rid, "hello there")
    spans = _trace_of(path_cluster.api, rid)
    by = {}
    for sp in spans:
        by.setdefault(sp["name"], []).append(sp)
    assert set(PATH_SPANS) <= set(by), set(PATH_SPANS) - set(by)
    for name in PATH_SPANS:
        for sp in by[name]:
            assert isinstance(sp["t0"], float) and sp["host"] == HOST, sp
            assert "dur_ms" in sp and isinstance(sp["parent"], str), sp
    # which span caused which: one chain from the handler to the delta
    one = {n: by[n][0] for n in PATH_SPANS}
    chain = ("http_first_byte",) + WAY
    for cause, name in zip(chain, chain[1:]):
        if name == "token_out":
            cause = "first_token"
        assert one[name]["parent"] == one[cause]["sid"], (name, cause)
    assert one["http_first_byte"]["parent"] == ""
    for name, cause in (("queue_wait", "first_token"),
                        ("admission", "queue_wait"),
                        ("prefill", "first_token"),
                        ("prefill_chunk", "prefill"),
                        ("first_decode", "first_token")):
        assert one[name]["parent"] == one[cause]["sid"], (name, cause)
    assert one["prepare"]["prompt_tokens"] > 0
    assert one["api_in"]["site"] == one["token_out"]["site"] == "api"
    assert one["hop_in"]["site"] == one["first_token"]["site"] != "api"
    assert isinstance(one["work_wait"]["chunk"], int)


@pytest.mark.e2e
def test_the_way_to_the_first_byte_lies_end_to_end_and_adds_up(path_cluster):
    """Laid end to end by ``t0`` the seven spans of the way do not
    overlap, start where ``http_first_byte`` starts, end where it ends,
    and leave less than 5 ms of it unnamed."""
    for attempt in range(3):  # a starved thread is not a hole in the way
        rid = mint_trace_id()
        _stream(path_cluster.api, rid, f"how long is the way {attempt}")
        by = {sp["name"]: sp for sp in _trace_of(path_cluster.api, rid)}
        whole = by["http_first_byte"]
        way = [by[n] for n in WAY]
        if whole["dur_ms"] - sum(sp["dur_ms"] for sp in way) < 5.0:
            break
    assert [sp["name"] for sp in sorted(way, key=lambda sp: sp["t0"])] \
        == list(WAY)
    end = lambda sp: sp["t0"] + sp["dur_ms"] / 1e3  # noqa: E731
    for a, b in zip(way, way[1:]):
        assert end(a) <= b["t0"] + 1e-6, (a["name"], b["name"])
    assert way[0]["t0"] == whole["t0"]
    assert end(way[-1]) == pytest.approx(end(whole), abs=1e-6)
    named = sum(sp["dur_ms"] for sp in way)
    assert 0.0 <= whole["dur_ms"] - named < 5.0, (whole["dur_ms"], named)
    # the engine's own three still add up to its first_token
    parts = sum(by[n]["dur_ms"]
                for n in ("queue_wait", "prefill", "first_decode"))
    assert parts == pytest.approx(by["first_token"]["dur_ms"], abs=0.1)


@pytest.mark.e2e
def test_the_trace_endpoint_gives_spans_in_start_order(path_cluster):
    rid = mint_trace_id()
    _stream(path_cluster.api, rid, "in what order")
    spans = _trace_of(path_cluster.api, rid)
    assert all(sp["host"] == HOST for sp in spans)
    starts = [sp["t0"] for sp in spans]
    assert starts == sorted(starts)
    assert spans[0]["name"] == "http_first_byte"  # it holds the others
    assert [sp["name"] for sp in spans[1:4]] == ["api_in", "prepare", "hop_in"]
    sids = {sp["sid"] for sp in spans}
    for sp in spans:
        if sp["name"] in PATH_SPANS and sp["name"] != "http_first_byte":
            assert sp["parent"] in sids, sp


@pytest.mark.e2e
def test_an_unstreamed_request_has_the_way_in_and_no_way_out(path_cluster):
    rid = mint_trace_id()
    reply = _stream(path_cluster.api, rid, "all at once", stream=False)
    assert b"200 OK" in reply
    by = {sp["name"]: sp for sp in _trace_of(path_cluster.api, rid)}
    for name in ("api_in", "prepare", "hop_in", "work_wait", "submit",
                 "first_token"):
        assert name in by and "dur_ms" in by[name], name
    assert by["api_in"]["parent"] == ""
    assert by["prepare"]["parent"] == by["api_in"]["sid"]
    assert "token_out" not in by and "http_first_byte" not in by


@pytest.mark.e2e
def test_a_request_behind_a_running_chunk_waits_for_it_and_names_it(
        path_cluster):
    """A GENERATE that arrives while a chunk's host phases run waits in
    ``work_wait``, outside the engine's ``queue_wait``, for what is left
    of them: the chunk's own wait takes it in (the worker's intake) and
    prepares its admission, and the span names that chunk's record. One
    that arrives when the wait has begun is taken there at once. One
    that arrives too late for the intake is taken when ``step_chunk`` has
    returned, and names the chunk it waited out."""
    import threading
    import time

    worker = path_cluster.test_worker
    (rt,) = [r for r in worker.executor.jobs.values() if r.cont is not None]
    cont = rt.cont
    pack = cont._pack_ragged
    # the cluster's warm request is answered before its step_chunk
    # returns, and where this test is the cluster's first the loop then
    # joins the narrow program's build (seconds on a loaded CPU): both
    # requests below would wait behind that and not behind a chunk. One
    # more request is taken only once the join is through
    _stream(path_cluster.api, mint_trace_id(), "past the build")

    def slow_pack():
        time.sleep(0.25)  # inside the chunk, in its pack phase
        return pack()

    cont._pack_ragged = slow_pack
    try:
        long_rid, rid = mint_trace_id(), mint_trace_id()
        first = threading.Thread(
            target=_stream, args=(path_cluster.api, long_rid, "go on"),
            kwargs={"new_tokens": 40})
        first.start()
        time.sleep(0.6)  # chunks of 0.25 s and more follow one another
        _stream(path_cluster.api, rid, "behind a chunk")
        first.join(timeout=120)
    finally:
        cont._pack_ragged = pack
    by = {sp["name"]: sp for sp in _trace_of(path_cluster.api, rid)}
    wait = by["work_wait"]
    rec = next(r for r in cont.recorder.records() if r["step"] == wait["chunk"])
    rec_end = rec["t0"] + sum(
        rec[f"{p}_ms"] for p in CHUNK_PHASES) / 1e3
    wait_end = wait["t0"] + wait["dur_ms"] / 1e3
    until_wait = rec["t0"] + sum(
        rec[f"{p}_ms"] for p in ("admit", "pack", "dispatch")) / 1e3
    if wait_end < rec_end and wait["t0"] < until_wait:
        # taken in by that chunk's wait: it was put on the queue during
        # the chunk's pack (a quarter of a second) and waited that out
        assert until_wait - 1e-3 <= wait_end
        left_ms = (until_wait - max(wait["t0"], rec["t0"])) * 1e3
        assert wait["dur_ms"] >= left_ms - 1.0 and left_ms > 0.0
        assert rec["intake_ms"] > 0.0
        assert by["admission"]["ahead"] is True
    elif wait_end < rec_end:
        # ... or it came when that chunk's wait had begun (on a loaded CPU
        # the step outlasts the pack): the intake took it where it arrived,
        # inside the wait, behind no pack and at most a stream stage
        in_wait_end = until_wait + (rec["wait_ms"] - rec["fetch_ms"]) / 1e3
        assert wait_end <= in_wait_end + 1e-3  # before the fetch
        assert wait["dur_ms"] < 250.0
        assert rec["intake_ms"] > 0.0
        assert by["admission"]["ahead"] is True
    else:
        # it came too late for the intake: put on the queue before that
        # chunk ended, and taken after
        assert wait["t0"] < rec_end <= wait_end + 1e-3
        left_ms = (rec_end - max(wait["t0"], rec["t0"])) * 1e3
        assert wait["dur_ms"] >= left_ms - 1.0 and left_ms > 0.0
        assert "ahead" not in by["admission"]
    # the wait the engine cannot see: queue_wait starts after it
    assert by["queue_wait"]["t0"] >= wait_end - 1e-6
    assert by["queue_wait"]["dur_ms"] < wait["dur_ms"] + 250.0
