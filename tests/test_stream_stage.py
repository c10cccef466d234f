"""The stream stage of the slot engine (docs/SERVING.md "Observability").

``step_chunk`` settles a chunk's tokens after the drain and hands them to
the stream callbacks behind the NEXT chunk's dispatch, while the device
runs it. Pinned here:

- order: chunk n's callbacks fire after chunk n + 1's dispatch and before
  its sync; the last chunk's fire with no further ``step_chunk`` call;
- at ``on_finish``, ``req.tokens`` is exactly the sequence ``stream_cb``
  was given, under EOS, budget and a stop at token i; ``on_finish`` runs
  after the last token; ``first_token`` is stamped when the token leaves;
- whatever answers for a request streams its pending tokens first:
  ``close``, preemption, ``begin_drain``, the migration freeze / export,
  a shed slot, a handoff commit, the worker's re-attach rebinding;
- a stop met with no step in flight frees the slot at once; a callback
  that raises loses its own stream and no other;
- the eight phases still add up, and ``stream_ms`` lies inside ``wait``
  (a step in flight) or ``deliver`` (none).
"""

import queue
import types

import jax
import jax.numpy as jnp
import pytest

from tensorlink_tpu.core.trace import mint_trace_id
from tensorlink_tpu.engine.continuous import CHUNK_PHASES, ContinuousEngine
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.models import ModelConfig, init_params

PROMPT, SEED = [3, 1, 4], 7


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


@pytest.fixture(scope="module")
def solo(tiny_engine):
    """The stream ``PROMPT`` / ``SEED`` makes alone: 16 greedy tokens."""
    ce = _cont(tiny_engine)
    req = ce.submit(PROMPT, max_new_tokens=16, seed=SEED)
    ce.run_until_idle()
    ce.close()
    return list(req.tokens)


def _streamed(ce, **kw):
    """A request whose callbacks record what they are given."""
    seen: list[int] = []
    at_finish: list = []
    req = ce.submit(
        PROMPT, seed=SEED,
        stream_cb=lambda t: seen.append(t) and False,
        on_finish=lambda r: at_finish.append((list(seen), list(r.tokens))),
        **kw,
    )
    return req, seen, at_finish


def _step_to_pending(ce, req, seen):
    """Chunks until ``req`` holds settled tokens its callback has not seen."""
    for _ in range(8):
        ce.step_chunk()
        if len(req.tokens) > len(seen):
            return
    raise AssertionError("nothing was left pending")


# -- (a) order --------------------------------------------------------------
def test_a_chunks_tokens_leave_behind_the_next_dispatch(tiny_engine, solo):
    ce = _cont(tiny_engine, max_slots=2)
    log: list = []
    ops, settle = ce._step_operands, ce._settle
    ce._step_operands = lambda *a, **k: (log.append("dispatch"), ops(*a, **k))[1]
    ce._settle = lambda *a, **k: (log.append("sync"), settle(*a, **k))[1]
    req = ce.submit(
        PROMPT, max_new_tokens=10, seed=SEED,
        stream_cb=lambda t: log.append(t) and False,
        on_finish=lambda r: log.append("finish"),
    )
    assert ce.step_chunk() and ce.step_chunk()
    assert not ce.step_chunk()  # and no call after it
    a, b, c = solo[:4], solo[4:8], solo[8:10]
    assert log == ["dispatch", "sync", "dispatch", *a, "sync",
                   "dispatch", *b, "sync", *c, "finish"]
    assert req.tokens == solo[:10]
    assert ce.stats["stream_tokens_overlapped"] == 8
    assert ce.stats["stream_tokens_flushed"] == 2
    ce.close()


def test_nothing_dispatched_streams_at_once(tiny_engine):
    """A call that dispatches nothing holds nothing back."""
    ce = _cont(tiny_engine, max_slots=1)
    req, seen, _ = _streamed(ce, max_new_tokens=16)
    ce.submit([9], max_new_tokens=2, seed=1)  # keeps the engine in work
    _step_to_pending(ce, req, seen)
    ce.step_chunk(admit_only=True)
    assert seen == req.tokens
    ce.close()


# -- (b) what on_finish sees ------------------------------------------------
@pytest.mark.parametrize("how", ["eos", "budget", "stop@1", "stop@4", "stop@6"])
def test_tokens_at_finish_are_the_streamed_sequence(tiny_engine, solo, how):
    ce = _cont(tiny_engine)
    tid = mint_trace_id()
    names = lambda: [s["name"] for s in ce.tracer.collect(tid)]
    kw = {"max_new_tokens": 12, "trace_id": tid}
    stop_at = 0
    if how == "eos":
        k = next(i for i in range(4, 12) if solo[i] not in solo[:i])
        kw["eos_ids"], expected = [solo[k]], solo[:k + 1]
    elif how == "budget":
        kw["max_new_tokens"], expected = 6, solo[:6]
    else:
        stop_at = int(how[5:])
        expected = solo[:stop_at]
    log: list = []

    def stream_cb(tok):
        if not log:
            log.append(names())  # the spans when the first token leaves
        log.append(tok)
        return len(log) - 1 == stop_at

    req = ce.submit(
        PROMPT, seed=SEED, stream_cb=stream_cb,
        on_finish=lambda r: log.append(("finish", list(r.tokens))), **kw,
    )
    ce.step_chunk()
    assert len(req.tokens) == 4 and not log  # settled, not yet streamed
    assert "first_token" not in names()
    ce.run_until_idle()
    at_first, *toks, (mark, at_finish) = log
    assert "first_token" in at_first and "first_decode" in at_first
    assert mark == "finish" and toks == expected  # on_finish after the last
    assert at_finish == expected == req.tokens and req.finished
    assert (ce.stats["stream_tokens_overlapped"]
            + ce.stats["stream_tokens_flushed"]) == len(expected)
    # a stopped stream ran one chunk more on the device and its slot is free
    assert ce.live_slots == 0 and not ce._unstreamed
    ce.close()  # checks page conservation


def test_a_stop_with_no_step_in_flight_frees_the_slot_at_once(tiny_engine):
    ce = _cont(tiny_engine, max_slots=2)
    seen: list[int] = []
    done: list = []
    req = ce.submit(
        PROMPT, max_new_tokens=16, seed=SEED,
        stream_cb=lambda t: seen.append(t) or len(seen) == 2,
        on_finish=lambda r: done.append(list(r.tokens)),
    )
    ce.step_chunk()
    assert len(req.tokens) == 4 and not seen
    ce.begin_drain()  # flushes: the stop arrives with nothing dispatched
    assert done == [seen] and len(seen) == 2 and req.tokens == seen
    assert req.finished and ce.live_manifest() == [] and not ce.has_work()
    ce.close()


def test_a_callback_that_raises_loses_no_other_stream(tiny_engine, solo):
    """The request that finished in the same chunk is out of its slot: the
    stream stage still answers for it when another's callback raises."""
    ce = _cont(tiny_engine, max_slots=2)

    def bad(tok):
        raise RuntimeError("relay gone")

    first, seen, at_finish = _streamed(ce, max_new_tokens=4)
    second = ce.submit([5, 6], max_new_tokens=16, seed=1, stream_cb=bad)
    third, seen3, at_finish3 = _streamed(ce, max_new_tokens=3)  # queued
    ce.step_chunk()
    assert len(first.tokens) == 4 and first.slot >= 0 and not first.done.is_set()
    with pytest.raises(RuntimeError, match="relay gone"):
        ce.step_chunk()  # first streamed and finished, then second raised
    assert at_finish == [(solo[:4], solo[:4])]
    ce.close(RuntimeError("engine failed"))
    assert second.done.is_set() and second.error is not None
    assert third.done.is_set()
    assert at_finish3 and at_finish3[0][0] == at_finish3[0][1]


# -- (c) whoever answers for a request streams its tokens first ---------------
def _fake_worker():
    from tensorlink_tpu.ml.worker import DistributedWorker

    sent: dict = {}

    class _Bridge:
        q = types.SimpleNamespace(work=queue.Queue())

        def notify(self, verb, p):
            if verb == "send_token":
                sent.setdefault(p["stream"], []).extend(
                    t for _row, t in p["tokens"])

        def request(self, verb, p, timeout=None):
            return [] if verb == "poll_cancel" else True

    w = DistributedWorker.__new__(DistributedWorker)
    w.bridge = _Bridge()
    w.node = types.SimpleNamespace(
        node_id="f" * 64,
        config=types.SimpleNamespace(ml=types.SimpleNamespace()),
    )
    import logging

    w.log = logging.getLogger("test.stream_stage")
    rt = types.SimpleNamespace(
        job_id="j", jstreams={}, orphans={}, cont_scheduled=False)
    return w, rt, sent


@pytest.mark.parametrize("entry", [
    "close", "preempt", "begin_drain", "freeze_export", "shed_slot",
    "commit_handoff", "reattach",
])
def test_pending_tokens_leave_before_anything_answers(tiny_engine, solo, entry):
    ce = _cont(tiny_engine, max_slots=2)
    if entry == "reattach":
        w, rt, sent = _fake_worker()
        cb, fin = w._cont_channels(
            rt, ce, peer="p0", rid="r0", stream_id="s0", tid="", jrid="J")
        req = ce.submit(PROMPT, max_new_tokens=16, seed=SEED,
                        stream_cb=cb, on_finish=fin)
        rt.jstreams["J"] = req
        seen = sent.setdefault("s0", [])
    else:
        req, seen, at_finish = _streamed(ce, max_new_tokens=16)
    _step_to_pending(ce, req, seen)
    n_before = len(seen)
    slot = req.slot
    if entry == "close":
        ce.close(RuntimeError("unhosted"))
        assert at_finish == [(req.tokens, req.tokens)]
        assert req.error is not None and len(seen) > n_before
        return
    if entry == "preempt":
        ce._preempt(slot)
        assert req.slot == -1 and not req.finished
    elif entry == "begin_drain":
        ce.begin_drain()
        assert [r for _k, _s, r in ce.live_manifest()] == [req]
        ce.end_drain()
    elif entry == "freeze_export":
        ce.freeze_slot(slot)
        assert seen == req.tokens
        blob = ce.export_slot(slot)
        assert list(blob["chain"]) == PROMPT + seen
        ce.abort_migration(slot)
    elif entry == "shed_slot":
        assert ce.shed_slot(slot) is req
    elif entry == "commit_handoff":
        # a handoff freezes before the first draw, so none is pending by
        # construction; held all the same: the commit redirects the stream
        ce._active[slot] = False
        ce._frozen.add(slot)
        assert ce.commit_handoff(slot, fell_back=True) is req
    elif entry == "reattach":
        hwm = 2  # the new client holds two tokens
        assert w._reattach_continuous(
            rt, ce, {"peer": "p1", "rid": "r1", "stream": "s1", "hwm": hwm},
            "J")
        assert sent["s1"] == req.tokens[hwm:]  # the backlog, once
    assert seen == req.tokens and len(seen) > n_before
    if entry in ("shed_slot", "commit_handoff"):
        ce.close()  # the stream went elsewhere; conservation holds here
        return
    ce.run_until_idle()
    assert req.finished and req.tokens == solo
    if entry == "reattach":
        assert sent["s0"] + sent["s1"][len(sent["s0"]) - hwm:] == solo
        assert sent["s1"] == solo[hwm:]  # no token twice on the new relay
    else:
        assert seen == solo
    ce.close()


# -- (d) the phases -----------------------------------------------------------
def test_phases_add_up_and_the_stream_lies_inside_its_phase(tiny_engine):
    ce = _cont(tiny_engine, chunk_steps=2)
    for seed in (1, 2, 3):
        ce.submit([seed, 2, 3], max_new_tokens=6 + 2 * seed, seed=seed,
                  stream_cb=lambda t: False)
    import time

    t_in = time.monotonic()
    ce.run_until_idle()
    t_out = time.monotonic()
    recs = ce.recorder.records()
    assert len(recs) >= 4
    for a, b in zip(recs, recs[1:]):
        wall_ms = (b["t0"] - a["t0"]) * 1e3
        parts = sum(a[f"{p}_ms"] for p in CHUNK_PHASES) + b["between_ms"]
        assert parts == pytest.approx(wall_ms, abs=1.0)
    for r in recs[1:-1]:
        # more work followed: all this call streamed left under its wait
        assert 0.0 < r["stream_ms"] <= r["wait_ms"] + 1e-3
    last = recs[-1]
    assert last["stream_ms"] <= last["wait_ms"] + last["deliver_ms"] + 1e-3
    assert last["stream_ms"] > 0.0
    assert recs[0]["stream_ms"] == 0.0  # nothing was pending yet
    total = sum(r[f"{p}_ms"] for r in recs for p in ("between",) + CHUNK_PHASES)
    assert total <= (t_out - t_in) * 1e3 + 1.0
    s = ce.stats
    assert s["chunk_us_stream"] == pytest.approx(
        sum(r["stream_ms"] for r in recs) * 1e3, abs=len(recs))
    assert s["chunk_us_stream"] <= s["chunk_us_wait"] + s["chunk_us_deliver"]
    assert s["stream_tokens_overlapped"] + s["stream_tokens_flushed"] == 30
    assert s["stream_tokens_flushed"] <= 2  # the last chunk's alone
    ce.close()
