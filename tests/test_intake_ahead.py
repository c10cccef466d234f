"""Intake ahead (docs/SERVING.md "The anatomy of a chunk"): while the
device runs a chunk, ``step_chunk``'s wait takes in the requests that
arrive (the worker's GENERATE frames, through ``ContinuousEngine.intake``)
and an ahead round prepares their admission into free slots; the next
``_admit`` commits it. Pinned here:

- (a) streams are bit for bit what an engine with no intake makes of the
  same arrival order: a dense model with a copy-on-write hit, a stateful
  patterned model (LFM2's tails), a sampled request beside greedy ones;
- (b) a frame that arrives during a chunk is admitted ahead and rides the
  NEXT chunk; one that arrives when the device is done rides the same
  next chunk through the edge; no request joins a later chunk than with
  the intake off;
- (c) the work queue's order is kept item for item: what is not a GENERATE
  of this job's slot path ends the intake and is handled first when the
  chunk returns, and ``cont_continue`` is never queued while a chunk runs;
  a frame the intake's test cannot read is such an item and fails alone;
- (d) an ahead round never preempts, never takes a slot that holds a
  request or a prepared admission, and leaves ``_slots``, ``_prefilling``
  and the benchmark's ``taps._contexts`` as they were until the next
  ``_admit``; with no slot or no pages free the edge admits, and so it
  does where the chunk in flight may lengthen the request's hit; two
  classes into one free slot cost one preemption, the placement is kept;
- (e) ``close``, a drain and a failed chunk with a prepared, uncommitted
  admission return every page and reference;
- (f) the wait returns at once when the result is ready and nothing
  arrives: no sleep, no timeout under a second;
- (g) the counters and the span attribute equal counts made by hand;
- (i) the inside of the wait: the driver asks the step's result whether it
  is ready after each stream callback's entry and each take, stamps the
  first True and asks no more; what of the stream stage and of the intake
  lay behind the stamp is the record's ``late_stream_ms`` /
  ``late_intake_ms`` (0 when the result was not ready before the fetch,
  or has no ``is_ready``), ``late_max_ms`` is that with the entry or take
  whose end first saw it, and ``fetch_ms`` is what the fetch blocked.
"""

from __future__ import annotations

import functools
import logging
import queue
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorlink_tpu.core.trace import get_tracer, mint_trace_id
from tensorlink_tpu.engine.continuous import ContinuousEngine
from tensorlink_tpu.engine.generate import GenerationEngine
from tensorlink_tpu.engine.sampling import SamplingParams
from tensorlink_tpu.models import ModelConfig, init_params
from tensorlink_tpu.models.registry import config_from_hf
from tensorlink_tpu.nodes.ipc import CHUNK_DONE, MLBridge
from tensorlink_tpu.p2p import protocol as proto


@pytest.fixture(scope="module")
def tiny_engine():
    cfg = ModelConfig(
        family="llama", vocab_size=128, d_model=32, n_layers=2, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, max_seq_len=96,
        dtype=jnp.float32, tie_embeddings=False,
    )
    params = init_params(cfg, jax.random.PRNGKey(0))
    return GenerationEngine(
        cfg, params, seq_buckets=(8, 32), batch_buckets=(1,), max_seq_len=96
    )


def _cont(eng, **kw):
    kw = dict(max_slots=4, page_size=8, chunk_steps=4, prefill_chunk=16) | kw
    return ContinuousEngine(eng, **kw)


class _Script:
    """An intake that hands over, in the wait of the k-th dispatched chunk
    (from 1), the submissions scripted for it."""

    def __init__(self, plan: dict):
        self.plan = plan
        self.chunk = 0

    def __call__(self, result):
        self.chunk += 1
        yield from self.plan.get(self.chunk, ())


def _run(make, plan: dict, *, ahead: bool, first=()):
    """Drive an engine over scripted arrivals: ``first`` before the first
    chunk, ``plan[k]`` during chunk k. With ``ahead`` the engine's intake
    takes them in inside chunk k's wait; without, they are submitted when
    chunk k has returned, as a worker with no intake handles a frame that
    arrived while the chunk ran. Each entry is ``name -> submit kwargs``;
    returns the engine, the requests by name and, a dispatched chunk,
    the names its block carried."""
    ce = make()
    reqs: dict = {}
    rode: list = []

    def sub(name, kw):
        reqs[name] = ce.submit(**kw)

    subs = {k: [functools.partial(sub, n, kw) for n, kw in v.items()]
            for k, v in plan.items()}
    pack = ce._pack_ragged

    def packed():
        out = pack()
        if out is not None:
            names = {r.rid: n for n, r in reqs.items()}
            rode.append(sorted(
                names[r.rid] for s, r in enumerate(ce._slots)
                if r is not None and out[2][s] > 0))
        return out

    ce._pack_ragged = packed
    if ahead:
        ce.intake = _Script(subs)
    for n, kw in dict(first).items():
        sub(n, kw)
    k = 0
    while True:
        more = ce.step_chunk()
        k += 1
        if not ahead:
            for s in subs.get(k, ()):
                s()
            more = more or ce.has_work()
        if not more and k >= max(plan, default=0):
            break
    ce.flush_stream()
    return ce, reqs, rode


def _both(make, plan, first=()):
    off = _run(make, plan, ahead=False, first=first)
    on = _run(make, plan, ahead=True, first=first)
    return off, on


def _same_streams(off, on):
    (_, r0, rode0), (ce1, r1, rode1) = off, on
    assert {n: list(r.tokens) for n, r in r1.items()} == {
        n: list(r.tokens) for n, r in r0.items()}
    assert all(r.finished and r.error is None for r in r1.values())
    # request for request the chunks it rode: none joins a later one
    assert rode1 == rode0
    ce1.check_page_conservation()


# -- (a) the same streams ----------------------------------------------------
def test_dense_streams_with_a_copy_on_write_hit_are_the_same(tiny_engine):
    """A warm prompt fills pages of the trie; arrivals during later chunks
    share whole pages with it and part of the next (a copy-on-write page
    made behind the step in flight), one samples."""
    rng = np.random.default_rng(3)
    doc = [int(t) for t in rng.integers(1, 128, 30)]
    first = {"warm": dict(prompt=doc, max_new_tokens=3, seed=1)}
    first["long"] = dict(prompt=[2, 3, 5, 7], max_new_tokens=40, seed=6)
    # the warm prompt retires with chunk 2: its pages are the trie's from
    # that chunk's settle on, where the edge and an ahead round both look
    plan = {
        3: {"cow": dict(prompt=doc[:20] + [5, 6, 7], max_new_tokens=9, seed=2),
            "cold": dict(prompt=[9, 8, 7, 6], max_new_tokens=7, seed=3)},
        4: {"sampled": dict(
            prompt=doc[:16] + [11], max_new_tokens=8, seed=4,
            sampling=SamplingParams.make(temperature=0.9, top_k=20))},
        6: {"late": dict(prompt=doc[:27], max_new_tokens=5, seed=5)},
    }
    off, on = _both(lambda: _cont(tiny_engine), plan, first)
    _same_streams(off, on)
    ce = on[0]
    assert ce.prefix.stats["cow_copies"] == off[0].prefix.stats["cow_copies"]
    assert ce.prefix.stats["cow_copies"] >= 1
    assert ce.stats["admitted_ahead"] == 4
    assert ce.stats["admitted"] == off[0].stats["admitted"] == 6
    assert off[0].stats["admitted_ahead"] == 0
    for c in (off[0], ce):
        c.close()


def test_a_stateful_models_streams_are_the_same():
    """LFM2's tails: an admission under a hit restores a tail snapshot
    with a device call of its own, issued behind the step in flight."""
    from test_laguna import _engine
    from test_lfm2 import TINY

    cfg = config_from_hf(TINY, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    doc = [int(t) for t in rng.integers(0, 97, 100)]
    first = {"doc": dict(prompt=doc, max_new_tokens=2),
             "long": dict(prompt=[7, 7, 7], max_new_tokens=90)}
    # the document retires with chunk 13 and is the trie's from there on
    plan = {
        15: {"t1": dict(prompt=doc + [5, 6, 7], max_new_tokens=8)},
        # t1 is resident and may leave a snapshot further up the document
        # than the trie's: t2 is the edge's (``_hit_may_grow``)
        16: {"cold": dict(prompt=[1, 2, 3], max_new_tokens=6),
             "t2": dict(prompt=doc + [5, 6, 9, 11], max_new_tokens=8)},
        # ... and with both retired t3 is prepared ahead again
        23: {"t3": dict(prompt=doc + [8, 8], max_new_tokens=4)},
    }
    off, on = _both(lambda: _engine(cfg, params, max_slots=4), plan, first)
    _same_streams(off, on)
    ce = on[0]
    assert ce.stats["conv_snapshots_restored"] == (
        off[0].stats["conv_snapshots_restored"]) >= 2
    assert ce.stats["admitted_ahead"] == 3
    for c in (off[0], ce):
        c.close()


# -- (b) the chunk a request joins -------------------------------------------
def test_an_arrival_during_a_chunk_rides_the_next_one(tiny_engine):
    plan = {2: {"b": dict(prompt=[4, 5, 6], max_new_tokens=6, seed=2)}}
    first = {"a": dict(prompt=[1, 2, 3], max_new_tokens=24, seed=1)}
    off, on = _both(lambda: _cont(tiny_engine), plan, first)
    _same_streams(off, on)
    rode = on[2]
    assert rode[0] == ["a"] and rode[1] == ["a"]  # chunk 2 was packed before
    assert rode[2] == ["a", "b"]  # ... and chunk 3 carries it
    assert on[0].stats["admitted_ahead"] == 1
    assert on[0].stats["submitted_ahead"] == 1
    assert on[0].stats["submitted"] == 2
    for c in (off[0], on[0]):
        c.close()


# -- the worker's side --------------------------------------------------------
class _Work(queue.Queue):
    """The work queue, with every ``get``'s timeout and every ``put`` kept."""

    def __init__(self):
        super().__init__()
        self.timeouts: list = []
        self.puts: list = []

    def get(self, block=True, timeout=None):
        self.timeouts.append(timeout)
        return super().get(block, timeout)

    def put(self, item, block=True, timeout=None):
        self.puts.append(item)
        super().put(item, block, timeout)


class _Bridge(MLBridge):
    """The real bridge over in-process queues; commands are recorded."""

    def __init__(self):
        super().__init__(types.SimpleNamespace(
            cmd=queue.Queue(), resp=queue.Queue(), work=_Work()))
        self.sent: list = []

    def notify(self, verb, p=None):
        self.sent.append((verb, p))

    def request(self, verb, p=None, timeout=None):
        self.sent.append((verb, p))
        return [] if verb == "poll_cancel" else True


def _worker(ce):
    """A ``DistributedWorker`` over ``_Bridge`` hosting ``ce`` as job "j",
    its intake bound as ``_ensure_cont`` binds it."""
    from tensorlink_tpu.ml.worker import DistributedWorker

    w = DistributedWorker.__new__(DistributedWorker)
    w.bridge = _Bridge()
    w.node = types.SimpleNamespace(
        node_id="f" * 64,
        config=types.SimpleNamespace(ml=types.SimpleNamespace()),
    )
    w.log = logging.getLogger("test.intake")
    w.draining = None
    w.faults = None
    w._held = None
    w._lock = threading.Lock()
    w._handoff_pools = {}
    w._kv_pools = {}
    rt = types.SimpleNamespace(
        job_id="j", jstreams={}, orphans={}, cont_scheduled=False,
        engine=ce.engine, cont=ce)
    w.jobs = {"j": rt}
    ce.intake = functools.partial(w._intake, rt)
    return w, rt


def _frame(rid, prompt, n=6, **extra):
    return {"job_id": "j", "prompts": [list(prompt)], "max_new_tokens": n,
            "continuous": True, "seed": 1, "peer": "p0", "rid": rid, **extra}


def _answers(w):
    """rid -> the body of its GENERATE_RESP."""
    return {p["rid"]: p["body"] for verb, p in w.bridge.sent
            if verb == "respond" and p["tag"] == proto.GENERATE_RESP}


def _stop_when_idle(w, rt, chunks: list | None = None):
    """Stop the run loop once the engine is out of work; optionally log
    what the work queue held each time a chunk started."""
    step = rt.cont.step_chunk

    def step_chunk(**kw):
        if chunks is not None:
            chunks.append([k for k, _ in list(w.bridge.q.work.queue)])
        more = step(**kw)
        if not more and not w.bridge.q.work.queue:
            w.bridge.q.work.put(("_stop", None))
        return more

    rt.cont.step_chunk = step_chunk


def test_a_frame_behind_the_chunk_is_taken_in_ahead(tiny_engine):
    """The queue holds ``cont_continue`` and, behind it, a second frame:
    the chunk's intake takes it in (``_generate``, the same handler),
    its admission is prepared, and ``cont_continue`` is queued again only
    when the chunk has returned."""
    ce = _cont(tiny_engine)
    w, rt = _worker(ce)
    _stop_when_idle(w, rt)
    w._generate(_frame("r1", [1, 2, 3], 12))  # queues cont_continue
    w.bridge.q.work.put((proto.GENERATE, _frame("r2", [4, 5, 6], 5)))
    w.run()
    got = _answers(w)
    assert set(got) == {"r1", "r2"}
    assert ce.stats["submitted"] == 2 and ce.stats["submitted_ahead"] == 1
    assert ce.stats["admitted_ahead"] == 1
    # one cont_continue at a time, and none while a chunk ran: each but the
    # first was put by _cont_step after its chunk, with rt.cont_scheduled
    # set all through the chunk
    kinds = [k for k, _ in w.bridge.q.work.puts]
    assert CHUNK_DONE in kinds
    assert not rt.cont_scheduled
    ref = _cont(tiny_engine)
    a = ref.submit([1, 2, 3], max_new_tokens=12, seed=1)
    ref.step_chunk()
    b = ref.submit([4, 5, 6], max_new_tokens=5, seed=1)
    ref.run_until_idle()
    assert got["r1"]["sequences"] == [a.tokens]
    assert got["r2"]["sequences"] == [b.tokens]
    ref.close()
    ce.close()
    w.bridge.close()


def test_a_frame_after_the_device_is_done_rides_the_same_chunk(tiny_engine):
    """The frame lands behind ``CHUNK_DONE``: the intake has ended, the
    run loop handles it before the ``cont_continue`` the chunk queued, and
    the edge admits it into the chunk an ahead admission would have
    joined."""
    rode = {}
    for late in (False, True):
        ce = _cont(tiny_engine)
        w, rt = _worker(ce)
        _stop_when_idle(w, rt)
        log: list = []
        pack = ce._pack_ragged

        def packed(ce=ce, pack=pack, log=log):
            out = pack()
            if out is not None:
                log.append(sorted(
                    r.client_meta["rid"] for r in ce._slots if r is not None))
            return out

        ce._pack_ragged = packed
        f2 = (proto.GENERATE, _frame("r2", [4, 5, 6], 5))
        if late:
            work, put = w.bridge.q.work, w.bridge.q.work.put

            def put_then_frame(item, *a, work=work, put=put, f2=f2):
                put(item, *a)
                if item[0] == CHUNK_DONE and f2 not in work.puts:
                    put(f2)  # right behind the first chunk's marker

            work.put = put_then_frame
        w._generate(_frame("r1", [1, 2, 3], 12))
        if not late:
            w.bridge.q.work.put(f2)
        w.run()
        assert set(_answers(w)) == {"r1", "r2"}
        assert ce.stats["admitted_ahead"] == (0 if late else 1)
        rode[late] = log
        ce.close()
        w.bridge.close()
    assert rode[True] == rode[False]
    assert rode[True][0] == ["r1"] and rode[True][1] == ["r1", "r2"]


def test_an_injected_error_before_a_chunk_leaves_the_engine_resumable(
        tiny_engine):
    """docs/FAILURE_MODEL.md's ``error`` op at ``worker.cont_step``: the
    ``cont_continue`` item fails with nobody to answer (it carries no
    rid), and ``cont_scheduled`` must not stay set over it, or no chunk is
    ever queued again. The next request resumes the engine and both
    streams finish, as with no intake."""
    from tensorlink_tpu.core.faults import FaultInjected, FaultPlan

    ce = _cont(tiny_engine)
    w, rt = _worker(ce)
    _stop_when_idle(w, rt)
    w.faults = FaultPlan.from_dict({"rules": [
        {"site": "worker.cont_step", "op": "error", "nth": 2}]})
    inject = w.faults.inject

    def inject_then_frame(site, key=""):
        try:
            return inject(site, key)
        except FaultInjected:
            # the next request comes after the fault
            w.bridge.q.work.put((proto.GENERATE, _frame("r2", [4, 5, 6], 5)))
            raise

    w.faults.inject = inject_then_frame
    w._generate(_frame("r1", [1, 2, 3], 12))
    loop = threading.Thread(target=w.run, daemon=True)
    loop.start()
    loop.join(timeout=60)
    stuck = loop.is_alive()
    if stuck:
        w.bridge.q.work.put(("_stop", None))
        loop.join(timeout=10)
    assert not stuck, "no chunk was queued after the injected error"
    assert w.faults.rules[0].fires == 1 and not rt.cont_scheduled
    got = _answers(w)
    assert set(got) == {"r1", "r2"}
    ref = _cont(tiny_engine)
    a = ref.submit([1, 2, 3], max_new_tokens=12, seed=1)
    ref.step_chunk()
    b = ref.submit([4, 5, 6], max_new_tokens=5, seed=1)
    ref.run_until_idle()
    assert got["r1"]["sequences"] == [a.tokens]
    assert got["r2"]["sequences"] == [b.tokens]
    ref.close()
    ce.close()
    w.bridge.close()


# -- (c) the queue's order ----------------------------------------------------
def test_what_is_not_a_slot_frame_ends_the_intake_and_goes_first(
        tiny_engine):
    """``[cont_continue, other, GENERATE]``: the intake holds ``other``
    and stops; when the chunk has returned ``other`` is handled, then the
    GENERATE, then the chunk's own ``cont_continue``."""
    ce = _cont(tiny_engine)
    w, rt = _worker(ce)
    chunks: list = []
    _stop_when_idle(w, rt, chunks)
    order: list = []
    handle = w._handle

    def handled(kind, p):
        order.append((kind, p.get("rid")))
        return handle(kind, p)

    w._handle = handled
    w._generate(_frame("r1", [1, 2, 3], 12))
    # a GENERATE of a static path is "another kind" too (two prompts)
    w.bridge.q.work.put((proto.REPLICA_SET, {"job_id": "j", "replicas": []}))
    w.bridge.q.work.put((proto.GENERATE, _frame("r2", [4, 5, 6], 5)))
    w.run()
    assert order[:4] == [
        ("cont_continue", None), (proto.REPLICA_SET, None),
        (proto.GENERATE, "r2"), ("cont_continue", None)]
    assert ce.stats["submitted_ahead"] == 0 and ce.stats["admitted_ahead"] == 0
    assert set(_answers(w)) == {"r1", "r2"}
    # no cont_continue stood in the queue when a chunk started
    assert all("cont_continue" not in held for held in chunks)
    ce.close()
    w.bridge.close()


@pytest.mark.parametrize("frame", [
    dict(prompts=[[1, 2], [3, 4]]),  # two prompts: the static path's
    dict(job_id="other"),
    dict(continuous=False),
    dict(num_beams=2),
    dict(temperature=[0.1]),
    dict(reattach="j1"),  # answers for a live stream: a chunk's edge
], ids=["two-prompts", "another-job", "not-continuous", "beams",
        "knob-list", "re-attach"])
def test_only_this_jobs_slot_frames_are_taken_in(tiny_engine, frame):
    ce = _cont(tiny_engine)
    w, rt = _worker(ce)
    rt.cont_scheduled = True
    item = (proto.GENERATE, _frame("r9", [1, 2, 3]) | frame)
    w.bridge.q.work.put(item)
    held = threading.Event()  # not ready: only the item can end it
    assert list(w._intake(rt, types.SimpleNamespace(
        block_until_ready=functools.partial(held.wait, 60)))) == []
    assert w._held == item
    held.set()
    ce.close()
    w.bridge.close()


@pytest.mark.parametrize("bad", [
    {"job_id": "j", "continuous": True, "peer": "p0", "rid": "bad"},
    _frame("bad", [4, 5, 6], num_beams="x"),
], ids=["no-prompts", "beams-no-number"])
def test_a_frame_the_intake_cannot_read_fails_alone(tiny_engine, bad):
    """A peer's body reaches the work queue unchecked. One that the
    intake's test cannot read, arriving while a chunk runs, is held and
    fails under the run loop's error reply, as with no intake: the engine
    is not closed over it and the live stream is what it would have been."""
    ce = _cont(tiny_engine)
    w, rt = _worker(ce)
    _stop_when_idle(w, rt)
    w._generate(_frame("r1", [1, 2, 3], 12))
    w.bridge.q.work.put((proto.GENERATE, bad))
    w.bridge.q.work.put((proto.GENERATE, _frame("r2", [4, 5, 6], 5)))
    loop = threading.Thread(target=w.run, daemon=True)
    loop.start()
    loop.join(timeout=60)
    if loop.is_alive():  # a closed engine never runs out of work
        w.bridge.q.work.put(("_stop", None))
        loop.join(timeout=10)
    got = _answers(w)
    assert set(got) == {"r1", "bad", "r2"}
    assert "error" in got["bad"] and "sequences" not in got["bad"]
    assert ce.recorder.last_dump is None  # no chunk failed
    ref = _cont(tiny_engine)
    a = ref.submit([1, 2, 3], max_new_tokens=12, seed=1)
    ref.step_chunk()
    b = ref.submit([4, 5, 6], max_new_tokens=5, seed=1)
    ref.run_until_idle()
    assert got["r1"]["sequences"] == [a.tokens]
    assert got["r2"]["sequences"] == [b.tokens]
    ref.close()
    ce.close()
    w.bridge.close()


# -- (d) what an ahead round leaves alone ------------------------------------
def _contexts(ce):
    from benchmarks.harness.taps import _contexts as ctx

    return ctx(ce)


def test_an_ahead_round_prepares_and_does_not_publish(tiny_engine):
    ce = _cont(tiny_engine, max_slots=2)
    seen: list = []

    def intake(result):
        if seen:
            return
        before = (list(ce._slots), dict(ce._prefilling), _contexts(ce),
                  ce.stats["admitted"], ce.live_slots)
        yield lambda: seen.append(
            ce.submit([4, 5, 6], max_new_tokens=4, seed=2))
        # the ahead round ran: prepared for slot 1, nothing published
        assert list(ce._prepared) == [1]
        assert (list(ce._slots), dict(ce._prefilling), _contexts(ce),
                ce.stats["admitted"], ce.live_slots) == before
        assert seen[0].slot == -1 and seen[0].pages
        assert ce._free_slots() == []
        assert ce.router_snapshot()["slots_free"] == 0
        ce.check_page_conservation()  # its pages count as a slot's
        # a second arrival: no slot without a request or a prepared
        # admission, so it waits for the edge and preempts nobody
        yield lambda: seen.append(ce.submit(
            [7, 8, 9], max_new_tokens=4, seed=3, priority="interactive"))
        assert list(ce._prepared) == [1] and ce._slots[0] is a
        assert ce.stats["preemptions"] == 0

    ce.intake = intake
    a = ce.submit([1, 2, 3], max_new_tokens=20, seed=1, priority="batch")
    ce.step_chunk()
    # still prepared when the chunk has returned: the taps read it here
    assert list(ce._prepared) == [1] and ce._slots[1] is None
    assert _contexts(ce)[1] == 0
    assert ce.has_work()
    ce._admit()  # the edge
    assert not ce._prepared and ce._slots[1] is seen[0]
    assert seen[0].slot == 1 and 1 in ce._prefilling
    assert ce.stats["admitted_ahead"] == 1
    # ... and the edge's own round did what only it may: the batch
    # resident made room for the interactive arrival
    assert ce.stats["preemptions"] == 1 and ce._slots[0] is seen[1]
    assert ce.stats["admitted"] == 3
    ce.run_until_idle()
    assert all(r.finished for r in [a] + seen)
    ce.close()


def test_with_no_pages_free_the_edge_admits(tiny_engine):
    """An ahead round whose page grab comes back empty leaves the request
    queued, releases the hit chain it had pinned and preempts nobody; the
    edge admits it, as with no intake."""
    dry: list = []

    def make():
        ce = _cont(tiny_engine, max_slots=2)
        grab = ce._alloc_pages

        def alloc_pages(n):
            if ce.taking_in:
                dry.append(n)
                return None
            return grab(n)

        ce._alloc_pages = alloc_pages
        return ce

    doc = list(range(1, 30))
    first = {"warm": dict(prompt=doc, max_new_tokens=2, seed=1),
             "a": dict(prompt=[5, 5, 5], max_new_tokens=30, seed=1)}
    plan = {3: {"b": dict(prompt=doc[:20] + [3], max_new_tokens=6, seed=2)}}
    off, on = _both(make, plan, first)
    _same_streams(off, on)
    assert len(dry) == 1
    assert on[0].stats["admitted_ahead"] == 0
    assert on[0].stats["submitted_ahead"] == 1
    assert on[0].stats["preemptions"] == 0
    assert (on[0].prefix.stats["hit_tokens"]
            == off[0].prefix.stats["hit_tokens"] > 0)
    for c in (off[0], on[0]):
        c.close()


def test_a_hit_the_chunk_in_flight_may_lengthen_is_the_edges(tiny_engine):
    """A burst that shares a fresh prefix: ``a`` retires in the chunk
    during which ``b`` arrives, and its prefill's pages enter the trie
    when that chunk settles. An ahead round would walk the trie before
    that and prefill again what the edge finds cached: it leaves ``b`` to
    the edge, and ``b``'s hit is what it is with no intake. ``c``, whose
    prompt no resident shares, is prepared ahead in the same wait."""
    shared = list(range(1, 17))  # two pages
    first = {"a": dict(prompt=shared + [20, 21, 22], max_new_tokens=3,
                       seed=1)}
    probe = _cont(tiny_engine)
    a = probe.submit(**first["a"])
    last = 0
    while not a.finished:
        probe.step_chunk()
        last += 1
    probe.close()
    plan = {last: {"b": dict(prompt=shared + [30, 31], max_new_tokens=4,
                             seed=2)}}
    off, on = _both(lambda: _cont(tiny_engine), plan, first)
    _same_streams(off, on)
    assert on[0].stats["submitted_ahead"] == 1
    assert on[0].stats["admitted_ahead"] == 0
    assert (on[0].prefix.stats["hit_tokens"]
            == off[0].prefix.stats["hit_tokens"] == len(shared))
    for c in (off[0], on[0]):
        c.close()
    # ... and only such a request waits: beside the same resident, one
    # that shares nothing with it is prepared while the chunk runs
    plan = {last: {"c": dict(prompt=[40, 41, 42], max_new_tokens=4, seed=2)}}
    off, on = _both(lambda: _cont(tiny_engine), plan, first)
    _same_streams(off, on)
    assert on[0].stats["admitted_ahead"] == 1
    for c in (off[0], on[0]):
        c.close()


def test_first_come_takes_the_free_slot_and_the_edge_puts_it_right(
        tiny_engine):
    """One slot free and two arrivals inside one chunk, the lower class
    first. With no intake the edge's ``select`` ranks both and the
    interactive request takes the slot. An ahead round cannot know what
    comes behind: it prepares the batch request; the edge commits it,
    then preempts it for the interactive one. The placement and the
    streams are the same; the price is one preemption and an admission
    done twice."""
    first = {"a": dict(prompt=[1, 2, 3], max_new_tokens=40, seed=1)}
    plan = {2: {
        "low": dict(prompt=[4, 5, 6], max_new_tokens=6, seed=2,
                    priority="batch"),
        "high": dict(prompt=[7, 8, 9], max_new_tokens=6, seed=3,
                     priority="interactive"),
    }}
    off, on = _both(lambda: _cont(tiny_engine, max_slots=2), plan, first)
    _same_streams(off, on)  # chunk for chunk who rode: the placement
    (c0, _, rode0), (c1, r1, _) = off, on
    assert rode0[2] == ["a", "high"]  # "low" waited for a slot
    assert c0.stats["preemptions"] == 0 and c1.stats["preemptions"] == 1
    assert c1.stats["admitted_ahead"] == 1
    assert c1.stats["admitted"] == c0.stats["admitted"] + 1
    for c in (c0, c1):
        c.close()


# -- (e) nothing leaks --------------------------------------------------------
def _prepared_engine(tiny_engine, **kw):
    """An engine one chunk in, with an admission prepared (under a prefix
    hit: it holds references as well as pages) and not committed."""
    ce = _cont(tiny_engine, **kw)
    doc = list(range(1, 30))
    ce.submit(doc, max_new_tokens=2, seed=1)
    ce.run_until_idle()  # the document's pages are the trie's
    a = ce.submit([9, 9, 9], max_new_tokens=40, seed=2)
    ce.step_chunk()
    held: list = []

    def intake(result):
        if not held:
            yield lambda: held.append(
                ce.submit(doc[:20] + [3], max_new_tokens=5, seed=3))

    ce.intake = intake
    ce.step_chunk()
    assert list(ce._prepared) and held[0].shared_nodes and held[0].pages
    return ce, a, held[0]


def test_close_returns_a_prepared_admission(tiny_engine):
    ce, a, b = _prepared_engine(tiny_engine)
    ce.close()  # its conservation check holds
    assert not ce._prepared and b.done.is_set() and b.error is not None
    assert not b.pages and not b.shared_nodes
    assert ce.alloc.n_free + len(ce.prefix.resident_pages) == (
        ce.cache.n_pages - 1)
    assert all(n.refs == 0 for n in ce.prefix._by_page.values())


def test_a_failed_chunk_returns_a_prepared_admission(tiny_engine):
    """The worker's ``close(e)`` after a chunk that raised with the
    admission prepared inside it."""
    ce = _cont(tiny_engine)
    ce.submit([9, 9, 9], max_new_tokens=40, seed=2)
    ce.step_chunk()
    held: list = []

    def intake(result):
        yield lambda: held.append(
            ce.submit([1, 2, 3, 4], max_new_tokens=5, seed=3))
        assert ce._prepared
        raise RuntimeError("the chunk failed")

    ce.intake = intake
    with pytest.raises(RuntimeError, match="the chunk failed"):
        ce.step_chunk()
    assert ce._prepared and not ce.taking_in
    err = RuntimeError("down")
    ce.close(err)
    assert held[0].error is err and not ce._prepared
    assert ce.alloc.n_free + len(ce.prefix.resident_pages) == (
        ce.cache.n_pages - 1)


def test_a_drain_commits_a_prepared_admission_first(tiny_engine):
    ce, a, b = _prepared_engine(tiny_engine)
    ce.begin_drain()
    assert not ce._prepared
    manifest = {s: (kind, r) for kind, s, r in ce.live_manifest()}
    assert ("prefill", b) in manifest.values()
    for kind, s, r in ce.live_manifest():
        assert ce.shed_slot(s) is r
    ce.check_page_conservation()
    ce.end_drain()
    ce.close()


# -- (f) the wait --------------------------------------------------------------
def test_the_wait_returns_at_once_when_the_result_is_ready(tiny_engine):
    """A result that is ready and a queue nothing arrives on: the intake
    ends on the bridge's marker, with no sleep and no timeout under a
    second; a stale marker before it is passed over."""
    ce = _cont(tiny_engine)
    w, rt = _worker(ce)
    rt.cont_scheduled = True
    ready = types.SimpleNamespace(block_until_ready=lambda: None)
    w.bridge.q.work.put((CHUNK_DONE, 0))  # of an earlier chunk
    t0 = time.monotonic()
    assert list(w._intake(rt, ready)) == []
    assert time.monotonic() - t0 < 0.5
    assert w._held is None and w.bridge.q.work.empty()
    assert w.bridge.q.work.timeouts
    assert all(t is not None and t >= 1.0 for t in w.bridge.q.work.timeouts)
    # ... and one that fails is reported ready: its reader's fetch raises

    def boom():
        raise RuntimeError("device")

    assert list(w._intake(rt, types.SimpleNamespace(
        block_until_ready=boom))) == []
    # driven by hand (no cont_continue stands for the chunk): no intake
    rt.cont_scheduled = False
    n = len(w.bridge.q.work.timeouts)
    assert list(w._intake(rt, ready)) == []
    assert len(w.bridge.q.work.timeouts) == n
    ce.close()
    w.bridge.close()


def test_the_wait_wakes_for_a_frame_and_then_for_the_result(tiny_engine):
    ce = _cont(tiny_engine)
    w, rt = _worker(ce)
    rt.cont_scheduled = True
    done = threading.Event()
    taken: list = []
    w._handle_guarded = lambda kind, p: taken.append(p["rid"])
    gen = w._intake(rt, types.SimpleNamespace(
        block_until_ready=functools.partial(done.wait, 60)))
    threading.Timer(0.05, w.bridge.q.work.put, [
        (proto.GENERATE, _frame("r1", [1, 2]))]).start()
    next(gen)()
    assert taken == ["r1"]
    threading.Timer(0.05, done.set).start()
    assert list(gen) == []  # the marker ends it
    assert w._held is None
    ce.close()
    w.bridge.close()


# -- (g) the counters ----------------------------------------------------------
def test_counters_and_the_span_attribute_equal_counts_by_hand(tiny_engine):
    ce = _cont(tiny_engine, max_slots=3)
    tids = [mint_trace_id() for _ in range(4)]
    plan = {
        1: [dict(prompt=[4, 5, 6], max_new_tokens=4, seed=2)],
        # two arrive in one wait, one slot is free: one ahead, one edge
        2: [dict(prompt=[7, 8, 9], max_new_tokens=4, seed=3),
            dict(prompt=[1, 1, 1], max_new_tokens=4, seed=4)],
    }
    reqs = [ce.submit([1, 2, 3], max_new_tokens=30, seed=1,
                      trace_id=tids[0])]
    it = iter(tids[1:])
    naps: list = []

    def take(kw):
        time.sleep(0.002)
        naps.append(0.002)
        reqs.append(ce.submit(trace_id=next(it), **kw))

    ce.intake = _Script({k: [functools.partial(take, kw) for kw in v]
                         for k, v in plan.items()})
    ce.run_until_idle()
    s = ce.stats
    assert s["submitted"] == 4 and s["submitted_ahead"] == 3
    assert s["admitted"] == 4 and s["admitted_ahead"] == 2
    recs = ce.recorder.records()
    assert s["chunk_us_intake"] == pytest.approx(
        sum(r["intake_ms"] for r in recs) * 1e3, abs=len(recs))
    assert s["chunk_us_intake"] >= sum(naps) * 1e6
    assert s["chunk_us_intake"] <= s["chunk_us_wait"]
    assert [r["intake_ms"] > 0 for r in recs[:3]] == [True, True, False]
    for r in recs:
        assert r["intake_ms"] <= r["wait_ms"] + 1e-3
    ahead = []
    for tid, r in zip(tids, reqs):
        spans = {sp["name"]: sp for sp in get_tracer().collect(tid)}
        adm, wait, pre = spans["admission"], spans["queue_wait"], spans[
            "prefill"]
        ahead.append(bool(adm.get("ahead")))
        # end to end: queue_wait ends where the round prepared, prefill
        # starts there (the wait for the edge lies inside it)
        assert wait["t0"] + wait["dur_ms"] / 1e3 == pytest.approx(
            pre["t0"], abs=1e-6)
        assert adm["t0"] + adm["dur_ms"] / 1e3 == pytest.approx(
            pre["t0"], abs=1e-6)
    assert ahead == [False, True, True, False]
    ce.close()


# -- (h) the wire: a long prompt's ids cross as one array ----------------------
def test_a_4k_prompt_crosses_both_bridges_as_one_array_and_reaches_submit():
    """A GENERATE frame of 4,096 ids goes the way a node's goes: the
    validator's ML side puts it on its ``cmd`` ring, its network process
    reads it and frames it for TCP, the worker's network process reads
    that and puts it on the ``work`` ring, the worker's run loop reads it
    and ``submit`` gets the ids it left with, plain ints; each of the three
    framings packed the prompt as one array and each reading unpacked it."""
    from tensorlink_tpu.core import serialization as ser
    from tensorlink_tpu.core.ring import RingChannel, ring_supported
    from tensorlink_tpu.nodes.ipc import BridgeQueues, NetBridge

    if not ring_supported():
        pytest.skip("native tlring not buildable here: the bridges pickle")
    cfg = ModelConfig(
        family="llama", vocab_size=128, d_model=32, n_layers=1, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=4224,
        dtype=jnp.float32, tie_embeddings=False,
    )
    eng = GenerationEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)), seq_buckets=(8, 32),
        batch_buckets=(1,), max_seq_len=4224)
    ce = _cont(eng, max_slots=2, page_size=64, prefill_chunk=1024)
    w, rt = _worker(ce)
    rings = [RingChannel(1 << 20) for _ in range(4)]
    val = MLBridge(BridgeQueues(cmd=rings[0], resp=rings[1], work=rings[2]))
    w.bridge.q = types.SimpleNamespace(cmd=None, resp=None, work=rings[3])
    ids = [int(t) for t in np.random.default_rng(4).integers(0, 128, 4096)]
    seen: list = []
    submit, step = ce.submit, ce.step_chunk

    def submit_seen(prompt, **kw):
        seen.append(prompt)
        return submit(prompt, **kw)

    def step_then_stop(**kw):
        more = step(**kw)
        if not more:
            rings[3].put(("_stop", None))
        return more

    ce.submit, ce.step_chunk = submit_seen, step_then_stop
    try:
        before = ser.counters()
        body = {"job_id": "j", "prompts": [[int(t) for t in ids]],
                "max_new_tokens": 4, "continuous": True, "seed": 1,
                "eos_ids": [], "temperature": 0.0}
        val.notify("tensor_request", {"tag": proto.GENERATE, "body": body})
        _, verb, payload = rings[0].get(timeout=5)  # the validator's net side
        assert verb == "tensor_request"
        tcp = bytes(ser.encode(payload["body"]))
        assert tcp[4] == ser.VERSION_PACKED and len(tcp) < 5 * 4096
        arrived = ser.decode(tcp, copy=True)  # the worker's net side
        NetBridge(w.bridge.q).post_work(
            proto.GENERATE, {**arrived, "peer": "p0", "rid": "r1"})
        w.run()
        after = ser.counters()
    finally:
        ce.close()
        for r in rings:
            r.release()
    assert len(seen) == 1 and seen[0] == ids
    assert type(seen[0]) is list and set(map(type, seen[0])) == {int}
    moved = {k: after[k] - before[k] for k in after}
    assert moved == {"tlts_lists_packed": 3, "tlts_ints_packed": 3 * 4096,
                     "tlts_lists_unpacked": 3}
    got = _answers(w)["r1"]
    assert len(got["sequences"][0]) == 4
    # the worker's stats carry its process's side of the wire
    assert got["serving"]["tlts_lists_unpacked"] >= 3
    assert got["serving"]["prefill_tokens"] >= 4096


# -- (i) the inside of the wait ------------------------------------------------
NAP = 0.01


class _Gate:
    """What a test holds of the step in flight: whether its result is ready
    (the test sets it), how often the driver asked, and where it was on the
    clock at the boundaries the lateness is read between."""

    def __init__(self):
        self.ready = self.seen = False
        self.asked = 0
        self.fetch_t0 = 0.0


class _Blind:
    """A step's result as a stub hands it on: fetched, never asked."""

    def __init__(self, out, gate):
        self.out, self.gate = out, gate

    def copy_to_host_async(self):
        self.out.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self.gate.fetch_t0 = time.monotonic()
        time.sleep(NAP)  # the driver blocks for the device
        return np.asarray(self.out)


class _Result(_Blind):
    """... and one whose readiness the test's gate controls."""

    def is_ready(self):
        assert not self.gate.seen, "asked again after the first True"
        self.gate.asked += 1
        self.gate.seen = self.gate.ready
        return self.gate.ready


def _gated(monkeypatch, kind=_Result) -> list:
    """Every step's result goes through ``kind``; the gates, a dispatch."""
    from tensorlink_tpu.engine import continuous

    gates: list = []
    step = continuous.paged_ragged_step

    def gated_step(*ops, **kw):
        out, cache, counts = step(*ops, **kw)
        gates.append(_Gate())
        return kind(out, gates[-1]), cache, counts

    gated_step._cache_size = step._cache_size  # the engine counts programs
    monkeypatch.setattr(continuous, "paged_ragged_step", gated_step)
    return gates


def _streaming(ce, gates, ready_at):
    """One request whose reader naps a token; the result of the chunk in
    flight turns ready inside the ``ready_at``-th callback of that chunk's
    stream stage (from 0; None: never). Returns, a dispatched chunk, where
    each callback of its in-flight stage ended (``cbs``) and where the
    stage had (``end``)."""
    marks: dict = {}
    flush = ce.flush_stream
    staged = {"on": False}

    def cb(tok):
        m = marks.setdefault(len(gates), {"cbs": []})
        if staged["on"]:
            if len(m["cbs"]) == ready_at:
                gates[-1].ready = True
            time.sleep(NAP)
            m["cbs"].append(time.monotonic())
        return False

    def flush_stream(*a, **kw):
        staged["on"] = bool(kw.get("in_flight"))
        flush(*a, **kw)
        if staged["on"]:
            marks.setdefault(len(gates), {"cbs": []})["end"] = (
                time.monotonic())
        staged["on"] = False

    ce.flush_stream = flush_stream
    ce.submit([1, 2, 3], max_new_tokens=12, seed=1, stream_cb=cb)
    return marks


@pytest.mark.parametrize("ready_at", [0, 3, None],
                         ids=["first_callback", "last_callback", "never"])
def test_late_stream_is_the_stage_behind_the_first_sight_of_ready(
        tiny_engine, monkeypatch, ready_at):
    """A chunk's stage hands a stream's four tokens on in two entries (its
    next token, then the rest): the driver asks behind each. Ready inside
    the first callback: the three callbacks of the second entry are late.
    Ready inside the last: the stamp falls where the stage ends. Never
    ready: zeros, and the driver asked at every boundary it passed."""
    gates = _gated(monkeypatch)
    ce = _cont(tiny_engine)
    marks = _streaming(ce, gates, ready_at)
    ce.run_until_idle()
    recs = ce.recorder.records()
    assert len(recs) == len(gates) == 3
    # the first chunk's wait has nothing to stream: one question, at the fetch
    assert (gates[0].asked, recs[0]["late_stream_ms"]) == (1, 0.0)
    for k in (2, 3):
        rec, gate, m = recs[k - 1], gates[k - 1], marks[k]
        assert len(m["cbs"]) == 4
        assert rec["fetch_ms"] >= NAP * 1e3
        assert rec["stream_ms"] >= 4 * NAP * 1e3
        if k == 2:  # the last chunk's record holds the flush behind it too
            assert rec["stream_ms"] + rec["fetch_ms"] <= rec["wait_ms"] + 0.1
        late = rec["late_stream_ms"] + rec["late_intake_ms"]
        if ready_at is None:
            assert gate.asked == 3 and not gate.seen
            assert late == rec["late_max_ms"] == 0.0
            continue
        seen_by = 0 if ready_at == 0 else 1  # the entry whose end saw it
        assert gate.asked == seen_by + 1 and gate.seen
        # at most: that entry too (one callback, or three), where the
        # result did turn ready; the stage before it is not in it
        naps = 1 if ready_at == 0 else 3
        assert naps * NAP * 1e3 <= rec["late_max_ms"] - late
        if ready_at == 0:  # from the wait's start
            assert rec["late_max_ms"] <= rec["wait_ms"] - rec["fetch_ms"] + 2e-3
        else:  # from the first entry's end
            assert rec["late_max_ms"] <= (gate.fetch_t0 - m["cbs"][0]) * 1e3
        # the stamp lies behind that entry's last callback, the fetch's
        # start before the result's own mark of it (the test's mark of the
        # stage's end lies BEFORE the engine's: it bounds nothing from
        # above, and a loaded machine parts the two)
        assert late <= (gate.fetch_t0 - m["cbs"][ready_at]) * 1e3
        assert rec["late_stream_ms"] >= (3 * NAP * 1e3 if ready_at == 0 else 0)
        # no intake: the driver came to the fetch where the stage ended
        assert rec["late_intake_ms"] <= (gate.fetch_t0 - m["end"]) * 1e3 + 0.1
    s = ce.stats
    assert s["chunk_us_late_stream"] == round(
        sum(r["late_stream_ms"] for r in recs) * 1e3)
    assert s["chunk_us_late_intake"] == round(
        sum(r["late_intake_ms"] for r in recs) * 1e3)
    ce.close()


@pytest.mark.parametrize("ready_in", ["first_take", "stream_stage", "never"])
def test_late_intake_is_the_intake_behind_the_first_sight_of_ready(
        tiny_engine, monkeypatch, ready_in):
    """Three takes of the second chunk's intake nap each. Ready inside the
    first: the two that follow are late. Ready while the stage before the
    intake still streamed: all three are, and the stage's rest besides.
    Never ready: zeros."""
    gates = _gated(monkeypatch)
    ce = _cont(tiny_engine, max_slots=2)
    ends: list = []

    def take(i):
        if ready_in == "first_take" and i == 0:
            gates[-1].ready = True
        time.sleep(NAP)
        ends.append(time.monotonic())

    ce.intake = _Script({2: [functools.partial(take, i) for i in range(3)]})
    marks = _streaming(ce, gates, 0 if ready_in == "stream_stage" else None)
    ce.step_chunk()
    ce.step_chunk()
    rec, gate, m = ce.recorder.records()[1], gates[1], marks[2]
    assert rec["intake_ms"] >= 3 * NAP * 1e3
    late = rec["late_stream_ms"] + rec["late_intake_ms"]
    if ready_in == "never":
        assert gate.asked == 2 + 3 + 1 and not gate.seen
        assert late == rec["late_max_ms"] == 0.0
    elif ready_in == "first_take":
        assert gate.asked == 2 + 1
        assert rec["late_stream_ms"] == 0.0
        assert 2 * NAP * 1e3 <= rec["late_intake_ms"] <= (
            gate.fetch_t0 - ends[0]) * 1e3
        # at most: the first take too, and nothing of the stage before it
        assert NAP * 1e3 <= rec["late_max_ms"] - late
        assert rec["late_max_ms"] <= (gate.fetch_t0 - m["end"]) * 1e3
    else:
        assert gate.asked == 1
        assert rec["late_stream_ms"] >= 3 * NAP * 1e3
        assert 3 * NAP * 1e3 <= rec["late_intake_ms"] <= (
            gate.fetch_t0 - m["cbs"][-1]) * 1e3
        # at most: the stage's first entry too, from the wait's start
        assert NAP * 1e3 <= rec["late_max_ms"] - late
        assert rec["late_max_ms"] <= rec["wait_ms"] - rec["fetch_ms"] + 2e-3
    assert (rec["late_stream_ms"] + rec["late_intake_ms"] + rec["fetch_ms"]
            <= rec["wait_ms"] + 2e-3)
    assert ce.stats["chunk_us_late_intake"] == round(
        rec["late_intake_ms"] * 1e3)
    ce.close()


def test_a_result_without_is_ready_records_zeros(tiny_engine, monkeypatch):
    """A stub's result says nothing of its readiness: the driver asks
    nothing, and every lateness reads 0 whatever the stage and the intake
    took; the fetch is timed all the same."""
    gates = _gated(monkeypatch, kind=_Blind)
    ce = _cont(tiny_engine)
    ce.intake = _Script({2: [functools.partial(time.sleep, NAP)]})
    _streaming(ce, gates, None)
    ce.run_until_idle()
    recs = ce.recorder.records()
    assert len(recs) == 3 and recs[1]["intake_ms"] >= NAP * 1e3
    for r in recs:
        assert r["late_stream_ms"] == r["late_intake_ms"] == 0.0
        assert r["late_max_ms"] == 0.0
        assert r["fetch_ms"] >= NAP * 1e3
    assert ce.stats["chunk_us_late_stream"] == 0
    assert ce.stats["chunk_us_late_intake"] == 0
    ce.close()


def test_a_device_result_is_seen_ready_and_asked_no_more(tiny_engine):
    """The real thing: a device array answers ``is_ready``; once the step
    is through, the driver's next question stamps it, and what the stage
    still handed on behind the stamp is late."""
    ce = _cont(tiny_engine)
    asked: list = []
    from tensorlink_tpu.engine import continuous

    seen = continuous.ContinuousEngine._seen_ready

    def seen_ready(self, since=None):
        asked.append((self._chunk_step, self._ready_poll is not None))
        if self._ready_poll is not None:
            # what the device would be by now on any machine
            self._ready_poll.__self__.block_until_ready()
        seen(self, since)

    ce._seen_ready = types.MethodType(seen_ready, ce)
    ce.submit([1, 2, 3], max_new_tokens=12, seed=1,
              stream_cb=lambda tok: time.sleep(NAP))
    ce.run_until_idle()
    recs = ce.recorder.records()
    assert len(recs) == 3
    for r in recs[1:]:
        # ready at the first entry's end: the second entry's three naps
        assert r["late_stream_ms"] >= 3 * NAP * 1e3
        assert r["late_stream_ms"] <= r["late_max_ms"] <= r["wait_ms"]
        assert r["late_stream_ms"] <= r["stream_ms"]
        # asked once with a result to ask, then at each boundary for nothing
        assert [a for c, a in asked if c == r["step"]] == [True, False, False]
    assert ce._ready_poll is None
    ce.close()


def test_a_callback_that_raises_in_the_wait_leaves_no_hold_on_the_result(
        tiny_engine):
    """A stream callback that raises inside a chunk's in-flight stage ends
    that ``step_chunk``: the driver keeps no ``is_ready`` of the result it
    left in flight, and the chunks that follow stamp as ever."""
    ce = _cont(tiny_engine)
    got: list = []

    def cb(tok):
        got.append(tok)
        if len(got) == 2:  # the second chunk's stage, its result in flight
            raise RuntimeError("reader gone")

    ce.submit([1, 2, 3], max_new_tokens=12, seed=1, stream_cb=cb)
    ce.step_chunk()
    with pytest.raises(RuntimeError, match="reader gone"):
        ce.step_chunk()
    assert ce._ready_poll is None
    ce.run_until_idle()
    assert ce._ready_poll is None
    for r in ce.recorder.records():
        assert r["late_stream_ms"] + r["late_intake_ms"] <= r["late_max_ms"]
    ce.close()
