"""Request scheduling for the serving path.

Two schedulers share the client API (``generate``/``close``/``stats``):

:class:`GenBatcher` — the STATIC batcher. Requests enqueue; the
dispatcher takes the head request, waits a short window for more, then
issues one ``model.generate`` with per-row sampling knobs and budgets,
demuxing the per-row stream callback back to each request. The whole
batch then runs to completion: finished rows dead-step until the batch
drains, and new arrivals queue behind it.

:class:`ContinuousBatcher` — continuous batching (the default,
MLConfig.continuous_batching). There is no window and no drain barrier:
each request joins the model's RUNNING slot batch within at most one
decode chunk, and finished requests free their KV immediately.

- single-stage jobs: the request passes straight through to the worker,
  whose slot engine (engine/continuous.py) decodes all residents over the
  paged KV cache and admits/evicts at chunk boundaries;
- pipelined jobs: a :class:`PipelinedSlotSession` runs slot admission
  through the PR-1 session path — one persistent seq-numbered decode
  session of B rows whose finished rows are recycled (``reset_rows``)
  for queued prompts, with the per-session recovery semantics intact.

See docs/SERVING.md for the scheduler's admission/eviction rules.
"""

from __future__ import annotations

import itertools
import queue
import secrets
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from tensorlink_tpu.core.metrics import MetricsRegistry
from tensorlink_tpu.core.trace import get_tracer
from tensorlink_tpu.engine.scheduler import (
    DEFAULT_PRIORITY,
    PRIORITY_RANK,
    normalize_priority,
)


@dataclass
class _Pending:
    ids: list[int]
    max_new_tokens: int
    temperature: float
    top_k: int
    top_p: float
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # speculative decode wish (greedy B=1 only): honored when the request
    # dispatches ALONE; in a co-batch it decodes vanilla — the emitted
    # tokens are identical either way, so this is purely a speed hint
    lookahead: bool = False
    # continuous speculative decoding (engine/continuous.py): the request
    # opts into draft/verify ragged slots on a spec_decode engine — also
    # a pure speed hint (streams bit-identical either way)
    speculative: bool = False
    done: threading.Event = field(default_factory=threading.Event)
    stream_cb: Callable[[list[int]], None] | None = None
    result: list[int] | None = None
    error: BaseException | None = None
    # continuous scheduling (ContinuousBatcher): per-request RNG seed and
    # the model's EOS set ride the record instead of the dispatch call
    seed: int = 0
    eos_ids: list[int] = field(default_factory=list)
    # SLO scheduling class (engine/scheduler.py); None → batcher default
    priority: str | None = None
    # distributed-trace id (core/trace.py); "" = untraced request
    trace_id: str = ""
    submit_t: float = 0.0


def _headroom_from(snap: dict) -> dict:
    """The /healthz per-replica headroom fields, projected from a
    router_snapshot — ONE definition of the field set so the two batcher
    kinds can never diverge (docs/SERVING.md "Fleet serving")."""
    return {
        k: snap[k]
        for k in ("slots_free", "kv_pages_free", "queue_depth", "draining")
    }


class GenBatcher:
    """One per hosted model; owns the model's generation serialization."""

    def __init__(
        self,
        model: Any,  # DistributedModel (or anything with .generate/.plan)
        eos_ids: list[int],
        *,
        max_batch: int = 8,
        window_s: float = 0.01,
        seed: int = 0,
        queue_cap: int = 256,
    ):
        self.model = model
        self.eos_ids = list(eos_ids)
        self.max_batch = max_batch
        self.window_s = window_s
        self.seed = seed
        self.queue_cap = int(queue_cap)
        self._q: queue.Queue[_Pending | None] = queue.Queue()
        self._seq = 0
        self._closed = False  #: guarded by self._submit_lock
        self._submit_lock = threading.Lock()  # orders submits vs close()
        from collections import deque

        self._stats_lock = threading.Lock()
        # dispatch stats: typed counters (core/metrics.py) plus the
        # bounded sample window stats() derives its batch shape from
        self.metrics = MetricsRegistry()
        self._m_requests = self.metrics.counter(
            "tlink_batcher_requests_total", "requests dispatched",
            mode="static",
        )
        self._m_dispatches = self.metrics.counter(
            "tlink_batcher_dispatches_total", "batched dispatches issued",
            mode="static",
        )
        self.batch_sizes: deque[int] = deque(maxlen=1000)  #: guarded by self._stats_lock
        self._thread = threading.Thread(
            target=self._loop, name="gen-batcher", daemon=True
        )
        self._thread.start()

    # -- client side -----------------------------------------------------
    def generate(
        self,
        ids: list[int],
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        stream_cb: Callable[[list[int]], None] | None = None,
        timeout: float = 600.0,
        lookahead: bool = False,
        speculative: bool = False,
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0,
        priority: str | None = None,
        trace_id: str | None = None,
        handoff: bool = True,
        jrid: str = "",
    ) -> list[int]:
        """Blocking submit; returns this request's generated ids.
        ``stream_cb`` receives this request's new tokens as they decode.
        ``priority``, ``speculative``, ``handoff``, and ``jrid`` are
        accepted for API symmetry with the continuous scheduler; the
        windowed batcher itself stays FCFS and decodes vanilla
        (speculation, the prefill→decode handoff, and journal re-attach
        are paged-engine features — all pure hints, streams identical
        either way).
        ``trace_id`` (core/trace.py) records the window-wait +
        batched-decode span."""
        req = _Pending(
            ids=list(ids), max_new_tokens=int(max_new_tokens),
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p), stream_cb=stream_cb,
            presence_penalty=float(presence_penalty),
            frequency_penalty=float(frequency_penalty),
            # speculation emits exactly vanilla greedy — penalties change
            # greedy's choices, so a penalized request takes the normal loop
            lookahead=bool(lookahead) and float(temperature) == 0.0
            and not presence_penalty and not frequency_penalty,
            trace_id=str(trace_id or ""),
        )
        req.submit_t = time.monotonic()
        # check-and-put under the lock close() drains under — a submit
        # racing close() must either land before the sentinel or fail fast,
        # never sit in a dead queue until the timeout
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("model is being unhosted")
            self._q.put(req)
        if not req.done.wait(timeout):
            raise TimeoutError("generation timed out in the batcher")
        if req.trace_id:
            # the static batcher has no admission seam to decompose — one
            # span covers window-wait + the run-to-completion batch
            get_tracer().record(
                req.trace_id, "static_batch", site="batcher",
                dur_s=time.monotonic() - req.submit_t,
                tokens=len(req.result or ()),
            )
        if req.error is not None:
            raise req.error
        return req.result or []

    def admission_check(self, priority=None, n: int = 1) -> dict | None:
        """Flat backpressure for the windowed batcher: reject when the
        dispatch queue is deeper than ``queue_cap``. Classes don't
        reorder anything here (FCFS), but the API layer's 429 +
        Retry-After contract is shared with the continuous scheduler."""
        depth = self._q.qsize()
        if depth + n > self.queue_cap:
            return {
                "priority": str(priority or "interactive"),
                "queue_depth": depth,
                "cap": self.queue_cap,
                "retry_after": max(1.0, min(depth * 0.5, 600.0)),
            }
        return None

    def router_snapshot(self) -> dict:
        """Fleet-router scoring view (docs/SERVING.md "Fleet serving").
        The windowed batcher has no paged engine behind it: no digest,
        no per-class queues — the flat dispatch depth stands in for
        every class so a fleet mixing batcher kinds still balances."""
        depth = self._q.qsize()
        return {
            "draining": False,
            "worker_role": "mixed",
            "max_slots": self.max_batch,
            "slots_free": max(self.max_batch - depth, 0),
            "kv_pages_free": 0,
            "kv_pages_total": 0,
            "service_ewma_s": 0.0,
            "queue_depth": {c: depth for c in PRIORITY_RANK},
            "prefix_digest": {},
        }

    def headroom(self) -> dict:
        """The /healthz per-replica headroom fields — cheap, no ML
        round trip (the same contract as health_snapshot)."""
        return _headroom_from(self.router_snapshot())

    def close(self, timeout: float = 600.0) -> None:
        """Serve everything already queued, then stop. Blocks until the
        dispatcher drains (unhost must not tear the model down under an
        in-flight batched decode); anything enqueued after the sentinel
        (submit/close race) is failed fast rather than left hanging."""
        with self._submit_lock:
            self._closed = True
            self._q.put(None)
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            # the dispatcher is still driving a decode on this model; the
            # caller is about to shut the model down under it — say so
            # instead of silently proceeding
            from tensorlink_tpu.core.logging import get_logger

            get_logger("ml.batching").warning(
                "GenBatcher.close(): dispatcher did not drain within %.0fs; "
                "a batched decode may still be in flight", timeout,
            )
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                req.error = RuntimeError("model is being unhosted")
                req.done.set()

    # -- dispatcher ------------------------------------------------------
    def _take_batch(self) -> list[_Pending] | None:
        head = self._q.get()
        if head is None:
            return None
        batch = [head]
        if self.max_batch > 1:
            # bounded wait: collect whatever arrives in the window
            t0 = time.monotonic()
            while len(batch) < self.max_batch:
                remaining = self.window_s - (time.monotonic() - t0)
                if remaining <= 0:
                    break
                try:
                    nxt = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                if nxt is None:
                    self._q.put(None)  # re-post the shutdown sentinel
                    break
                batch.append(nxt)
        return batch

    def _loop(self) -> None:
        while True:
            batch = self._take_batch()
            if batch is None:
                return
            try:
                self._run(batch)
            except BaseException as e:  # noqa: BLE001 — fan the error out
                for r in batch:
                    r.error = e
                    r.done.set()

    def stats(self) -> dict | None:
        """Dispatch stats snapshot, safe against the dispatcher's appends
        (iterating a deque mutated concurrently raises RuntimeError)."""
        with self._stats_lock:
            sizes = list(self.batch_sizes)
        if not sizes:
            return None
        return {
            "dispatches": len(sizes),
            "requests": sum(sizes),
            "mean_batch": round(sum(sizes) / len(sizes), 2),
            "max_batch": max(sizes),
        }

    def _run(self, batch: list[_Pending]) -> None:
        self._m_dispatches.inc()
        self._m_requests.inc(len(batch))
        with self._stats_lock:
            self.batch_sizes.append(len(batch))
        budgets = [r.max_new_tokens for r in batch]
        emitted_counts = [0] * len(batch)

        def demux(emitted: list[int | None]) -> list[int]:
            # returns rows to CANCEL: a request's stream_cb may return
            # truthy (confirmed stop-sequence match) — the decode loop
            # freezes that row (host-driven paths) or the drain stops
            # forwarding it (compiled-loop paths)
            cancel: list[int] = []
            for i, r in enumerate(batch):
                if i < len(emitted) and emitted[i] is not None:
                    if emitted_counts[i] < budgets[i] and r.stream_cb:
                        if r.stream_cb([int(emitted[i])]):
                            cancel.append(i)
                    emitted_counts[i] += 1
            return cancel

        any_stream = any(r.stream_cb for r in batch)
        self._seq += 1
        if len(batch) == 1 and batch[0].lookahead:
            # quiet moment + speculative wish: run the prompt-lookup decode
            # (greedy B=1; same tokens as vanilla, fewer model passes)
            r = batch[0]
            seqs = self.model.generate(
                [r.ids],
                max_new_tokens=budgets[0],
                temperature=0.0,
                eos_ids=self.eos_ids,
                stream_cb=demux if any_stream else None,
                lookahead=True,
            )
            r.result = [int(t) for t in seqs[0][: budgets[0]]]
            r.done.set()
            return
        seqs = self.model.generate(
            [r.ids for r in batch],
            max_new_tokens=max(budgets),
            temperature=[r.temperature for r in batch],
            top_k=[r.top_k for r in batch],
            top_p=[r.top_p for r in batch],
            presence_penalty=[r.presence_penalty for r in batch],
            frequency_penalty=[r.frequency_penalty for r in batch],
            eos_ids=self.eos_ids,
            seed=self.seed + self._seq,
            stream_cb=demux if any_stream else None,
            budgets=budgets,
        ) if self.max_batch > 1 else self.model.generate(
            [batch[0].ids],
            max_new_tokens=budgets[0],
            temperature=batch[0].temperature,
            top_k=batch[0].top_k,
            top_p=batch[0].top_p,
            presence_penalty=batch[0].presence_penalty,
            frequency_penalty=batch[0].frequency_penalty,
            eos_ids=self.eos_ids,
            seed=self.seed + self._seq,
            stream_cb=demux if any_stream else None,
        )
        for i, r in enumerate(batch):
            r.result = [int(t) for t in seqs[i][: budgets[i]]]
            r.done.set()


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


class PipelinedSlotSession:
    """Slot admission for MULTI-STAGE jobs through the distributed session
    path: one persistent decode session of ``B = max_slots`` rows across
    every stage worker. A queued request is admitted into a free row by a
    masked prefill op (only its row's tokens carry attention mask, so
    neighbors' caches don't move); a finished row is recycled by zeroing
    its write offset on every stage (``reset_rows`` rides the next op) —
    the dense-session analogue of returning KV pages to the free-list.

    PR-1 semantics are preserved: every op carries the session's
    monotonically-increasing ``seq`` (worker-side dedup makes retries and
    frame dups idempotent), and a lost stage worker triggers repair +
    re-prefill of each live row's prompt + emitted tokens under a fresh
    session id. Sampling is per-row stateless —
    ``fold_in(PRNGKey(seed_r), n)`` for row r's nth token
    (ml/worker.py::_sample_from_logits "seeds" path) — so both co-residency
    and recovery are bit-exact for every request.

    Single-driver discipline like the engine-side slot loop: one
    dispatcher thread calls ``admit``/``step``.
    """

    MAX_RECOVERIES = 3

    def __init__(self, model: Any, *, max_slots: int = 4):
        from collections import deque

        self.model = model
        self.B = int(max_slots)
        self.cache_len = int(model.spec["seq_len"])
        self.session = secrets.token_hex(8)
        self.seq = 0
        self.slots: list[dict | None] = [None] * self.B
        self.queue: deque = deque()
        self.reset_rows: set[int] = set()
        self.recoveries = 0

    # -- helpers ---------------------------------------------------------
    def _live(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    @property
    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def _samp(self) -> dict:
        def rows(key, fill):
            return [
                (s[key] if s is not None else fill) for s in self.slots
            ]

        return {
            "temperature": rows("temperature", 0.0),
            "top_k": rows("top_k", 0),
            "top_p": rows("top_p", 1.0),
            "seeds": rows("seed", 0),
            "steps": rows("step", 0),
        }

    def _emit(self, slot: dict, tok: int) -> bool:
        """Deliver one token to a slot's request; True when it finished."""
        req: _Pending = slot["req"]
        slot["emitted"].append(tok)
        slot["step"] += 1
        cancel = False
        if req.stream_cb is not None:
            cancel = bool(req.stream_cb([tok]))
        return (
            cancel
            or tok in slot["eos"]
            or len(slot["emitted"]) >= slot["budget"]
        )

    def _finish_row(self, row: int) -> None:
        slot = self.slots[row]
        self.slots[row] = None
        self.reset_rows.add(row)
        req: _Pending = slot["req"]
        req.result = [int(t) for t in slot["emitted"][: req.max_new_tokens]]
        req.done.set()

    def _apply_step_tokens(self, tok, rows: list[int]) -> None:
        for r in rows:
            slot = self.slots[r]
            if slot is None:
                continue
            slot["last_tok"] = int(tok[r])
            if self._emit(slot, int(tok[r])):
                self._finish_row(r)

    def _forward(self, **kw):
        """One session op with in-flight recovery. On SessionLost (a stage
        worker died) the whole slot set re-establishes — including any
        rows this op was admitting, since their slot records are already
        placed — and the re-prefill op itself advances every live row one
        token, so the lost op is SUBSUMED: callers get ``None`` and must
        not re-apply."""
        from .module import SessionLost, _transportish

        try:
            out = self.model.forward(
                session=self.session, cache_len=self.cache_len,
                seq=self.seq, **kw,
            )
            self.seq += 1
            self.reset_rows.clear()  # applied by this op
            # a clean op closes any recovery episode: the budget bounds
            # CONSECUTIVE failures, not lifetime ones — a session serving
            # for days must not stop recovering after its 3rd distant blip
            self.recoveries = 0
            return out
        except Exception as e:
            recoverable = isinstance(e, SessionLost) or _transportish(e)
            if not recoverable or self.recoveries >= self.MAX_RECOVERIES:
                raise
            # the re-establishment itself may hit a transient failure right
            # when the mesh is churning — retry it within the same bounded
            # recovery budget instead of failing every live request on the
            # first double-fault
            while True:
                self.recoveries += 1
                try:
                    self._reestablish()
                    return None
                except Exception as e2:
                    still_recoverable = (
                        isinstance(e2, SessionLost) or _transportish(e2)
                        or "no connection" in str(e2)
                    )
                    if not still_recoverable \
                            or self.recoveries >= self.MAX_RECOVERIES:
                        raise

    def _reestablish(self) -> None:
        """Repair dead stages and re-prefill every live row's prompt +
        emitted tokens under a FRESH session id (PR 1 recovery). The
        sampled token at each row's last position is exactly its next
        pending draw (per-row keys are stateless in the step index), so
        streams resume with no duplicated and no missing tokens."""
        import numpy as np

        live_peers = set(self.model.node.send_request("peers", timeout=10.0))
        for st in self.model.plan.stages:
            if self.model.workers.get(st.worker_id) not in live_peers:
                self.model._repair(st.worker_id)
        self.model._end_decode_session(self.session)
        self.session = secrets.token_hex(8)
        self.seq = 0
        self.reset_rows.clear()
        rows = self._live()
        if not rows:
            return
        seqs = {
            r: self.slots[r]["prompt"] + self.slots[r]["emitted"]
            for r in rows
        }
        T = max(len(v) for v in seqs.values())
        toks = np.zeros((self.B, T), np.int32)
        mask = np.zeros((self.B, T), bool)
        last_idx = np.zeros((self.B,), np.int32)
        for r, ids in seqs.items():
            toks[r, : len(ids)] = ids
            mask[r, : len(ids)] = True
            last_idx[r] = len(ids) - 1
        tok = self.model.forward(
            toks, mask, session=self.session, cache_len=self.cache_len,
            sample=self._samp(), last_idx=last_idx, seq=0,
        )
        self.seq = 1
        self._apply_step_tokens(tok, rows)

    # -- driver API ------------------------------------------------------
    def submit(self, req: "_Pending") -> None:
        self.queue.append(req)

    def has_work(self) -> bool:
        return bool(self.queue) or bool(self._live())

    def pump(self) -> None:
        """Admit queued requests into free rows. Guard: the admission op's
        masked [B, T] write lands at every LIVE row's current offset too
        (invisible garbage at [len, len+T)) — a row within T of the cache
        end would see that write CLAMP backward over real KV, so admission
        defers until near-capacity rows finish (bounded: their budgets are
        room-capped)."""
        while self.queue:
            free = self.free_slots
            if not free:
                return
            group: list[_Pending] = []
            # class-ordered admission (stable: FIFO within a class) —
            # the pipelined session has no preemption or aging, but an
            # interactive turn never waits behind queued batch work
            ordered = sorted(
                self.queue,
                key=lambda r: PRIORITY_RANK.get(r.priority or "", 0),
            )
            for req in ordered[: len(free)]:
                eff = min(req.max_new_tokens, self.cache_len - len(req.ids))
                if eff <= 0:
                    # zero room: finished with an empty completion, the
                    # static paths' contract
                    self.queue.remove(req)
                    req.result = []
                    req.done.set()
                    continue
                group.append(req)
            if not group:
                continue
            live_max = max(
                (
                    len(s["prompt"]) + len(s["emitted"])
                    for s in self.slots if s is not None
                ),
                default=0,
            )
            # drop the LONGEST-prompt members until the op's write span is
            # safe — shorter requests behind an oversized head still admit
            # now (the skipped one re-queues for the next pump, when
            # evictions have freed room)
            while group:
                longest = max(group, key=lambda r: len(r.ids))
                if live_max + len(longest.ids) <= self.cache_len:
                    break
                group.remove(longest)
            if not group:
                return  # wait for evictions to free cache room
            for req in group:
                self.queue.remove(req)
            self._admit_group(group)

    def _admit_group(self, group: list["_Pending"]) -> None:
        """One masked prefill op admits the whole group and emits each
        member's first token."""
        import numpy as np

        placed: list[tuple[int, _Pending]] = []
        for req in group:
            row = self.free_slots[0]
            self.slots[row] = {
                "req": req,
                "prompt": [int(t) for t in req.ids],
                "emitted": [],
                "budget": min(
                    req.max_new_tokens, self.cache_len - len(req.ids)
                ),
                "eos": set(req.eos_ids),
                "seed": req.seed,
                "step": 0,
                "last_tok": 0,
                "temperature": req.temperature,
                "top_k": req.top_k,
                "top_p": req.top_p,
            }
            placed.append((row, req))
        # a recycled row being re-admitted stays in the reset list: the op
        # zeroes its stale write offset BEFORE the prefill's KV writes land
        recycled = sorted(self.reset_rows)
        now = time.monotonic()
        for row, req in placed:
            if req.trace_id:
                # the pipelined analogue of the engine's queue_wait span;
                # the admission op below carries the trace ids so every
                # stage worker can record its session-prefill hop too
                get_tracer().record(
                    req.trace_id, "queue_wait", site="pipeline",
                    dur_s=(now - req.submit_t) if req.submit_t else None,
                    row=row,
                )
        traces = [req.trace_id for _, req in placed if req.trace_id]
        T = max(len(req.ids) for _, req in placed)
        toks = np.zeros((self.B, T), np.int32)
        mask = np.zeros((self.B, T), bool)
        last_idx = np.zeros((self.B,), np.int32)
        for row, req in placed:
            toks[row, : len(req.ids)] = req.ids
            mask[row, : len(req.ids)] = True
            last_idx[row] = len(req.ids) - 1
        tok = self._forward(
            tokens=toks, attn_mask=mask, sample=self._samp(),
            last_idx=last_idx, reset_rows=recycled,
            trace=traces or None,
        )
        if tok is not None:
            self._apply_step_tokens(tok, [r for r, _ in placed])

    def step(self) -> None:
        """One decode step over the active rows (inactive rows ride the
        fixed batch shape with a zero attention mask, so their caches
        don't move)."""
        import numpy as np

        rows = self._live()
        if not rows:
            return
        toks = np.zeros((self.B, 1), np.int32)
        mask = np.zeros((self.B, 1), bool)
        for r in rows:
            toks[r, 0] = self.slots[r]["last_tok"]
            mask[r, 0] = True
        tok = self._forward(
            tokens=toks, attn_mask=mask, sample=self._samp(),
            reset_rows=sorted(self.reset_rows),
        )
        if tok is not None:
            self._apply_step_tokens(tok, rows)

    def fail(self, err: BaseException) -> None:
        """Fan ``err`` out to every live and queued request (driver crash
        path and close share this teardown)."""
        for r in self._live():
            slot = self.slots[r]
            self.slots[r] = None
            slot["req"].error = err
            slot["req"].done.set()
        while self.queue:
            req = self.queue.popleft()
            req.error = err
            req.done.set()

    def close(self) -> None:
        try:
            self.model._end_decode_session(self.session)
        except Exception as e:
            from tensorlink_tpu.core.logging import get_logger

            get_logger("ml.batching").debug(
                "end_decode_session at close failed: %s", e
            )
        self.fail(RuntimeError("model is being unhosted"))


class ContinuousBatcher:
    """Continuous serving scheduler — GenBatcher's client API (blocking
    ``generate`` with stream demux, ``close``, ``stats``) without its
    window/drain semantics: a request starts decoding within one decode
    chunk of submission regardless of what else is in flight.

    Modes (picked from what it wraps):

    - ``engine=`` (a GenerationEngine or ContinuousEngine): drives a local
      slot engine on a dispatcher thread — the in-process serving path,
      used by tests.
    - ``model=`` single-stage DistributedModel: pure pass-through; each
      request RPCs the worker with ``continuous=True`` and the worker's
      slot engine co-batches concurrent requests (admission happens where
      the accelerator is, so there is nothing to coalesce here).
    - ``model=`` pipelined DistributedModel: a PipelinedSlotSession on a
      dispatcher thread runs slot admission through the session path.

    Requests the continuous paths can't serve (speculative-decode hints,
    penalized requests on pipelined jobs) fall back to a direct
    ``model.generate`` — never an error.
    """

    def __init__(
        self,
        model: Any = None,
        eos_ids: list[int] | None = None,
        *,
        engine: Any = None,
        max_slots: int = 8,
        page_size: int = 16,
        chunk_steps: int = 8,
        prefill_chunk: int = 128,
        prefix_cache: bool = True,
        host_tier_pages: int = 0,
        kv_quant: str = "none",
        spec_decode: bool = False,
        spec_draft: int = 8,
        spec_budget: int = 0,
        seed: int = 0,
        default_priority: str = DEFAULT_PRIORITY,
        sched_queue_cap: int = 64,
        sched_aging_ticks: int = 32,
        sched_preemption: bool = True,
        sched_policy: str = "slo",
        sched_max_wait_s: float = 60.0,
        trace_site: str = "",
        pool: Any = None,
        model_id: str = "",
        page_quota: int = 0,
        worker_role: str = "mixed",
    ):
        from collections import deque

        self.model = model
        self.eos_ids = list(eos_ids or [])
        self.seed = int(seed)
        # control-plane journal hook: (jrid, seed) called write-ahead per
        # jrid-tagged admission (the validator wires its journal here)
        self.on_admit: Callable[[str, int], None] | None = None
        self.default_priority = normalize_priority(default_priority)
        self.max_slots = int(max_slots)
        self.sched_queue_cap = int(sched_queue_cap)
        # per-class in-flight counters: the validator-side backpressure
        # view for modes whose engine lives elsewhere (remote workers /
        # pipelined sessions); local mode asks the engine scheduler
        self._inflight_cls = {c: 0 for c in PRIORITY_RANK}  #: guarded by self._idle
        self._seq = itertools.count(1)
        self._closed = False  #: guarded by self._submit_lock
        self._submit_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._served = 0  #: guarded by self._stats_lock
        self._inflight = 0  #: guarded by self._idle
        self._idle = threading.Condition()
        self.live_samples: deque[int] = deque(maxlen=1000)  #: guarded by self._stats_lock
        self._q: queue.Queue[_Pending | None] = queue.Queue()
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None
        # driver-confined control work (fleet autopilot migration verbs):
        # (fn, box) pairs the dispatcher executes against the local
        # engine between chunks — deque append/popleft are atomic
        self._ctl: deque = deque()
        # background work hook (docs/TRAINING.md "Serve-and-train"): a
        # callable the DRIVER runs once per loop iteration, between
        # serving chunks — returns True when it did work (keeps the loop
        # hot). The serve-and-train loop attaches its train tick here;
        # gating (yield to interactive/batch) lives in the tick itself.
        self._bg: Callable[[], bool] | None = None
        self._cont = None
        self._sess = None
        if engine is not None:
            from tensorlink_tpu.engine.continuous import ContinuousEngine

            self._cont = (
                engine
                if isinstance(engine, ContinuousEngine)
                else ContinuousEngine(
                    engine, max_slots=max_slots, page_size=page_size,
                    chunk_steps=chunk_steps, prefill_chunk=prefill_chunk,
                    prefix_cache=prefix_cache,
                    host_tier_pages=host_tier_pages, kv_quant=kv_quant,
                    spec_decode=spec_decode, spec_draft=spec_draft,
                    spec_budget=spec_budget,
                    default_priority=self.default_priority,
                    sched_queue_cap=sched_queue_cap,
                    sched_aging_ticks=sched_aging_ticks,
                    sched_preemption=sched_preemption,
                    sched_policy=sched_policy,
                    sched_max_wait_s=sched_max_wait_s,
                    trace_site=trace_site or "local",
                    # multi-tenant co-hosting: share ONE page pool with
                    # the other tenants under a per-model quota
                    pool=pool, model_id=model_id, page_quota=page_quota,
                )
            )
            self.mode = "local"
        elif model is not None and model.plan.n_stages == 1:
            self.mode = "remote"
        else:
            self._sess = PipelinedSlotSession(model, max_slots=max_slots)
            self.mode = "pipelined"
        self.trace_site = trace_site or "batcher"
        # configured throughput modes, surfaced by serving_modes() when
        # the engine lives in another process (remote/pipelined)
        self._modes = {
            "kv_quant": str(kv_quant or "none"),
            "weight_quant": str(
                (getattr(model, "model_spec", None) or {}).get("quant")
                or "none"
            ),
            "spec_decode": bool(spec_decode),
            # tiered prefix cache: whether evicted prefix pages demote
            # to host RAM instead of being destroyed (docs/SERVING.md
            # "Tiered prefix cache")
            "host_tier": int(host_tier_pages) > 0,
            # the ENTRY worker's advertised pool role (the validator read
            # it off the placement stats) — what serving_modes reports
            # for a remote engine before any traffic produces a snapshot
            "worker_role": str(worker_role or "mixed"),
        }
        if self.mode in ("local", "pipelined"):
            self._thread = threading.Thread(
                target=self._drive, name="cont-batcher", daemon=True
            )
            self._thread.start()

    def metrics_registry(self):
        """The engine's metrics registry when it lives in-process (local
        mode) — the validator's /metrics renders it per hosted model.
        Remote/pipelined engines expose their counters through the
        serving snapshot instead (snapshot_gauges)."""
        return self._cont.metrics if self._cont is not None else None

    def serving_modes(self) -> dict:
        """Throughput-mode summary for /healthz (cheap attribute reads —
        no engine round trip): which KV storage and decode modes this
        hosted model actually runs, so an operator/router can see a
        replica's throughput shape before sending traffic. Local mode
        reads the live engine; remote/pipelined report the configured
        knobs (the worker engine is built from the same MLConfig)."""
        if self._cont is not None:
            modes = {
                "kv_quant": self._cont.kv_quant,
                "weight_quant": (
                    getattr(self._cont.engine, "quant", None) or "none"
                ),
                "spec_decode": bool(self._cont.spec_decode),
                # tiered prefix cache: /healthz shows whether this
                # replica keeps evicted prefixes warm in host RAM
                "host_tier": self._cont.host_tier is not None,
                # disaggregated prefill/decode: which pool the serving
                # engine runs in — a fleet router reads the pool shape
                # off /healthz before placing traffic (docs/SERVING.md)
                "worker_role": str(
                    getattr(self._cont, "worker_role", "mixed")
                ),
                # serve-and-train (docs/TRAINING.md): the model version
                # this replica serves — bumps on every live weight
                # publish, so a router can see which replicas picked a
                # rolling model update up
                "weights_version": int(
                    getattr(self._cont, "weights_version", 1)
                ),
            }
            if self._cont.pool is not None:
                # co-hosting view: a router sizing placement needs the
                # tenant's quota headroom, not just the mode strings
                modes["pool"] = {
                    "quota": self._cont.alloc.quota,
                    "used": self._cont.alloc.used,
                    "free": self._cont.pool.alloc.n_free,
                }
            return modes
        # remote engines report the PLACEMENT-TIME role of the entry
        # worker (the admission point a router places traffic on). The
        # last serving snapshot is deliberately NOT consulted: after a
        # handoff it comes from whichever pool answered last (usually
        # the decode worker), and a prefill entry replica flapping to
        # "decode" on /healthz is exactly the misclassification the
        # role plumbing exists to prevent. weights_version is the one
        # genuinely DYNAMIC field: read it from the last snapshot (1
        # until traffic produces one — remote publishes ride deploys).
        modes = dict(self._modes)
        snap = getattr(self.model, "cont_serving_stats", None)
        modes["weights_version"] = int(
            (snap or {}).get("weights_version", 1)
            if isinstance(snap, dict) else 1
        )
        return modes

    def router_snapshot(self) -> dict:
        """Fleet-router scoring view (docs/SERVING.md "Fleet serving"):
        headroom + per-class depth + service EWMA + the prefix digest.
        Local mode reads the live engine; remote mode reads the last
        serving snapshot riding GENERATE_RESP (the existing stats
        sweep refreshes it) floored by the validator-side in-flight
        counts; pipelined reads the session queue. Cheap by contract —
        no device work, no worker round trip."""
        if self._cont is not None:
            return self._cont.router_snapshot()
        if self.mode == "local":
            # the driver closed the engine (error path): the replica is
            # dead — say so, so the router marks the view unhealthy
            # instead of scoring a ghost
            raise RuntimeError("local engine is closed")
        with self._idle:
            inflight = dict(self._inflight_cls)
        if self.mode == "remote":
            snap = getattr(self.model, "cont_serving_stats", None)
            snap = snap if isinstance(snap, dict) else {}
            classes = snap.get("sched_classes") or {}
            depth = {
                c: max(
                    int((classes.get(c) or {}).get("queue_depth", 0)),
                    inflight.get(c, 0),
                )
                for c in PRIORITY_RANK
            }
            live = sum(inflight.values())
            return {
                "draining": snap.get("drain_state") == "draining",
                "worker_role": self._modes.get("worker_role", "mixed"),
                "max_slots": int(snap.get("max_slots") or self.max_slots),
                "slots_free": int(
                    snap.get("slots_free", max(self.max_slots - live, 0))
                ),
                "kv_pages_free": int(snap.get("kv_pages_free") or 0),
                "kv_pages_total": int(snap.get("kv_pages_total") or 0),
                "service_ewma_s": float(
                    snap.get("sched_service_ewma_s") or 0.0
                ),
                "queue_depth": depth,
                "prefix_digest": snap.get("prefix_digest") or {},
            }
        sess = self._sess
        queued = len(sess.queue) if sess is not None else 0
        free = len(sess.free_slots) if sess is not None else 0
        return {
            "draining": False,
            "worker_role": "mixed",
            "max_slots": self.max_slots,
            "slots_free": free,
            "kv_pages_free": 0,
            "kv_pages_total": 0,
            "service_ewma_s": 0.0,
            "queue_depth": {c: queued for c in PRIORITY_RANK},
            "prefix_digest": {},
        }

    def headroom(self) -> dict:
        """The /healthz per-replica headroom fields — cheap, no ML
        round trip (the same contract as health_snapshot)."""
        return _headroom_from(self.router_snapshot())

    def set_background(self, fn: "Callable[[], bool] | None") -> None:
        """Attach (or clear) the driver's background hook — local mode
        only. The hook runs on the DISPATCHER thread after each serving
        chunk (and while idle), so anything it touches on the engine
        honors single-driver discipline for free; an exception detaches
        it loudly rather than killing the serving loop."""
        if fn is not None and (self._cont is None or self._thread is None):
            raise RuntimeError("background work requires a local engine")
        self._bg = fn
        self._wake.set()

    def publish_weights(
        self, params, *, version: int | None = None, timeout: float = 120.0,
    ) -> int:
        """Double-buffered live weight publish (docs/TRAINING.md): stage
        the new tree on device HERE (old weights keep serving while the
        transfer runs), then hot-swap it at a chunk boundary on the
        driver thread. Local mode only — remote replicas pick new
        weights up through the rolling-deploy path."""
        if self._cont is None:
            raise RuntimeError(
                "weight publish requires a local engine — remote replicas "
                "take the fleet rolling-deploy path (docs/SERVING.md)"
            )
        import jax
        import jax.numpy as jnp

        cur = getattr(self._cont.engine, "params", None)
        try:
            # stage onto the serving tree's own placements — but ONLY
            # where the current leaf is explicitly committed (sharded /
            # multi-device engines): committing a tree the engine holds
            # UNCOMMITTED would change the step's jit cache key and
            # recompile it, exactly what a publish must never do
            # (measured; _committed is the array's placement flag)
            staged = jax.tree.map(
                lambda x, c: jax.device_put(x, c.sharding)
                if getattr(c, "_committed", False)
                and getattr(c, "sharding", None) is not None
                else jnp.asarray(x),
                params, cur,
            )
        except (ValueError, TypeError):
            # weight-quantized engines hold a QTensor tree — the engine
            # quantizes the published raw tree itself; stage it plainly
            staged = jax.tree.map(jnp.asarray, params)
        jax.block_until_ready(staged)
        if self._thread is None or not self._thread.is_alive():
            raise RuntimeError("engine driver is not running")
        return self.run_on_driver(
            lambda e: e.publish_weights(staged, version=version),
            timeout=timeout,
        )

    def run_on_driver(self, fn, timeout: float = 60.0):
        """Execute ``fn(engine)`` on the dispatcher thread between
        chunks (local mode only) — the fleet autopilot's entry to the
        engine's driver-thread-only migration verbs (freeze/export/
        stage/adopt) without violating single-driver discipline."""
        if self._cont is None or self._thread is None:
            raise RuntimeError("run_on_driver requires a local engine")
        box: dict = {"done": threading.Event()}
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("model is being unhosted")
            self._ctl.append((fn, box))
            self._wake.set()
        if not box["done"].wait(timeout):
            # CANCEL, don't just abandon: an unpicked fn must never run
            # later with no waiter (a stale freeze/export would wedge
            # slots nobody will commit or abort). A fn the driver is
            # ALREADY executing when the timeout fires still completes —
            # the flag only stops un-started work.
            box["abandoned"] = True
            raise TimeoutError("driver did not pick up control work")
        if "error" in box:
            raise box["error"]
        return box.get("result")

    def pull_prefix(self, chain, limit: int, n_skip: int = 0):
        """Source side of a fleet prefix pull (docs/SERVING.md "Tiered
        prefix cache"): export this replica's resident pages covering
        ``chain`` as a stageable blob, or None when the chain already
        fell out of both tiers (the puller degrades to its next rung).
        Routed through the dispatcher because the trie walk + page
        gather are driver-thread-only; read-only, so it composes with a
        drain (unlike probe/put, which the drain fence refuses)."""
        return self.run_on_driver(
            lambda cont: cont.export_prefix_pages(
                chain, int(limit), n_skip=int(n_skip)
            )
        )

    def _run_ctl(self, cont) -> None:
        """Drain the control queue on the driver (or fail it when the
        engine is gone)."""
        while self._ctl:
            try:
                fn, box = self._ctl.popleft()
            except IndexError:
                return
            if box.get("abandoned"):
                box["done"].set()  # waiter already raised; nothing runs
                continue
            try:
                if cont is None:
                    raise RuntimeError("engine is closed")
                box["result"] = fn(cont)
            except BaseException as e:  # noqa: BLE001 — hand to the waiter
                box["error"] = e
            finally:
                box["done"].set()

    # -- client side -----------------------------------------------------
    def generate(
        self,
        ids: list[int],
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 1.0,
        stream_cb: Callable[[list[int]], None] | None = None,
        timeout: float = 600.0,
        lookahead: bool = False,
        speculative: bool = False,
        presence_penalty: float = 0.0,
        frequency_penalty: float = 0.0,
        priority: str | None = None,
        trace_id: str | None = None,
        handoff: bool = True,
        jrid: str = "",
    ) -> list[int]:
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("model is being unhosted")
            req_seed = self.seed + next(self._seq)
        priority = normalize_priority(priority or self.default_priority)
        penalized = bool(presence_penalty or frequency_penalty)
        trace_id = str(trace_id or "")
        if jrid and self.on_admit is not None:
            # crash safety (core/journal.py): tell the journal the seed
            # this admission will decode with BEFORE dispatch — with the
            # journaled prompt digest it makes the admission replayable
            try:
                self.on_admit(str(jrid), int(req_seed))
            # tlint: disable=TL005(journal telemetry must never fail an admission)
            except Exception:
                pass
        if self.mode == "remote":
            # drain accounting for close(): unhost must not tear the job
            # down under requests the worker is still decoding. Per-class
            # counts feed admission_check — the validator-side view of a
            # queue that actually lives on the worker's engine.
            with self._idle:
                self._inflight += 1
                self._inflight_cls[priority] += 1
            try:
                return self._generate_remote(
                    ids, max_new_tokens=max_new_tokens,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    stream_cb=stream_cb, lookahead=lookahead,
                    speculative=speculative,
                    presence_penalty=presence_penalty,
                    frequency_penalty=frequency_penalty, seed=req_seed,
                    priority=priority, trace_id=trace_id,
                    handoff=handoff, jrid=str(jrid or ""),
                )
            finally:
                with self._idle:
                    self._inflight -= 1
                    self._inflight_cls[priority] -= 1
                    self._idle.notify_all()
        if self.mode == "pipelined" and (penalized or lookahead):
            # features the slot session doesn't carry (per-row context
            # counts; speculation) run as a direct solo generate
            seqs = self.model.generate(
                [list(ids)], max_new_tokens=int(max_new_tokens),
                temperature=float(temperature), top_k=int(top_k),
                top_p=float(top_p), eos_ids=self.eos_ids, seed=req_seed,
                stream_cb=(
                    (lambda e: [0] if (
                        e[0] is not None and stream_cb([int(e[0])])
                    ) else None)
                    if stream_cb else None
                ),
                lookahead=lookahead and float(temperature) == 0.0
                and not penalized,
                presence_penalty=presence_penalty,
                frequency_penalty=frequency_penalty,
            )
            self._note_served()
            return [int(t) for t in seqs[0][: int(max_new_tokens)]]
        if trace_id and self.mode == "pipelined" and stream_cb is not None:
            # the pipelined session has no engine-side spans; catch the
            # first delivered token here so the trace still carries TTFT
            inner_cb = stream_cb
            first_seen = [False]
            t_sub = time.monotonic()

            def stream_cb(toks, _cb=inner_cb):
                if not first_seen[0]:
                    first_seen[0] = True
                    get_tracer().record(
                        trace_id, "first_token", site=self.trace_site,
                        dur_s=time.monotonic() - t_sub,
                    )
                return _cb(toks)

        req = _Pending(
            ids=[int(t) for t in ids],
            max_new_tokens=int(max_new_tokens),
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p), stream_cb=stream_cb,
            presence_penalty=float(presence_penalty),
            frequency_penalty=float(frequency_penalty),
            speculative=bool(speculative),
            priority=priority,
            trace_id=trace_id,
        )
        req.submit_t = time.monotonic()
        req.seed = req_seed
        req.eos_ids = self.eos_ids
        with self._submit_lock:
            if self._closed:
                raise RuntimeError("model is being unhosted")
            self._q.put(req)
            self._wake.set()
        if not req.done.wait(timeout):
            raise TimeoutError("generation timed out in the batcher")
        if req.error is not None:
            raise req.error
        self._note_served()
        return req.result or []

    def _generate_remote(
        self, ids, *, max_new_tokens, temperature, top_k, top_p, stream_cb,
        lookahead, presence_penalty, frequency_penalty, seed,
        speculative=False, priority=None, trace_id="", handoff=True,
        jrid="",
    ) -> list[int]:
        """Single-stage pass-through: the worker's slot engine is the
        scheduler, so each request ships immediately — concurrency comes
        from the API's request threads, admission (and any preemption)
        from the worker's scheduler, which reads ``priority`` off the
        GENERATE body."""
        spec = bool(lookahead) and float(temperature) == 0.0 \
            and not presence_penalty and not frequency_penalty
        cb = None
        if stream_cb is not None:
            def cb(emitted):
                if emitted and emitted[0] is not None:
                    if stream_cb([int(emitted[0])]):
                        return [0]
                return None
        seqs = self.model.generate(
            [list(ids)], max_new_tokens=int(max_new_tokens),
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p), eos_ids=self.eos_ids, seed=int(seed),
            stream_cb=cb, lookahead=spec,
            # continuous speculation rides the slot batch itself — the
            # worker's engine packs draft rows when ITS spec_decode is on
            speculative=bool(speculative),
            presence_penalty=presence_penalty,
            frequency_penalty=frequency_penalty,
            priority=priority,
            trace_id=trace_id,
            # per-request opt-out of the prefill→decode handoff on a
            # disaggregated pool (docs/SERVING.md)
            handoff=handoff,
            # the journal rid (control-plane crash safety): the worker
            # keys its live/orphan stream ledgers on it for re-attach
            jrid=str(jrid or ""),
            # legacy lookahead runs the solo engine path; everything else
            # joins the worker's slot batch
            continuous=not spec,
        )
        self._note_served()
        return [int(t) for t in seqs[0][: int(max_new_tokens)]]

    def _note_served(self) -> None:
        with self._stats_lock:
            self._served += 1

    def admission_check(self, priority=None, n: int = 1) -> dict | None:
        """The API layer's backpressure gate (None = admit, else a
        rejection record the server turns into 429 + Retry-After).

        - local mode: the engine scheduler's real admission check (class
          queue depth, estimated wait from observed service time);
        - remote / pipelined: the engine queue lives elsewhere, so the
          gate is the validator-side per-class in-flight count against
          the same cap — coarser, but it bounds the queue the worker
          would otherwise accumulate (its own scheduler still backstops
          with SchedulerOverloaded).
        """
        cls = normalize_priority(priority or self.default_priority)
        if self._cont is not None:
            return self._cont.admission_check(cls, n)
        with self._idle:
            depth = self._inflight_cls.get(cls, 0)
        if self.mode == "pipelined":
            depth = max(depth, len(self._sess.queue) if self._sess else 0)
        if depth + n > self.sched_queue_cap:
            return {
                "priority": cls,
                "queue_depth": depth,
                "cap": self.sched_queue_cap,
                # no service-time estimator on this side: scale by how
                # oversubscribed the class is, clamped like the engine's
                "retry_after": max(
                    1.0, min(depth / max(self.max_slots, 1) * 5.0, 600.0)
                ),
            }
        return None

    # -- dispatcher ------------------------------------------------------
    def _drain_queue(self, limit: int) -> list[_Pending]:
        out: list[_Pending] = []
        while len(out) < limit:
            try:
                nxt = self._q.get_nowait()
            except queue.Empty:
                break
            if nxt is None:
                # under the submit lock like every other _closed write: a
                # generate() racing the close sentinel must observe either
                # open-and-enqueued or closed-and-refused, never a torn
                # read (found by tlint TL001)
                with self._submit_lock:
                    self._closed = True
                break
            out.append(nxt)
        return out

    def _drive(self) -> None:
        """Dispatcher loop: admit whatever is queued, decode one chunk,
        repeat; park on the wake event when idle."""
        sess = self._sess
        cont = self._cont
        while True:
            try:
                if cont is not None:
                    self._run_ctl(cont)  # autopilot verbs, driver-confined
                    for req in self._drain_queue(1 << 30):
                        self._submit_local(req)
                    busy = cont.has_work()
                    if busy:
                        with self._stats_lock:
                            self.live_samples.append(cont.live_slots)
                        cont.step_chunk()
                    bg = self._bg
                    if bg is not None:
                        # background work (serve-and-train ticks) runs at
                        # chunk granularity on THIS thread — between
                        # serving chunks, never under one. A tick that
                        # raises detaches itself; serving never dies for
                        # a training bug.
                        try:
                            if bg():
                                busy = True
                        except BaseException:  # noqa: BLE001 — detach loudly
                            from tensorlink_tpu.core.logging import get_logger

                            get_logger("ml.batching").exception(
                                "background task failed — detaching it"
                            )
                            self._bg = None
                else:
                    for req in self._drain_queue(1 << 30):
                        sess.submit(req)
                    sess.pump()
                    live = sess._live()
                    if live:
                        with self._stats_lock:
                            self.live_samples.append(len(live))
                        sess.step()
                    busy = sess.has_work()
            except BaseException as e:  # noqa: BLE001 — fan out and keep serving
                if cont is not None:
                    # the local engine is gone: refuse NEW work loudly (the
                    # _closed check) and fail everything already queued —
                    # otherwise callers block their full client timeout on
                    # requests that can never run
                    with self._submit_lock:
                        self._closed = True
                    cont.close(e)
                    self._cont = cont = None
                    self._run_ctl(None)  # fail waiters, don't hang them
                    while True:
                        try:
                            req = self._q.get_nowait()
                        except queue.Empty:
                            return
                        if req is not None:
                            req.error = e
                            req.done.set()
                sess.fail(e)
                busy = False
            with self._submit_lock:
                closed = self._closed
            if closed and not busy and self._q.empty():
                self._run_ctl(None)  # nothing races a finished driver
                return
            if not busy:
                self._wake.wait(timeout=0.05)
                self._wake.clear()

    def _submit_local(self, req: "_Pending") -> None:
        from tensorlink_tpu.engine.sampling import SamplingParams

        def tok_cb(tok: int) -> bool:
            if req.stream_cb is not None:
                return bool(req.stream_cb([int(tok)]))
            return False

        def on_finish(creq) -> None:
            if creq.error is not None:
                req.error = creq.error
            else:
                req.result = [
                    int(t) for t in creq.tokens[: req.max_new_tokens]
                ]
            req.done.set()

        self._cont.submit(
            req.ids, max_new_tokens=req.max_new_tokens,
            sampling=SamplingParams.make(
                temperature=req.temperature, top_k=req.top_k,
                top_p=req.top_p, presence_penalty=req.presence_penalty,
                frequency_penalty=req.frequency_penalty,
            ),
            eos_ids=self.eos_ids, seed=req.seed,
            priority=req.priority,
            stream_cb=tok_cb, on_finish=on_finish,
            trace_id=req.trace_id,
            speculative=req.speculative,
        )

    def stats(self) -> dict | None:
        with self._stats_lock:
            served = self._served
            live = list(self.live_samples)
        if not served and not live:
            return None
        out = {"requests": served, "continuous": True, "mode": self.mode}
        if live:
            out["mean_live_slots"] = round(sum(live) / len(live), 2)
            out["max_live_slots"] = max(live)
        # ONE telemetry shape for both engine locations: the slot
        # engine's full serving_snapshot() (scheduler counters +
        # prefix-cache/occupancy) under "engine" — locally from the
        # in-process engine, for single-stage remote jobs from the
        # snapshot riding each GENERATE_RESP (ml/module.py::_note_serving)
        if self._cont is not None:
            st = self._cont.stats
            if st["slot_steps_total"]:
                out["slot_occupancy"] = round(
                    st["slot_steps_live"] / st["slot_steps_total"], 3
                )
            out["engine"] = self._cont.serving_snapshot()
        elif self.mode == "remote":
            snap = getattr(self.model, "cont_serving_stats", None)
            if isinstance(snap, dict) and snap:
                out["engine"] = snap
        return out

    def close(self, timeout: float = 600.0) -> None:
        """Serve everything already submitted, then stop."""
        with self._submit_lock:
            self._closed = True
            if self._thread is not None:
                self._q.put(None)
                self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():
                # the driver is wedged mid-decode: do NOT touch the engine
                # from this thread (concurrent mutation of slots/cache
                # could double-fire responses) — say so, like GenBatcher
                from tensorlink_tpu.core.logging import get_logger

                get_logger("ml.batching").warning(
                    "ContinuousBatcher.close(): dispatcher did not drain "
                    "within %.0fs; a slot decode may still be in flight",
                    timeout,
                )
                return
        if self.mode == "remote":
            # in-flight pass-through requests are blocked inside worker
            # RPCs — wait them out so unhost doesn't tear the job down
            # under a live decode
            deadline = time.monotonic() + timeout
            with self._idle:
                while self._inflight > 0:
                    left = deadline - time.monotonic()
                    if left <= 0 or not self._idle.wait(timeout=min(left, 5.0)):
                        if time.monotonic() >= deadline:
                            break
        # local engines may still hold queued work if the driver died
        if self._cont is not None:
            self._cont.close()
        if self._sess is not None:
            self._sess.close()
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            if req is not None:
                req.error = RuntimeError("model is being unhosted")
                req.done.set()
        self._run_ctl(None)  # control waiters must not hang on a close


__all__ = ["GenBatcher", "ContinuousBatcher", "PipelinedSlotSession"]
