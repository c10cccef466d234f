"""End-to-end request tracing + the engine flight recorder.

**Tracing.** The API server mints one trace id per HTTP request (echoed
as ``X-Request-Id``); the id rides the GENERATE / session-op / MIGRATE
wire frames, and every hop records *spans* — host-side timing records
(queue-wait, admission, per-prefill-chunk, first-token, decode,
freeze/export/stage/adopt) — into its process-local :class:`Tracer`.
Spans recorded on a remote worker ride its responses back (the
``trace`` field next to the serving snapshot) and are :meth:`ingested
<Tracer.ingest>` into the validator's tracer, so a stream migrated
between workers stitches spans from BOTH under one trace id, queryable
at ``GET /trace/<rid>``.

Hot-path contract (the reason this is a module and not a logging
sprinkle): spans are recorded only at boundaries the host already
synchronizes (the per-chunk boundary in the slot engine, admission, the
migration verbs). Recording is a ``time.monotonic()`` read plus a dict
append under a short lock — no device sync, no compiled programs, and
with no trace id on a request the engine skips the calls entirely.

Span timestamps: ``dur_ms`` comes from ``time.monotonic`` pairs on one
host (drift-free). ``t0`` is the span's START on the same clock
(``time.monotonic()`` seconds: CLOCK_MONOTONIC, one clock for every
process of a host, the clock of the flight recorder's ``t0``), ``host``
a short tag of the machine's boot: two ``t0`` are compared, and spans
laid end to end, only where their ``host`` agrees. ``parent`` is the
``sid`` of the span that caused this one (empty at the root). ``ts`` is
a wall-clock epoch anchor recorded ONCE per span for cross-worker
ordering/joining only — it is never subtracted or compared for
durations (tlint TL004 discipline).

**The request path.** From the API's handler to the first SSE delta a
request crosses four processes; a span is recorded at every boundary,
where the work or the wait happens (``PATH_SPANS``; docs/SERVING.md
"Telemetry" has the table). A start that was taken in another process
rides the frame as a :func:`stamp` ``{t, host}``; the receiver records
``dur_ms`` only where the stamp's ``host`` is its own
(:meth:`Tracer.record_since`). Only the FIRST ``send_token`` of a
stream carries one: nothing is stamped per token, step or chunk.

**Flight recorder.** A bounded per-engine ring of per-step records
(occupied slots, prefill grants, tokens emitted, page occupancy,
preemptions, and the chunk's host phases in ms beside ``t0``, the
monotonic stamp of its entry), appended at the same per-chunk boundary,
dumped on engine error — chaos-test postmortems read data instead of
print archaeology. A record's ``step`` is the id its chunk carries
everywhere else: the ``chunk=`` argument of the engine's ``tlink:chunk``
profiler annotation and the ``chunk`` attribute of the request spans
that rode in it.
"""

from __future__ import annotations

import contextvars
import itertools
import secrets
import threading
import time
from collections import OrderedDict, deque


def _boot_tag() -> str:
    """A short tag of this machine's boot: every process of the host
    reads the same one, a reboot or another machine reads another.
    Where the kernel offers none, a tag of this process alone — its
    stamps then compare with nobody else's, which is the safe side."""
    try:
        with open("/proc/sys/kernel/random/boot_id", encoding="ascii") as f:
            return f.read().strip().replace("-", "")[:12]
    except OSError:
        return "p" + secrets.token_hex(5)


#: the clock ``time.monotonic()`` reads here, by name (span field ``host``)
HOST = _boot_tag()

# the request path's spans, in the order a request crosses them; the
# recording sites use these names and benchmarks/layer_metrics/*.json
# select them (tests/test_metrics.py holds the two together)
HTTP_FIRST_BYTE = "http_first_byte"  # api: handler entry -> first delta out
API_IN = "api_in"  # api: handler entry -> generate_api on its pool thread
PREPARE = "prepare"  # validator: chat template, encode -> handed on
HOP_IN = "hop_in"  # module's bridge -> the worker's work queue
WORK_WAIT = "work_wait"  # in the worker's work queue -> handler starts
SUBMIT = "submit"  # worker: handler start -> the engine stamped the request
TOKEN_OUT = "token_out"  # engine hands on the first token -> delta drained
PATH_SPANS = (
    HTTP_FIRST_BYTE, API_IN, PREPARE, HOP_IN, WORK_WAIT, SUBMIT,
    # the engine's own (engine/continuous.py), contiguous from submit:
    # queue_wait + prefill + first_decode == first_token
    "queue_wait", "admission", "prefill_chunk", "prefill", "first_decode",
    "first_token", TOKEN_OUT,
)
# which span caused which, among one request's spans on the engine; the
# two that end AFTER what they caused get their sid ahead of themselves
SPANS_NAMED_AHEAD = ("first_token", "prefill")
# tlint: disable=TL006(which span caused which — read-only table)
ENGINE_SPAN_PARENT = {
    "first_token": SUBMIT,
    "queue_wait": "first_token",
    "admission": "queue_wait",
    "prefill": "first_token",
    "prefill_chunk": "prefill",
    "first_decode": "first_token",
}


def stamp() -> dict:
    """A moment on this host's monotonic clock, fit to cross a process
    boundary in a frame: the receiver measures from it only where
    ``host`` is its own (:meth:`Tracer.record_since`)."""
    return {"t": time.monotonic(), "host": HOST}


# the stamp the engine took as it handed a stream's first token on
# (where ``first_token`` ends, with that span's sid as ``parent``), on
# the thread that runs the stream callbacks: the engine sets it around
# the first token's callback, the worker's callback sends it with the
# stream's first frame, ml/module.py sets it as the relay delivers that
# frame, and the API's delta callback, further down the same call,
# reads it: ``token_out`` starts there
first_token_stamp: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "tlink_first_token_stamp", default=None
)

# the sid of the span that causes what the CURRENT thread does next for
# its request: set as api_in and prepare are recorded, read where the
# next hop's stamp is made (``parent`` of the span recorded from it)
current_span: contextvars.ContextVar[str] = contextvars.ContextVar(
    "tlink_span", default=""
)

# active trace id for log joining (core/logging.py json mode): set by the
# code driving a request on the CURRENT thread (generate_api entry, the
# API handler); contextvars keep thread/task isolation for free
current_trace: contextvars.ContextVar[str] = contextvars.ContextVar(
    "tlink_trace", default=""
)


def mint_trace_id() -> str:
    """A fresh request/trace id (also the ``X-Request-Id`` echo)."""
    return secrets.token_hex(8)


class Tracer:
    """Bounded per-process span store keyed by trace id.

    One instance per process (:func:`get_tracer`); several in-process
    nodes (the test clusters run every node's ML thread in one process)
    share it, so every span carries its recording ``site`` (node id /
    engine tag) and a process-unique ``sid`` — :meth:`ingest` dedups on
    ``sid`` so a span that arrives both locally and over the wire lands
    once."""

    def __init__(self, max_traces: int = 512, max_spans: int = 256):
        self.max_traces = int(max_traces)
        self.max_spans = int(max_spans)
        self._lock = threading.Lock()
        self._traces: OrderedDict[str, list[dict]] = OrderedDict()  #: guarded by self._lock
        self._sid = itertools.count(1)
        # process-unique sid prefix: two processes ingesting each other's
        # spans must never collide on (prefix, n)
        self._tag = secrets.token_hex(4)

    # -- recording -------------------------------------------------------
    def new_sid(self) -> str:
        """A span id ahead of its span, for a span that others name as
        their ``parent`` before it is recorded (a span is recorded at
        its END; what it caused may end first)."""
        return f"{self._tag}:{next(self._sid)}"

    def record(
        self,
        trace_id: str,
        name: str,
        *,
        site: str = "",
        dur_s: float | None = None,
        t0: float | None = None,
        parent: str = "",
        sid: str = "",
        host: str = "",
        **attrs,
    ) -> str:
        """Append one span; returns its ``sid`` ("" when ``trace_id`` is
        empty and nothing was stored). ``dur_s`` is a monotonic-pair
        duration measured by the caller (None = an instantaneous event,
        or a span whose start was stamped on another host's clock).
        ``t0`` is its start on the monotonic clock named by ``host``
        (this process's unless given); left out, the span is taken to
        end now: ``t0`` = now - ``dur_s``."""
        if not trace_id:
            return ""
        if t0 is None:
            t0 = time.monotonic() - (dur_s or 0.0)
        span = {
            "sid": sid or self.new_sid(),
            "name": str(name),
            "site": str(site),
            # wall anchor for cross-worker ordering/log joining ONLY —
            # durations always come from the monotonic pair in dur_ms
            "ts": time.time(),
            "t0": float(t0),
            "host": host or HOST,
            "parent": str(parent or ""),
        }
        if dur_s is not None:
            span["dur_ms"] = round(float(dur_s) * 1e3, 4)
        if attrs:
            span.update(attrs)
        with self._lock:
            spans = self._traces.get(trace_id)
            if spans is None:
                spans = []
                self._traces[trace_id] = spans
                while len(self._traces) > self.max_traces:
                    self._traces.popitem(last=False)  # LRU-ish: oldest out
            if len(spans) < self.max_spans:
                spans.append(span)
        return span["sid"]

    def record_since(
        self,
        trace_id: str,
        name: str,
        start: dict | None,
        *,
        end: dict | float | None = None,
        site: str = "",
        parent: str = "",
        **attrs,
    ) -> str:
        """A span that began at a :func:`stamp` ``start`` (taken in any
        process, maybe on another machine) and ends at ``end``: a stamp,
        a reading of this process's ``time.monotonic()``, or now. It
        gets a ``dur_ms`` only where both ends lie on one host's clock;
        a start stamped on a foreign host gives a span at that host's
        ``t0`` with no duration. A frame without a stamp (an old peer)
        records nothing."""
        if not trace_id or not isinstance(start, dict) or "t" not in start:
            return ""
        host = str(start.get("host") or "")
        if isinstance(end, dict):
            end_t, end_host = end.get("t"), str(end.get("host") or "")
        else:
            end_t = time.monotonic() if end is None else end
            end_host = HOST
        t0 = float(start["t"])
        dur = None
        if host and host == end_host and end_t is not None:
            dur = max(float(end_t) - t0, 0.0)
        return self.record(
            trace_id, name, site=site, dur_s=dur, t0=t0,
            parent=parent or str(start.get("parent") or ""),
            host=host or "?", **attrs,
        )

    # -- merge / query ---------------------------------------------------
    def ingest(self, trace_id: str, spans: list[dict]) -> int:
        """Merge spans that arrived over the wire (a worker's response).
        Dedups on ``sid`` — duplicated frames / in-process double-sight
        (local record + wire echo) land once. Returns spans added."""
        if not trace_id or not spans:
            return 0
        added = 0
        with self._lock:
            mine = self._traces.get(trace_id)
            if mine is None:
                mine = []
                self._traces[trace_id] = mine
                while len(self._traces) > self.max_traces:
                    self._traces.popitem(last=False)
            seen = {s.get("sid") for s in mine}
            for s in spans:
                if not isinstance(s, dict) or s.get("sid") in seen:
                    continue
                if len(mine) >= self.max_spans:
                    break
                mine.append(dict(s))
                seen.add(s.get("sid"))
                added += 1
        return added

    def collect(self, trace_id: str) -> list[dict]:
        """All spans recorded/ingested for a trace, in start order: by
        ``t0`` among the spans of one ``host`` (one clock, whichever
        process stamped them: two processes of a host may read the wall
        clock in the wrong order within a millisecond), by the wall
        anchor ``ts`` across hosts and for spans of a peer that sends no
        ``t0``. A copy."""
        with self._lock:
            spans = list(self._traces.get(trace_id, ()))
        spans.sort(key=lambda s: s.get("ts", 0.0))
        # each host's spans keep the places ts gave that host, and take
        # them in the order of their own clock
        places: dict[str, list[int]] = {}
        for i, s in enumerate(spans):
            if "t0" in s and s.get("host"):
                places.setdefault(s["host"], []).append(i)
        for idx in places.values():
            for i, s in zip(idx, sorted(
                (spans[i] for i in idx),
                # of two that start together the longer one holds the other
                key=lambda s: (s["t0"], -s.get("dur_ms", 0.0)),
            )):
                spans[i] = s
        return spans

    def known(self, trace_id: str) -> bool:
        with self._lock:
            return trace_id in self._traces

    def reset(self) -> None:
        """Drop every stored trace (tests / bench isolation)."""
        with self._lock:
            self._traces.clear()


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-global tracer: the API server, locally-hosted engines
    and the DistributedModel ingest side all share it, which is what
    makes ``GET /trace/<rid>`` one lookup."""
    return _TRACER


class FlightRecorder:
    """Bounded ring of per-engine-step records — the postmortem buffer.

    The engine appends one record per ``step_chunk`` boundary (already a
    host sync point; the append is a deque op). On engine error the ring
    is dumped (``last_dump``) so a chaos failure ships its final N steps
    of slot/page state with the exception instead of losing them."""

    def __init__(self, capacity: int = 1024):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._ring: deque[dict] = deque(maxlen=self.capacity)  #: guarded by self._lock
        self._next = 1  # single writer: the engine's driver thread
        self.last_dump: dict | None = None  #: guarded by self._lock

    @property
    def next_step(self) -> int:
        """The ``step`` the next :meth:`record` will carry: what the
        engine names a chunk by while it runs, before its record exists."""
        return self._next

    def record(self, **fields) -> None:
        rec = {"step": self._next, **fields}
        self._next += 1
        with self._lock:
            self._ring.append(rec)

    def records(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def dump(self, error: BaseException | None = None) -> dict:
        """Snapshot the ring (with the triggering error) and remember it
        on ``last_dump`` for tests/operators to query after teardown."""
        with self._lock:
            out = {
                "error": (
                    f"{type(error).__name__}: {error}" if error else None
                ),
                "n_records": len(self._ring),
                "records": list(self._ring),
            }
            self.last_dump = out
        return out


__all__ = [
    "ENGINE_SPAN_PARENT",
    "FlightRecorder",
    "HOST",
    "PATH_SPANS",
    "SPANS_NAMED_AHEAD",
    "Tracer",
    "current_span",
    "current_trace",
    "first_token_stamp",
    "get_tracer",
    "mint_trace_id",
    "stamp",
]
