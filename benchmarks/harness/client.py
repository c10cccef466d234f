"""The load generator: one thread, one selector, every stream of
``POST /v1/generate`` with ``stream: true`` read as it arrives.

A token is stamped with ``time.monotonic()`` taken when the socket that
carries its SSE event became readable. The benchmark's tokenizer makes one
character per token, so an event's text length is its token count. The
generator's own lateness (sent minus due) is kept for ``gen_late_p99_ms``.
"""

from __future__ import annotations

import json
import selectors
import socket
import time

from .e2e import Rec
from .plan import Plan, Req
from .tokenizer import random_text


class _Stream:
    __slots__ = ("rec", "sock", "buf", "head_done", "status_line", "client",
                 "req", "message")

    def __init__(self, rec, sock, client, req):
        self.rec, self.sock, self.client, self.req = rec, sock, client, req
        self.buf = b""
        self.head_done = False
        self.status_line = b""


class LoadDriver:
    def __init__(self, port: int, model: str, vocab_size: int, run_seed: int):
        self.port, self.model = port, model
        self.vocab_size, self.run_seed = vocab_size, run_seed
        self.template_tokens = 0  # the chat template's length, from warm-up
        self.sel = selectors.DefaultSelector()
        self.recs: list[Rec] = []
        self.live: dict[int, _Stream] = {}  # fileno -> stream
        self.sessions: dict[int, list[dict]] = {}
        self.system_text = ""
        self._rid = 0

    # -- building a request --------------------------------------------
    def body_for(self, req: Req, message: str | None = None) -> dict:
        n = req.prompt_tokens
        if req.count_template:
            n -= self.template_tokens
        if n < 1:
            raise ValueError(
                f"request {req.idx}: {req.prompt_tokens} prompt tokens leave "
                f"no room beside a template of {self.template_tokens}"
            )
        if message is None:
            message = random_text(req.content_seed, n, self.vocab_size)
        body = {
            "hf_name": self.model,
            "message": message,
            "max_new_tokens": int(req.output_tokens),
            "do_sample": False, "stream": True,
        }
        if req.session is not None:
            hist = self.sessions.setdefault(req.session, [])
            if not hist and self.system_text:
                hist.append({"role": "system", "content": self.system_text})
            body["history"] = list(hist)
        return body

    # -- sending ----------------------------------------------------------
    def send(self, req: Req, due: float, *, measured: bool = True,
             client: int | None = None, message: str | None = None) -> Rec:
        body = self.body_for(req, message)
        self._rid += 1
        rid = f"bench-{self.run_seed % 100000}-{self._rid}"
        rec = Rec(idx=req.idx, due=due, asked=int(req.output_tokens), rid=rid,
                  measured=measured)
        self.recs.append(rec)
        payload = json.dumps(body).encode()
        try:
            sock = socket.create_connection(("127.0.0.1", self.port), timeout=10)
            sock.sendall(
                b"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
                b"Content-Type: application/json\r\n"
                + f"X-Request-Id: {rid}\r\n".encode()
                + f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload
            )
            sock.setblocking(False)
        except OSError as e:
            rec.sent = time.monotonic()
            rec.status, rec.detail = "error", f"connect/send: {e}"
            rec.end = rec.sent
            return rec
        rec.sent = time.monotonic()
        st = _Stream(rec, sock, client, req)
        st.message = body["message"]
        self.live[sock.fileno()] = st
        self.sel.register(sock, selectors.EVENT_READ, st)
        return rec

    # -- reading ----------------------------------------------------------
    def poll(self, timeout: float) -> list[_Stream]:
        """Read whatever arrived within ``timeout``; returns the streams
        that ended."""
        ended = []
        for key, _ in self.sel.select(max(timeout, 0.0)):
            st: _Stream = key.data
            now = time.monotonic()
            try:
                data = st.sock.recv(1 << 16)
            except BlockingIOError:
                continue
            except OSError as e:
                self._close(st, now, "error", f"recv: {e}")
                ended.append(st)
                continue
            if data:
                st.buf += data
                self._parse(st, now)
                if st.rec.status != "open":
                    self._close(st, now, st.rec.status, st.rec.detail)
                    ended.append(st)
            else:
                if st.rec.status == "open":
                    st.rec.status = "error"
                    st.rec.detail = "stream closed without [DONE]"
                self._close(st, now, st.rec.status, st.rec.detail)
                ended.append(st)
        return ended

    def _parse(self, st: _Stream, now: float) -> None:
        rec = st.rec
        if not st.head_done:
            head, sep, rest = st.buf.partition(b"\r\n\r\n")
            if not sep:
                return
            st.head_done = True
            st.status_line = head.split(b"\r\n", 1)[0]
            st.buf = rest
            if b" 200 " not in st.status_line + b" ":
                rec.status = "refused" if b" 429 " in st.status_line else "error"
                rec.detail = (st.status_line + b" " + rest[:200]).decode(
                    "utf-8", "replace")
                return
        while True:
            block, sep, rest = st.buf.partition(b"\n\n")
            if not sep:
                return
            st.buf = rest
            line = block.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):]
            if data == b"[DONE]":
                if rec.status == "open":
                    rec.status = "ok"
                return
            ev = json.loads(data)
            if "error" in ev:
                rec.status, rec.detail = "error", str(ev["error"])[:200]
                return
            usage = ev.get("usage")
            if usage:
                rec.prompt_tokens = usage.get("prompt_tokens")
                rec.completion_tokens = usage.get("completion_tokens")
            piece = ev.get("token") or ""
            if piece:
                rec.text += piece
                rec.stamps.extend([now] * len(piece))

    def _close(self, st: _Stream, now: float, status: str, detail: str) -> None:
        st.rec.status, st.rec.detail = status, detail
        st.rec.end = now
        try:
            self.sel.unregister(st.sock)
        except (KeyError, ValueError):
            pass
        self.live.pop(st.sock.fileno(), None)
        st.sock.close()
        if st.req.session is not None and status == "ok":
            hist = self.sessions[st.req.session]
            hist.append({"role": "user", "content": st.message})
            hist.append({"role": "assistant", "content": st.rec.text})

    def cut_all(self) -> None:
        """Drop every stream still open: each is cut, not failed."""
        now = time.monotonic()
        for st in list(self.live.values()):
            self._close(st, now, "cut", "cut after the window")

    # -- whole phases -----------------------------------------------------
    def one(self, req: Req, timeout: float = 600.0,
            message: str | None = None) -> Rec:
        """Send one request outside the window and read it to its end."""
        rec = self.send(req, time.monotonic(), measured=False, message=message)
        deadline = time.monotonic() + timeout
        while rec.status == "open" and time.monotonic() < deadline:
            self.poll(0.05)
        if rec.status == "open":
            self.cut_all()
            rec.status, rec.detail = "error", f"no end within {timeout}s"
        return rec


def run_closed(drv: LoadDriver, plan: Plan, seconds: float, grace: float,
               ramp_timeout: float = 120.0, on_open=None, on_close=None):
    """Clients start in set-up; the window opens once every one of them
    has its first token, and they are cut off after it. Returns
    ``(t0, t1)``."""
    nxt = [0] * len(plan.clients)
    cur: dict[int, Rec] = {}

    def start(c: int) -> None:
        reqs = plan.clients[c]
        cur[c] = drv.send(reqs[nxt[c] % len(reqs)], time.monotonic(), client=c)
        nxt[c] += 1

    def pump(timeout: float, restart: bool = True) -> None:
        for st in drv.poll(timeout):
            if st.client is not None and restart:
                start(st.client)

    for c in range(len(plan.clients)):
        start(c)
    deadline = time.monotonic() + ramp_timeout
    while any(not r.stamps and r.status == "open" for r in cur.values()):
        if time.monotonic() > deadline:
            raise TimeoutError("ramp: a client got no first token")
        pump(0.02)
    t0 = time.monotonic()
    t1 = t0 + seconds
    if on_open:
        on_open(t0)
    while (now := time.monotonic()) < t1:
        pump(min(0.05, t1 - now))
    if on_close:
        on_close(t1)
    # the wait: first tokens of what started in the window
    limit = t1 + grace
    while time.monotonic() < limit and any(
        r.due >= t0 and r.status == "open" and not r.stamps for r in cur.values()
    ):
        pump(0.05, restart=False)
    drv.cut_all()
    return t0, t1


def run_open(drv: LoadDriver, plan: Plan, seconds: float, grace: float,
             on_open=None, on_close=None):
    """Arrivals by the schedule whatever the system does. Returns
    ``(t0, t1)``; ``t0`` is ``lead_in_s`` after the first arrival slot."""
    start = time.monotonic()
    t0 = start + plan.lead_in_s
    t1 = t0 + seconds
    todo = list(plan.schedule)
    i = 0
    opened = False
    while True:
        now = time.monotonic()
        if not opened and now >= t0:
            opened = True
            if on_open:
                on_open(t0)
        while i < len(todo) and t0 + todo[i].due_s <= now:
            due = t0 + todo[i].due_s
            drv.send(todo[i], due, measured=todo[i].due_s >= 0)
            i += 1
            now = time.monotonic()
        if now >= t1:
            break
        nxt = t0 + todo[i].due_s if i < len(todo) else t1
        drv.poll(min(max(nxt - now, 0.0), 0.05, t1 - now))
    if on_close:
        on_close(t1)
    limit = t1 + grace
    while time.monotonic() < limit and drv.live:
        drv.poll(0.05)
    drv.cut_all()
    return t0, t1
