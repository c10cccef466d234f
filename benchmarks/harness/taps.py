"""What the benchmark hangs on the program from outside, the way
``chip_smoke.py`` taps ``ContinuousEngine.submit``. Nothing inside
``tensorlink_tpu/`` changes; what should move inside it is listed in
PERF.md for the ``tracing`` issue.

* :func:`tap_submit`: every request admitted to a slot engine in this
  process. A request served by anything else never shows up.
* :class:`BuildCounter`: every executable built (compiled or fetched from
  the persistent cache), with the time it happened.
* :class:`ChunkTracer`: ``TraceAnnotation``s around ``step_chunk`` and its
  phases, one record per chunk (decode steps, each slot's context length),
  and, in a ``--trace 1`` run, the profiler started and stopped on chunk
  boundaries so that the traced window holds whole chunks. It is installed
  in every run: the frames of its wrappers are in the step program's
  source locations, which are part of the compile cache's key, so a run
  without them would compile a second copy of the same program.
"""

from __future__ import annotations

import threading
import time


def tap_submit() -> list:
    from tensorlink_tpu.engine.continuous import ContinuousEngine

    seen: list = []
    orig = ContinuousEngine.submit

    def submit(self, prompt, **kw):
        req = orig(self, prompt, **kw)
        seen.append(req)
        return req

    ContinuousEngine.submit = submit
    return seen


class BuildCounter:
    def __init__(self):
        import jax

        self.built: list[tuple[str, float, float]] = []  # name, secs, at
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.built.append(
                (str(kw.get("fun_name", "?")), float(duration), time.monotonic())
            )

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def since(self, t: float) -> list[tuple[str, float, float]]:
        return [b for b in self.built if b[2] >= t]


def _contexts(eng, grants: dict | None = None) -> list[int]:
    """Each slot's context length in tokens, from the engine's host-side
    records (no device read)."""
    out = []
    for s, req in enumerate(eng._slots):
        if req is None:
            out.append(0)
        elif s in eng._prefilling:
            out.append(int(req.prefill_pos) + int((grants or {}).get(s, 0)))
        else:
            out.append(len(req.prompt) + len(req.tokens))
    return out


class ChunkTracer:
    """Annotates and records the engine's chunks; once :meth:`arm` was
    called, traces whole chunks until ``min_seconds`` have passed (two at
    least)."""

    def __init__(self, trace_dir: str, min_seconds: float):
        self.trace_dir = trace_dir
        self.min_seconds = float(min_seconds)
        self.traced: list[dict] = []  # the chunks inside the trace
        self.t_start = None  # monotonic, when the trace began
        self._stopper = None
        self.stall_s: dict = {}  # how long starting and stopping took
        self._armed = False
        self._tracing = False
        self._cur: dict | None = None
        self._install()

    def arm(self) -> None:
        self._armed = True

    def _install(self) -> None:
        import jax
        from tensorlink_tpu.engine.continuous import ContinuousEngine as CE

        ann = jax.profiler.TraceAnnotation
        tracer = self
        orig_step, orig_admit = CE.step_chunk, CE._admit
        orig_pack, orig_ops = CE._pack_ragged, CE._step_operands

        def _admit(eng):
            with ann("bench:admission"):
                return orig_admit(eng)

        def _pack_ragged(eng):
            with ann("bench:pack_ragged"):
                pack = orig_pack(eng)
            if pack is not None and tracer._cur is not None:
                tracer._cur["ctx_before"] = _contexts(eng, pack[-1])
            return pack

        def _step_operands(eng, *a, **kw):
            if tracer._cur is not None:
                tracer._cur["real"] = True
            return orig_ops(eng, *a, **kw)

        def step_chunk(eng, *a, **kw):
            if tracer._armed and not tracer._tracing and eng.has_work():
                tracer._armed = False
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                t = time.monotonic()
                jax.profiler.start_trace(tracer.trace_dir,
                                         profiler_options=opts)
                tracer._tracing = True
                tracer.t_start = time.monotonic()
                tracer.stall_s["start"] = tracer.t_start - t
            steps0 = eng.stats["decode_steps"]
            cur = tracer._cur = {"t0": time.monotonic(), "real": False}
            with ann("bench:step_chunk"):
                out = orig_step(eng, *a, **kw)
            tracer._cur = None
            if cur["real"]:
                cur["t1"] = time.monotonic()
                cur["decode_steps"] = eng.stats["decode_steps"] - steps0
                cur["ctx_after"] = _contexts(eng)
                if tracer._tracing:
                    tracer.traced.append(cur)
                    if (len(tracer.traced) >= 2 and cur["t1"] - tracer.t_start
                            >= tracer.min_seconds):
                        # the window is these chunks; stopping and writing
                        # takes the profiler many seconds, so another
                        # thread does it and the engine goes on
                        tracer._tracing = False
                        tracer._stopper = threading.Thread(
                            target=tracer._stop, daemon=True)
                        tracer._stopper.start()
            return out

        CE._admit, CE._pack_ragged = _admit, _pack_ragged
        CE._step_operands, CE.step_chunk = _step_operands, step_chunk

    def _stop(self) -> None:
        import jax

        t = time.monotonic()
        jax.profiler.stop_trace()
        self.stall_s["stop"] = time.monotonic() - t

    def finish(self) -> None:
        """After the window, from the main thread: wait for the trace to be
        written, or stop one that never saw its last chunk (the engine ran
        out of work)."""
        self._armed = False
        if self._tracing:
            self._tracing = False
            self._stop()
        elif self._stopper is not None:
            self._stopper.join()
