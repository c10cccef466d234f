"""End-to-end metrics from the client's own stamps. Pure arithmetic: the
tests drive it with hand-made stamps.

Every clock is the client's. A request is timed from when it was **due**
(open loop: its place in the schedule; closed loop: the moment its client
became free). A token is stamped when the SSE event that carries it is
read. The window is ``[t0, t1)``.

* ``out_tok_s``: tokens whose stamp lies in the window, whatever request
  they belong to, over the window's length. A request that straddles an
  edge contributes the tokens that arrived inside.
* ``ttft_p90_ms``: over every request due in the window, first token minus
  due time. A request refused, failed, or without a first token when the
  wait after the window ends counts as failed and as the worst: it is
  given the time from its due moment to the end of the wait.
* ``tpot_p50_ms``: median over completed requests of
  (last token - first token) / (tokens - 1).
* ``itl_max_p50_ms``: median over the same requests of the longest gap
  between two consecutive tokens.
* ``stall8_p50_ms``: median over the same requests (those of more than
  ``STALL_TOKENS`` tokens) of the longest time to receive ``STALL_TOKENS``
  more tokens, ``max_i(stamps[i + 8] - stamps[i])``: the stall as a reader
  feels it. Tokens leave the engine a chunk at a time and trickle through
  the path to the client, so the longest gap between TWO tokens is a
  chunk's start-to-start less the trickle and falls when the path gets
  slower; a span of a chunk's worth of tokens always crosses one burst's
  edge and reads the start-to-start plus what the trickle differs by from
  one burst to the next: it does not fall when the path gets slower, and
  still rises when a chunk grows. The 8 is this benchmark's (the chunk
  every configuration has run since PR 23), not read from the program.

The set-up clock (:func:`setup_clock`): ``setup_s`` runs from the moment
``jax.devices()`` returned to the window's opening; what came before is the
interpreter's and the machine's (``imports_s``, ``backend_up_s``) and is
kept beside it, per layer.

Completed means: every token asked for arrived, the last of them by the
end of the wait (open loop) or inside the window (closed loop).
"""

from __future__ import annotations

from dataclasses import dataclass, field

STALL_TOKENS = 8  # the span of ``stall8_ms``, in tokens


@dataclass
class Rec:
    """One request as its client saw it (times in seconds, one clock)."""

    idx: int
    due: float
    asked: int
    sent: float | None = None
    stamps: list[float] = field(default_factory=list)  # one per token
    end: float | None = None  # the stream's last event ([DONE] or close)
    status: str = "open"  # open | ok | refused | error | cut
    detail: str = ""
    rid: str = ""
    prompt_tokens: int | None = None  # usage, from the final event
    completion_tokens: int | None = None
    text: str = ""
    measured: bool = True  # False: warm-up, lead-in, checks


def percentile(values: list[float], q: float) -> float | None:
    """Linear interpolation between order statistics (numpy's default)."""
    if not values:
        return None
    v = sorted(values)
    if len(v) == 1:
        return float(v[0])
    pos = q / 100.0 * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def tokens_in_window(recs: list[Rec], t0: float, t1: float) -> int:
    return sum(1 for r in recs for s in r.stamps if t0 <= s < t1)


def in_flight(recs: list[Rec], t: float) -> int:
    """Requests due by ``t`` whose stream had not ended at ``t``."""
    return sum(1 for r in recs
               if r.due <= t and (r.end is None or r.end > t))


def due_in_window(recs: list[Rec], t0: float, t1: float) -> list[Rec]:
    return [r for r in recs if r.measured and t0 <= r.due < t1]


def is_failed(r: Rec, limit: float) -> bool:
    """Refused, errored, came back short, or no first token by ``limit``.
    One still streaming correctly when the wait ends is cut, not failed."""
    if r.status in ("refused", "error"):
        return True
    if not r.stamps or r.stamps[0] > limit:
        return True
    if r.status == "ok" and len(r.stamps) != r.asked:
        return True
    if r.completion_tokens is not None and r.completion_tokens != r.asked:
        return True
    return False


def completed(r: Rec, limit: float) -> bool:
    return (
        r.status == "ok" and len(r.stamps) == r.asked and r.asked >= 2
        and r.stamps[-1] <= limit
    )


def ttfts_ms(recs: list[Rec], t0: float, t1: float, limit: float) -> list[float]:
    out = []
    for r in due_in_window(recs, t0, t1):
        if is_failed(r, limit):
            out.append((limit - r.due) * 1e3)
        else:
            out.append((r.stamps[0] - r.due) * 1e3)
    return out


def tpot_ms(r: Rec) -> float:
    return (r.stamps[-1] - r.stamps[0]) / (len(r.stamps) - 1) * 1e3


def itl_max_ms(r: Rec) -> float:
    return max(b - a for a, b in zip(r.stamps, r.stamps[1:])) * 1e3


def stall_ms(r: Rec, k: int = STALL_TOKENS) -> float | None:
    """The longest time to receive ``k`` more tokens; nothing for a request
    of ``k`` tokens or fewer."""
    if len(r.stamps) <= k:
        return None
    return max(b - a for a, b in zip(r.stamps, r.stamps[k:])) * 1e3


def setup_clock(t_process: float, t_imported: float, t_backend: float,
                t_open: float) -> dict:
    """The stages of a set-up from four stamps of one clock: process start,
    imports done, ``jax.devices()`` returned, window open."""
    return {"imports_s": t_imported - t_process,
            "backend_up_s": t_backend - t_imported,
            "setup_s": t_open - t_backend}


def summarize(recs: list[Rec], *, mode: str, t0: float, t1: float,
              grace: float) -> dict:
    """Every end-to-end number of one window, by metric name, with the
    counts the result line needs. A metric with no sample is absent."""
    limit = t1 + grace
    due = due_in_window(recs, t0, t1)
    if mode == "open":
        done = [r for r in due if completed(r, limit)]
    else:
        done = [r for r in recs
                if r.measured and completed(r, t1) and r.stamps[-1] >= t0]
    out: dict = {
        "attempted": len(due),
        "failed": sum(1 for r in due if is_failed(r, limit)),
        "completed": len(done),
        "tokens_in_window": tokens_in_window(recs, t0, t1),
        # the knee's test: a queue that grows through the window
        "in_flight_mid": in_flight(recs, (t0 + t1) / 2),
        "in_flight_close": in_flight(recs, t1),
    }
    ttfts = ttfts_ms(recs, t0, t1, limit)
    vals = {
        "ttft_p90_ms": percentile(ttfts, 90),
        "ttft_p50_ms": percentile(ttfts, 50),
        "tpot_p50_ms": percentile([tpot_ms(r) for r in done], 50),
        "itl_max_p50_ms": percentile([itl_max_ms(r) for r in done], 50),
        f"stall{STALL_TOKENS}_p50_ms": percentile(
            [s for s in map(stall_ms, done) if s is not None], 50),
        "out_tok_s": out["tokens_in_window"] / (t1 - t0),
    }
    out["metrics"] = {k: v for k, v in vals.items() if v is not None}
    return out
