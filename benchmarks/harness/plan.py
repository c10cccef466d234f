"""The work a run offers: a fixed set of requests that the seed orders,
fills and jitters, and never resamples.

A generator (``generators/<kind>.py``) is a pure function of its traffic
file, the cell's own numbers, the deployment, ``--seed`` and ``--seconds``.
It returns a :class:`Plan`. Two seeds give the same multiset of sizes and
the same number of arrivals, in another order.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np


@dataclass(frozen=True)
class Req:
    """One request to send. ``prompt_tokens`` counts the new user message
    alone, or the whole prompt with the chat template when
    ``count_template`` is set (the driver then subtracts the template's
    length, which it learns from the warm-up request's usage)."""

    idx: int
    prompt_tokens: int
    output_tokens: int
    content_seed: int
    count_template: bool = False
    due_s: float | None = None  # open loop: seconds after the window opens
    client: int | None = None  # closed loop: the client that sends it
    session: int | None = None  # sessions: prompt is the session's history
    turn: int | None = None


@dataclass
class Plan:
    mode: str  # "open" | "closed"
    schedule: list[Req] = field(default_factory=list)  # open, by due_s
    clients: list[list[Req]] = field(default_factory=list)  # closed
    system_tokens: int = 0  # sessions: the shared system prompt
    system_seed: int = 0
    setup: list[Req] = field(default_factory=list)  # sent once before the ramp
    lead_in_s: float = 0.0  # open loop: arrivals before the window opens

    def sizes(self) -> list[tuple[int, int]]:
        """The multiset of (prompt, output) sizes of the measured work."""
        reqs = self.schedule if self.mode == "open" else [
            r for c in self.clients for r in c
        ]
        return sorted((r.prompt_tokens, r.output_tokens) for r in reqs
                      if r.due_s is None or r.due_s >= 0)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent streams of one ``--seed`` (any whole number ≥ 0)."""
    return np.random.default_rng([int(seed), *map(int, stream)])


def quantiles(lo: float, hi: float, n: int, spacing: str = "log") -> list[int]:
    """``n`` whole sizes from ``lo`` to ``hi``: the mid-quantiles of a
    log-uniform (or uniform) distribution over that range."""
    if n <= 0:
        return []
    qs = (np.arange(n) + 0.5) / n
    if spacing == "log":
        vals = np.exp(math.log(lo) + qs * (math.log(hi) - math.log(lo)))
    elif spacing == "linear":
        vals = lo + qs * (hi - lo)
    else:
        raise ValueError(f"unknown spacing {spacing!r}")
    return [int(round(v)) for v in vals]


def content_seed(*parts: int) -> int:
    """A 31-bit seed for one request's token contents."""
    h = 0x9E3779B97F4A7C15
    for p in parts:
        h = ((h ^ (int(p) + 0x7F4A7C15)) * 0xBF58476D1CE4E5B9) % (1 << 64)
        h ^= h >> 31
    return int(h % (1 << 31))
