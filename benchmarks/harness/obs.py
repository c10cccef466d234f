"""Everything one run observed, as the readers of per-layer metrics get it."""

from __future__ import annotations

from dataclasses import dataclass, field

from .e2e import Rec


@dataclass
class Obs:
    mode: str  # "open" | "closed"
    recs: list[Rec]
    t0: float
    t1: float
    grace: float
    stats0: dict  # the engine's counters when the window opened
    stats1: dict  # ... and when it closed
    recorder: list[dict] = field(default_factory=list)  # chunks in the window
    spans: dict = field(default_factory=dict)  # rid -> spans of /trace/<rid>
    trace: object | None = None  # xplane.Trace of the traced chunks
    chunks: list[dict] = field(default_factory=list)  # the traced chunks
    builds_in_window: list = field(default_factory=list)
    memory_peak_bytes: int = 0
    # the configuration's published keys + its deployment with ``ml``
    # resolved (cluster.deployed_model): the bytes functions size from it
    model: dict = field(default_factory=dict)
    peaks: dict = field(default_factory=dict)
    client: dict = field(default_factory=dict)  # e2e.summarize's metrics
    setup: dict = field(default_factory=dict)  # e2e.setup_clock's stages
