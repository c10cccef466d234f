"""From a profiler trace (``.xplane.pb``) to numbers. Read with nothing but
``jax.profiler.ProfileData``; checked on a recorded trace in
``benchmarks/tests/``.

What a TPU trace holds (one ``/device:TPU:<n>`` plane per chip):

* line ``XLA Ops``: one event per HLO operation executed, start and
  duration on the device's clock. Container operations (``while``,
  ``conditional``, ``call``) span the operations of their bodies, so sums
  use each event's **self time**: its duration minus its children's.
* line ``XLA Modules``: one event per program execution.
* plane ``/host:CPU``: host threads; the benchmark's ``TraceAnnotation``s
  (``bench:step_chunk``, ``bench:admission``, ``bench:pack_ragged``) are
  on the engine driver's thread line, on the same time axis.

Busy time is the union of the device's operation intervals; the idle share
is one minus busy over the window. Each idle gap is attributed to what the
host was doing during it: inside ``step_chunk`` by phase (admission,
packing the ragged block, and the rest: dispatch + sync + delivery),
outside it ``engine had no work``.
"""

from __future__ import annotations

import glob
import gzip
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION = "bench:"
CHUNK = "bench:step_chunk"
PHASES = {"bench:admission": "admission",
          "bench:pack_ragged": "packing the ragged block"}
REST = "dispatch + sync + token delivery"
NO_WORK = "engine had no work"


@dataclass
class Op:
    name: str
    start: float  # seconds on the trace's axis
    end: float
    self_s: float = 0.0


@dataclass
class DeviceTrace:
    ordinal: int
    ops: list[Op] = field(default_factory=list)
    modules: list[Op] = field(default_factory=list)
    busy: list[tuple[float, float]] = field(default_factory=list)  # union

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy)


@dataclass
class Trace:
    devices: list[DeviceTrace]
    host: list[Op]  # the benchmark's annotations
    t0: float
    t1: float

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def busy_s(self) -> float:
        """Averaged over the devices that ran anything."""
        used = [d for d in self.devices if d.ops]
        return sum(d.busy_s for d in used) / len(used) if used else 0.0

    def idle_share(self) -> float | None:
        if self.window_s <= 0 or not any(d.ops for d in self.devices):
            return None
        return 1.0 - self.busy_s / self.window_s

    # -- sums over operations --------------------------------------------
    def op_seconds(self, patterns: list[str]) -> float:
        """Self time of the operations whose name matches any pattern,
        averaged over the devices used."""
        rx = [re.compile(p) for p in patterns]
        used = [d for d in self.devices if d.ops]
        if not used:
            return 0.0
        total = sum(o.self_s for d in used for o in d.ops
                    if any(r.search(o.name) for r in rx))
        return total / len(used)

    def module_seconds(self, patterns: list[str]) -> float:
        rx = [re.compile(p) for p in patterns]
        used = [d for d in self.devices if d.modules]
        if not used:
            return 0.0
        total = sum(m.end - m.start for d in used for m in d.modules
                    if any(r.search(m.name) for r in rx))
        return total / len(used)

    def exposed_seconds(self, patterns: list[str]) -> float:
        """Time in matching operations during which no other operation
        runs on that device, averaged over the devices."""
        rx = [re.compile(p) for p in patterns]
        used = [d for d in self.devices if d.ops]
        if not used:
            return 0.0
        total = 0.0
        for d in used:
            mine = [o for o in d.ops if any(r.search(o.name) for r in rx)]
            leaf = [(o.start, o.end) for o in d.ops
                    if o.self_s > 0 and not any(r.search(o.name) for r in rx)
                    and not _is_container(o.name)]
            other = union(leaf)
            for o in mine:
                total += (o.end - o.start) - overlap(other, o.start, o.end)
        return total / len(used)

    def top_ops(self, n: int = 10) -> list[list]:
        used = [d for d in self.devices if d.ops]
        if not used:
            return []
        by: dict[str, float] = {}
        for d in used:
            for o in d.ops:
                by[o.name] = by.get(o.name, 0.0) + o.self_s
        top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
        return [[clean(k), v / len(used)] for k, v in top]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle seconds of the first device used, by what the host was
        doing."""
        dev = next((d for d in self.devices if d.ops), None)
        if dev is None:
            return []
        gaps = complement(dev.busy, self.t0, self.t1)
        chunks = [(h.start, h.end) for h in self.host if h.name == CHUNK]
        out: dict[str, float] = {}
        for a, b in gaps:
            inside = 0.0
            for name, label in PHASES.items():
                iv = [(h.start, h.end) for h in self.host if h.name == name]
                s = overlap(union(iv), a, b)
                if s > 0:
                    out[label] = out.get(label, 0.0) + s
                inside += s
            in_chunk = overlap(union(chunks), a, b)
            if in_chunk - inside > 0:
                out[REST] = out.get(REST, 0.0) + in_chunk - inside
            if (b - a) - in_chunk > 0:
                out[NO_WORK] = out.get(NO_WORK, 0.0) + (b - a) - in_chunk
        top = sorted(out.items(), key=lambda kv: -kv[1])[:n]
        return [[clean(k), v] for k, v in top]


def clean(name: str) -> str:
    """An operation's event name is its whole HLO text; keep the name the
    compiler gave it (``%paged_attention.6 = bf16[...] custom-call(...)``
    becomes ``paged_attention.6``)."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[^A-Za-z0-9_.\-:+]+", "_", name).strip("_")[:96]


def _is_container(name: str) -> bool:
    base = name.lstrip("%").split(".")[0].split(" ")[0]
    return base in ("while", "conditional", "call")


# -- intervals -------------------------------------------------------------
def union(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(iv):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def overlap(merged: list[tuple[float, float]], a: float, b: float) -> float:
    """Length of ``[a, b]`` covered by an already merged interval list."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in merged
               if y > a and x < b)


def complement(merged, a: float, b: float) -> list[tuple[float, float]]:
    out, cur = [], a
    for x, y in merged:
        if y <= a or x >= b:
            continue
        if x > cur:
            out.append((cur, x))
        cur = max(cur, y)
    if cur < b:
        out.append((cur, b))
    return out


def self_times(ops: list[Op]) -> None:
    """Set ``self_s``: duration minus the time of directly nested events
    (an event nests in the nearest earlier one that still covers it)."""
    ops.sort(key=lambda o: (o.start, -(o.end - o.start)))
    stack: list[Op] = []
    for o in ops:
        o.self_s = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end + 1e-12:
            stack[-1].self_s -= o.end - o.start
        stack.append(o)
    for o in ops:
        o.self_s = max(o.self_s, 0.0)


# -- reading -----------------------------------------------------------------
def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    return files[-1] if files else None


def load_profile(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(line) -> list[Op]:
    return [Op(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def _cpu_device(profile) -> DeviceTrace:
    """The CPU backend's executor threads taken as one device: only for the
    rehearsal in ``benchmarks/tests``, where no chip is attached."""
    dev = DeviceTrace(ordinal=0)
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if line.name.startswith("tf_XLA"):
                dev.ops.extend(o for o in _events(line) if o.end > o.start)
    return dev


def reduce_profile(profile, cpu_as_device: bool = False,
                   n_chunks: int | None = None) -> Trace:
    """``profile``: a ``ProfileData``. The window runs from the first
    ``bench:step_chunk`` annotation's start to the last one's end when the
    trace has any (the benchmark starts the profiler on a chunk boundary),
    else over the span of the device's operations. ``n_chunks`` keeps the
    first so many chunks: the profiler is stopped from another thread, and
    what the engine ran meanwhile is not part of the window."""
    devices: list[DeviceTrace] = []
    host: list[Op] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = DeviceTrace(ordinal=int(m.group(2)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops.extend(_events(line))
                elif line.name == MODULES_LINE:
                    dev.modules.extend(_events(line))
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(o for o in _events(line)
                            if o.name.startswith(ANNOTATION))
    if cpu_as_device and not devices:
        devices.append(_cpu_device(profile))
    devices.sort(key=lambda d: d.ordinal)
    chunks = sorted((h for h in host if h.name == CHUNK),
                    key=lambda h: h.start)[:n_chunks]
    if chunks:
        t0 = min(h.start for h in chunks)
        t1 = max(h.end for h in chunks)
    else:
        starts = [o.start for d in devices for o in d.ops]
        ends = [o.end for d in devices for o in d.ops]
        t0, t1 = (min(starts), max(ends)) if starts else (0.0, 0.0)
    for d in devices:
        d.ops = [o for o in d.ops if o.end > t0 and o.start < t1]
        d.modules = [o for o in d.modules if o.end > t0 and o.start < t1]
        self_times(d.ops)
        d.busy = union([(max(o.start, t0), min(o.end, t1)) for o in d.ops])
    host = [h for h in host if h.end > t0 and h.start < t1]
    return Trace(devices=devices, host=host, t0=t0, t1=t1)


def describe(profile, top: int = 25) -> str:
    """Planes, lines and the commonest event names: what to look at by
    hand before trusting a pattern."""
    out = []
    for plane in profile.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            by: dict[str, list[float]] = {}
            for e in evs:
                rec = by.setdefault(e.name, [0, 0.0])
                rec[0] += 1
                rec[1] += e.duration_ns * 1e-9
            for name, (n, s) in sorted(by.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"      {n:7d}x {s:10.6f}s  {name[:140]}")
    return "\n".join(out)
