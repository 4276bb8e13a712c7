"""Reduce a profiler trace of the measured window to device numbers.

The trace is JAX's ``.xplane.pb`` (read with ``jax.profiler.ProfileData``).
On a TPU each chip is a plane named ``/device:TPU:<n>``.  Its line
``XLA Ops`` holds one event per HLO instruction executed, and its line
``XLA Modules`` one event per program run, named ``jit_<fn>(<program id>)``.
Control-flow instructions (``while``, ``conditional``, ``call``) appear as
events that span the instructions they run, so sums over instructions skip
them.  Host threads are lines of the plane ``/host:CPU``; the benchmark marks
its window there with the annotation :data:`WINDOW`, on the line of the
main Python thread, and every number below is clipped to that window.

All times are nanoseconds on the trace's clock unless a name says seconds.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench/window"
MARK_PREFIX = "bench/"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINER_OPS = frozenset({"while", "conditional", "call"})

Interval = Tuple[float, float]


@dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def opcode(self) -> str:
        return hlo_opcode(self.name)


@dataclass
class Device:
    index: int
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[Device]
    host: List[List[Event]]          # one list per host thread line
    window: Interval
    main_threads: List[List[Event]] = field(default_factory=list)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


@lru_cache(maxsize=1 << 16)
def hlo_opcode(text: str) -> str:
    """Opcode of an instruction event named ``%x = <type> <opcode>(...)``.

    The result type may be a tuple and holds parentheses of its own inside
    layouts (``{1,0:T(8,128)}``), so it is skipped by bracket depth."""
    _, sep, rest = text.partition(" = ")
    if not sep:
        return ""
    depth, i = 0, 0
    while i < len(rest):
        ch = rest[i]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
        i += 1
    return rest[i:].strip().split("(", 1)[0].strip()


@lru_cache(maxsize=1 << 16)
def short_name(text: str) -> str:
    """``%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.3 bf16[8,128] fusion``: instruction, result shape, opcode."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:96]
    shape = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0])
    return f"{head.lstrip('%')} {shape[:48]} {hlo_opcode(text)}"


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------
def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def from_profile(data, window_name: str = WINDOW) -> Trace:
    """Build a :class:`Trace` from ``jax.profiler.ProfileData``."""
    devices, host, main = [], [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = Device(int(m.group(1)))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = _events(line.events)
                elif line.name == MODULES_LINE:
                    dev.modules = _events(line.events)
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = _events(line.events)
                host.append(events)
                # the runtime's events of the main thread; its Python line
                # is named after the process (``python3``) and is found by
                # the window's mark below
                if line.name.startswith("main"):
                    main.append(events)
    devices.sort(key=lambda d: d.index)
    marked = [t for t in host if any(e.name == window_name for e in t)]
    if not marked:
        raise ValueError(f"trace holds no host annotation {window_name!r}")
    marks = [e for e in marked[0] if e.name == window_name]
    window = (min(e.start for e in marks), max(e.end for e in marks))
    return Trace(devices, host, window, [marked[0]] + main)


def load(log_dir: str, window_name: str = WINDOW) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(find_xplane(log_dir)),
                        window_name)


def _events(events: Iterable) -> List[Event]:
    out = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for e in events]
    out.sort(key=lambda e: e.start)
    return out


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(base: Sequence[Interval], cut: Sequence[Interval]) -> List[Interval]:
    """Parts of the merged intervals ``base`` that no interval of the merged
    ``cut`` covers."""
    out, j = [], 0
    for a, b in base:
        cur = a
        while j < len(cut) and cut[j][1] <= cur:
            j += 1
        k = j
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _spans(events: Iterable[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def busy_ns(trace: Trace, dev: Device) -> float:
    """Time in the window in which any instruction ran on ``dev``."""
    return total(merge(clip(_spans(dev.ops), *trace.window)))


def busy_s(trace: Trace) -> float:
    """Busy seconds, mean over the devices."""
    if not trace.devices:
        return 0.0
    return sum(busy_ns(trace, d) for d in trace.devices) / len(trace.devices) / 1e9


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window, mean over the devices; None without devices."""
    if not trace.devices or trace.window_ns <= 0:
        return None
    shares = [1.0 - busy_ns(trace, d) / trace.window_ns for d in trace.devices]
    return sum(shares) / len(shares)


def leaf_ops(dev: Device) -> List[Event]:
    return [e for e in dev.ops if e.opcode not in CONTAINER_OPS]


def module_runs(trace: Trace, prefix: str) -> dict:
    """``{module name: [durations in ns]}`` of the program runs whose name
    starts with ``prefix`` and that began inside the window (device 0)."""
    out: dict = {}
    if not trace.devices:
        return out
    lo, hi = trace.window
    for e in trace.devices[0].modules:
        if e.name.startswith(prefix) and lo <= e.start < hi:
            out.setdefault(e.name, []).append(e.end - e.start)
    return out


def top_ops(trace: Trace, n: int = 10) -> list:
    """``[[short name, seconds]]`` of the instructions that took the most
    time in the window, summed over their runs, mean over the devices."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    sums: dict = {}
    for d in trace.devices:
        for a, b, name in ((max(e.start, lo), min(e.end, hi), e.name)
                           for e in leaf_ops(d)):
            if b > a:
                key = short_name(name)
                sums[key] = sums.get(key, 0.0) + (b - a)
    k = len(trace.devices)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k / 1e9] for name, ns in ranked]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """``[[host activity, seconds]]``: device 0's idle time in the window,
    each gap named by the innermost event of the main host thread (the
    line that holds the window's mark and the lines ``main/<tid>``) that
    covers the middle of the gap,
    summed by name, largest first.  Where that is one of the benchmark's own
    marks (``bench/...``) or nothing, the name adds the last other event
    that ended before the middle: ``bench/step after np.asarray(jax.Array)``
    is plain Python inside ``step()`` after its fetch from the device."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    busy = merge(clip(_spans(trace.devices[0].ops), lo, hi))
    gaps = subtract([(lo, hi)], busy)
    spans = sorted((e for t in trace.main_threads for e in t
                    if e.name != WINDOW), key=lambda e: e.start)
    ended = sorted((e.end, e.name) for e in spans
                   if not e.name.startswith(MARK_PREFIX))
    ends = [end for end, _ in ended]
    sums: dict = {}
    active: list = []          # heap of (end, start, name) begun before mid
    i = 0
    for a, b in gaps:
        mid = (a + b) / 2
        while i < len(spans) and spans[i].start <= mid:
            heapq.heappush(active, (spans[i].end, spans[i].start,
                                    spans[i].name))
            i += 1
        while active and active[0][0] <= mid:
            heapq.heappop(active)
        name = (min(active, key=lambda x: x[0] - x[1])[2] if active
                else "host: no event")
        if not active or name.startswith(MARK_PREFIX):
            j = bisect.bisect_right(ends, mid) - 1
            if j >= 0:
                name = f"{name} after {ended[j][1]}"
        sums[name] = sums.get(name, 0.0) + (b - a)
    ranked = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]
