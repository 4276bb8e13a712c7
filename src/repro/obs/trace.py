"""Round-trace flight recorder: spans, ring buffer, JSONL exporter.

Design constraints (why this looks the way it does):

* **The profiler is the clock.**  While a ``jax.profiler`` session records,
  every :func:`span` is also a ``jax.profiler.TraceAnnotation``: a host
  event of the session's trace, on the same clock as the device's events,
  with its tags as the event's stats.  No switch turns this on: the session
  does.

* **Near-zero cost when off.**  With the recorder off and no profiler
  session, ``span()`` checks one module-level flag and the profiler's
  enabled bit and returns a single shared no-op context manager — no object
  allocation, no clock read, no lock.  The recorder is off unless
  ``enable()`` is called or ``REPRO_TRACE=1`` is set in the environment.

* **No host sync inside jit.**  Host-clock spans belong at *dispatch
  boundaries* (the training loop, the serving scheduler, codec round
  boundaries, benchmark harnesses).  Code that runs under ``jax.jit`` uses
  :func:`annotate` instead — a trace-time ``jax.named_scope`` that names the
  phase in the program's op metadata without ever blocking on a device
  value.

* **Flight recorder.**  Spans land in a fixed-capacity thread-safe ring
  buffer: a long run keeps the most recent window instead of growing without
  bound, and ``n_evicted`` says how much history scrolled off.

Usage::

    from repro.obs import trace

    trace.enable()
    with trace.span("sync/encode", level="inter") as sp:
        payload = encode(...)
        sp.tag(nbytes=payload.nbytes)

    trace.export_jsonl("TRACE_round.jsonl")

A profiler trace (``jax.profiler.trace(dir, create_perfetto_trace=True)``)
carries the same spans next to the device's, whether the recorder is on or
not.
"""
from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax

_TRUTHY = ("1", "true", "yes", "on")

DEFAULT_CAPACITY = 1 << 16  # spans kept before the flight recorder wraps


@dataclass(frozen=True)
class Span:
    """One completed span: [ts_us, ts_us + dur_us) on the tracer's epoch."""
    name: str
    ts_us: float          # start, microseconds since the tracer's epoch
    dur_us: float
    tid: int              # recording thread ident
    depth: int            # nesting depth within the thread (0 = top level)
    tags: Dict[str, object] = field(default_factory=dict)

    def to_json(self) -> dict:
        out = {"name": self.name, "ts_us": round(self.ts_us, 3),
               "dur_us": round(self.dur_us, 3), "tid": self.tid,
               "depth": self.depth}
        if self.tags:
            out["tags"] = self.tags
        return out

    @classmethod
    def from_json(cls, d: dict) -> "Span":
        return cls(d["name"], float(d["ts_us"]), float(d["dur_us"]),
                   int(d.get("tid", 0)), int(d.get("depth", 0)),
                   dict(d.get("tags", {})))

    def encloses(self, other: "Span") -> bool:
        """Interval containment on the same thread (parent candidate)."""
        return (self.tid == other.tid
                and self.ts_us <= other.ts_us
                and self.ts_us + self.dur_us >= other.ts_us + other.dur_us)


class Tracer:
    """Thread-safe fixed-capacity ring buffer of spans + run metadata."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._buf: List[Optional[Span]] = [None] * self.capacity
        self._next = 0          # write cursor
        self._recorded = 0      # total spans ever recorded
        self.meta: Dict[str, object] = {}
        self.epoch_ns = time.perf_counter_ns()

    # -- recording ----------------------------------------------------------
    def record(self, sp: Span) -> None:
        with self._lock:
            self._buf[self._next] = sp
            self._next = (self._next + 1) % self.capacity
            self._recorded += 1

    def reset(self) -> None:
        with self._lock:
            self._buf = [None] * self.capacity
            self._next = 0
            self._recorded = 0
            self.meta = {}
            self.epoch_ns = time.perf_counter_ns()

    # -- introspection ------------------------------------------------------
    @property
    def n_recorded(self) -> int:
        return self._recorded

    @property
    def n_evicted(self) -> int:
        return max(0, self._recorded - self.capacity)

    def spans(self) -> List[Span]:
        """Retained spans in recording (completion) order, oldest first."""
        with self._lock:
            if self._recorded < self.capacity:
                return [s for s in self._buf[:self._next] if s is not None]
            return ([s for s in self._buf[self._next:] if s is not None]
                    + [s for s in self._buf[:self._next] if s is not None])

    def now_us(self) -> float:
        return (time.perf_counter_ns() - self.epoch_ns) / 1e3


def wall_s() -> float:
    """Monotonic host wall clock in seconds.

    The one sanctioned host-time call outside this module: training loops and
    launch tooling time compile/step phases through here so measured wall
    clocks share a clock source with the trace epoch (``repro.lint`` rule
    RL003 rejects raw ``time.*`` calls elsewhere in ``src/repro``).
    """
    return time.perf_counter()


# ---------------------------------------------------------------------------
# module state: one default tracer + the enable flag everything checks
# ---------------------------------------------------------------------------
_tracer = Tracer()
_enabled = os.environ.get("REPRO_TRACE", "").lower() in _TRUTHY
_tls = threading.local()


def get_tracer() -> Tracer:
    return _tracer


def enabled() -> bool:
    return _enabled


def enable(capacity: Optional[int] = None) -> None:
    """Turn the flight recorder on (optionally resizing the ring buffer)."""
    global _enabled, _tracer
    if capacity is not None and capacity != _tracer.capacity:
        _tracer = Tracer(capacity)
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def set_meta(**kv) -> None:
    """Attach run-level metadata (sync config, n_params, ...) to the trace;
    exported as the JSONL header line so the report CLI can self-configure."""
    _tracer.meta.update(kv)


def _profiling() -> bool:
    """Whether a ``jax.profiler`` session is recording host events now."""
    return jax.profiler.TraceAnnotation.is_enabled()


def _depth_stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _ambient_tags() -> Optional[dict]:
    return getattr(_tls, "ambient", None)


# ---------------------------------------------------------------------------
# span context managers
# ---------------------------------------------------------------------------
class _NullSpan:
    """Shared no-op: what ``span()`` returns with the recorder off and no
    profiler session."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, **kv):
        return self


NULL_SPAN = _NullSpan()


class _ProfilerSpan(jax.profiler.TraceAnnotation):
    """A span as a host event of the profiler's trace: tags given at entry
    and by ``tag()`` become the event's stats."""

    def tag(self, **kv) -> "_ProfilerSpan":
        self.set_metadata(**kv)
        return self


class _SpanCtx:
    """A span of the flight recorder, also a profiler event while a session
    records."""
    __slots__ = ("name", "tags", "_t0_ns", "_event")

    def __init__(self, name: str, tags: dict):
        self.name = name
        self.tags = tags
        self._t0_ns = 0
        self._event = _ProfilerSpan(name, **tags)

    def tag(self, **kv) -> "_SpanCtx":
        self.tags.update(kv)
        self._event.tag(**kv)
        return self

    def __enter__(self):
        _depth_stack().append(self.name)
        self._event.__enter__()
        self._t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1_ns = time.perf_counter_ns()
        self._event.__exit__(*exc)
        stack = _depth_stack()
        depth = len(stack) - 1
        if stack:
            stack.pop()
        amb = _ambient_tags()
        tags = {**amb, **self.tags} if amb else self.tags
        _tracer.record(Span(self.name,
                            (self._t0_ns - _tracer.epoch_ns) / 1e3,
                            (t1_ns - self._t0_ns) / 1e3,
                            threading.get_ident(), depth, tags))
        return False


def span(name: str, **tags):
    """Host span: ``with span("codec/encode", level="inter") as sp:``.

    Recorded in the ring buffer when the recorder is on, and a host event of
    the profiler's trace while a session records.  With neither it returns
    the shared :data:`NULL_SPAN` — no allocation beyond the call itself, no
    clock read.  ``sp.tag(nbytes=...)`` adds tags that are only known at
    exit time.
    """
    if _enabled:
        return _SpanCtx(name, tags)
    if _profiling():
        return _ProfilerSpan(name, **tags)
    return NULL_SPAN


class _AmbientCtx:
    """Thread-local tags merged into every span recorded inside the block —
    how codec spans inherit the aggregation level they run under without the
    codec knowing about levels."""
    __slots__ = ("tags", "_prev")

    def __init__(self, tags: dict):
        self.tags = tags
        self._prev = None

    def __enter__(self):
        self._prev = _ambient_tags()
        merged = {**self._prev, **self.tags} if self._prev else self.tags
        _tls.ambient = merged
        return self

    def __exit__(self, *exc):
        _tls.ambient = self._prev
        return False


def ambient(**tags):
    """``with ambient(level="inter"):`` — tag every span recorded within."""
    if not _enabled:
        return NULL_SPAN
    return _AmbientCtx(tags)


# ---------------------------------------------------------------------------
# jax passthrough (trace-safe: never reads the host clock inside jit)
# ---------------------------------------------------------------------------
def annotate(name: str):
    """Phase annotation for code *inside* jit: a ``jax.named_scope`` so the
    phase shows up in the jaxpr and in each HLO instruction's metadata.  This
    is the only instrumentation allowed under a jit trace — it acts at trace
    time, changes op metadata and not the compiled program, and never forces
    a host sync."""
    return jax.named_scope(name)


def step_annotation(step: int, name: str = "train"):
    """``jax.profiler.StepTraceAnnotation`` for round boundaries (lines host
    rounds up with device steps in an XLA profile) while a profiler session
    records; the shared no-op otherwise."""
    if not _profiling():
        return NULL_SPAN
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def export_jsonl(path: str, tracer: Optional[Tracer] = None) -> str:
    """One JSON object per line: a ``{"type": "meta", ...}`` header (run
    metadata + eviction counters) followed by one ``span`` line per span."""
    tr = tracer or _tracer
    spans = tr.spans()
    with open(path, "w") as f:
        header = {"type": "meta", "n_recorded": tr.n_recorded,
                  "n_evicted": tr.n_evicted, "capacity": tr.capacity}
        header.update(tr.meta)
        f.write(json.dumps(header) + "\n")
        for s in spans:
            rec = s.to_json()
            rec["type"] = "span"
            f.write(json.dumps(rec) + "\n")
    return path


def load_jsonl(path: str) -> Tuple[dict, List[Span]]:
    """Inverse of :func:`export_jsonl`: (meta, spans)."""
    meta: dict = {}
    spans: List[Span] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            if d.get("type") == "meta":
                meta = {k: v for k, v in d.items() if k != "type"}
            else:
                spans.append(Span.from_json(d))
    return meta, spans

