"""What every cell shares: the spec, the device, the window, the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything
that belongs to one configuration, traffic mix, cell or per-layer metric is
a file of its own, found by the name the spec gives it:

* ``configs/<config>.json``: the configuration's sizes (``file`` in the spec);
* ``workloads/<traffic>.json``: the traffic mix's parameters, with ``kind``
  naming the generator ``traffic/<kind>.py`` that reads them;
* ``limits/<cell>.json``: the limit of each number the cell's check
  compares, with the readings it was set from;
* ``metrics/<metric>.py``: the reader of one per-layer metric, a function
  ``read(run) -> float | None``.

A traffic generator is a module with ``run(ctx) -> Outcome``.  It builds the
system under test, warms it up, measures for ``ctx.seconds`` inside
``ctx.window``, frees the program's state and checks what the timed path
produced against the plain reference.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import resource
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# A traced run measures (and traces) at most this long: a trace of a longer
# window of the serving cell runs to about a hundred MB.
TRACE_SECONDS_MAX = 10.0
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class BenchmarkError(RuntimeError):
    """The spec, a data file or the device does not allow a run."""


# ---------------------------------------------------------------------------
# spec and data files
# ---------------------------------------------------------------------------
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    root: Path
    spec: dict
    workload: dict
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic mix file's contents
    limits: dict            # name -> {"limit": x, ...}

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def end_to_end(self) -> List[dict]:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        # a metric without ``workloads`` is reported in every cell that
        # reports the end-to-end metric it moves, later cells too
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in reported)]


def chip_dir(root: Path) -> Path:
    """Where the data files of a benchmark rooted at ``root`` live."""
    return root / HERE.relative_to(ROOT)


def load_cell(root: Path, name: str) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    workloads = {w["name"]: w for w in spec["workloads"]}
    if name not in workloads:
        raise BenchmarkError(f"no cell {name!r}; cells: {sorted(workloads)}")
    w = workloads[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    base = chip_dir(root)
    traffic = load_json(base / "workloads" / f"{w['traffic']}.json")
    limits_path = base / "limits" / f"{name}.json"
    limits = load_json(limits_path) if limits_path.exists() else {}
    return Cell(root, spec, w, config, traffic, limits)


def load_generator(root: Path, kind: str):
    return _load_module(chip_dir(root) / "traffic" / f"{kind}.py",
                        f"benchmarks.chip.traffic.{kind}")


def load_reader(root: Path, metric: str) -> Callable:
    mod = _load_module(chip_dir(root) / "metrics" / f"{metric}.py",
                       f"benchmarks.chip.metrics.{metric.replace('.', '_')}")
    return mod.read


def _load_module(path: Path, name: str):
    if not path.exists():
        raise BenchmarkError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    table = load_json(chip_dir(root) / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchmarkError(f"no peaks for device kind {device_kind!r} in "
                             f"peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


def now() -> float:
    """The benchmark's host clock, in seconds: every time it reports is
    read here."""
    return time.perf_counter()  # repro: noqa[RL003] the benchmark times the host


def jax_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` from any whole number."""
    import numpy as np

    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


# ---------------------------------------------------------------------------
# run context
# ---------------------------------------------------------------------------
@dataclass
class Check:
    """One number the check compares, and its limit (fails above it)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Outcome:
    """What a traffic generator hands back to the harness."""
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: int
    facts: dict = field(default_factory=dict)   # counts the readers use

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def _host_counts() -> dict:
    """This process's CPU seconds, context switches the kernel forced on it,
    major page faults and garbage collections, so far."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    return {"cpu_s": r.ru_utime + r.ru_stime, "preempted": r.ru_nivcsw,
            "major_faults": r.ru_majflt,
            "gc_runs": sum(g["collections"] for g in gc.get_stats())}


class Window:
    """The measured window: host clock, compile count and, in a traced run,
    the profiler with the window marked on the host timeline."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.t0 = self.t1 = None
        self.compiles = 0
        self._dir = None
        self._mark = None
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if self.t0 is not None and self.t1 is None and event in COMPILE_EVENTS:
            self.compiles += 1

    def open(self) -> float:
        import jax

        if self.trace:
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._mark = jax.profiler.TraceAnnotation("bench/window")
            self._mark.__enter__()
        self._counts0 = _host_counts()
        self.t0 = now()
        return self.t0

    def close(self) -> float:
        """Close at the current time; the caller has waited for the device."""
        import jax

        self.t1 = now()
        counts = _host_counts()
        self.host = {k: counts[k] - self._counts0[k] for k in counts}
        self.host["cpu_share"] = self.host.pop("cpu_s") / (self.t1 - self.t0)
        self.host["loadavg_1m"] = os.getloadavg()[0]
        if self.trace:
            self._mark.__exit__(None, None, None)
            jax.profiler.stop_trace()
        return self.t1

    def reduce(self):
        """The window's trace as a ``devtrace.Trace`` (traced runs only)."""
        from benchmarks.chip import devtrace

        if not self.trace:
            return None
        try:
            return devtrace.load(self._dir)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)

    def annotate(self, name: str):
        """A host span on the trace's timeline in a traced run, so that the
        breakdown can name the device's idle gaps; nothing otherwise."""
        if not self.trace:
            return nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)


@dataclass
class Context:
    """What a traffic generator gets: the cell, the run's arguments, the
    window, and the set-up clock."""
    cell: Cell
    seed: int
    seconds: float
    window: Window
    t_start: float
    check_s: float = 0.0      # time spent capturing for the check before
    #                           the window, which set-up does not count
    setup_s: Optional[float] = None
    phases: Dict[str, float] = field(default_factory=dict)

    def mark(self, phase: str) -> None:
        """Note the end of a phase of set-up, in seconds from the start."""
        self.phases[phase] = now() - self.t_start

    @contextmanager
    def check_capture(self):
        t = now()
        try:
            yield
        finally:
            self.check_s += now() - t

    def open_window(self) -> float:
        # Everything set-up made is moved out of the collector's reach, so
        # that a full collection in the window does not walk the millions
        # of objects JAX keeps (a pause of about a second once in some runs
        # of the serving cell, where every host stall is exposed).
        gc.collect()
        gc.freeze()
        t0 = self.window.open()
        self.setup_s = t0 - self.t_start - self.check_s
        self.phases["window"] = t0 - self.t_start
        self.phases["check_capture"] = self.check_s
        return t0


@dataclass
class Run:
    """What a per-layer metric reader gets."""
    cell: Cell
    outcome: Outcome
    trace: object           # devtrace.Trace
    peaks: dict
    chips: int

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def facts(self) -> dict:
        return self.outcome.facts


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; anything else is an error."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchmarkError(f"found platform {devices[0].platform!r} "
                             f"({devices[0].device_kind}); this benchmark "
                             f"runs on a TPU only")
    if len(devices) < chips:
        raise BenchmarkError(f"{len(devices)} TPU device(s), the cell needs "
                             f"{chips}")
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` so far."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def free_device_memory() -> None:
    """Delete every array still on a device: the program's state must be
    gone before the reference runs."""
    import jax

    gc.unfreeze()
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, devices=None) -> dict:
    """Run one cell and return its result line as a dict.

    ``devices`` is found with :func:`tpu_devices` unless given (tests drive
    the rest of a run on the CPU)."""
    cell = load_cell(root, name)
    ctx = Context(cell, seed, min(seconds, TRACE_SECONDS_MAX) if trace
                  else seconds, Window(trace), t_start)
    ctx.mark("imports")
    if devices is None:
        devices = tpu_devices(cell.chips)
    ctx.mark("devices")
    gen = load_generator(root, cell.traffic["kind"])
    outcome = gen.run(ctx, devices)
    check_s = now() - ctx.window.t1
    # memory_peak_bytes was read by the generator before the reference ran
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": outcome.memory_peak_bytes}
    metrics: Dict[str, dict] = {}
    result: dict = {"correct": outcome.correct, "attempted": outcome.attempted,
                    "failed": outcome.failed}
    if not trace:
        values = dict(outcome.end_to_end, setup_s=ctx.setup_s)
        for m in cell.end_to_end():
            if m["name"] not in values:
                raise BenchmarkError(f"cell {name} reports no {m['name']}")
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    else:
        from benchmarks.chip import devtrace

        tr = ctx.window.reduce()
        run = Run(cell, outcome, tr, peaks(devices[0].device_kind, root),
                  len(devices))
        for m in cell.per_layer():
            value = load_reader(root, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = devtrace.busy_s(tr)
        device["window_s"] = tr.window_ns / 1e9
        result["breakdown"] = {"device_ops": devtrace.top_ops(tr),
                               "idle_gaps": devtrace.idle_gaps(tr)}
    result["metrics"] = metrics
    result["device"] = device
    result["compiles_in_window"] = ctx.window.compiles
    result["check_s"] = check_s
    # where set-up went, and what the host did in the window: this process's
    # CPU seconds per window second, forced context switches, major page
    # faults, collections, the machine's load and the time per step where it
    # is known
    result["setup_phases"] = ctx.phases
    result["host"] = dict(ctx.window.host, **outcome.facts.get("host", {}))
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in outcome.checks}
    return result


def check_lines(result: dict) -> List[str]:
    return [f"check {k} {v['value']!r} limit {v['limit']!r} "
            f"{'ok' if v['value'] <= v['limit'] else 'FAIL'}"
            for k, v in result["checks"].items()]
