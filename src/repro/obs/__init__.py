"""repro.obs — observability for the comm stack: round-trace flight recorder,
metrics registry, and measured-vs-modeled round reports.

Layers:
  trace    lightweight span API (``span("sync/encode", level="inter")`` as a
           context manager) over a monotonic clock and a thread-safe ring
           buffer acting as a flight recorder, with a per-round JSONL
           exporter; while a ``jax.profiler`` session records, every span is
           also a host event of its trace, on the device's clock.
           Near-zero cost when both are off: the enable flag and the
           profiler's enabled bit short-circuit to a shared no-op span, and
           code *inside* jit uses ``annotate`` (trace-time
           ``jax.named_scope``) — host spans only wrap dispatch boundaries,
           never force a device sync.
  metrics  counter/gauge/histogram registry with per-round time series; it
           ingests ``CommLedger.bytes_by_tag`` and per-level ``LevelCost``
           so bytes-by-level/compressor are first-class series next to loss
           and grad-norm.
  report   joins a trace JSONL with the ``RoundCost`` model: per-round
           breakdown of measured wall-time per phase (pack -> encode ->
           allreduce -> decode -> adopt) vs ``serial_time_s`` /
           ``pipelined_time_s`` predictions with a model_error% column, and
           a per-level measured-bytes-vs-CommLedger audit.
           CLI: ``python -m repro.obs.report TRACE.jsonl [--metrics M.json]``
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               registry)
from repro.obs.trace import (Span, Tracer, ambient, annotate, disable, enable,
                             enabled, export_jsonl, get_tracer, load_jsonl,
                             set_meta, span, step_annotation)

__all__ = [
    "Span", "Tracer", "span", "ambient", "annotate",
    "step_annotation", "enable", "disable", "enabled", "get_tracer",
    "set_meta", "export_jsonl", "load_jsonl",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "registry",
]
