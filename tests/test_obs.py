"""repro.obs: flight recorder, metrics registry, measured-vs-modeled report."""
import json
import os
import sys
import time

import pytest

from repro.configs.base import SyncConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _reset_obs():
    # restore the default-capacity tracer (a test may have shrunk the ring)
    obs_trace.enable(capacity=obs_trace.DEFAULT_CAPACITY)
    obs_trace.disable()
    obs_trace.get_tracer().reset()
    obs_trace.get_tracer().meta.clear()
    obs_metrics.registry.reset()


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Every test starts and ends with tracing off and empty global state."""
    _reset_obs()
    yield
    _reset_obs()


# ---------------------------------------------------------------------------
# trace: spans, nesting, ring buffer
# ---------------------------------------------------------------------------
def test_span_nesting_and_ordering():
    obs_trace.enable()
    with obs_trace.span("outer", level="inter") as outer:
        with obs_trace.span("inner") as inner:
            time.sleep(0.001)
            inner.tag(nbytes=42)
        outer.tag(ok=True)
    spans = obs_trace.get_tracer().spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    inner, outer = spans
    assert inner.depth == 1 and outer.depth == 0
    assert outer.encloses(inner) and not inner.encloses(outer)
    assert inner.tags == {"nbytes": 42}
    assert outer.tags == {"level": "inter", "ok": True}
    assert inner.dur_us > 0 and outer.dur_us >= inner.dur_us


def test_traced_decorator_and_ambient_tags():
    obs_trace.enable()
    with obs_trace.ambient(level="dcn"):
        with obs_trace.span("work/fn", kind="unit"):
            pass
    (s,) = obs_trace.get_tracer().spans()
    assert s.name == "work/fn"
    assert s.tags["kind"] == "unit" and s.tags["level"] == "dcn"


def test_ring_buffer_eviction():
    obs_trace.enable(capacity=8)
    for i in range(20):
        with obs_trace.span(f"s{i}"):
            pass
    tr = obs_trace.get_tracer()
    spans = tr.spans()
    assert len(spans) == 8
    assert tr.n_recorded == 20 and tr.n_evicted == 12
    # the survivors are the most recent spans, in chronological order
    assert [s.name for s in spans] == [f"s{i}" for i in range(12, 20)]


def test_disabled_mode_is_null():
    assert not obs_trace.enabled()
    s1 = obs_trace.span("a", big="tag")
    s2 = obs_trace.span("b")
    assert s1 is s2 is obs_trace.NULL_SPAN  # shared singleton, no allocation
    with s1 as s:
        s.tag(nbytes=1)  # must be a no-op, not an error
    assert obs_trace.get_tracer().n_recorded == 0


def test_export_jsonl_roundtrip(tmp_path):
    obs_trace.enable()
    with obs_trace.span("phase/x", nbytes=10):
        pass
    obs_trace.set_meta(label="t", n_params=7)
    path = obs_trace.export_jsonl(str(tmp_path / "t.jsonl"))
    meta, spans = obs_trace.load_jsonl(path)
    assert meta["label"] == "t" and meta["n_params"] == 7
    assert meta["n_recorded"] == 1 and meta["n_evicted"] == 0
    (s,) = spans
    assert s.name == "phase/x" and s.tags == {"nbytes": 10}


def _host_events(log_dir) -> dict:
    """``{line name: [(name, start_ns, end_ns, stats)]}`` of the host plane
    of the profiler trace written under ``log_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out[line.name] = [(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns, dict(e.stats))
                                  for e in line.events]
    return out


def _profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def test_span_reaches_profiler_with_recorder_off(tmp_path):
    import jax

    assert not obs_trace.enabled()
    with jax.profiler.trace(str(tmp_path), profiler_options=_profiler_options()):
        with obs_trace.span("serve/fetch", live=3) as sp:
            assert sp is not obs_trace.NULL_SPAN
            sp.tag(rows=5)
        with obs_trace.step_annotation(7):
            pass
    assert obs_trace.span("serve/fetch") is obs_trace.NULL_SPAN
    assert obs_trace.get_tracer().n_recorded == 0
    lines = _host_events(tmp_path)
    (main,) = [name for name in lines if name.startswith("python")]
    spans = {name: stats for name, _, _, stats in lines[main]}
    assert spans["serve/fetch"] == {"live": 3, "rows": 5}
    assert spans["train"]["step_num"] == 7


def test_annotate_names_ops_with_recorder_off():
    import jax
    import jax.numpy as jnp

    assert not obs_trace.enabled()

    def f(x):
        with obs_trace.annotate("step/grad"):
            return jnp.sin(x) * 2

    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "step/grad" in text


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("c").inc(3, step=0)
    reg.counter("c").inc(4, step=1)
    assert reg.counter("c").total == 7
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    reg.gauge("g").set(2.5, step=0)
    assert reg.gauge("g").value == 2.5
    h = reg.histogram("h")
    for v in range(100):
        h.observe(float(v))
    assert h.count == 100 and h.percentile(50) == pytest.approx(50, abs=1)
    with pytest.raises(TypeError):
        reg.gauge("c")  # name already bound to a counter


def test_level_byte_gauges_sum_to_round_cost_total():
    from repro.comm import round_cost

    for sync in (SyncConfig(mode="hier", compressor="qsgd", quant_bits=8,
                            sync_period=4),
                 SyncConfig(mode="hier", topology="edge_fl"),
                 SyncConfig(mode="efbv", compressor="top_k",
                            compress_ratio=0.05)):
        reg = obs_metrics.MetricsRegistry()
        cost = round_cost(sync, 1 << 14)
        reg.observe_round_cost(0, cost)
        assert sum(reg.level_bytes().values()) == pytest.approx(
            cost.total_bytes, rel=0, abs=1e-9)


def test_ingest_ledger_matches_bytes_by_tag():
    from repro.comm import round_ledger

    sync = SyncConfig(mode="hier", compressor="qsgd", quant_bits=8,
                      sync_period=4)
    led = round_ledger(sync, 1 << 14)
    reg = obs_metrics.MetricsRegistry()
    reg.ingest_ledger(led)
    assert reg.ledger_bytes() == {k: float(v)
                                  for k, v in led.bytes_by_tag().items()}
    assert reg.counter("comm/ledger/total").total == float(led.total_bytes)


# ---------------------------------------------------------------------------
# report: phases, byte audit, e2e
# ---------------------------------------------------------------------------
def test_phase_classification_outermost_only():
    from repro.obs import report

    obs_trace.enable()
    with obs_trace.span("codec/encode", nbytes=100, level="inter"):
        with obs_trace.span("codec/encode_chunk", chunk=0, nbytes=50):
            pass
        with obs_trace.span("codec/encode_chunk", chunk=1, nbytes=50):
            pass
    spans = obs_trace.get_tracer().spans()
    measured = report.measured_phase_seconds(spans)
    # nested same-phase chunk spans don't double the encode total
    outer = [s for s in spans if s.name == "codec/encode"][0]
    assert measured["encode"] == pytest.approx(outer.dur_us / 1e6)
    # ...and chunk spans don't re-count payload bytes
    assert report.measured_bytes_by_level(spans) == {"inter": 100.0}


def test_report_e2e_traced_round(tmp_path):
    from benchmarks.bench_comm import traced_round
    from repro.obs import report

    trace_path, metrics_path = traced_round(out_dir=str(tmp_path),
                                            n_params=1 << 13)
    assert not obs_trace.enabled()  # restored
    text, result = report.build_report(trace_path, metrics_path=metrics_path)
    assert result["bytes_match"] is True
    assert result["trace_bytes"] == result["ledger_bytes"]
    assert set(result["trace_bytes"]) == {"intra", "inter"}
    for phase in ("pack", "encode", "allreduce", "decode", "adopt"):
        assert result["measured_s"][phase] > 0.0, phase
    assert "per-level measured bytes match CommLedger: True" in text
    # the CLI agrees and exits 0
    assert report.main([trace_path, "--metrics", metrics_path]) == 0


def test_report_cli_fails_on_byte_mismatch(tmp_path):
    from benchmarks.bench_comm import traced_round
    from repro.obs import report

    trace_path, metrics_path = traced_round(out_dir=str(tmp_path),
                                            n_params=1 << 13)
    with open(metrics_path) as f:
        doc = json.load(f)
    doc["ledger_bytes_by_tag"]["inter"] += 1  # corrupt the ledger
    with open(metrics_path, "w") as f:
        json.dump(doc, f)
    assert report.main([trace_path, "--metrics", metrics_path]) == 1


# ---------------------------------------------------------------------------
# instrumented paths stay live
# ---------------------------------------------------------------------------
def test_codec_spans_record_nbytes():
    import jax

    from repro.comm import codecs
    from repro.core import compressors as C

    obs_trace.enable()
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4096,))
    p = codecs.encode(C.qsgd(8), key, x)
    codecs.decode(p)
    spans = {s.name: s for s in obs_trace.get_tracer().spans()}
    assert spans["codec/encode"].tags["nbytes"] == p.nbytes
    assert spans["codec/decode"].tags["nbytes"] == p.nbytes


def test_train_loop_traced_smoke():
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.data.synthetic import SyntheticLMDataset, lm_batch_iterator
    from repro.training.loop import train

    cfg = get_config("h2o-danube-1.8b").reduced()
    tc = TrainConfig(model=cfg, seq_len=32, global_batch=4, lr=1e-3,
                     warmup_steps=1, total_steps=2)
    ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, length=2000, seed=0)

    obs_trace.enable()
    _, history = train(cfg, tc, lm_batch_iterator(ds, 4, 32, seed=1),
                       steps=2, log_every=1)
    assert len(history) == 2
    names = [s.name for s in obs_trace.get_tracer().spans()]
    assert names.count("round/step") == 2
    assert names.count("round/blocking_fetch") == 2
    loss = obs_metrics.registry.gauge("train/loss")
    assert len(loss.series) == 2


def test_train_fetches_metrics_at_log_steps_only():
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.data.synthetic import SyntheticLMDataset, lm_batch_iterator
    from repro.training.loop import train

    cfg = get_config("h2o-danube-1.8b").reduced()
    tc = TrainConfig(model=cfg, seq_len=32, global_batch=4, lr=1e-3,
                     warmup_steps=1, total_steps=3)
    ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, length=2000, seed=0)

    obs_trace.enable()
    _, history = train(cfg, tc, lm_batch_iterator(ds, 4, 32, seed=1),
                       steps=3, log_every=10)
    assert len(history) == 3
    spans = obs_trace.get_tracer().spans()
    fetches = [s.tags["round"] for s in spans
               if s.name == "round/blocking_fetch"]
    assert fetches == [0, 2]
    assert [s.name for s in spans].count("round/next_batch") == 3
    loss = obs_metrics.registry.gauge("train/loss")
    assert len(loss.series) == 3
