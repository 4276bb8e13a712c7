"""The trace reduction on a small hand-made trace whose answers are known.

The fixture (``fixtures/trace_small.pbtxt``) has two chips and a window of
10,000 ns (1,000 to 11,000).  Chip 0 runs instructions at 500-1,200 (a
fusion begun before the window), a while loop at 1,500-6,000 whose body is
a fusion at 1,500-3,000, a kernel at 3,000-4,000 and an all-reduce at
3,500-5,500, then fusions at 7,000-8,000 and 10,500-11,500.  Chip 1 runs a
fusion at 1,000-6,000 and an all-reduce at 5,000-7,000.  The host's
``python3`` line holds the window, a draw at 1,300-1,400, a fetch at
5,500-6,300 and a step at 6,000-7,000; ``main/1`` a program launch at
8,000-10,400.
"""
from __future__ import annotations

from pathlib import Path

import pytest

from benchmarks.chip import devtrace

FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.pbtxt"


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return devtrace.from_profile(ProfileData.from_text_proto(FIXTURE.read_text()))


def test_window_and_devices(trace):
    assert trace.window == (1000.0, 11000.0)
    assert [d.index for d in trace.devices] == [0, 1]


def test_idle_share_and_busy(trace):
    # chip 0 busy 200 + 4,500 + 1,000 + 500 = 6,200 ns; chip 1 6,000 ns
    assert devtrace.busy_ns(trace, trace.devices[0]) == 6200
    assert devtrace.busy_ns(trace, trace.devices[1]) == 6000
    assert devtrace.idle_share(trace) == pytest.approx((0.38 + 0.40) / 2)
    assert devtrace.busy_s(trace) == pytest.approx(6.1e-6)


def test_opcodes():
    assert devtrace.hlo_opcode(
        "%while.1 = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)}) while(x)") == "while"
    assert devtrace.hlo_opcode(
        "%a = f32[64,512]{1,0:T(8,128)S(1)} custom-call(f32[1]{0} %b)") == \
        "custom-call"
    assert devtrace.hlo_opcode("PJRT_LoadedExecutable_Execute") == ""


def test_breakdown(trace):
    top = dict(devtrace.top_ops(trace))
    assert top["fusion.9 f32[8,128] fusion"] == pytest.approx(2.5e-6)
    assert top["all-reduce.1 f32[1024] all-reduce"] == pytest.approx(2e-6)
    assert top["fusion.0 f32[8] fusion"] == pytest.approx(1e-7)   # clipped
    assert not any(name.startswith("while") for name in top)
    gaps = devtrace.idle_gaps(trace)
    assert gaps == [["PJRT_LoadedExecutable_Execute", pytest.approx(2.5e-6)],
                    ["bench/step after np.asarray(jax.Array)",
                     pytest.approx(1e-6)],
                    ["bench/draw", pytest.approx(3e-7)]]


def test_module_runs(trace):
    # the run of jit__lambda began before the window and is left out
    assert devtrace.module_runs(trace, "jit") == {"jit_step(123)": [4500.0, 1000.0]}


def test_interval_arithmetic():
    assert devtrace.merge([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert devtrace.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert devtrace.clip([(0, 5), (6, 9)], 4, 7) == [(4, 5), (6, 7)]


@pytest.mark.parametrize("line", ["python", "python3.12", "bench_main"])
def test_main_thread_found_by_window_mark(line):
    from jax.profiler import ProfileData

    text = FIXTURE.read_text().replace('name: "python3"', f'name: "{line}"')
    trace = devtrace.from_profile(ProfileData.from_text_proto(text))
    assert trace.window == (1000.0, 11000.0)
    assert ["bench/step after np.asarray(jax.Array)", pytest.approx(1e-6)] in \
        devtrace.idle_gaps(trace)
