"""The readers of the program's serving spans against arithmetic by hand.

The fixture (``fixtures/trace_serve.pbtxt``) holds three scheduler ticks on
one chip in a window of 1,000 to 31,000 ns; its header lays them out.  The
device is busy 4,500 + 1,300 + 3,600 + 4,600 = 14,000 ns of the window, so
idle 16,000.  It has nothing queued from the window's start or a fetch's end
until the next dispatch returns: 1,000-1,400, 7,000-11,600, 13,500-15,300,
19,800-21,300 and 26,600-31,000.  Of that host time:

* inside ``serve/step`` (the scheduler): 300 + 1,900 + 600 + 1,800 + 700 +
  300 + 2,400 = 8,000 ns;
* outside it (the harness between ticks): 100 + 2,100 + 500 + 2,000 =
  4,700 ns.

The fetch holds the rest of the idle: 16,000 - 8,000 - 4,700 = 3,300 ns,
which is 1,400-1,500, 6,000-7,000, 11,600-11,700, 13,000-13,500,
15,300-15,400, 19,000-19,800, 21,300-21,400 and 26,000-26,600.  Three
``serve/decode`` spans start in the window.
"""
from __future__ import annotations

import re
from pathlib import Path

import pytest

from benchmarks.chip import devtrace, harness

from conftest import REPO

FIXTURE = Path(__file__).parent / "fixtures" / "trace_serve.pbtxt"
READERS = ["serve_fetch_idle_ms", "serve_sched_idle_ms"]


def load(text: str):
    from jax.profiler import ProfileData

    return devtrace.from_profile(ProfileData.from_text_proto(text))


def read(metric: str, trace):
    cell = harness.Cell(REPO, {}, {"name": "x", "chips": 1}, {}, {}, {})
    run = harness.Run(cell, harness.Outcome({}, 0, 0, [], 0, {}), trace,
                      {}, 1)
    return harness.load_reader(REPO, metric)(run)


def shift_device(text: str, ns: int) -> str:
    """The fixture with every device event moved by ``ns``: another offset
    between the trace's host and device clocks."""
    cut = text.index('name: "/host:CPU"')
    return re.sub(r"offset_ps: (\d+)",
                  lambda m: f"offset_ps: {int(m.group(1)) + ns * 1000}",
                  text[:cut]) + text[cut:]


@pytest.fixture(scope="module")
def text():
    return FIXTURE.read_text()


@pytest.mark.parametrize("metric,idle_ns", [("serve_fetch_idle_ms", 3300),
                                            ("serve_sched_idle_ms", 8000)])
def test_readers_by_hand(text, metric, idle_ns):
    assert read(metric, load(text)) == pytest.approx(idle_ns / 3 / 1e6)


def test_split_sums_to_idle_inside_steps(text):
    trace = load(text)
    inside = sum(read(m, trace) for m in READERS) * 3 * 1e6
    assert inside + 4700 == pytest.approx(devtrace.idle_share(trace)
                                          * trace.window_ns)


@pytest.mark.parametrize("metric,idle_ns", [("serve_fetch_idle_ms", 2600),
                                            ("serve_sched_idle_ms", 5350)])
def test_window_clips_spans_and_counts_decodes_begun_in_it(text, metric,
                                                           idle_ns):
    # the window ends at 21,050: the third tick's decode (21,100) is not
    # counted, and of that tick only 21,000-21,050 lies in the window
    short = text.replace("offset_ps: 1000000 duration_ps: 30000000",
                         "offset_ps: 1000000 duration_ps: 20050000")
    assert read(metric, load(short)) == pytest.approx(idle_ns / 2 / 1e6)


@pytest.mark.parametrize("metric", READERS)
@pytest.mark.parametrize("ns", [-300, 250])
def test_readers_ignore_an_offset_between_the_clocks(text, metric, ns):
    # moved 300 ns early, each decode starts before its dispatch returns;
    # the split holds as it never cuts a device gap at a host span's edge
    assert read(metric, load(shift_device(text, ns))) == pytest.approx(
        read(metric, load(text)))


@pytest.mark.parametrize("metric", READERS)
def test_silent_without_decode_spans_or_devices(text, metric):
    assert read(metric, load(text.replace('"serve/decode"',
                                          '"serve/other"'))) is None
    no_device = load(text)
    no_device.devices = []
    assert read(metric, no_device) is None


def test_program_spans_name_the_gaps(text):
    names = [name for name, _ in devtrace.idle_gaps(load(text))]
    assert names == ["serve/step", "serve/admit", "serve/emit",
                     "serve/decode"]
