"""What the two serving span readers share: device 0's idle time in the
window, split by the program's ``serve/*`` spans without setting a time on
one clock against a time on the other.

On a TPU v5e the trace's host and device clocks differed by up to about a
millisecond from one profiler session to the next, against about 2.3 ms of
idle a decode step, so a device gap is never cut at a host span's edge.
Instead:

* the device has nothing queued from the end of each ``serve/fetch`` (and
  from the window's start, which follows the warm-up's fetch) until the
  next dispatch, ``serve/prefill`` or ``serve/decode``, returns.  That host
  time, inside ``serve/step``, is the scheduler's (its Python and the
  dispatch calls); outside ``serve/step`` it is the harness's;
* the rest of the device's idle time, on the device's own clock, is the
  fetch's: the wait for the result to reach the host, and the runtime's
  launch of the next program after its dispatch returned.

Only lengths on the two clocks are subtracted, so an offset between them
cancels."""
from benchmarks.chip import devtrace

DISPATCH = ("serve/prefill", "serve/decode")


def split(run):
    """``(fetch_ns, sched_ns, steps)`` in the window, ``steps`` the
    ``serve/decode`` spans begun in it; None without a device or such a
    span."""
    tr = run.trace
    lo, hi = tr.window
    events = sorted((e for line in tr.main_threads for e in line),
                    key=lambda e: e.start)
    steps = sum(1 for e in events
                if e.name == "serve/decode" and lo <= e.start < hi)
    if not tr.devices or not steps:
        return None
    busy = devtrace.merge(devtrace.clip(
        ((e.start, e.end) for e in tr.devices[0].ops), lo, hi))
    idle = (hi - lo) - devtrace.total(busy)
    free, start = [], lo
    for e in events:
        if e.name == "serve/fetch":
            start = e.end
        elif e.name in DISPATCH and start is not None:
            free.append((start, e.end))
            start = None
    if start is not None:
        free.append((start, hi))
    free = devtrace.merge(devtrace.clip(free, lo, hi))
    in_steps = devtrace.merge(devtrace.clip(
        ((e.start, e.end) for e in events if e.name == "serve/step"), lo, hi))
    outside = devtrace.total(devtrace.subtract(free, in_steps))
    sched = devtrace.total(free) - outside
    return idle - sched - outside, sched, steps
