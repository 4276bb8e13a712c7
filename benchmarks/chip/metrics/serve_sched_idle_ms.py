"""Device idle time of the scheduler, per decode step, in ms: its own
Python and the dispatch of its programs.  The host time inside
``serve/step`` from the end of each ``serve/fetch`` to the return of the
next dispatch, when the device has nothing queued (``_serve_idle``), over
the number of ``serve/decode`` spans that start in the window.  Silent
without a device or without the program's ``serve/decode`` spans."""
from benchmarks.chip.metrics import _serve_idle


def read(run):
    got = _serve_idle.split(run)
    return None if got is None else got[1] / got[2] / 1e6
