"""Device idle time of the fetch, per decode step, in ms: the wait for the
sampled tokens on their way from the device to the host (the prefill's
fetch included), and the runtime's launch of the next program.  Device 0's
idle time in the window, on its own clock, less the host time in which it
had nothing queued outside a fetch (``_serve_idle``), over the number of
``serve/decode`` spans that start in the window.  Silent without a device
or without the program's ``serve/decode`` spans."""
from benchmarks.chip.metrics import _serve_idle


def read(run):
    got = _serve_idle.split(run)
    return None if got is None else got[0] / got[2] / 1e6
