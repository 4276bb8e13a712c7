"""Share of the training window in which no operation ran on the device,
mean over the chips (profiler trace), in percent."""
from benchmarks.chip import devtrace


def read(run):
    share = devtrace.idle_share(run.trace)
    return None if share is None else 100.0 * share
