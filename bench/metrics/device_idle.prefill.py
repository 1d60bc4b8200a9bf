"""Device: share of the window in which no operation ran on it, from the
profiler trace (%)."""
import readers


def read(r):
    return readers.device_idle(r)
