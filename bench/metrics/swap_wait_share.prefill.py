"""Swapped executor: share of the window the executor stalled on a
prefetch (the swap engine's "wait" spans), in %."""
import readers


def read(r):
    return readers.stage_share(r, "wait")
