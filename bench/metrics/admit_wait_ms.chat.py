"""Scheduler and admission queue: mean time a generate request waited from
entering the engine's queue (on submit, or on a re-queue after
preemption) to its admission, in ms (the program's
``engine.admit_wait_s`` over its ``engine.admitted``, deltas over the
window). A sanity reading in the chat cells: their closed loop has as
many clients as batch slots, so the queue is almost always empty, and
the mean over a window's handful of admissions (the harness's ``[bench]
samples`` line counts them as ``prefills``) sits near 0 unless one
request misses an admission loop and waits a whole step. It loads the
queue only under open-loop or bursty traffic."""
import readers


def read(r):
    n = readers.delta(r, "engine.admitted")
    s = readers.delta(r, "engine.admit_wait_s")
    return 1e3 * s / n if n and s is not None else None
