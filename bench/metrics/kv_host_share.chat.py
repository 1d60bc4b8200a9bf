"""Paged KV: share of the window the executor spent in the KV host round
trip, in %: the program's ``engine.kv_host_s`` (seconds inside its
``swapnet.kv.readback``, ``swapnet.kv.write`` and ``swapnet.kv.upload``
spans; the wait for the programs that make the K/V,
``swapnet.kv.readback.wait``, is left out), delta over the window.
Temporary: once ``bench/trace.py`` keeps the ``swapnet.*`` events, this
reads the spans' union in the trace instead, and the counter goes."""
import readers


def read(r):
    s = readers.delta(r, "engine.kv_host_s")
    return 100.0 * s / r.seconds if s is not None else None
