"""Batch engine: admission prefill time over admission prefill plus
decode time, both counted by the engine, over the window (%)."""
import readers


def read(r):
    p = readers.delta(r, "engine.prefill_s")
    d = readers.delta(r, "engine.decode_s")
    return 100.0 * p / (p + d) if p is not None and d and p + d > 0 else None
