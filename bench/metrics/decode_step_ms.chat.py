"""Batch engine: seconds inside batched decode steps over decode steps,
both counted by the engine, over the window."""
import readers


def read(r):
    n = readers.delta(r, "engine.decode_steps")
    s = readers.delta(r, "engine.decode_s")
    return 1e3 * s / n if n else None
