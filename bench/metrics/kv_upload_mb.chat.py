"""Paged KV: host-to-device bytes of the KV pool re-uploads per decode
step, in MB (the program's ``engine.kv_upload_bytes`` over its
``engine.decode_steps``, deltas over the window). A count of the layout:
each decode step re-uploads every layer's K and V pools whatever the
batch."""
import readers


def read(r):
    n = readers.delta(r, "engine.decode_steps")
    b = readers.delta(r, "engine.kv_upload_bytes")
    return b / n / 1e6 if n and b is not None else None
