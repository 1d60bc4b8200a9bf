"""95th percentile of every gap between consecutive output tokens of every
sequence, both tokens inside the window (host clock)."""
import readers


def read(r):
    p = readers.percentile(readers.token_gaps(r), 95)
    return None if p is None else p * 1e3
