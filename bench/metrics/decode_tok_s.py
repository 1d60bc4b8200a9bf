"""Output tokens emitted inside the window over its length (host clock)."""
import readers


def read(r):
    toks = readers.tokens_in_window(r)
    return len(toks) / r.seconds if toks else None
