"""Stores and loader: bytes swapped in the window over the time the loader
spent reading, unpacking or dispatching them (GB/s)."""
import readers


def read(r):
    return readers.loader_gbps(r)
