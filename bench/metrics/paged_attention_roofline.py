"""Kernels: the paged decode-attention kernel's share of its roofline
over the decode steps of the window, from the profiler trace (%)."""
import readers


def read(r):
    return readers.kernel_roofline(r, "paged_attention")
