"""MoE expert program: the expert-group programs' share of their roofline
over the decode steps of the window, from the profiler trace (%): each
group's weights read once, and the routed work, at the chip's peaks
(the family's ``kernel_cost``), over their device time."""
import readers


def read(r):
    return readers.kernel_roofline(r, "expert_group")
