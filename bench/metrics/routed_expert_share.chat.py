"""Swap units and MoE: of the routed experts whose weights the window's
passes streamed (decode steps and admissions), the share some token of
the pass routed to, in % (the program's ``engine.experts_routed`` over
its ``engine.experts_streamed``, deltas over the window). What is left
of 100 is expert weight streamed and not used."""
import readers


def read(r):
    routed = readers.delta(r, "engine.experts_routed")
    streamed = readers.delta(r, "engine.experts_streamed")
    if routed is None or not streamed:
        return None
    return 100.0 * routed / streamed
