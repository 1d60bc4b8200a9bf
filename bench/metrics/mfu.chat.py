"""Model step: FLOPs of every prefill and decode token processed in the
window, as the configuration's family counts them, over the window times
the chip's bf16 peak (%)."""
import readers


def read(r):
    return readers.mfu(r)
