"""``mfu.serve``: the whole step's share of the chip's int8 peak (%):
model operations of the tokens computed in the traced slice per second."""
import readers


def read(run):
    return readers.mfu(run)
