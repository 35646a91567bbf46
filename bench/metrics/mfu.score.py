"""``mfu.score``: the whole step's share of the chip's int8 peak (%):
model operations of the passages scored in the traced slice, attention
counted over the causal triangle (``drivers/score.py``), per second."""
import readers


def read(run):
    return readers.mfu(run)
