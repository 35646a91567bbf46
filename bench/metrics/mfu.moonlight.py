"""``mfu.moonlight``: the whole step's share of the chip's int8 peak (%):
model operations of the chunks scored in the traced slice (the routed
experts over the rows the program's counter read, attention over the
causal triangle; ``drivers/mla_moe_score.py``), per second."""
import readers


def read(run):
    return readers.mfu(run)
