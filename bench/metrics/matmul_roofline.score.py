"""``matmul_roofline.score``: the ``int8_matmul`` launches' share of
their roofline over the traced slice (%), for the matmuls the cell's
calls required."""
import readers


def read(run):
    return readers.roofline(run, "int8_matmul", "matmul")
