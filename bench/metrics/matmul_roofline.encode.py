"""``matmul_roofline.encode``: the ``int8_matmul`` kernel's share of its roofline over the
traced slice (%), for the work the cell's calls required."""
import readers


def read(run):
    return readers.roofline(run, "int8_matmul", "matmul")
