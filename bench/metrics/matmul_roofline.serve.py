"""``matmul_roofline.serve``: the int8 matmul kernel's share of its
roofline over every call in the traced slice (%), for the live lanes' and
real prompt tokens' rows."""
import readers


def read(run):
    return readers.roofline(run, "int8_matmul", "matmul")
