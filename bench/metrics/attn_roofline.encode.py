"""``attn_roofline.encode``: the ``attention`` kernel's share of its roofline over the
traced slice (%), for the work the cell's calls required."""
import readers


def read(run):
    return readers.roofline(run, "attention", "attention")
