"""``idle_share.moonlight``: share of the traced slice in which no
operation ran on the device (%)."""
import readers


def read(run):
    return readers.idle_share(run)
