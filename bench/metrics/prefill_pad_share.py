"""``prefill_pad_share``: share of the chunked-prefill slots launched in
the window that held no real prompt token (%): one minus real tokens over
launches x lanes x chunk."""
import readers


def read(run):
    return readers.share(run, "prefill_real", "prefill_slots",
                         complement=True)
