"""``decode_step_ms``: device time of one decode-step execution (ms),
averaged over the traced slice."""
import readers


def read(run):
    return readers.step_ms(run, "decode")
