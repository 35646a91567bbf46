"""``prefill_chunk_ms``: device time of one chunked-prefill execution
(ms), averaged over the traced slice."""
import readers


def read(run):
    return readers.step_ms(run, "prefill")
