"""``glue_ms.encode``: device time of the program's ops outside every
named Pallas launch (requant, residual clip-adds, converts, reshapes,
the embedding gather, the head's dequant) per encoder call (ms); the
split by sublayer scope is printed on standard error."""
import scopes


def read(run):
    return scopes.per_call_ms(run, lambda a: a.glue_s)
