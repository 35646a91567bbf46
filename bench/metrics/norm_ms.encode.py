"""``norm_ms.encode``: device time of the ``int_norm`` launches (the
integer LayerNorm kernel) per encoder call (ms), read by the launch's
name in each op's path."""
import scopes


def read(run):
    return scopes.per_call_ms(run, lambda a: a.kernels.get("int_norm", 0.0))
