"""``norm_ms.moonlight``: device time of the ``int_norm`` launches (the
integer RMSNorm kernel: both norms of each layer, the latent's and the
final one) per call (ms), read by the launch's name in each op's
path."""
import scopes


def read(run):
    return scopes.per_call_ms(run, lambda a: a.kernels.get("int_norm", 0.0))
