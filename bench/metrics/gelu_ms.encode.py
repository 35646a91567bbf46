"""``gelu_ms.encode``: device time of every op under the ``ffn.act``
scope (the i-GELU launch and the converts and reshapes around it) per
encoder call (ms)."""
import scopes


def read(run):
    return scopes.per_call_ms(run, lambda a: a.owners.get("ffn.act", 0.0))
