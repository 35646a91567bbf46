"""``moe_ms.moonlight``: device time of every op under the ``moe`` scope
(routing, dispatch, the grouped expert launches, i-SiLU, the combine and
the shared experts) per scoring call (ms)."""
import moe_trace
import scopes


def read(run):
    return scopes.per_call_ms(
        run, lambda a: moe_trace.seconds_under(run, "moe"))
