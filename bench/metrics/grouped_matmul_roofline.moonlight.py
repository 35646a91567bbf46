"""``grouped_matmul_roofline.moonlight``: the ``int_grouped_matmul``
launches' share of their roofline over the traced slice (%), for the
routed experts' rows that the program's counter read
(``drivers/mla_moe_score.py``)."""
import moe_trace


def read(run):
    return moe_trace.roofline_under(run, "int_grouped_matmul",
                                    "grouped_matmul")
