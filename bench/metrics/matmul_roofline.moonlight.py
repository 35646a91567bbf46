"""``matmul_roofline.moonlight``: the ``int8_matmul`` launches' share of
their roofline over the traced slice (%), for the matmuls the cell's
calls required: the latent attention's projections, the dense layer's
SwiGLU, the routers and the shared experts (``drivers/mla_moe_score.py``;
the routed experts run on ``int_grouped_matmul`` and are not counted
here)."""
import readers


def read(run):
    return readers.roofline(run, "int8_matmul", "matmul")
