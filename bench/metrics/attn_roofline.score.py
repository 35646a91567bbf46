"""``attn_roofline.score``: the ``int_attention_fused`` launch's share of
its roofline over the traced slice (%), for the causal attention the
cell's calls required (``drivers/score.py``)."""
import readers


def read(run):
    return readers.roofline(run, "attention", "attention")
