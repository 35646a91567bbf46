"""``attn_roofline.moonlight``: the latent attention's
``int_attention_fused`` launch's share of its roofline over the traced
slice (%), for the causal attention with query/key width 192 and value
width 128 that the cell's calls required (``drivers/mla_moe_score.py``)."""
import readers


def read(run):
    return readers.roofline(run, "attention", "attention")
