"""``decode_attn_roofline``: the fused paged decode-attention kernel's
share of its roofline over the traced slice (%), for the live lanes' keys
(and the folded output projection)."""
import readers


def read(run):
    return readers.roofline(run, "decode_attention", "decode_attention")
