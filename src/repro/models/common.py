"""Architecture configuration and shared model utilities."""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One assigned architecture (src/repro/configs/<id>.py instantiates)."""

    name: str
    family: str                  # dense | encdec | vlm | moe | ssm | hybrid | encoder
    num_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0            # 0 -> d_model // n_heads

    # attention
    window: int = 0              # sliding-window attention (0 = full)
    attn_bias: bool = False
    rope_theta: float = 10000.0
    pos: str = "rope"            # rope | learned | sinusoidal | none

    # ffn / activation / norm
    activation: str = "swiglu"   # swiglu | gelu
    norm: str = "rmsnorm"        # rmsnorm | layernorm
    norm_eps: float = 1e-6       # the float graph's; integer norms have none
    post_norm: bool = False      # True: BERT/RoBERTa-style post-LN
    tie_embeddings: bool = False

    # MoE
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int = 0
    moe_every: int = 1           # MoE FFN on layers where idx % moe_every == moe_offset
    moe_offset: int = 0
    # the leading ``first_k_dense`` layers keep a dense FFN of width
    # ``d_ff`` and run ahead of the (MoE) layer stack (DeepSeek-V3's
    # ``first_k_dense_replace``)
    first_k_dense: int = 0
    # router: "softmax" (top-k of the softmax, renormalised) or
    # "sigmoid" (DeepSeek-V3's ``noaux_tc``: top-k of sigmoid scores
    # plus a per-expert correction bias, gates the unbiased scores
    # normalised over the k, times ``routed_scaling``)
    router: str = "softmax"
    routed_scaling: float = 1.0
    # the chip's share of the routed experts: experts
    # [expert_first, expert_first + experts_held) of the router's
    # n_experts (0: all of them).  Pairs routed elsewhere add nothing.
    expert_first: int = 0
    experts_held: int = 0

    # multi-head latent attention (DeepSeek-V2/V3) without a q LoRA:
    # ``kv_lora_rank`` > 0 switches it on; a query/key head is
    # ``qk_nope_head_dim + qk_rope_head_dim`` wide (``head_dim``), a
    # value head ``v_head_dim``
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # SSM (mamba2 / SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_groups: int = 1

    # hybrid (jamba): attention on layers where idx % attn_every == attn_offset
    attn_every: int = 0
    attn_offset: int = 0

    # enc-dec
    enc_layers: int = 0
    dec_layers: int = 0

    # vlm: cross-attention every ``cross_every`` layers
    cross_every: int = 0
    n_img_tokens: int = 0
    # audio frontend stub
    n_audio_frames: int = 0

    # scalar multipliers of the Granite 3.x block (neutral defaults): the
    # embedding is scaled by ``embedding_multiplier``, attention scores
    # by ``attention_multiplier`` (None: 1/sqrt(head_dim)), every
    # residual branch by ``residual_multiplier``, and the logits divided
    # by ``logits_scaling``.  The integer path folds each into a dyadic
    # constant or the head's dequant scale (quant.plans).
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # numerics / execution
    dtype: str = "bfloat16"
    kernel_backend: str = "ref"  # ref | pallas_fused
    remat: bool = True
    scan_layers: bool = True
    # quantization design scales (shared across layers; DESIGN.md §4)
    s_act8: float = 8.0 / 127.0        # int8 activation grid
    s_res: float = 2.0 ** -9           # residual stream (int, ~14 bit)
    qmax_res: int = 1 << 13
    s_act10: float = 16.0 / 1024.0     # 10-bit activation (GELU/SiLU inputs)

    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def vd(self) -> int:
        """Width of a value head (the attention output per head)."""
        return self.v_head_dim if self.is_mla else self.hd

    @property
    def rope_dim(self) -> int:
        """Width of the part of a query/key head that RoPE rotates."""
        return self.qk_rope_head_dim if self.is_mla else self.hd

    @property
    def held_experts(self) -> int:
        """How many routed experts this chip holds (all, by default)."""
        return self.experts_held or self.padded_experts()

    @property
    def score_scale(self) -> float:
        """The real factor on Q·Kᵀ before the softmax."""
        if self.attention_multiplier is not None:
            return self.attention_multiplier
        return 1.0 / math.sqrt(self.hd)

    @property
    def s_branch(self) -> float:
        """The scale a residual branch's output projection requantizes
        to: the residual grid over ``residual_multiplier``, so the
        branch lands on the stream already multiplied."""
        return self.s_res / self.residual_multiplier

    @property
    def q_group(self) -> int:
        return self.n_heads // max(self.n_kv_heads, 1)

    @property
    def is_causal(self) -> bool:
        return self.family != "encoder"

    def padded_vocab(self, multiple: int = 16) -> int:
        return ((self.vocab + multiple - 1) // multiple) * multiple

    def padded_experts(self, multiple: int = 16) -> int:
        if self.n_experts == 0:
            return 0
        return ((self.n_experts + multiple - 1) // multiple) * multiple

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    def param_count(self) -> int:
        """Analytic parameter count (embedding + layers), for MODEL_FLOPS."""
        d, v = self.d_model, self.padded_vocab()
        emb = v * d * (1 if self.tie_embeddings else 2)
        total = emb
        for i in range(self.num_layers):
            total += self.layer_param_count(i)
        if self.family == "encdec":
            total += sum(self.layer_param_count(i, cross=True)
                         for i in range(self.dec_layers))
        return total

    def layer_param_count(self, idx: int, cross: bool = False) -> int:
        d, hd = self.d_model, self.hd
        n = 0
        if self.is_mla and self._layer_kind(idx) == "attn":
            r, h = self.kv_lora_rank, self.n_heads
            n += d * h * hd + d * (r + self.qk_rope_head_dim) + r
            n += r * h * (self.qk_nope_head_dim + self.v_head_dim)
            n += h * self.v_head_dim * d
        elif self._layer_kind(idx) in ("attn", "cross") or cross:
            n += d * (self.n_heads + 2 * self.n_kv_heads) * hd
            n += self.n_heads * hd * d
        if self._layer_kind(idx) == "ssm":
            di = self.ssm_d_inner
            n += d * (2 * di + 2 * self.ssm_groups * self.ssm_state
                      + self.ssm_heads)
            n += di * d + di * self.ssm_conv
        if self._is_moe_layer(idx):
            e = self.n_experts
            fe = self.moe_d_ff or self.d_ff
            per = d * fe * (3 if self.activation == "swiglu" else 2)
            n += e * per + d * e
            n += self.n_shared_experts * per
        elif self._layer_kind(idx) != "ssm":
            n += d * self.d_ff * (3 if self.activation == "swiglu" else 2)
        n += 2 * d
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k + shared experts only)."""
        if self.n_experts == 0:
            return self.param_count()
        d = self.d_model
        fe = self.moe_d_ff or self.d_ff
        per = d * fe * (3 if self.activation == "swiglu" else 2)
        inactive = 0
        for i in range(self.num_layers):
            if self._is_moe_layer(i):
                inactive += (self.n_experts - self.top_k) * per
        return self.param_count() - inactive

    def _layer_kind(self, idx: int) -> str:
        if self.family == "hybrid" and self.attn_every > 0:
            return ("attn" if idx % self.attn_every == self.attn_offset
                    else "ssm")
        if self.family == "ssm":
            return "ssm"
        if self.family == "vlm" and self.cross_every > 0 \
                and idx % self.cross_every == self.cross_every - 1:
            return "cross"
        return "attn"

    def _is_moe_layer(self, idx: int) -> bool:
        return (self.n_experts > 0 and idx >= self.first_k_dense
                and idx % self.moe_every == self.moe_offset)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One of the assigned input shapes."""
    name: str
    seq_len: int
    global_batch: int
    kind: str       # train | prefill | decode

    @property
    def is_serve(self) -> bool:
        return self.kind in ("prefill", "decode")


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                    # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq: int, d: int, dtype=jnp.float32):
    pos = jnp.arange(seq)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, d, 2).astype(jnp.float32)
                  * (-math.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe.astype(dtype)


def truncated_normal_init(key, shape, scale: float, dtype):
    stddev = scale / max(1.0, math.sqrt(shape[0] if shape else 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * stddev).astype(dtype)
