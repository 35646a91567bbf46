"""Tensor-parallel sharding of the serving engine over a device mesh.

The software analogue of "more PEs" on the SwiftTron array: the paged
serving engine partitions its attention datapath along the **head axis**
across a 1-D ``("tp",)`` mesh — each device owns ``Hkv/tp`` KV heads
(and the matching ``H/tp`` query heads) of *every* physical page:

  * ``wq``/``wk``/``wv`` weights shard by output column (head-major
    layout from ``quant.convert._q_attn``: columns ``[d·N/tp, (d+1)·N/tp)``
    are exactly device ``d``'s head slice), together with their
    per-channel ``b_mult`` / ``bias32`` vectors;
  * ``wo`` shards by *row* (its K dim is the flattened head axis); each
    device computes a raw int32 partial o-projection which
    :func:`repro.distributed.collectives.psum_int32` combines exactly,
    and the per-channel requant epilogue runs **once, after** the
    all-reduce — so it rounds on the same accumulator a single device
    would have produced (the requant-rounds-once rule);
  * the K/V pools shard on their ``Hkv`` axis (axis 3 of both the paged
    ``(ng, num_pages, page_size, Hkv, hd)`` and contiguous
    ``(ng, B, L, Hkv, hd)`` layouts) — page *ids* are device-agnostic,
    so the allocator, page table, prefix index and scheduler stay
    replicated host-side and CoW / preempt / evict logic is untouched.

Everything that is not attention (embedding, norms, FFN/MoE, logits)
runs replicated in lock-step: its inputs are identical on every device
after the exact psum, so its outputs are too — bit-exact by
construction, no further collectives.

GQA stays aligned under the shard: ``H/tp = q_group · Hkv/tp``, so a
device's local query head ``j`` maps to its local KV head
``j // q_group`` exactly as in the global layout.

Speculative decoding composes transparently: the engine's batched
verify launch widens the query axis to ``Sq = spec_k + 1`` rows per
lane, and ``Sq`` — like batch — is a *replicated* dimension under this
mesh (only the head axes shard).  The same per-head pspecs serve both
the ``Sq = 1`` decode step and the verify step, psum'd partial
o-projections included, so sharded spec streams are bit-exact against
single-device spec streams and against ``spec_k = 0``.
"""
from __future__ import annotations

import dataclasses

from jax.sharding import PartitionSpec as P

from repro.models.common import ArchConfig
from repro.models.transformer import layer_group_spec
from repro.ops import OP_NAMES
from repro.ops.spec import QuantLinearParams

#: the serving tensor-parallel mesh axis.  Deliberately NOT one of the
#: logical-rule axes in ``distributed.sharding.LOGICAL_RULES`` ("data" /
#: "model") — the model layers' ``shard()`` constraints can never bind
#: to it (and they no-op inside shard_map bodies anyway).
TP_AXIS = "tp"


def tp_arch_supported(cfg: ArchConfig) -> bool:
    """Whether the head-sharded serving step serves this arch: every
    sublayer must be plain self-attention (+ dense FFN or MoE — both run
    replicated).  SSM state and cross-attention memory are lane-indexed,
    not head-shaped, so those archs keep single-device serving."""
    _, _, kinds = layer_group_spec(cfg)
    return all(mix == "attn" and not has_cross
               for (mix, ff, has_cross) in kinds)


def validate_tp(cfg: ArchConfig, tp: int) -> None:
    """Typed validation of a tensor-parallel degree (engine / CLI
    boundary — fail here, not as a kernel-shape error inside a launch).
    Device availability is checked by the engine, once the lowering is
    known (the exact single-device gather lowering needs no devices)."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp == 1:
        return
    hkv = cfg.n_kv_heads
    if hkv == 0 or hkv % tp:
        raise ValueError(
            f"tp={tp} must divide the KV head count (n_kv_heads={hkv}): "
            "each device owns Hkv/tp heads of every page")
    # tp | Hkv implies tp | H (H = q_group * Hkv), asserted for clarity
    assert cfg.n_heads % tp == 0
    if not tp_arch_supported(cfg):
        raise ValueError(
            f"tp={tp} is unsupported for arch {cfg.name!r}: tensor-"
            "parallel serving shards attention heads, but SSM / cross-"
            "attention sublayers carry lane-indexed state that has no "
            "head axis; serve this arch with tp=1")


def backends_support_tp(ops) -> bool:
    """Capability negotiation (the PR 4-5 story): every backend in the
    OpSet must advertise ``tp_serving`` for the sharded step to trace
    its ops under shard_map.  A single non-advertising backend drops the
    engine to the exact single-device gather lowering."""
    return all(getattr(ops.backend_for(op), "tp_serving", False)
               for op in OP_NAMES)


def make_tp_mesh(tp: int):
    """1-D ``("tp",)`` mesh over the first ``tp`` devices."""
    from repro.launch.mesh import make_mesh
    return make_mesh((tp,), (TP_AXIS,))


def local_cfg(cfg: ArchConfig, tp: int) -> ArchConfig:
    """The per-device view of the arch: ``H/tp`` query heads and
    ``Hkv/tp`` KV heads, with ``head_dim`` pinned explicitly so the
    derived ``hd`` property cannot drift when ``n_heads`` shrinks."""
    if tp == 1:
        return cfg
    return dataclasses.replace(cfg, n_heads=cfg.n_heads // tp,
                               n_kv_heads=cfg.n_kv_heads // tp,
                               head_dim=cfg.hd)


# ------------------------------------------------------ PartitionSpecs --

def _replicated(tree):
    import jax
    return jax.tree.map(lambda _: P(), tree)


def _col_sharded(x):
    """Shard the last (output-channel) axis: head-major columns."""
    return P(*([None] * (x.ndim - 1)), TP_AXIS)


def _attn_pspecs(attn: dict) -> dict:
    """Specs for one attention sublayer's parameter dict."""
    out = {}
    for name, qw in attn.items():
        q = QuantLinearParams.of(qw)
        if name == "wo":
            # rows (the flattened head axis, dim -2); the per-channel
            # requant vector and bias stay replicated — they apply once,
            # after the psum of the partial int32 slabs
            w8 = P(*([None] * (q.w8.ndim - 2)), TP_AXIS, None)
            out[name] = QuantLinearParams(
                w8,
                None if q.b_mult is None else P(),
                None if q.bias32 is None else P())
        else:                       # wq / wk / wv: head-major columns
            out[name] = QuantLinearParams(
                _col_sharded(q.w8),
                None if q.b_mult is None else _col_sharded(q.b_mult),
                None if q.bias32 is None else _col_sharded(q.bias32))
    return out


def qparam_pspecs(qparams) -> dict:
    """PartitionSpec pytree for the quantized parameters: attention
    projections sharded per :mod:`~repro.distributed.tp_serving`,
    everything else (embedding, norms, FFN/MoE, head) replicated."""
    specs = {k: _replicated(v) for k, v in qparams.items()
             if k != "layers"}
    layers = []
    for group in qparams["layers"]:
        g = {}
        for k, v in group.items():
            g[k] = _attn_pspecs(v) if k == "attn" else _replicated(v)
        layers.append(g)
    specs["layers"] = layers
    return specs


def cache_pspecs(caches) -> list:
    """PartitionSpec pytree for the decode caches: the K/V pools shard
    on their ``Hkv`` axis (axis 3 in both the paged and contiguous
    layouts); any other cache leaf would be lane-indexed state, which
    :func:`tp_arch_supported` rules out."""
    specs = []
    for c in caches:
        s = {}
        for key, leaf in c.items():
            assert key in ("k8", "v8", "k_shift", "v_shift"), \
                f"unexpected cache leaf {key!r} under tensor parallelism"
            if key in ("k_shift", "v_shift"):
                # per-page requant shifts (ng, num_pages): page ids are
                # device-agnostic, so the shifts replicate
                s[key] = P(None, None)
            else:
                s[key] = P(None, None, None, TP_AXIS, None)
        specs.append(s)
    return specs


def shard_put(tree, specs, mesh):
    """``device_put`` every leaf with its NamedSharding(mesh, spec)."""
    import jax
    from jax.sharding import NamedSharding
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        tree, specs)
