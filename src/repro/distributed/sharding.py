"""Logical-axis sharding (pjit style), DESIGN.md §7.

Model code annotates activations with *logical* axes ("batch", "heads",
"ffn", ...); this module maps them onto whatever physical mesh is in scope
(single-pod ``(data, model)`` or multi-pod ``(pod, data, model)``) and
silently no-ops outside a mesh context (unit tests on one device).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# logical axis -> tuple of physical mesh axes (filtered by availability)
LOGICAL_RULES = {
    "batch": ("pod", "data"),
    "seq": (),                  # sequence kept replicated (SP is a §Perf knob)
    "seq_sharded": ("model",),  # long-context sequence sharding
    "heads": ("model",),
    "kv_heads": ("model",),     # only applied when kv_heads divides
    "ffn": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "embed": (),                # d_model replicated
    "state": (),
    None: (),
}


def current_axes() -> Tuple[str, ...]:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return ()
    return tuple(mesh.axis_names)


def pspec(*logical) -> P:
    """Build a PartitionSpec from logical axis names for the current mesh."""
    avail = current_axes()
    out = []
    for name in logical:
        phys = tuple(a for a in LOGICAL_RULES.get(name, ()) if a in avail)
        if len(phys) == 0:
            out.append(None)
        elif len(phys) == 1:
            out.append(phys[0])
        else:
            out.append(phys)
    return P(*out)


def shard(x, *logical):
    """with_sharding_constraint by logical axes; no-op without a mesh."""
    if not current_axes():
        return x
    return jax.lax.with_sharding_constraint(x, pspec(*logical))


def shard_residual(x):
    """Residual stream: batch over (pod,data) + Megatron-style sequence
    parallelism — the seq dim shards over ``model`` between layers (norms /
    residual adds are pointwise), so remat-saved activations shrink by the
    TP degree.  XLA inserts the all-gather before attention/FFN (whose
    constraints shard heads/ffn instead) and the reduce-scatter after —
    exactly the Megatron-SP collective pair.  Applied only when the seq dim
    divides."""
    axes = current_axes()
    if not axes:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    msize = dict(zip(mesh.axis_names, mesh.axis_sizes)).get("model", 1)
    if x.ndim >= 2 and msize > 1 and x.shape[1] % msize == 0 \
            and x.shape[1] >= msize * 16:
        return jax.lax.with_sharding_constraint(
            x, pspec("batch", "seq_sharded", "embed"))
    return shard(x, "batch", "seq", "embed")


def param_pspec(path: Tuple[str, ...], shape: Tuple[int, ...],
                mesh_axes: Tuple[str, ...]) -> P:
    """Fallback parameter spec (used when a param has no explicit rule)."""
    return P(*([None] * len(shape)))


def comm_quant_gather(x, scale: float, enabled: bool = True):
    """INT8 transport for the sequence-parallel gather boundary.

    The residual stream is seq-sharded between layers; attention/FFN need
    the full sequence, so XLA all-gathers here.  Under QAT the value is
    about to be fake-quantized anyway — quantizing *before* the gather
    halves the wire bytes (bf16 -> int8), the paper's Fig.-2 economics
    applied to the interconnect.  Straight-through gradients; the backward
    reduce-scatter stays bf16.
    """
    if not enabled or not current_axes():
        return x
    return _cq_gather(x, scale)


@jax.custom_vjp
def _cq_gather(x, scale):
    # NOTE: custom_vjp (not a stop-gradient STE) — an `x + sg(deq - x)`
    # formulation would keep a full-seq bf16 dependence on x and XLA would
    # gather it anyway, defeating the int8 transport.
    q8 = jnp.clip(jnp.round(x / jnp.asarray(scale, x.dtype)), -127, 127) \
        .astype(jnp.int8)
    if current_axes():
        # pin the int8 value in seq-SHARDED form first, then request the
        # gathered form: without the first constraint XLA hoists the
        # gather above the quantize chain and moves f32 bytes instead
        q8 = jax.lax.with_sharding_constraint(
            q8, pspec("batch", "seq_sharded", "embed"))
        q8 = jax.lax.with_sharding_constraint(
            q8, pspec("batch", "seq", "embed"))  # seq -> full (gather int8)
    return q8.astype(x.dtype) * jnp.asarray(scale, x.dtype)


def _cq_fwd(x, scale):
    return _cq_gather(x, scale), None


def _cq_bwd(_, g):
    # the primal x is seq-sharded: constrain the cotangent likewise so the
    # partitioner emits a reduce-scatter (half the wire of all-reduce+slice)
    if current_axes():
        g = jax.lax.with_sharding_constraint(
            g, pspec("batch", "seq_sharded", "embed"))
    return (g, None)


_cq_gather.defvjp(_cq_fwd, _cq_bwd)


def constrain_like_params(tree):
    """Re-assert the parameter sharding rules on per-layer weight slices
    *inside* a scan body.  Without this, XLA hoists the all-gather of
    FSDP-sharded stacked weights out of the while loop (gathering every
    layer at once — 100+ GiB); with the in-body constraint the gather
    applies to one layer's slice at a time."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return tree
    from repro.launch.shardings import param_pspecs  # lazy: avoid cycle
    specs = param_pspecs(tree, mesh)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(x, s), tree, specs)
