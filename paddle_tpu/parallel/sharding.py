"""Sharding infrastructure: the GSPMD replacement for fleet's process-group
topology (reference: paddle/distributed/fleet/base/topology.py and
meta_parallel/* — which shard by slicing weights per-rank and inserting NCCL
calls by hand).

TPU-native: parameters stay *logically full-size*; each carries a
`ParamMeta.partition` tuple of mesh-axis names (e.g. ``("tp", None)``).
`shard_layer` device_puts every param with the NamedSharding its partition
resolves to, and the jitted step's in_shardings keep it there. XLA/GSPMD
then inserts the collectives the reference writes by hand. ZeRO stages 1-3
(reference: fleet sharding stage1/2/3) are not separate codepaths: sharding
optimizer state / grads / params over the ``fsdp`` axis IS stages 1/2/3.

Also hosts the trace-time mesh-axis validator — the TPU analogue of the
reference's NCCL race detection (SURVEY.md §5): it rejects partitions that
name axes missing from the mesh or that don't divide the dim size, at
sharding-resolution time rather than at runtime.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributed.env import get_mesh, has_mesh
from ..nn.layer import Layer


class ShardingError(ValueError):
    """Invalid partition: unknown mesh axis or non-divisible dimension."""


def validate_partition(shape: Tuple[int, ...], partition, mesh: Mesh,
                       name: str = "<param>") -> None:
    """Trace-time validation (SURVEY.md §5 'race detection' analogue)."""
    if partition is None:
        return
    if len(partition) > len(shape):
        raise ShardingError(
            f"{name}: partition {partition} has more entries than shape {shape}")
    for dim, axes in enumerate(partition):
        if axes is None:
            continue
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        degree = 1
        for ax in axes:
            if ax not in mesh.shape:
                raise ShardingError(
                    f"{name}: unknown mesh axis {ax!r}; mesh has {tuple(mesh.shape)}")
            degree *= mesh.shape[ax]
        if shape[dim] % degree != 0:
            raise ShardingError(
                f"{name}: dim {dim} of shape {shape} not divisible by "
                f"{axes} degree {degree}")


def partition_to_sharding(partition, mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh or get_mesh()
    spec = P(*partition) if partition else P()
    return NamedSharding(mesh, spec)


def _drop_dead_axes(partition, mesh: Mesh):
    """Drop axes of degree 1 (or absent) so specs stay minimal."""
    if partition is None:
        return None
    out = []
    for axes in partition:
        if axes is None:
            out.append(None)
            continue
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        kept = tuple(a for a in tup if mesh.shape.get(a, 1) > 1)
        out.append(None if not kept else (kept[0] if len(kept) == 1 else kept))
    while out and out[-1] is None:
        out.pop()
    return tuple(out)


def param_shardings(layer: Layer, mesh: Optional[Mesh] = None,
                    fsdp_axis: Optional[str] = "fsdp",
                    fsdp_min_size: int = 2 ** 16
                    ) -> Dict[str, NamedSharding]:
    """Resolve every parameter's partition into a NamedSharding.

    If the mesh has a non-trivial ``fsdp_axis``, parameters above
    ``fsdp_min_size`` elements additionally get fsdp sharding on their
    largest still-unsharded divisible dim (ZeRO-3 == fsdp param sharding;
    stages 1/2 reuse these specs for opt-state/grads only).
    """
    mesh = mesh or get_mesh()
    metas = layer.param_meta()
    out: Dict[str, NamedSharding] = {}
    fsdp_n = mesh.shape.get(fsdp_axis, 1) if fsdp_axis else 1
    for name, value in layer.named_parameters():
        part = _drop_dead_axes(metas[name].partition, mesh)
        part = list(part) if part else []
        part += [None] * (value.ndim - len(part))
        if fsdp_n > 1 and value.size >= fsdp_min_size:
            # choose largest unsharded dim divisible by fsdp degree
            cand = [(value.shape[d], d) for d in range(value.ndim)
                    if part[d] is None and value.shape[d] % fsdp_n == 0]
            if cand:
                _, d = max(cand)
                part[d] = fsdp_axis
        part = tuple(part)
        validate_partition(value.shape, part, mesh, name)
        out[name] = partition_to_sharding(part, mesh)
    return out


def shard_layer(layer: Layer, mesh: Optional[Mesh] = None, **kw) -> Dict[str, NamedSharding]:
    """device_put every parameter according to param_shardings; returns the
    sharding dict (feed it to jit in_shardings so params stay put)."""
    mesh = mesh or get_mesh()
    shardings = param_shardings(layer, mesh, **kw)
    for name, value in list(layer.named_parameters()):
        layer._set_by_path(name, jax.device_put(value, shardings[name]))
    return shardings


def constraint(x, *spec):
    """`lax.with_sharding_constraint` against the global mesh; no-op when no
    mesh is installed or it is single-device (keeps layers usable eagerly).
    Axes that don't evenly divide their dim are dropped (a hint must never
    make a program invalid — e.g. a debug batch of 2 on an 8-way dp mesh)."""
    if not has_mesh():
        return x
    mesh = get_mesh()
    if mesh.size == 1:
        return x
    cleaned = _drop_dead_axes(tuple(spec), mesh)
    if not cleaned:
        return x
    fitted = []
    for dim, axes in enumerate(cleaned):
        if axes is None:
            fitted.append(None)
            continue
        tup = (axes,) if isinstance(axes, str) else tuple(axes)
        degree = 1
        for a in tup:
            degree *= mesh.shape.get(a, 1)
        fitted.append(axes if x.shape[dim] % degree == 0 else None)
    while fitted and fitted[-1] is None:
        fitted.pop()
    if not fitted:
        return x
    abstract = jax.sharding.get_abstract_mesh()
    if not abstract.empty:
        # inside a mesh context — e.g. the partial-manual 1F1B body
        # (shard_map axis_names={'pp'}): a NamedSharding built on the
        # outer all-Auto mesh would clash with the context mesh's axis
        # types, so hand over a bare PartitionSpec (manual axes in the
        # hint would be invalid; drop them)
        fitted = [None if _mentions_manual(a, abstract) else a
                  for a in fitted]
        while fitted and fitted[-1] is None:
            fitted.pop()
        if not any(a is not None for a in fitted):
            return x
        return jax.lax.with_sharding_constraint(x, P(*fitted))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*fitted)))


def _mentions_manual(axes, abstract_mesh) -> bool:
    if axes is None:
        return False
    tup = (axes,) if isinstance(axes, str) else tuple(axes)
    manual_t = jax.sharding.AxisType.Manual
    manual = {n for n, t in zip(abstract_mesh.axis_names,
                                abstract_mesh.axis_types)
              if t == manual_t}
    return any(a in manual for a in tup)


def tree_shardings(tree, like: Dict[str, NamedSharding], default=None):
    """Map a flat {name: Array} tree to its shardings, falling back to
    `default` (replicated if None) for names absent from `like`."""
    mesh = get_mesh()
    default = default or NamedSharding(mesh, P())
    return {k: like.get(k, default) for k in tree}
