"""Collective ops (reference: python/paddle/distributed/communication/*.py —
all_reduce, all_gather, broadcast, reduce_scatter, alltoall, send/recv over
NCCL).

TPU-native: these are XLA collectives (`lax.psum` etc.), which are only
meaningful *inside* an spmd region (shard_map). Two surfaces:

1. Inside `shard_map`: the `all_reduce`/`all_gather`/... functions here are
   thin lax wrappers keyed by mesh axis name.
2. Eager (outside spmd): `eager_all_reduce` and friends wrap the op in a
   one-shot shard_map over the global mesh, giving paddle's eager
   collective semantics for sharded arrays.

There are no process groups: a "group" is a mesh axis name.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils import faults
from ..utils.faults import retry_with_backoff
from .env import get_mesh

AxisName = Union[str, Sequence[str]]


class CollectiveError(RuntimeError):
    """A transient collective failure (flaky ICI/DCN link, preempted
    peer, or the injected `collective_fail` fault). Retryable — the
    eager wrappers re-run the collective under retry_with_backoff."""


def _collective_retries() -> int:
    """Total attempts per eager collective (so '3' = 2 actual retries);
    0/negative clamps to 1 = run once, no retry."""
    return max(1, int(os.environ.get("PADDLE_TPU_COLLECTIVE_RETRIES", "3")))


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


# ---------------------------------------------------------- in-spmd wrappers
def all_reduce(x, op: str = ReduceOp.SUM, group: AxisName = "dp"):
    if op == ReduceOp.SUM:
        return lax.psum(x, group)
    if op == ReduceOp.MAX:
        return lax.pmax(x, group)
    if op == ReduceOp.MIN:
        return lax.pmin(x, group)
    if op == ReduceOp.AVG:
        return lax.pmean(x, group)
    if op == ReduceOp.PROD:
        return jnp.exp(lax.psum(jnp.log(x), group))
    raise ValueError(op)


def all_gather(x, group: AxisName = "dp", axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, group, axis=axis, tiled=tiled)


def reduce_scatter(x, group: AxisName = "dp", axis: int = 0):
    return lax.psum_scatter(x, group, scatter_dimension=axis, tiled=True)


def all_to_all(x, group: AxisName = "ep", split_axis: int = 0,
               concat_axis: int = 0):
    return lax.all_to_all(x, group, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def ppermute(x, perm, group: AxisName):
    return lax.ppermute(x, group, perm)


def broadcast(x, src: int = 0, group: AxisName = "dp"):
    """Take src's shard everywhere (inside spmd). ppermute forbids fan-out
    from one source, so broadcast = mask-to-src + psum (XLA folds this into
    a single collective on TPU)."""
    idx = lax.axis_index(group)
    return lax.psum(jnp.where(idx == src, x, jnp.zeros_like(x)), group)


def axis_index(group: AxisName):
    return lax.axis_index(group)


def axis_size(group: AxisName):
    return lax.axis_size(group)


# ------------------------------------------------------------ eager facades
def _eager(fn, x, group, out_spec=None, in_spec=None):
    from jax import shard_map
    mesh = get_mesh()
    in_spec = in_spec if in_spec is not None else P(group)
    out_spec = out_spec if out_spec is not None else in_spec

    def attempt():
        # chaos: a transient link failure surfaces BEFORE the collective
        # runs (the XLA program either runs whole or not at all) — the
        # retry below is the recovery contract for both the injected
        # and the real case
        if faults.inject("collective_fail", group=str(group)):
            raise CollectiveError(
                f"injected transient collective failure on axis {group!r}")
        out = shard_map(fn, mesh=mesh, in_specs=(in_spec,),
                        out_specs=out_spec, check_vma=False)(x)
        # materialize INSIDE the attempt: jax dispatch is async, so an
        # execution-time failure would otherwise surface at the caller's
        # first read, past the retry. Eager collectives are semantically
        # synchronous anyway.
        return jax.block_until_ready(out)

    # retry real runtime failures too, not just the injected kind: a
    # flaky link surfaces as JaxRuntimeError. Deterministic errors
    # (compile bugs) cost two pointless short retries, then propagate
    # with their ORIGINAL type — retry_with_backoff re-raises as-is.
    retryable = (CollectiveError,)
    jax_rt = getattr(jax.errors, "JaxRuntimeError", None)
    if jax_rt is not None:
        retryable += (jax_rt,)
    return retry_with_backoff(attempt, max_attempts=_collective_retries(),
                              base_delay=0.05, max_delay=2.0,
                              retryable=retryable)


def eager_all_reduce(x, op: str = ReduceOp.SUM, group: str = "dp"):
    """x sharded on `group` along axis 0; returns the reduction, replicated."""
    return _eager(lambda v: all_reduce(v, op, group), x, group, out_spec=P())


def eager_all_gather(x, group: str = "dp"):
    return _eager(lambda v: all_gather(v, group), x, group, out_spec=P())


def eager_broadcast(x, src: int = 0, group: str = "dp"):
    return _eager(lambda v: broadcast(v, src, group), x, group, out_spec=P())
