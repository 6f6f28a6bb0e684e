"""Collective/overlap helpers.

GSPMD inserts collectives automatically from shardings; these helpers cover
the places where *explicit* control matters:

  * ``async_allreduce_grads`` — kicks off the cross-pod gradient all-reduce
    per-bucket so XLA's latency-hiding scheduler can overlap it with the
    remaining backward compute (bucketing is what makes overlap possible —
    one giant fused all-reduce can't start until the last grad is ready).
  * ``pod_psum`` — shard_map psum over the "pod" axis only (the slow DCN
    hop), used with optim.compression for int8 cross-pod traffic.
"""

from __future__ import annotations

from typing import Any, List

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any


def bucket_leaves(tree: PyTree, bucket_bytes: int = 32 * 2**20) -> List[List]:
    """Greedy size-bucketing of tree leaves for staged all-reduce."""
    flat = jax.tree.leaves(tree)
    buckets, cur, cur_b = [], [], 0
    for leaf in flat:
        nb = leaf.size * leaf.dtype.itemsize
        if cur and cur_b + nb > bucket_bytes:
            buckets.append(cur)
            cur, cur_b = [], 0
        cur.append(leaf)
        cur_b += nb
    if cur:
        buckets.append(cur)
    return buckets


def pod_psum(tree: PyTree, mesh, in_specs) -> PyTree:
    """Explicit psum over the 'pod' mesh axis via shard_map."""

    def f(x):
        return jax.tree.map(lambda v: jax.lax.psum(v, "pod"), x)

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=in_specs,
                         check_vma=False)(tree)


def with_optimization_barrier(x: PyTree) -> PyTree:
    """Prevent XLA from sinking comm past this point (manual overlap)."""
    return jax.lax.optimization_barrier(x)
