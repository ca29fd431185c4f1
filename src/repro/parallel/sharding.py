"""Logical-axis sharding rules (MaxText-style) -> NamedSharding resolution.

Every param leaf carries a tuple of logical axis names (see
models/param_utils).  A rule table maps logical names to mesh axes; the
resolver drops any assignment that fails divisibility or would reuse a mesh
axis already consumed by an earlier dim of the same leaf — this is what lets
one rule table serve all 40 heterogeneous (arch × shape) cells without
GSPMD padding surprises (e.g. qwen2's 12 heads are not 16-way shardable; its
ff=8960 is).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "make_rules", "logical_to_pspec",
           "named_sharding_tree", "make_sharder", "mesh_axis_size",
           "data_axis_size", "serve_batch_pspec"]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (str), tuple of axes, or None."""

    table: dict

    def get(self, name: Optional[str]):
        if name is None:
            return None
        return self.table.get(name)


def make_rules(mesh: Mesh, *, fsdp: bool = False,
               seq_shard: bool = False, overrides: dict | None = None
               ) -> ShardingRules:
    """Default rule table for a ("pod"?, "data", "model") mesh."""
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    table = {
        "batch": dp,
        "seq": "model" if seq_shard else None,
        "attn_seq": "model",         # SP fallback inside attention when
                                     # heads don't divide the model axis
        "cache_seq": "model",        # decode caches: shard time over model
        "vocab": "model",
        "embed": "data" if fsdp else None,   # FSDP/ZeRO param+opt sharding
        "ff": "model",
        "ff_expert": None,
        "experts": "model",          # expert parallelism
        "q_heads": "model",
        "kv_heads": "model",
        "kv_lora": None,
        "lora": None,
        "heads": "model",
        "layers": None,
    }
    if overrides:
        table.update(overrides)
    return ShardingRules(table)


def mesh_axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return int(mesh.shape[axis])


# When several logical axes of one leaf map to the same mesh axis, assign in
# priority order (lower = first claim).  This is what makes the resolver pick
# head-sharding when heads divide the model axis and fall back to
# sequence-sharding (attn_seq) when they don't (e.g. qwen2's 12 heads on a
# 16-way model axis).
_PRIORITY = {
    "vocab": 0, "experts": 0, "ff": 0, "ff_expert": 0, "embed": 0,
    "batch": 0, "q_heads": 1, "kv_heads": 1, "heads": 1,
    "cache_seq": 2, "attn_seq": 3, "seq": 4,
}


def logical_to_pspec(axes: tuple, shape: tuple, mesh: Mesh,
                     rules: ShardingRules) -> P:
    """Resolve one leaf.  Divisibility-, reuse- and priority-checked."""
    n = len(axes)
    order = sorted(range(n), key=lambda i: (_PRIORITY.get(axes[i], 9), i))
    used: set = set()
    out = [None] * n
    for i in order:
        dim, name = shape[i], axes[i]
        mesh_ax = rules.get(name)
        if mesh_ax is None:
            continue
        ax_tuple = mesh_ax if isinstance(mesh_ax, tuple) else (mesh_ax,)
        if any(a in used for a in ax_tuple):
            continue                 # mesh axis already consumed by this leaf
        if dim % mesh_axis_size(mesh, mesh_ax) != 0:
            continue                 # not divisible: keep replicated
        used.update(ax_tuple)
        out[i] = mesh_ax
    # Trailing Nones can be dropped (PartitionSpec convention).
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding_tree(specs, shapes, mesh: Mesh, rules: ShardingRules):
    """specs: logical-axes tree; shapes: matching ShapeDtypeStruct tree."""
    is_axes = lambda x: isinstance(x, tuple)

    def resolve(axes, sds):
        return NamedSharding(mesh, logical_to_pspec(tuple(axes), sds.shape,
                                                    mesh, rules))

    return jax.tree.map(resolve, specs, shapes, is_leaf=is_axes)


def data_axis_size(mesh: Mesh) -> int:
    """Total data-parallel width of a ("pod"?, "data", ...) mesh."""
    return int(np.prod([mesh.shape[a] for a in ("pod", "data")
                        if a in mesh.axis_names]))


def serve_batch_pspec(mesh: Mesh, batch: int, ndim: int = 4,
                      rules: ShardingRules | None = None) -> P:
    """Batch-leading activation PartitionSpec for a serve bucket.

    Resolves through the same rule table / divisibility logic as every
    other leaf in the repo: the leading axis shards over the data axes
    when ``batch`` divides them (bucket 1 on a multi-device mesh stays
    replicated instead of tripping pjit's divisibility check).
    """
    rules = rules or make_rules(mesh)
    axes = ("batch",) + (None,) * (ndim - 1)
    return logical_to_pspec(axes, (batch,) + (1,) * (ndim - 1), mesh, rules)


def make_sharder(mesh: Mesh, rules: ShardingRules):
    """Returns sc(x, logical_axes) for activation sharding constraints."""

    def sc(x, axes):
        pspec = logical_to_pspec(tuple(axes), x.shape, mesh, rules)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))

    return sc
