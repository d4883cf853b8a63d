"""Logical-axis → mesh sharding resolver.

Model code tags every parameter/cache dim with a *logical* axis name
(see models/common.P).  This module maps those names onto mesh axes via
an ordered rule table, with automatic fallback: a rule only applies if
the mesh axes exist, are not already used by another dim of the same
tensor, and divide the dim size — otherwise progressively shorter
prefixes of the rule are tried, ending at replication.

Default layout = ZeRO-3 FSDP (+TP):
  * tensor-parallel dims (vocab, heads, mlp, experts, …) → ``model``
  * the ``embed`` dim of every weight → ``("pod","data")``  (FSDP)
  * decode KV caches: batch → ``("pod","data")``, sequence → ``model``
    (sequence-parallel decode; overridden to ("data","model") for the
    batch=1 long-context cell)
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import (AbstractMesh, AxisType, Mesh, NamedSharding,
                          PartitionSpec)

from ..models.common import Axes

Rules = Dict[str, Tuple[str, ...]]


def abstract_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Device-less mesh for spec resolution (Auto axes, like
    launch/mesh.py's)."""
    return AbstractMesh(tuple(shape), tuple(axes),
                        axis_types=(AxisType.Auto,) * len(axes))

# rule values are *ordered preferences*; () / missing = replicate
DEFAULT_RULES: Rules = {
    # ---- weights: TP dims
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "kv_heads_dim": ("model",),      # kv heads × head_dim, heads major
    "mlp": ("model",),
    "experts": ("model",),
    "inner": ("model",),
    "lru": ("model",),
    "ssm_heads": ("model",),
    # ---- weights: FSDP dim
    "embed": ("pod", "data"),
    # ---- replicated / small
    "layers": (),
    "head_dim": (),
    "state": (),
    "state_proj": (),
    "conv": (),
    "conv_ch": (),
    "frontend": (),
    "experts_unsharded": (),
    # ---- activations & caches
    "batch": ("pod", "data"),
    "kv_seq": ("model",),
    "enc_seq": (),
}


def merge_rules(base: Rules, override: Optional[Rules]) -> Rules:
    out = dict(base)
    if override:
        out.update(override)
    return out


def spec_for(shape: Tuple[int, ...], axes: Tuple[str, ...], mesh: Mesh,
             rules: Rules) -> PartitionSpec:
    """Resolve one tensor's PartitionSpec."""
    assert len(shape) == len(axes), (shape, axes)
    used: set = set()
    entries = []
    for dim, name in zip(shape, axes):
        pref = tuple(a for a in rules.get(name, ())
                     if a in mesh.shape and a not in used)
        # longest prefix whose product divides the dim
        chosen = None
        for k in range(len(pref), 0, -1):
            cand = pref[:k]
            prod = int(np.prod([mesh.shape[a] for a in cand]))
            if prod > 1 and dim % prod == 0:
                chosen = cand
                break
        if chosen:
            used.update(chosen)
            entries.append(chosen if len(chosen) > 1 else chosen[0])
        else:
            entries.append(None)
    while entries and entries[-1] is None:
        entries.pop()
    return PartitionSpec(*entries)


def tree_specs(shape_tree, axes_tree, mesh: Mesh,
               rules: Optional[Rules] = None):
    """(ShapeDtypeStruct tree, axes tree) → PartitionSpec tree."""
    rules = merge_rules(DEFAULT_RULES, rules) if rules is not None \
        else DEFAULT_RULES

    def one(sds, axes):
        return spec_for(tuple(sds.shape), axes, mesh, rules)

    return jax.tree_util.tree_map(one, shape_tree, axes_tree)


def tree_shardings(shape_tree, axes_tree, mesh: Mesh,
                   rules: Optional[Rules] = None):
    specs = tree_specs(shape_tree, axes_tree, mesh, rules)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))


def bytes_per_device(shape_tree, axes_tree, mesh: Mesh,
                     rules: Optional[Rules] = None) -> int:
    """Analytic bytes/device of a sharded tree (sanity vs memory_analysis)."""
    specs = tree_specs(shape_tree, axes_tree, mesh, rules)
    total = 0

    def add(sds, spec):
        nonlocal total
        n = int(np.prod(sds.shape)) if sds.shape else 1
        div = 1
        for entry in spec:
            if entry is None:
                continue
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                div *= mesh.shape[a]
        total += n * sds.dtype.itemsize // max(div, 1)

    jax.tree_util.tree_map(add, shape_tree, specs,
                           is_leaf=lambda x: isinstance(x, PartitionSpec))
    return total
