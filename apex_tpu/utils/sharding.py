"""Small shared PartitionSpec / mesh-axis helpers.

One home for the two questions several modules kept re-answering locally:
which mesh axes does a PartitionSpec leaf bind, and which of a set of axis
names are bound in the current trace (inside ``shard_map``).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
from jax import lax

__all__ = ["spec_axis_names", "bound_axes", "broadcast_spec", "shard_map",
           "axis_size"]


#: static size of a bound mesh axis (a plain Python int at trace time —
#: callers build grid/schedule structure from it)
axis_size = lax.axis_size

#: ``jax.shard_map`` with the replication check off: the package's
#: per-rank bodies use explicit collective regions (custom-vjp psums)
#: the varying-manual-axes checker cannot type
shard_map = functools.partial(jax.shard_map, check_vma=False)


def spec_axis_names(spec) -> set:
    """Mesh axis names a PartitionSpec binds across all its dims (empty for
    ``None``/replicated)."""
    used = set()
    if spec is None:
        return used
    for entry in tuple(spec):
        for ax in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            if ax is not None:
                used.add(ax)
    return used


def bound_axes(axis_names: Sequence[str]) -> Tuple[str, ...]:
    """The subset of ``axis_names`` bound as collective axes in this trace."""
    out = []
    for a in axis_names:
        try:
            lax.axis_index(a)
            out.append(a)
        except NameError:
            pass
    return tuple(out)


def broadcast_spec(spec_prefix_tree, full_tree) -> list:
    """Expand a (possibly prefix) PartitionSpec pytree to one spec per leaf
    of ``full_tree`` — the same prefix semantics ``shard_map``'s in_specs
    accept, so spec trees valid there stay valid for per-leaf walks."""
    result: list = []
    num_leaves = lambda t: jax.tree_util.tree_structure(t).num_leaves

    def add(spec_leaf, subtree):
        result.extend([spec_leaf] * num_leaves(subtree))

    jax.tree_util.tree_map(add, spec_prefix_tree, full_tree,
                           is_leaf=lambda t: t is None)
    return result
