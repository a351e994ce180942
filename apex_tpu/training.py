"""Parallel train-step builder.

The reference has no trainer — users wire ``amp`` + DDP + fused optimizers
into their own loops (``examples/imagenet/main_amp.py:333-362``). On TPU the
equivalent wiring is one ``shard_map`` over the global mesh: per-rank autodiff
(torch's one-process-per-rank model), explicit collective regions inside the
model (the ``tensor_parallel.mappings`` custom-vjp functions), and a
data-axis gradient ``pmean`` standing in for DDP's bucketed all-reduce
(``apex/parallel/distributed.py:429-480`` — bucketing/overlap are XLA's job).

``make_train_step`` returns a jitted function
``(params, opt_state, batch, rng) -> (params, opt_state, loss)`` with params
and optimizer state donated (in-place update semantics, the analog of the
reference's in-place ``multi_tensor`` optimizer kernels).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from apex_tpu.transformer.parallel_state import DATA_AXIS

__all__ = ["make_train_step", "sync_data_parallel_grads"]


def sync_data_parallel_grads(grads, axis_names: Sequence[str],
                             param_spec=None):
    """pmean grads over the bound data axes (DDP's allreduce + divide,
    reference ``distributed.py:429-480`` predivide/postdivide semantics).

    With ``param_spec`` (full or prefix pytree, same semantics as shard_map
    in_specs), leaves *sharded over a data axis* (expert-parallel parameters
    riding the data axis) are handled per-leaf: their local grads already
    accumulate every rank's token contributions through the ``all_to_all``
    transpose, so averaging them across that axis would mix different
    experts — instead they are divided by the axis size so every leaf's
    synced grad equals d(global mean loss)/d(leaf), matching the pmean
    convention of the replicated leaves.
    """
    from apex_tpu.utils.sharding import (
        axis_size,
        bound_axes,
        broadcast_spec,
        spec_axis_names,
    )

    axes = bound_axes(axis_names)
    if not axes:
        return grads
    if param_spec is None:
        return jax.tree.map(lambda g: lax.pmean(g, axes), grads)

    def one(g, spec):
        used = spec_axis_names(spec)
        rest = tuple(a for a in axes if a not in used)
        if rest:
            g = lax.pmean(g, rest)
        for a in axes:
            if a in used:
                g = g / axis_size(a)
        return g

    g_leaves, treedef = jax.tree_util.tree_flatten(grads)
    spec_leaves = broadcast_spec(param_spec, grads)
    return jax.tree_util.tree_unflatten(
        treedef, [one(g, s) for g, s in zip(g_leaves, spec_leaves)])


def make_train_step(
    loss_fn: Callable,
    optimizer,
    mesh: Mesh,
    param_spec,
    batch_spec,
    *,
    opt_state_spec=None,
    params_template=None,
    data_axes: Sequence[str] = (DATA_AXIS,),
    donate: bool = True,
) -> Callable:
    """Build ``step(params, opt_state, batch, rng) -> (params, opt_state, loss)``.

    Args:
      loss_fn: ``loss_fn(params, batch, rng) -> scalar`` written against the
        per-rank (local shard) view — i.e. a model ``apply`` built from the
        tensor_parallel layers.
      optimizer: a :class:`~apex_tpu.optimizers.base.FusedOptimizer`.
      mesh: the global device mesh (see ``parallel_state``).
      param_spec / batch_spec: PartitionSpec pytrees for params and batch.
      opt_state_spec: optional; derived via ``optimizer.state_spec`` from
        ``params_template`` when omitted.
      data_axes: mesh axes carrying replicated model copies whose grads are
        averaged (the DDP axis; add the context axis when batch also shards
        over it).
    """
    if opt_state_spec is None:
        if params_template is None:
            raise ValueError(
                "need opt_state_spec or params_template to derive it")
        opt_state_spec = optimizer.state_spec(params_template, param_spec)

    # A ZeRO-style optimizer syncs grads itself, but only over its own axis
    # (its reduce-scatter IS the DP allreduce on that axis — reference
    # DistributedFusedAdam grad pipeline); any other data axes still need
    # the pmean here.
    if getattr(optimizer, "handles_grad_sync", False):
        opt_axis = getattr(optimizer, "axis_name", None)
        grad_sync_axes = tuple(a for a in data_axes if a != opt_axis)
    else:
        grad_sync_axes = tuple(data_axes)

    def per_rank(params, opt_state, batch, rng):
        if rng is not None:
            # independent dropout streams per data shard (DDP's per-rank RNG);
            # the tensor axis is folded inside model-parallel regions only.
            # Unbound axes fold index 0 so the single-device fast path below
            # draws the identical stream as a size-1 shard_map would.
            for a in data_axes:
                try:
                    idx = lax.axis_index(a)
                except NameError:
                    idx = 0
                rng = jax.random.fold_in(rng, idx)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
        grads = sync_data_parallel_grads(grads, grad_sync_axes, param_spec)
        loss = sync_data_parallel_grads(loss, data_axes)
        new_params, new_state = optimizer.step(grads, params, opt_state)
        return new_params, new_state, loss

    if mesh.size == 1:
        # single-device mesh: there is nothing to partition, so skip
        # shard_map and jit the per-rank body directly — one plain
        # program, no manual-axes lowering to compile or to reason about.
        # Semantics match: every mesh axis has size 1, and all collective
        # regions no-op behind axis_bound() guards.
        return jax.jit(per_rank, donate_argnums=(0, 1) if donate else ())

    from apex_tpu.utils.sharding import shard_map

    sharded = shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(param_spec, opt_state_spec, batch_spec, PartitionSpec()),
        out_specs=(param_spec, opt_state_spec, PartitionSpec()),
    )
    return jax.jit(sharded, donate_argnums=(0, 1) if donate else ())
