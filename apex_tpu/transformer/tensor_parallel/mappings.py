"""Forward/backward collective region functions.

TPU-native counterpart of the reference's autograd communication Functions
(``apex/transformer/tensor_parallel/mappings.py:141-268``): each torch
``autograd.Function`` whose forward is one NCCL collective and whose backward
is the conjugate collective becomes a ``jax.custom_vjp`` over the matching XLA
collective (``psum`` / ``all_gather`` / ``psum_scatter``), executed over a
named mesh axis inside ``shard_map``.

All functions degrade to the identity when the axis is unbound (world size 1
semantics, mirroring the reference's early-outs when
``get_tensor_model_parallel_world_size() == 1``, e.g. ``mappings.py:36-40``),
so layer code runs unchanged in unsharded unit tests.

Tensor-model-parallel regions shard the **last** dim (hidden); sequence-
parallel regions shard dim **0** (sequence), exactly as the reference
(``mappings.py:63-138``).

Canonical AD usage: compute gradients **inside** ``shard_map`` (per-rank
autodiff, mirroring torch's one-rank-per-process model, e.g.
``jax.value_and_grad`` of the per-rank loss with param grads exiting through
the params' own sharded specs). Differentiating *through* the shard_map
boundary composes shard_map's own boundary transposes (replicated out-specs
scale cotangents by 1/axis_size; replicated in-specs psum them) with these
explicit backward collectives and double-counts reductions.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.observability.tracing import SCOPE_TP_ALL_REDUCE
from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from apex_tpu.utils.profiling import nvtx_range

__all__ = [
    "copy_to_tensor_model_parallel_region",
    "reduce_from_tensor_model_parallel_region",
    "scatter_to_tensor_model_parallel_region",
    "gather_from_tensor_model_parallel_region",
    "scatter_to_sequence_parallel_region",
    "gather_from_sequence_parallel_region",
    "reduce_scatter_to_sequence_parallel_region",
    "mark_sequence_parallel_parameter",
]


def axis_bound(axis_name: str) -> bool:
    """True when ``axis_name`` is a bound collective axis (inside shard_map)."""
    try:
        lax.axis_index(axis_name)
        return True
    except NameError:
        return False


def axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name)


def _local_chunk(x: jax.Array, axis_name: str, dim: int) -> jax.Array:
    """This rank's chunk of ``x`` along ``dim`` (reference ``mappings.py:45-60``)."""
    n = axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    local = x.shape[dim] // n
    return lax.dynamic_slice_in_dim(x, rank * local, local, axis=dim)


# ---------------------------------------------------------------------------
# tensor-model-parallel regions (hidden dim = last dim)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def copy_to_tensor_model_parallel_region(x, axis_name=TENSOR_AXIS):
    """Identity forward, all-reduce backward (``_CopyToModelParallelRegion``,
    reference ``mappings.py:141-156``)."""
    return x


def _copy_fwd(x, axis_name):
    return x, None


def _copy_bwd(axis_name, _, g):
    if axis_bound(axis_name):
        with nvtx_range(SCOPE_TP_ALL_REDUCE):
            g = lax.psum(g, axis_name)
    return (g,)


copy_to_tensor_model_parallel_region.defvjp(_copy_fwd, _copy_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_from_tensor_model_parallel_region(x, axis_name=TENSOR_AXIS):
    """All-reduce forward, identity backward (``_ReduceFromModelParallelRegion``,
    reference ``mappings.py:159-172``)."""
    if axis_bound(axis_name):
        with nvtx_range(SCOPE_TP_ALL_REDUCE):
            return lax.psum(x, axis_name)
    return x


def _reduce_fwd(x, axis_name):
    return reduce_from_tensor_model_parallel_region(x, axis_name), None


def _reduce_bwd(axis_name, _, g):
    return (g,)


reduce_from_tensor_model_parallel_region.defvjp(_reduce_fwd, _reduce_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def scatter_to_tensor_model_parallel_region(x, axis_name=TENSOR_AXIS):
    """Keep own last-dim chunk forward, all-gather backward
    (``_ScatterToModelParallelRegion``, reference ``mappings.py:175-190``)."""
    if axis_bound(axis_name):
        return _local_chunk(x, axis_name, x.ndim - 1)
    return x


def _scatter_fwd(x, axis_name):
    return scatter_to_tensor_model_parallel_region(x, axis_name), None


def _scatter_bwd(axis_name, _, g):
    if axis_bound(axis_name):
        g = lax.all_gather(g, axis_name, axis=g.ndim - 1, tiled=True)
    return (g,)


scatter_to_tensor_model_parallel_region.defvjp(_scatter_fwd, _scatter_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def gather_from_tensor_model_parallel_region(x, axis_name=TENSOR_AXIS):
    """All-gather last dim forward, keep-own-chunk backward
    (``_GatherFromModelParallelRegion``, reference ``mappings.py:193-210``)."""
    if axis_bound(axis_name):
        return lax.all_gather(x, axis_name, axis=x.ndim - 1, tiled=True)
    return x


def _gather_fwd(x, axis_name):
    return gather_from_tensor_model_parallel_region(x, axis_name), None


def _gather_bwd(axis_name, _, g):
    if axis_bound(axis_name):
        g = _local_chunk(g, axis_name, g.ndim - 1)
    return (g,)


gather_from_tensor_model_parallel_region.defvjp(_gather_fwd, _gather_bwd)


# ---------------------------------------------------------------------------
# sequence-parallel regions (sequence dim = dim 0)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1,))
def scatter_to_sequence_parallel_region(x, axis_name=TENSOR_AXIS):
    """Keep own dim-0 chunk forward, all-gather backward
    (``_ScatterToSequenceParallelRegion``, reference ``mappings.py:213-228``)."""
    if axis_bound(axis_name):
        return _local_chunk(x, axis_name, 0)
    return x


def _sp_scatter_fwd(x, axis_name):
    return scatter_to_sequence_parallel_region(x, axis_name), None


def _sp_scatter_bwd(axis_name, _, g):
    if axis_bound(axis_name):
        g = lax.all_gather(g, axis_name, axis=0, tiled=True)
    return (g,)


scatter_to_sequence_parallel_region.defvjp(_sp_scatter_fwd, _sp_scatter_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def gather_from_sequence_parallel_region(
    x, tensor_parallel_output_grad: bool = True, axis_name: str = TENSOR_AXIS
):
    """All-gather dim 0 forward; backward is reduce-scatter when the gathered
    activation enters a tensor-parallel matmul (each rank contributes a
    partial grad), or plain chunk-split otherwise
    (``_GatherFromSequenceParallelRegion``, reference ``mappings.py:231-251``).
    """
    if axis_bound(axis_name):
        return lax.all_gather(x, axis_name, axis=0, tiled=True)
    return x


def _sp_gather_fwd(x, tensor_parallel_output_grad, axis_name):
    return gather_from_sequence_parallel_region(
        x, tensor_parallel_output_grad, axis_name), None


def _sp_gather_bwd(tensor_parallel_output_grad, axis_name, _, g):
    if axis_bound(axis_name):
        if tensor_parallel_output_grad:
            with nvtx_range(SCOPE_TP_ALL_REDUCE):
                g = lax.psum_scatter(g, axis_name, scatter_dimension=0,
                                     tiled=True)
        else:
            g = _local_chunk(g, axis_name, 0)
    return (g,)


gather_from_sequence_parallel_region.defvjp(_sp_gather_fwd, _sp_gather_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def reduce_scatter_to_sequence_parallel_region(x, axis_name=TENSOR_AXIS):
    """Reduce-scatter dim 0 forward, all-gather backward
    (``_ReduceScatterToSequenceParallelRegion``, reference ``mappings.py:254-268``)."""
    if axis_bound(axis_name):
        with nvtx_range(SCOPE_TP_ALL_REDUCE):
            return lax.psum_scatter(x, axis_name, scatter_dimension=0,
                                    tiled=True)
    return x


def _sp_rs_fwd(x, axis_name):
    return reduce_scatter_to_sequence_parallel_region(x, axis_name), None


def _sp_rs_bwd(axis_name, _, g):
    if axis_bound(axis_name):
        g = lax.all_gather(g, axis_name, axis=0, tiled=True)
    return (g,)


reduce_scatter_to_sequence_parallel_region.defvjp(_sp_rs_fwd, _sp_rs_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def mark_sequence_parallel_parameter(p, axis_name=TENSOR_AXIS):
    """Identity forward; backward psums the parameter cotangent over the
    tensor axis.

    Counterpart of the reference's ``sequence_parallel_enabled`` attribute on
    layer-norm / row-linear-bias params (``transformer/layers/layer_norm.py:
    26-99``, ``tensor_parallel/layers.py:758-775``) plus the trainer-side
    grad all-reduce: under sequence parallelism those params consume
    *sequence-sharded* activations, so per-rank grads are partial sums. Here
    the sync is part of the parameter's use site instead of trainer
    bookkeeping — wrap the param where it meets the sharded activation and
    autodiff produces fully-reduced grads on every rank.
    """
    return p


def _mark_sp_fwd(p, axis_name):
    return p, None


def _mark_sp_bwd(axis_name, _, g):
    if axis_bound(axis_name):
        with nvtx_range(SCOPE_TP_ALL_REDUCE):
            g = lax.psum(g, axis_name)
    return (g,)


mark_sequence_parallel_parameter.defvjp(_mark_sp_fwd, _mark_sp_bwd)
