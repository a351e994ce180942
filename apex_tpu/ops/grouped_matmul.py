"""Grouped products for a routed expert layer: rows sorted by expert.

The serving path of a sparse-expert model multiplies each routed row by
the weights of the expert it chose, and by no other. This module holds
the two pieces that make that one dispatch:

- :func:`routed_layout` turns the experts chosen per assignment into a
  *tile-aligned* layout: assignments sorted by expert, each expert's group
  padded up to a whole number of ``tile_rows``-row tiles, so that **every
  tile belongs to exactly one expert**. Nothing is dropped: the padded
  buffer is sized for the worst case (every non-empty group wastes less
  than one tile) and only the tiles in use are computed.
- :func:`grouped_gated_ffn` runs ``silu(x Wg_e) * (x Wu_e)`` and then
  ``(.) Wd_e`` over those tiles. On TPU each product is one Pallas call
  whose grid walks ``(output column block, tile)``: the tile -> expert map
  is scalar-prefetched, the weight block of tile ``t`` DMAs from expert
  ``tile_expert[t]``, and consecutive tiles of one expert keep the block
  resident, so **each touched expert's weights are read from HBM once per
  call** (the decode step's bound) while a prefill's many tiles per expert
  keep the MXU fed (its bound). Tiles past the last one in use pin their
  block indices to the last tile in use (no DMA) and skip the body.

Off the TPU the same contract is met by a per-tile ``einsum`` over the
tile's gathered expert weights (tiny sizes only: the CPU tests).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.observability.tracing import SCOPE_MOE_EXPERTS
from apex_tpu.ops._support import pallas_interpret, round_up, use_pallas
from apex_tpu.utils.profiling import nvtx_range

__all__ = ["routed_layout", "grouped_gated_ffn", "tile_rows_for"]

#: widest output-column block of one grid step (bf16 weight blocks of
#: ``[2048, 512]`` are 2 MB: two of them double-buffered stay well inside
#: the scoped VMEM limit set below)
_COLS = 512
_VMEM_LIMIT = 48 * 1024 * 1024


def tile_rows_for(assignments: int, num_experts: int) -> int:
    """Rows of one tile for ``assignments`` routed rows over
    ``num_experts``: the power of two nearest above the mean group, held
    between 16 (a bf16 sublane tile: the decode step, where the weight
    stream is the bound and padding costs nothing) and 128 (the MXU's
    height: a long prefill)."""
    mean = max(1, assignments // max(num_experts, 1))
    rows = 16
    while rows < mean and rows < 128:
        rows *= 2
    return rows


def padded_rows(assignments: int, num_experts: int, tile_rows: int) -> int:
    """Rows of the tile-aligned buffer that holds any routing of
    ``assignments`` rows: each non-empty group wastes under one tile."""
    groups = min(assignments, num_experts)
    return round_up(assignments + groups * (tile_rows - 1), tile_rows)


def routed_layout(expert_of: jax.Array, num_experts: int, tile_rows: int):
    """``expert_of`` ``[R]`` int32: the held expert (``0..num_experts-1``)
    each assignment goes to, or ``num_experts`` for one that is not held
    here. Returns ``(dest, tile_expert, tiles_used)``:

    - ``dest`` ``[R]``: the assignment's row in the tile-aligned buffer of
      :func:`padded_rows` rows (that row count itself for one not held:
      an out-of-range index, dropped by scatters);
    - ``tile_expert`` ``[tiles]``: the expert whose rows tile ``t`` holds
      (tiles past ``tiles_used`` repeat the last one in use);
    - ``tiles_used``: how many leading tiles hold rows.
    """
    r = expert_of.shape[0]
    rows = padded_rows(r, num_experts, tile_rows)
    tiles = rows // tile_rows
    order = jnp.argsort(expert_of, stable=True)
    sorted_e = expert_of[order]
    sizes = jnp.zeros((num_experts + 1,), jnp.int32).at[expert_of].add(1)
    held = sizes[:num_experts]
    starts = jnp.cumsum(sizes) - sizes                 # unpadded, sorted
    padded = (held + tile_rows - 1) // tile_rows * tile_rows
    ends = jnp.cumsum(padded)
    pstarts = jnp.concatenate([ends - padded, jnp.full((1,), rows)])
    rank = jnp.arange(r, dtype=jnp.int32) - starts[sorted_e]
    dest_sorted = jnp.where(sorted_e < num_experts,
                            pstarts[sorted_e] + rank, rows)
    dest = jnp.zeros((r,), jnp.int32).at[order].set(
        dest_sorted.astype(jnp.int32))
    tiles_used = (ends[-1] // tile_rows).astype(jnp.int32)
    first_row = jnp.arange(tiles, dtype=jnp.int32) * tile_rows
    last_used = jnp.maximum(tiles_used - 1, 0) * tile_rows
    tile_expert = jnp.searchsorted(
        ends, jnp.minimum(first_row, last_used), side="right")
    tile_expert = jnp.minimum(tile_expert, num_experts - 1).astype(jnp.int32)
    return dest, tile_expert, tiles_used


# -- kernels ------------------------------------------------------------------


def _up_kernel(te_ref, nu_ref, x_ref, wg_ref, wu_ref, o_ref):
    @pl.when(pl.program_id(1) < nu_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


def _down_kernel(te_ref, nu_ref, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < nu_ref[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def _cols(n: int) -> int:
    return _COLS if n % _COLS == 0 else n


def _tile(t, nu):
    # tiles past the last one in use revisit it: no DMA, no write-back
    return jnp.maximum(jnp.minimum(t, nu[0] - 1), 0)


def _call(kernel, name, x, weights, w_maps, out_cols, cols, tile_rows,
          tile_expert, tiles_used):
    rows, k = x.shape
    tiles = rows // tile_rows
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(out_cols // cols, tiles),
        in_specs=[pl.BlockSpec((tile_rows, k),
                               lambda n, t, te, nu: (_tile(t, nu), 0))]
        + [pl.BlockSpec((1, w.shape[1], cols), m)
           for w, m in zip(weights, w_maps)],
        out_specs=pl.BlockSpec((tile_rows, cols),
                               lambda n, t, te, nu: (_tile(t, nu), n)))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, out_cols), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pallas_interpret(), name=name,
    )(tile_expert, tiles_used.reshape(1), x, *weights)


@functools.partial(jax.jit, static_argnames=("tile_rows",))
def _pallas(x, w_in, w_out, tile_expert, tiles_used, tile_rows):
    f = w_out.shape[1]
    cols = _cols(f)
    nb = f // cols
    mid = _call(
        _up_kernel, "moe_experts_up", x, (w_in, w_in),
        (lambda n, t, te, nu: (te[t], 0, n),
         lambda n, t, te, nu: (te[t], 0, n + nb)),
        f, cols, tile_rows, tile_expert, tiles_used)
    h = w_out.shape[2]
    return _call(
        _down_kernel, "moe_experts_down", mid, (w_out,),
        (lambda n, t, te, nu: (te[t], 0, n),),
        h, _cols(h), tile_rows, tile_expert, tiles_used)


def _reference(x, w_in, w_out, tile_expert, tiles_used, tile_rows):
    """The same tiles, one ``einsum`` over each tile's own expert (its
    weights gathered per tile: small sizes only). float32 accumulation
    and one rounding after the gate, as in the kernels."""
    rows, k = x.shape
    f = w_out.shape[1]
    tiles = rows // tile_rows
    xt = x.reshape(tiles, tile_rows, k)
    gu = jnp.einsum("trk,tkn->trn", xt, w_in[tile_expert],
                    preferred_element_type=jnp.float32)
    g, u = gu[..., :f], gu[..., f:]
    mid = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
    out = jnp.einsum("trf,tfh->trh", mid, w_out[tile_expert],
                     preferred_element_type=jnp.float32).astype(x.dtype)
    used = (jnp.arange(tiles) < tiles_used)[:, None, None]
    return jnp.where(used, out, 0).reshape(rows, -1)


def grouped_gated_ffn(x, w_in, w_out, tile_expert, tiles_used, *,
                      tile_rows: int):
    """``x`` ``[rows, h]`` in the tile-aligned layout of
    :func:`routed_layout`; ``w_in`` ``[E, h, 2 f]`` with columns
    ``[gate | up]``; ``w_out`` ``[E, f, h]``. Returns ``[rows, h]``: row
    ``i`` of tile ``t`` is ``(silu(x_i Wg_e) * (x_i Wu_e)) Wd_e`` with
    ``e = tile_expert[t]``. Rows of tiles not in use are unspecified
    (the kernels leave them unwritten); the caller gathers only the rows
    it scattered."""
    if x.shape[0] % tile_rows or w_in.shape[2] != 2 * w_out.shape[1]:
        raise ValueError(
            f"grouped_gated_ffn: rows {x.shape[0]} must be whole tiles of "
            f"{tile_rows}, and w_in {w_in.shape} must be [E, h, 2f] for "
            f"w_out {w_out.shape} = [E, f, h]")
    fn = _pallas if use_pallas() else _reference
    with nvtx_range(SCOPE_MOE_EXPERTS):
        return fn(x, w_in, w_out, tile_expert, tiles_used, tile_rows)
