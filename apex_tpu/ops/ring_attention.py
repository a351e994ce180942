"""Context-parallel attention: flash ring + Ulysses (all-to-all).

The reference has **no** context parallelism — its only long-context
mechanism is Megatron SP (sequence sharded between TP ranks outside matmuls,
SURVEY.md §5) and its attention kernels cap at 16k tokens
(``csrc/megatron/scaled_masked_softmax.h:460``). These two ops are the
TPU-native long-context story that closes that gap:

- :func:`ring_attention` — every rank keeps its query chunk; K/V chunks
  rotate around the ``context`` mesh axis one ``ppermute`` hop per step
  (ICI-neighbor traffic only). Each hop runs the **Pallas flash kernel** on
  the (q chunk, kv chunk) pair with global-position masking
  (:func:`apex_tpu.ops.attention.flash_chunk_fwd`), and per-hop results
  merge by log-sum-exp weights — O(block) memory per hop, bf16 MXU matmuls,
  never an O(s_local²) logit tensor. Under a causal mask, chunks entirely
  in the future are skipped *inside* the kernel grid (every k-block masked
  -> ``pl.when`` short-circuits), so the causal ring does ~half work like
  single-chip flash. ``kv_lengths`` (global valid lengths) and causal
  ``sliding_window`` are exact across chunk boundaries.
- :func:`ulysses_attention` — DeepSpeed-Ulysses-style all-to-all: exchange
  sequence sharding for head sharding, run the fused flash kernel on the
  full sequence with ``heads/cp`` local heads, all-to-all back. Two
  collectives total; better for moderate sequence lengths where the full-seq
  flash kernel wins.

The ring backward is explicit (``jax.custom_vjp``), the standard
ring-attention reverse pass: a second rotation where every rank applies the
flash backward kernel per chunk pair with the *global* ``lse``/``delta``
residuals; dK/dV partial sums ride the rotating carry and arrive home after
a full circle. Both functions degrade to plain :func:`flash_attention`
outside ``shard_map`` (context world size 1).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.ops.attention import (
    _LSE_PAD,
    flash_attention,
    flash_chunk_bwd,
    flash_chunk_fwd,
)
from apex_tpu.transformer.parallel_state import CONTEXT_AXIS
from apex_tpu.transformer.tensor_parallel.mappings import axis_bound, axis_size

__all__ = ["ring_attention", "ulysses_attention"]

# rows whose lse reaches this are fully-masked sentinels (the flash kernels
# write _LSE_PAD for them; real lse values are nowhere near it)
_PAD_THRESH = _LSE_PAD / 10


def _merge(o_a, lse_a, o_b, lse_b):
    """Combine two normalized partial attentions by log-sum-exp weights.
    fp32 ``o`` accumulators; ``_LSE_PAD`` rows (no visible keys) carry
    weight zero."""
    la = jnp.where(lse_a > _PAD_THRESH, -jnp.inf, lse_a)
    lb = jnp.where(lse_b > _PAD_THRESH, -jnp.inf, lse_b)
    lnew = jnp.logaddexp(la, lb)
    wa = jnp.where(jnp.isneginf(la), 0.0, jnp.exp(la - lnew))
    wb = jnp.where(jnp.isneginf(lb), 0.0, jnp.exp(lb - lnew))
    o = wa[..., None] * o_a + wb[..., None] * o_b.astype(jnp.float32)
    return o, lnew


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring(q, k, v, kv_lengths, causal, window, scale, axis_name):
    o, _ = _ring_fwd_impl(q, k, v, kv_lengths, causal, window, scale,
                          axis_name)
    return o


def _ring_fwd_impl(q, k, v, kv_lengths, causal, window, scale, axis_name):
    cp = axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    sc = q.shape[2]
    q_start = rank * sc

    def chunk(kc, vc, j):
        return flash_chunk_fwd(
            q, kc, vc, q_start=q_start, k_start=j * sc, causal=causal,
            window=window, kv_lengths=kv_lengths, softmax_scale=scale)

    o0, lse0 = chunk(k, v, rank)

    def hop(carry, t):
        kc, vc, o, lse = carry
        kc, vc = _rotate((kc, vc), axis_name, cp)
        j = (rank - t) % cp
        o_j, lse_j = chunk(kc, vc, j)
        o, lse = _merge(o, lse, o_j, lse_j)
        return (kc, vc, o, lse), None

    init = (k, v, o0.astype(jnp.float32),
            jnp.where(lse0 > _PAD_THRESH, -jnp.inf, lse0))
    (_, _, o, lse), _ = lax.scan(hop, init, jnp.arange(1, cp))
    return o.astype(q.dtype), lse


def _rotate(tree, axis_name, cp):
    perm = [(r, (r + 1) % cp) for r in range(cp)]
    return jax.tree.map(lambda t: lax.ppermute(t, axis_name, perm), tree)


def _ring_vjp_fwd(q, k, v, kv_lengths, causal, window, scale, axis_name):
    o, lse = _ring_fwd_impl(q, k, v, kv_lengths, causal, window, scale,
                            axis_name)
    return o, (q, k, v, kv_lengths, o, lse)


def _ring_vjp_bwd(causal, window, scale, axis_name, res, do):
    q, k, v, kv_lengths, o, lse = res
    cp = axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    sc = q.shape[2]
    q_start = rank * sc
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # the chunk backward kernel expects the flash pad sentinel for rows
    # with no visible keys (merged lse keeps them at -inf)
    lse_b = jnp.where(jnp.isneginf(lse), _LSE_PAD, lse)

    def chunk_bwd(kc, vc, j):
        return flash_chunk_bwd(
            q, kc, vc, do, lse_b, delta, q_start=q_start, k_start=j * sc,
            causal=causal, window=window, kv_lengths=kv_lengths,
            softmax_scale=scale)

    def hop(carry, t):
        kc, vc, dk, dv, dq = carry
        dq_j, dk_j, dv_j = chunk_bwd(kc, vc, (rank - t) % cp)
        dq = dq + dq_j.astype(jnp.float32)
        dk = dk + dk_j.astype(jnp.float32)
        dv = dv + dv_j.astype(jnp.float32)
        # dK/dV partials travel WITH their chunk; after cp total rotations
        # each accumulator is back at its owner
        kc, vc, dk, dv = _rotate((kc, vc, dk, dv), axis_name, cp)
        return (kc, vc, dk, dv, dq), None

    init = (k, v, jnp.zeros(k.shape, jnp.float32),
            jnp.zeros(v.shape, jnp.float32), jnp.zeros(q.shape, jnp.float32))
    (kc, vc, dk, dv, dq), _ = lax.scan(hop, init, jnp.arange(cp - 1))
    # final chunk: accumulate, then rotate ONLY the accumulators home — the
    # K/V chunks' last rotation would be discarded traffic
    dq_j, dk_j, dv_j = chunk_bwd(kc, vc, (rank - (cp - 1)) % cp)
    dq = dq + dq_j.astype(jnp.float32)
    dk, dv = _rotate((dk + dk_j.astype(jnp.float32),
                      dv + dv_j.astype(jnp.float32)), axis_name, cp)
    dkvl = (None if kv_lengths is None
            else np.zeros(kv_lengths.shape, dtype=jax.dtypes.float0))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dkvl)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    kv_lengths: Optional[jax.Array] = None,
    sliding_window: Optional[int] = None,
    axis_name: str = CONTEXT_AXIS,
) -> jax.Array:
    """Exact attention over a context-sharded sequence.

    Args:
      q, k, v: ``[batch, heads, s_local, head_dim]`` — this rank's
        contiguous sequence chunk; the global sequence is the rank-order
        concatenation over ``axis_name``. ``kv_heads`` may divide ``heads``
        (GQA/MQA): the smaller K/V chunks are what rotates.
      causal: global causal mask. Rank ``i``'s queries see chunks ``j < i``
        fully, chunk ``i`` triangularly, chunks ``j > i`` not at all — and
        the skipped work is skipped *inside* the flash kernel (masked
        k-blocks never issue their matmuls).
      kv_lengths: optional int32 ``[batch]`` — GLOBAL valid key lengths
        (pad-free varlen across the whole sharded sequence).
      sliding_window: causal local attention; the window is exact across
        chunk boundaries (far-past chunks cost only grid overhead).
    """
    if sliding_window is not None and not causal:
        raise ValueError("sliding_window requires causal attention")
    if not axis_bound(axis_name) or axis_size(axis_name) == 1:
        return flash_attention(q, k, v, causal=causal,
                               softmax_scale=softmax_scale,
                               kv_lengths=kv_lengths,
                               sliding_window=sliding_window)
    scale = float(softmax_scale if softmax_scale is not None
                  else 1.0 / np.sqrt(q.shape[-1]))
    return _ring(q, k, v, kv_lengths, causal, sliding_window, scale,
                 axis_name)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    kv_lengths: Optional[jax.Array] = None,
    sliding_window: Optional[int] = None,
    axis_name: str = CONTEXT_AXIS,
) -> jax.Array:
    """All-to-all sequence parallelism: trade the sequence shard for a head
    shard, run flash attention over the full sequence, trade back.

    Requires ``heads % cp == 0``. Layouts as :func:`ring_attention`;
    ``kv_lengths``/``sliding_window`` apply to the full gathered sequence.
    """
    if not axis_bound(axis_name) or axis_size(axis_name) == 1:
        return flash_attention(q, k, v, causal=causal,
                               softmax_scale=softmax_scale,
                               kv_lengths=kv_lengths,
                               sliding_window=sliding_window)
    cp = axis_size(axis_name)
    if q.shape[1] % cp:
        raise ValueError(
            f"ulysses_attention needs heads ({q.shape[1]}) divisible by the "
            f"context-parallel size ({cp}); use ring_attention otherwise")

    def seq_to_heads(x):
        # [b, h, s/cp, d] -> [b, h/cp, s, d]; concat order over ranks is
        # rank-major, preserving the global sequence order
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = flash_attention(qh, kh, vh, causal=causal,
                          softmax_scale=softmax_scale, kv_lengths=kv_lengths,
                          sliding_window=sliding_window)
    # [b, h/cp, s, d] -> [b, h, s/cp, d]
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)
