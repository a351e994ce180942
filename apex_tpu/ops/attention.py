"""Flash attention — Pallas TPU kernels with custom VJP.

Capability parity with the reference's fused attention extensions:

- ``fmha`` (``apex/contrib/fmha/fmha.py:33-90``, kernels under
  ``apex/contrib/csrc/fmha/``): BERT-style fused multi-head attention,
  padded/varlen batches, seq <= 512.
- ``fast_multihead_attn`` (``apex/contrib/multihead_attn/*.py``): fused
  self/encdec attention fwd/bwd built from strided-batched GEMMs + fused
  softmax (``softmax.cuh``).

The TPU design is *not* a port of those kernels: it is an online-softmax
(flash) attention tiled for the MXU, O(sq) memory, with no sequence-length
cap (the CUDA kernels cap at 512/16k). The backward recomputes attention
probabilities blockwise (the standard flash backward), trading FLOPs for HBM
traffic — the right trade on TPU where HBM bandwidth is the bottleneck.

Layout: ``[batch, heads, seq, head_dim]``; accumulation in fp32 regardless of
input dtype (matching the CUDA kernels' fp32 softmax accumulators).

Masking supports the reference's two modes: ``causal`` (upper-triangular,
``scaled_upper_triang_masked_softmax`` semantics with the usual
``sk - sq`` offset for cross/incremental attention) and per-batch valid
key/value lengths ``kv_lengths`` (the fmha varlen/padded-batch capability,
``fmha.py:41-56``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.observability.tracing import SCOPE_FLASH_BWD, SCOPE_FLASH_FWD
from apex_tpu.ops._support import pallas_interpret, round_up, use_pallas
from apex_tpu.utils.profiling import nvtx_range

__all__ = ["flash_attention", "flash_attention_packed",
           "packed_attention_supported", "flash_chunk_fwd",
           "flash_chunk_bwd"]

_NEG_INF = -1e30
# lse sentinel for fully-masked (padding) query rows: exp(s - BIG) == 0 in the
# backward recompute, so padded rows contribute nothing to dk/dv.
_LSE_PAD = 1e30

# Tuned on TPU v5e (fwd+bwd, causal, head_dim 64): (1024, 1024) wins for
# every key length >= 1024 — in-jit chained microbenches (round 4) measure
# it 25-30% faster than (512, 512) at s=1k/2k/4k (grid-step overhead and
# softmax VPU work amortize over bigger blocks) and 1.47x faster at 32k
# (PERF.md round 3). Round 3's "(512,512) optimum for 1k-4k" was an
# artifact of dispatch-overhead-polluted timing. Below 1k keys the
# (512, 512) default stays: call sites clamp blocks to the (rounded)
# sequence anyway, so the gate's effect is keeping the measured
# power-of-two tiles rather than unmeasured clamped odd sizes.
# (1024, 2048) exceeds the 16MB scoped-vmem budget.
_DEFAULT_BLOCK_Q = 512
_DEFAULT_BLOCK_K = 512
_LONG_SEQ = 1024
_LONG_BLOCK = 1024


def _auto_blocks(block_q, block_k, sk):
    """Resolve None block sizes by key length (see tuning note above).
    The long-seq upgrade applies only when the caller specified neither
    block: auto-completing one side of an explicit choice could assemble
    an over-VMEM pair like (1024, 2048)."""
    if block_q is None and block_k is None and sk >= _LONG_SEQ:
        return _LONG_BLOCK, _LONG_BLOCK
    return (block_q or _DEFAULT_BLOCK_Q), (block_k or _DEFAULT_BLOCK_K)


def _mask_block(s, i, j, bq, bk, sk, kvl, causal, window, q_off, k_off):
    """Mask a (bq, bk) logit block; returns (masked logits, validity).

    Positions are GLOBAL: query row ``r`` sits at ``r + q_off``, key column
    ``c`` at ``c + k_off``. Plain (single-chunk) attention passes
    ``q_off = sk - sq, k_off = 0``, reproducing the standard causal offset;
    context-parallel ring chunks pass ``q_off = rank*sc, k_off = j*sc`` so
    cross-chunk causality, sliding windows, and varlen limits are exact
    across shard boundaries. ``kvl`` (valid key length) is in global
    positions. ``window``: keep the last ``window`` keys incl. self
    (requires causal)."""
    row_g = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + i * bq + q_off
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bk
    col_g = col + k_off
    valid = col < sk                       # local K padding bound
    if kvl is not None:
        valid = jnp.logical_and(valid, col_g < kvl)
    if causal:
        valid = jnp.logical_and(valid, col_g <= row_g)
    if window is not None:
        valid = jnp.logical_and(valid, col_g > row_g - window)
    return jnp.where(valid, s, _NEG_INF), valid


def _causal_block_skip(i, j, bq, bk, causal, window, q_off, k_off):
    """True when k-block j has at least one unmasked column for q-block i
    (below the causal diagonal AND, with a sliding window, not entirely in
    the masked-out far past — the skipped far-past blocks are what makes
    window attention O(s*window) instead of O(s^2), and what makes
    fully-future ring chunks near-free). Offsets as in :func:`_mask_block`;
    with traced offsets (ring chunks) the result is a traced bool for
    ``pl.when``."""
    keep = True
    if causal:
        keep = j * bk + k_off <= i * bq + bq - 1 + q_off
    if window is not None:
        keep = jnp.logical_and(
            keep, j * bk + bk - 1 + k_off > i * bq + q_off - window)
    return keep


def _causal_block_full(i, j, bq, bk, causal, q_off, k_off):
    """True when EVERY element of block (i, j) is causally valid (the
    block sits entirely on/below the diagonal): its mask arithmetic —
    two iotas, compares, selects over bq x bk elements — can be skipped.
    At long sequence almost every live block is interior (32k at
    (1024,1024): 496 of 528), and the mask was ~4 of the ~9 VPU ops per
    softmax element (round 5). Callers must separately establish that no
    window/varlen/key-padding mask applies."""
    if not causal:
        return True
    return j * bk + bk - 1 + k_off <= i * bq + q_off


def _when_blocks(step, keep, i, j, bq, bk, causal, window, have_kvl, pad,
                 q_off, k_off):
    """The one block-dispatch gate every flash kernel (fwd/dq/dkv) shares:
    ``step(masked)`` returns the kernel-body thunk with or without mask
    arithmetic; live interior causal blocks run the unmasked variant (see
    :func:`_causal_block_full`), everything else the masked one, and
    ``keep`` (the caller's :func:`_causal_block_skip`, possibly clamped
    for banded grids) gates liveness. Single-sourced so forward and
    backward masking can never desynchronize."""
    if causal or window is not None:
        if causal and window is None and not have_kvl and not pad:
            full = _causal_block_full(i, j, bq, bk, causal, q_off, k_off)
            pl.when(jnp.logical_and(keep, full))(step(False))
            pl.when(jnp.logical_and(keep, jnp.logical_not(full)))(
                step(True))
        else:
            pl.when(keep)(step(True))
    elif have_kvl or pad:
        step(True)()
    else:
        step(False)()


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_single_kernel(offs_ref, kvl_ref, q_ref, k_ref, v_ref, o_ref,
                       lse_ref, *, scale, bq, bk, sk, causal, window,
                       need_mask):
    """One-pass forward for the single-block case (sq <= bq and sk <= bk):
    plain max/exp/sum softmax with no m/l/acc scratch, no online-softmax
    rescale, and — when ``need_mask`` is statically False (non-causal, no
    window/varlen, keys unpadded) — no mask arithmetic at all. At short
    sequence the general kernel's per-grid-step bookkeeping dominates:
    BERT-shape (16,12,512,64) fwd measured 468 us against a 65 us FLOP
    bound, almost all of it scratch init + masking + rescale overhead
    across 192 one-block cells (round 5); this kernel removes it."""
    b = pl.program_id(0)
    q_off, k_off = offs_ref[0], offs_ref[1]

    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if need_mask:
            kvl = kvl_ref[b] if kvl_ref is not None else None
            s, valid = _mask_block(s, 0, 0, bq, bk, sk, kvl, causal, window,
                                   q_off, k_off)
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.where(valid, jnp.exp(s - m), 0.0)
        else:
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        o = jax.lax.dot(p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        o = o * jnp.where(l > 0, 1.0 / l, 0.0)
        o_ref[0, 0] = o.astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(l), _LSE_PAD)
        lse_ref[0, 0] = jnp.broadcast_to(lse.T, lse_ref.shape[2:])

    if causal or window is not None:
        # fully-masked chunks (ring hops entirely in the causal future)
        # stay near-free, mirroring _dqkv_single_kernel
        keep = _causal_block_skip(0, 0, bq, bk, causal, window,
                                  q_off, k_off)
        pl.when(keep)(_compute)

        @pl.when(jnp.logical_not(keep))
        def _masked_out():
            o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])
            lse_ref[0, 0] = jnp.full_like(lse_ref[0, 0], _LSE_PAD)
    else:
        _compute()


def _single_need_mask(causal, window, kv_lengths, skp, sk):
    """Whether a single-block kernel needs mask arithmetic at all. Shared
    by the fwd and bwd dispatches — they MUST agree or the backward
    recompute diverges from the forward silently."""
    return (causal or window is not None or kv_lengths is not None
            or skp != sk)


def _run_fwd_single(q, k, v, kv_lengths, scale, causal, sq, sk, bq, bk,
                    group, window, q_off, k_off,
                    name="flash_attention_fwd_single"):
    """Single-block forward dispatch — see _fwd_single_kernel."""
    batch, heads, sqp, dp = q.shape
    dv = v.shape[3]
    need_mask = _single_need_mask(causal, window, kv_lengths, k.shape[2], sk)
    kvl_spec = []
    args = [_offsets(q_off, k_off, sq, sk)]
    if kv_lengths is not None:
        kvl_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args.append(kv_lengths.astype(jnp.int32))
    o, lse = pl.pallas_call(
        _wrap_kernel(_fwd_single_kernel, kv_lengths, scale=scale, bq=bq,
                     bk=bk, sk=sk, causal=causal, window=window,
                     need_mask=need_mask),
        grid=(batch, heads),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + kvl_spec + [
            pl.BlockSpec((1, 1, bq, dp), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, dp), lambda b, h: (b, h // group, 0, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda b, h: (b, h // group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, sqp, dv), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, 1, sqp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=pallas_interpret(),
        name=name,
    )(*args, q, k, v)
    return o, lse[:, :, 0, :]


def _win_j_base(i, bq, bk, qoff_static, window):
    """First k-block that can intersect q-block ``i``'s window band (static
    offsets only — the banded-grid fast path for sliding windows)."""
    lo = i * bq + qoff_static - window + 1
    return jnp.maximum(lo // bk, 0)


def _win_i_base(j, bq, bk, qoff_static, window):
    """First q-block whose window band can reach k-block ``j``."""
    lo = j * bk - qoff_static
    return jnp.maximum(lo // bq, 0)


def _fwd_kernel(offs_ref, kvl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, bq, bk, nk, sk,
                causal, window=None, win_grid=None):
    b, i, jl = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    # banded grid: the j axis only walks blocks near the window diagonal;
    # jl is the grid coordinate, j the actual k-block index
    j = (jl + _win_j_base(i, bq, bk, win_grid, window)
         if win_grid is not None else jl)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(jl == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _step(masked):
        def go():
            q = q_ref[0, 0]
            k = k_ref[0, 0]
            v = v_ref[0, 0]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                kvl = kvl_ref[b] if kvl_ref is not None else None
                s, valid = _mask_block(s, i, j, bq, bk, sk, kvl, causal,
                                       window, q_off, k_off)
            m_prev = m_scr[:, :1]
            l_prev = l_scr[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = (jnp.where(valid, jnp.exp(s - m_new), 0.0) if masked
                 else jnp.exp(s - m_new))
            l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        return go

    keep = _causal_block_skip(i, j, bq, bk, causal, window, q_off, k_off)
    if win_grid is not None:
        # banded grid can run past the last real k-block at the bottom
        # rows; those steps are skipped (their DMA is clipped in the
        # index maps)
        keep = jnp.logical_and(keep, j <= nk - 1)
    _when_blocks(_step, keep, i, j, bq, bk, causal, window,
                 kvl_ref is not None, nk * bk != sk, q_off, k_off)

    @pl.when(jl == pl.num_programs(3) - 1)
    def _finish():
        l = l_scr[:, :1]
        m = m_scr[:, :1]
        o = acc_scr[:] * jnp.where(l > 0, 1.0 / l, 0.0)
        o_ref[0, 0] = o.astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(l), _LSE_PAD)
        lse_ref[0, 0] = jnp.broadcast_to(lse.T, lse_ref.shape[2:])


def _offsets(q_off, k_off, sq, sk):
    """SMEM [q_off, k_off] operand; defaults to the classic queries-at-the-
    end convention (``q_off = sk - sq``)."""
    if q_off is None:
        q_off = sk - sq
    if k_off is None:
        k_off = 0
    return jnp.stack([jnp.asarray(q_off, jnp.int32),
                      jnp.asarray(k_off, jnp.int32)])


def _run_fwd(q, k, v, kv_lengths, scale, causal, sq, sk, bq, bk,
             group=1, window=None, q_off=None, k_off=None, name=None):
    """q/k/v padded to block multiples; returns padded (o, lse). ``group``
    q heads share each K/V head (GQA/MQA): the K/V index maps divide the
    head coordinate, so grouped heads reread the same blocks instead of the
    caller materializing a broadcast copy in HBM. ``q_off``/``k_off``:
    global-position offsets (traced OK) — see :func:`_mask_block`."""
    batch, heads, sqp, dp = q.shape
    skp, dv = k.shape[2], v.shape[3]
    nq, nk = sqp // bq, skp // bk
    if nq == 1 and nk == 1:
        # whole problem fits one (bq, bk) tile: one-pass kernel, no
        # online-softmax machinery (see _fwd_single_kernel)
        return _run_fwd_single(
            q, k, v, kv_lengths, scale, causal, sq, sk, bq, bk, group,
            window, q_off, k_off,
            **({} if name is None else {"name": name}))
    # banded grid for sliding windows with STATIC offsets (the plain flash
    # path): only the ~(window+bq)/bk k-blocks near the diagonal are walked,
    # making windowed attention O(s*window) in grid steps too, not just in
    # executed matmuls (grid overhead dominated the skip-only version)
    win_grid = None
    nk_grid = nk
    if window is not None and q_off is None and k_off is None:
        win_grid = sk - sq
        nk_grid = min(nk, (bq + window - 2) // bk + 2)

    def _kj(i, j):
        if win_grid is None:
            return j
        return jnp.minimum(j + _win_j_base(i, bq, bk, win_grid, window),
                           nk - 1)

    grid = (batch, heads, nq, nk_grid)
    kvl_spec = []
    args = [_offsets(q_off, k_off, sq, sk)]
    if kv_lengths is not None:
        kvl_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args.append(kv_lengths.astype(jnp.int32))
    kernel = _wrap_kernel(_fwd_kernel, kv_lengths, scale=scale, bq=bq,
                          bk=bk, nk=nk, sk=sk, causal=causal,
                          window=window, win_grid=win_grid)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + kvl_spec + [
            pl.BlockSpec((1, 1, bq, dp), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, dp),
                         lambda b, h, i, j: (b, h // group, _kj(i, j), 0)),
            pl.BlockSpec((1, 1, bk, dv),
                         lambda b, h, i, j: (b, h // group, _kj(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, heads, sqp, dv), q.dtype),
            jax.ShapeDtypeStruct((batch, heads, 1, sqp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=pallas_interpret(),
        name=name or "flash_attention_fwd",
    )(*args, q, k, v)
    return o, lse[:, :, 0, :]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _recompute_p_ds(q, k, v, do, lse, delta, i, j, *, scale, bq, bk, sk,
                    kvl, causal, window, q_off, k_off, need_mask=True,
                    keep=None, inv_keep=1.0):
    """The flash-backward block recompute every backward kernel shares:
    rebuild the (bq, bk) probabilities from the stashed lse and form
    ``ds = p * (dp - delta)``. Returns ``(p, ds)`` (both fp32).
    ``need_mask=False`` (statically all-valid block: non-causal, no
    window/varlen, keys unpadded) skips the mask arithmetic — at short
    sequence it is a measurable share of the kernel (round 5).
    ``keep``/``inv_keep``: attention-dropout mask regenerated from the
    forward's seed — dp is masked+rescaled BEFORE the ds identity, which
    stays exact because delta = do.o already sums the DROPPED probs (the
    same softmax-jacobian algebra as the dropout-free case)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if need_mask:
        s, _ = _mask_block(s, i, j, bq, bk, sk, kvl, causal, window,
                           q_off, k_off)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if keep is not None:
        dp = jnp.where(keep, dp * inv_keep, 0.0)
    return p, p * (dp - delta)


def _dq_kernel(offs_ref, kvl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, dq_scr, *, scale, bq, bk, nk, sk, causal,
               window=None, win_grid=None):
    b, i, jl = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    j = (jl + _win_j_base(i, bq, bk, win_grid, window)
         if win_grid is not None else jl)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(jl == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _step(masked):
        def go():
            k = k_ref[0, 0]
            kvl = kvl_ref[b] if kvl_ref is not None else None
            _, ds = _recompute_p_ds(
                q_ref[0, 0], k, v_ref[0, 0], do_ref[0, 0],
                lse_ref[0, 0].reshape(1, bq).T,
                delta_ref[0, 0].reshape(1, bq).T,
                i, j, scale=scale, bq=bq, bk=bk, sk=sk, kvl=kvl,
                causal=causal, window=window, q_off=q_off, k_off=k_off,
                need_mask=masked)
            dq_scr[:] = dq_scr[:] + scale * jax.lax.dot(
                ds.astype(k.dtype), k, preferred_element_type=jnp.float32)
        return go

    keep = _causal_block_skip(i, j, bq, bk, causal, window, q_off, k_off)
    if win_grid is not None:
        keep = jnp.logical_and(keep, j <= nk - 1)
    _when_blocks(_step, keep, i, j, bq, bk, causal, window,
                 kvl_ref is not None, nk * bk != sk, q_off, k_off)

    @pl.when(jl == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(offs_ref, kvl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, bq, bk, nq, sk, causal, group=1,
                window=None, win_grid=None, nq_grid=None):
    # grid: (batch, kv_heads, nk, group * nq_grid) — the trailing dim walks
    # every (q head in group, q block) pair so dk/dv accumulate over the
    # whole query group in one scratch pass (GQA/MQA backward); with a
    # banded window grid only the q-blocks near the diagonal are walked
    b, j, t = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    ng = nq if nq_grid is None else nq_grid
    il = t % ng
    i = (il + _win_i_base(j, bq, bk, win_grid, window)
         if win_grid is not None else il)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step(masked):
        def go():
            q = q_ref[0, 0]
            do = do_ref[0, 0]
            kvl = kvl_ref[b] if kvl_ref is not None else None
            p, ds = _recompute_p_ds(
                q, k_ref[0, 0], v_ref[0, 0], do,
                lse_ref[0, 0].reshape(1, bq).T,
                delta_ref[0, 0].reshape(1, bq).T,
                i, j, scale=scale, bq=bq, bk=bk, sk=sk, kvl=kvl,
                causal=causal, window=window, q_off=q_off, k_off=k_off,
                need_mask=masked)
            dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_scr[:] = dk_scr[:] + scale * jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return go

    keep = _causal_block_skip(i, j, bq, bk, causal, window, q_off, k_off)
    if win_grid is not None:
        keep = jnp.logical_and(keep, i <= nq - 1)
    _when_blocks(_step, keep, i, j, bq, bk, causal, window,
                 kvl_ref is not None, pl.num_programs(2) * bk != sk,
                 q_off, k_off)

    @pl.when(t == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _dqkv_fused_kernel(offs_ref, kvl_ref, dq_in_ref, q_ref, k_ref, v_ref,
                       do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                       dk_scr, dv_scr, *, scale, bq, bk, nq, sk, causal,
                       group=1, window=None):
    """Fused multi-block backward: ONE pass over the (j, t=(g, i)) grid
    computes dq, dk and dv together. The separate dq/dkv kernels each
    redo the s = qk^T recompute, the exp, the mask arithmetic and the
    dp = do.v^T matmul, and re-DMA every operand block — at 32k that
    duplication was ~30% of the whole backward (PERF.md round 5). Here
    dk/dv accumulate in scratch over the inner t sweep exactly as in
    :func:`_dkv_kernel`, while dq blocks accumulate across the OUTER j
    dim through an fp32 buffer aliased input->output: each step reads its
    dq block, adds this j's contribution (or passes it through unchanged
    for causally dead blocks — every step must write its window), and
    writes it back. Correctness of the read-modify-write needs every
    consecutive grid step to touch a DIFFERENT dq window (else the input
    window is not re-fetched and a contribution is lost): guaranteed by
    the dispatch condition nq >= 2 with no banded-window grid (the
    banded clamp can revisit the same window; those shapes keep the
    two-kernel path)."""
    b, j, t = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    i = t % nq
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step(masked):
        def go():
            q = q_ref[0, 0]
            k = k_ref[0, 0]
            do = do_ref[0, 0]
            kvl = kvl_ref[b] if kvl_ref is not None else None
            p, ds = _recompute_p_ds(
                q, k, v_ref[0, 0], do,
                lse_ref[0, 0].reshape(1, bq).T,
                delta_ref[0, 0].reshape(1, bq).T,
                i, j, scale=scale, bq=bq, bk=bk, sk=sk, kvl=kvl,
                causal=causal, window=window, q_off=q_off, k_off=k_off,
                need_mask=masked)
            dq_ref[0, 0] = dq_in_ref[0, 0] + scale * jax.lax.dot(
                ds.astype(k.dtype), k, preferred_element_type=jnp.float32)
            dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_scr[:] = dk_scr[:] + scale * jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return go

    keep = _causal_block_skip(i, j, bq, bk, causal, window, q_off, k_off)
    _when_blocks(_step, keep, i, j, bq, bk, causal, window,
                 kvl_ref is not None, pl.num_programs(2) * bk != sk,
                 q_off, k_off)
    if causal or window is not None:
        # dead blocks contribute nothing but MUST still write their dq
        # window (an unwritten window would flush stale VMEM on the next
        # index change)
        @pl.when(jnp.logical_not(keep))
        def _passthrough():
            dq_ref[0, 0] = dq_in_ref[0, 0]

    @pl.when(t == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _run_bwd_fused(q, k, v, do, lse, delta, kv_lengths, scale, causal,
                   sq, sk, bq, bk, group, window, q_off, k_off):
    """Dispatch for :func:`_dqkv_fused_kernel` (win_grid-free multi-block
    shapes). Returns (dq fp32, dk, dv)."""
    batch, heads, sqp, dp = q.shape
    kv_heads, skp = k.shape[1], k.shape[2]
    nq, nk = sqp // bq, skp // bk
    # machine-check of the aliased dq read-modify-write precondition
    # (_dqkv_fused_kernel: every consecutive grid step must touch a
    # DISTINCT dq window, guaranteed by nq >= 2 with no banded-window
    # grid). The default CI suite runs interpret mode, which never takes
    # this path — so the invariant must hold by construction, not by
    # suite coverage; a dispatcher change that violates it fails loudly
    # here instead of corrupting gradients.
    banded = window is not None and q_off is None and k_off is None
    if nq < 2 or banded:
        raise AssertionError(
            f"_run_bwd_fused dispatched outside its precondition "
            f"(nq={nq}, banded_window_grid={banded}): the aliased dq "
            f"accumulation requires nq >= 2 and a non-banded grid — "
            f"these shapes must keep the two-kernel backward")

    def _qh(h, t):
        return h * group + t // nq

    kvl_spec = []
    args = [_offsets(q_off, k_off, sq, sk)]
    if kv_lengths is not None:
        kvl_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args.append(kv_lengths.astype(jnp.int32))
    dq_zero = jnp.zeros(q.shape, jnp.float32)
    qi_spec = pl.BlockSpec((1, 1, bq, dp),
                           lambda b, h, j, t: (b, _qh(h, t), t % nq, 0))
    dq, dk, dv = pl.pallas_call(
        _wrap_kernel(_dqkv_fused_kernel, kv_lengths, scale=scale, bq=bq,
                     bk=bk, nq=nq, sk=sk, causal=causal, group=group,
                     window=window),
        grid=(batch, kv_heads, nk, group * nq),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + kvl_spec + [
            qi_spec,                                                # dq acc
            qi_spec,                                                # q
            pl.BlockSpec((1, 1, bk, dp), lambda b, h, j, t: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, dp), lambda b, h, j, t: (b, h, j, 0)),
            qi_spec,                                                # do
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b, h, j, t: (b, _qh(h, t), 0, t % nq)),
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b, h, j, t: (b, _qh(h, t), 0, t % nq)),
        ],
        out_specs=[
            qi_spec,
            pl.BlockSpec((1, 1, bk, dp), lambda b, h, j, t: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, dp), lambda b, h, j, t: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, dp), jnp.float32),
                        pltpu.VMEM((bk, dp), jnp.float32)],
        input_output_aliases={len(kvl_spec) + 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=pallas_interpret(),
        name="flash_attention_bwd_fused",
    )(*args, dq_zero, q, k, v, do, lse, delta)
    return dq.astype(q.dtype), dk, dv


def _dqkv_single_kernel(offs_ref, kvl_ref, q_ref, k_ref, v_ref, do_ref,
                        lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                        dk_scr, dv_scr, *, scale, bq, bk, sk, causal,
                        window, need_mask=True):
    """Fused one-pass backward for the single-block case (sq <= bq and
    sk <= bk): s/p are computed ONCE and all three cotangents come out of
    the same VMEM residency — at short seq the separate dq/dkv kernels
    each redo the s=qk^T recompute and re-DMA q/k/v/do, and that (not
    FLOPs) dominates; measured 1.4x faster fwd+bwd at the GPT bench shape
    (b8 h16 s1024 d64). Grid (batch, kv_heads, group): the trailing dim
    walks the query heads sharing this K/V head (GQA — grouping lives
    entirely in the grid/index maps), accumulating dk/dv in scratch and
    writing dq per head."""
    b, t = pl.program_id(0), pl.program_id(2)
    q_off, k_off = offs_ref[0], offs_ref[1]

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        do = do_ref[0, 0]
        kvl = kvl_ref[b] if kvl_ref is not None else None
        p, ds = _recompute_p_ds(
            q, k, v_ref[0, 0], do,
            lse_ref[0, 0].reshape(1, bq).T,
            delta_ref[0, 0].reshape(1, bq).T,
            0, 0, scale=scale, bq=bq, bk=bk, sk=sk, kvl=kvl, causal=causal,
            window=window, q_off=q_off, k_off=k_off, need_mask=need_mask)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] = dk_scr[:] + scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_ref[0, 0] = (scale * jax.lax.dot(
            ds.astype(k.dtype), k,
            preferred_element_type=jnp.float32)).astype(dq_ref.dtype)

    if causal or window is not None:
        # the fully-masked case (causal future / window far past) must stay
        # near-free: ring-attention backward hops route here whenever the
        # chunk fits one block, and cp/2 of them are entirely future
        keep = _causal_block_skip(0, 0, bq, bk, causal, window,
                                  q_off, k_off)
        pl.when(keep)(_step)

        @pl.when(jnp.logical_not(keep))
        def _zero_dq():
            dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])
    else:
        _step()

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# packed-QKV path (layout-native single-block attention)
# ---------------------------------------------------------------------------
# The fused QKV projection emits [s, b, G*(qpg+2)*d] with each group's
# columns ordered q_0..q_{qpg-1} | k | v. The kernels here consume that
# buffer DIRECTLY (flattened to [s, b*W], one contiguous column block per
# grid cell) and the backward writes dqkv back in the same packed layout —
# so the [s,b,..] <-> [b,h,s,d] transposes around the attention call and
# the [s,b,h,3,d]-minor cotangent reassembly disappear entirely. At 355M
# those copies were ~18 ms of a 202 ms step (PERF.md round 5); a strided/
# contiguous DMA A/B measured the layout-native reads at parity with the
# [b,h,s,d] blocks (428 vs 445 us/call at b8 h16 s1024 d64). Single-block
# only — any s with round_up(s, 8) <= 1024 (see _packed_supported: ragged
# lengths pad to the sublane multiple internally, padded keys masked via
# kv_lengths) — because the (s, s) fp32 logits of one cell must fit VMEM,
# which is also the regime where the copies dominate (at 32k the O(s)
# copies vanish next to O(s^2) attention work). RoPE and attention dropout
# run in-kernel on this path (rot/rate kernel params below).


def packed_geometry(num_groups: int, qpg: int, head_dim: int):
    """Choose groups-per-cell so both the per-cell qkv slab and the output
    slab are 128-lane aligned. Returns (gpc, in_w, out_w) or None when no
    alignment exists (then callers fall back to the 4D path)."""
    for gpc in (1, 2):
        if num_groups % gpc:
            continue
        in_w = gpc * (qpg + 2) * head_dim
        out_w = gpc * qpg * head_dim
        if in_w % 128 == 0 and out_w % 128 == 0:
            return gpc, in_w, out_w
    return None


def _packed_supported(s, num_groups, qpg, head_dim):
    # any s up to 1024: rows pad to the 8-sublane multiple inside
    # flash_attention_packed (padded keys masked via kv_lengths; padded
    # query rows sliced off), and Mosaic handles the ragged lane extents
    # of the (s, s) logits block correctly (verified on hardware at
    # s=200/520 — reductions respect logical shapes)
    return (round_up(s, 8) <= 1024 and head_dim % 8 == 0
            and packed_geometry(num_groups, qpg, head_dim) is not None)


def _drop_combo(b, head):
    """The ONE (batch, global-head) -> hash-key mapping every dropout
    mask shares: forward kernel, backward regeneration, XLA fallback and
    the parity test all call this — a drifted copy would make the
    backward regenerate a different mask than the forward applied, with
    no error raised. Stride 4096 bounds heads per model."""
    return b * 4096 + head


def _hash_keep(seed, combo, shape, rate):
    """Deterministic per-position dropout keep-mask: a murmur3-style
    integer hash of (seed, combo, row, col) in pure elementwise uint32
    math. The forward kernel, the backward's regeneration, interpret
    mode and the XLA fallback therefore produce BIT-IDENTICAL masks —
    unlike the Mosaic PRNG, whose bit-to-position assignment is not
    stable across differently-compiled kernels (measured: a mask
    extracted by a second kernel with the same seed differed). This is
    how the backward re-derives the forward's mask without storing s^2
    bytes (the reference fmha stores a philox offset for the same
    purpose). ``combo`` folds (batch, global head) — scalar in-kernel,
    broadcastable array on the XLA path. Keep probability = 1 - rate.
    The row/col position keys are THIN (s,1)/(1,s) iotas combined by one
    broadcasting op — two full-tile (s,s) uint32 iotas plus the hash
    chain exceeded the 16 MB scoped-vmem stack by 2.4 MB in the s=1024
    backward kernel."""
    ones = tuple(1 for _ in shape[:-2])
    r = jax.lax.broadcasted_iota(jnp.uint32, ones + (shape[-2], 1),
                                 len(shape) - 2)
    c = jax.lax.broadcasted_iota(jnp.uint32, ones + (1, shape[-1]),
                                 len(shape) - 1)
    k = (jnp.asarray(seed).astype(jnp.uint32)
         + jnp.asarray(combo).astype(jnp.uint32) * jnp.uint32(0x27D4EB2F))
    x = (r * jnp.uint32(0x9E3779B1) + k) ^ (c * jnp.uint32(0x85EBCA77))
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    thresh = jnp.uint32(min(int(rate * 2.0 ** 32), 2 ** 32 - 1))
    return x >= thresh


def _rope_block(t, cos, sin, rot):
    """Rotate-half RoPE over the first ``rot`` columns of a (s, d) block
    (Megatron ``concat(f, f)`` convention: the sin/cos halves repeat, so
    the inverse is the same map with ``-sin`` — pass negated sin). ``cos``/
    ``sin`` are fp32 (s, d) with cos=1/sin=0 past ``rot``."""
    tf = t.astype(jnp.float32)
    half = jnp.concatenate([-tf[:, rot // 2: rot], tf[:, : rot // 2]],
                           axis=1)
    if rot < t.shape[1]:
        half = jnp.concatenate(
            [half, jnp.zeros((t.shape[0], t.shape[1] - rot), jnp.float32)],
            axis=1)
    return (tf * cos + half * sin).astype(t.dtype)


def _fwd_packed_kernel(kvl_ref, rope_refs, seed_ref, qkv_ref, o_ref,
                       lse_ref, *,
                       scale, s, d, qpg, gpc, causal, window, need_mask,
                       rot=0, rate=0.0):
    """One grid cell = ``gpc`` whole K/V groups of one batch row. Slices are
    static column offsets into the packed slab; per-head math is the same
    one-pass softmax as :func:`_fwd_single_kernel` (sq == sk == s, offsets
    0 — a self-attention block is never fully masked, so no skip gate).
    ``rot > 0``: apply RoPE to the q/k slices in-kernel (the packed layout
    has no pre-kernel [s,b,h,d] view to rotate). ``rate > 0``: attention
    dropout on the (normalized) probabilities with an in-kernel PRNG mask
    (torch semantics: softmax, then dropout, then @v — the 1/l
    normalization commutes with the positionwise mask)."""
    b = pl.program_id(0)
    cell = pl.program_id(1)
    for g in range(gpc):
        base = g * (qpg + 2) * d
        k = qkv_ref[:, base + qpg * d: base + (qpg + 1) * d]
        v = qkv_ref[:, base + (qpg + 1) * d: base + (qpg + 2) * d]
        if rot:
            k = _rope_block(k, rope_refs[0][...], rope_refs[1][...], rot)
        for j in range(qpg):
            q = qkv_ref[:, base + j * d: base + (j + 1) * d]
            if rot:
                q = _rope_block(q, rope_refs[0][...], rope_refs[1][...],
                                rot)
            sm = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32
                                     ) * scale
            if need_mask:
                kvl = kvl_ref[b] if kvl_ref is not None else None
                sm, valid = _mask_block(sm, 0, 0, s, s, s, kvl, causal,
                                        window, 0, 0)
                m = jnp.max(sm, axis=1, keepdims=True)
                p = jnp.where(valid, jnp.exp(sm - m), 0.0)
            else:
                m = jnp.max(sm, axis=1, keepdims=True)
                p = jnp.exp(sm - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            h = g * qpg + j
            if rate > 0.0:
                keep = _hash_keep(seed_ref[0],
                                  _drop_combo(b, cell * (gpc * qpg) + h),
                                  p.shape, rate)
                p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
            o = jax.lax.dot(p.astype(v.dtype), v,
                            preferred_element_type=jnp.float32)
            o = o * jnp.where(l > 0, 1.0 / l, 0.0)
            o_ref[:, h * d:(h + 1) * d] = o.astype(o_ref.dtype)
            lse = jnp.where(l > 0, m + jnp.log(l), _LSE_PAD)
            lse_ref[0, h] = lse.reshape(1, s)


def _dqkv_packed_kernel(kvl_ref, rope_refs, seed_ref, qkv_ref, do_ref,
                        o_ref, lse_ref,
                        dqkv_ref, *, scale, s, d, qpg, gpc, causal, window,
                        need_mask, rot=0, rate=0.0):
    """Fused one-pass backward writing dq/dk/dv straight into the packed
    [s, cell-width] layout. dK/dV accumulate over the cell's query group in
    registers (the whole group lives in one cell by construction). delta
    (rowwise do . o) is computed in-kernel from the o block — as an XLA
    pre-pass it cost ~107 us/layer of separate HBM traffic at 355M.
    ``rot > 0``: the recompute rotates q/k exactly as the forward did, and
    the emitted dq/dk are un-rotated (RoPE is skew-orthogonal per row:
    inverse = same map with -sin) so the cotangent matches the RAW packed
    projection output. ``rate > 0``: the dropout keep-mask is regenerated
    from the forward's (seed, batch, cell, head) coordinates — nothing is
    stored."""
    b = pl.program_id(0)
    cell = pl.program_id(1)
    if rot:
        cos, sin = rope_refs[0][...], rope_refs[1][...]
    for g in range(gpc):
        base = g * (qpg + 2) * d
        k = qkv_ref[:, base + qpg * d: base + (qpg + 1) * d]
        v = qkv_ref[:, base + (qpg + 1) * d: base + (qpg + 2) * d]
        if rot:
            k = _rope_block(k, cos, sin, rot)
        dk_acc = jnp.zeros((s, d), jnp.float32)
        dv_acc = jnp.zeros((s, d), jnp.float32)
        for j in range(qpg):
            q = qkv_ref[:, base + j * d: base + (j + 1) * d]
            if rot:
                q = _rope_block(q, cos, sin, rot)
            h = g * qpg + j
            do = do_ref[:, h * d:(h + 1) * d]
            delta = jnp.sum(do.astype(jnp.float32)
                            * o_ref[:, h * d:(h + 1) * d].astype(
                                jnp.float32),
                            axis=1, keepdims=True)
            kvl = kvl_ref[b] if kvl_ref is not None else None
            keep = (None if rate == 0.0
                    else _hash_keep(seed_ref[0],
                                    _drop_combo(b, cell * (gpc * qpg) + h),
                                    (s, s), rate))
            p, ds = _recompute_p_ds(
                q, k, v, do,
                lse_ref[0, h].reshape(1, s).T,
                delta,
                0, 0, scale=scale, bq=s, bk=s, sk=s, kvl=kvl,
                causal=causal, window=window, q_off=0, k_off=0,
                need_mask=need_mask, keep=keep,
                inv_keep=1.0 / (1.0 - rate) if rate else 1.0)
            dq = scale * jax.lax.dot(ds.astype(k.dtype), k,
                                     preferred_element_type=jnp.float32)
            if rot:
                dq = _rope_block(dq, cos, -sin, rot)
            dqkv_ref[:, base + j * d: base + (j + 1) * d] = \
                dq.astype(dqkv_ref.dtype)
            if keep is not None:
                # dV flows through the DROPPED probabilities
                p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
            dv_acc = dv_acc + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc = dk_acc + scale * jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if rot:
            dk_acc = _rope_block(dk_acc, cos, -sin, rot)
        dqkv_ref[:, base + qpg * d: base + (qpg + 1) * d] = \
            dk_acc.astype(dqkv_ref.dtype)
        dqkv_ref[:, base + (qpg + 1) * d: base + (qpg + 2) * d] = \
            dv_acc.astype(dqkv_ref.dtype)


def _run_fwd_packed(qkv2, kv_lengths, rope, drop, *, scale, s, batch, W,
                    d, qpg, geom, heads, causal, window):
    """qkv2: [s, batch*W]; returns (o2 [s, batch*heads*d], lse [b,H,1,s]).
    ``geom`` is packed_geometry's (gpc, in_w, out_w) — the ONE source of
    the cell widths the BlockSpecs and kernel loop bounds share. ``rope``:
    None or (cos, sin) fp32 [s, d] (padded past the rotary dim)."""
    gpc, in_w, out_w = geom
    n_cells = W // in_w
    hpc = gpc * qpg
    need_mask = causal or window is not None or kv_lengths is not None
    kvl_spec = []
    args = []
    if kv_lengths is not None:
        kvl_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args.append(kv_lengths.astype(jnp.int32))
    rot = 0
    if rope is not None:
        rot = int(rope[2])
        kvl_spec = kvl_spec + [pl.BlockSpec((s, d), lambda b, c: (0, 0))] * 2
        args += [rope[0], rope[1]]
    rate = 0.0
    if drop is not None:
        rate = float(drop[1])
        kvl_spec = kvl_spec + [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args.append(drop[0])
    o, lse = pl.pallas_call(
        _wrap_kernel_nooffs(_fwd_packed_kernel, kv_lengths, rope,
                            dropout=drop is not None,
                            scale=scale,
                            s=s, d=d, qpg=qpg, gpc=gpc, causal=causal,
                            window=window, need_mask=need_mask, rot=rot,
                            rate=rate),
        grid=(batch, n_cells),
        in_specs=kvl_spec + [
            pl.BlockSpec((s, in_w), lambda b, c: (0, b * n_cells + c)),
        ],
        out_specs=[
            pl.BlockSpec((s, out_w), lambda b, c: (0, b * n_cells + c)),
            pl.BlockSpec((1, hpc, 1, s), lambda b, c: (b, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, batch * heads * d), qkv2.dtype),
            jax.ShapeDtypeStruct((batch, heads, 1, s), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=pallas_interpret(),
        name="flash_attention_fwd_packed",
    )(*args, qkv2)
    return o, lse


def _run_bwd_packed(qkv2, do2, o2, lse, kv_lengths, rope, drop, *, scale,
                    s, batch, W, d, qpg, geom, heads, causal, window):
    gpc, in_w, out_w = geom
    n_cells = W // in_w
    hpc = gpc * qpg
    need_mask = causal or window is not None or kv_lengths is not None
    kvl_spec = []
    args = []
    if kv_lengths is not None:
        kvl_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args.append(kv_lengths.astype(jnp.int32))
    rot = 0
    if rope is not None:
        rot = int(rope[2])
        kvl_spec = kvl_spec + [pl.BlockSpec((s, d), lambda b, c: (0, 0))] * 2
        args += [rope[0], rope[1]]
    rate = 0.0
    if drop is not None:
        rate = float(drop[1])
        kvl_spec = kvl_spec + [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args.append(drop[0])
    return pl.pallas_call(
        _wrap_kernel_nooffs(_dqkv_packed_kernel, kv_lengths, rope,
                            dropout=drop is not None,
                            scale=scale,
                            s=s, d=d, qpg=qpg, gpc=gpc, causal=causal,
                            window=window, need_mask=need_mask, rot=rot,
                            rate=rate),
        grid=(batch, n_cells),
        in_specs=kvl_spec + [
            pl.BlockSpec((s, in_w), lambda b, c: (0, b * n_cells + c)),
            pl.BlockSpec((s, out_w), lambda b, c: (0, b * n_cells + c)),
            pl.BlockSpec((s, out_w), lambda b, c: (0, b * n_cells + c)),
            pl.BlockSpec((1, hpc, 1, s), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((s, in_w), lambda b, c: (0, b * n_cells + c)),
        out_shape=jax.ShapeDtypeStruct(qkv2.shape, qkv2.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=pallas_interpret(),
        name="flash_attention_bwd_packed",
    )(*args, qkv2, do2, o2, lse)


def _wrap_kernel_nooffs(fn, kv_lengths, rope, dropout=False, **kw):
    """Like :func:`_wrap_kernel` for the packed kernels (no offsets
    operand: sq == sk == s, offsets statically zero). Slots None into the
    kernel's ``kvl_ref``/``rope_refs``/``seed_ref`` positions for absent
    operands."""
    have_kvl = kv_lengths is not None

    def wrapped(*refs, **k2):
        idx = 0
        kvl = None
        if have_kvl:
            kvl, idx = refs[0], 1
        rope_refs = None
        if rope is not None:
            rope_refs, idx = (refs[idx], refs[idx + 1]), idx + 2
        seed_ref = None
        if dropout:
            seed_ref, idx = refs[idx], idx + 1
        return fn(kvl, rope_refs, seed_ref, *refs[idx:], **k2)

    return functools.partial(wrapped, **kw)


def _packed_unpack(qkv, qpg, d):
    """[s, b, G*(qpg+2)*d] -> q/k/v in [b, h, s, d] (reference path)."""
    s, b, W = qkv.shape
    g = W // ((qpg + 2) * d)
    qkv5 = qkv.reshape(s, b, g, qpg + 2, d)
    q = qkv5[:, :, :, :qpg].reshape(s, b, g * qpg, d)
    k = qkv5[:, :, :, qpg]
    v = qkv5[:, :, :, qpg + 1]
    return (t.transpose(1, 2, 0, 3) for t in (q, k, v))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_packed(qkv, kv_lengths, rope_cos, rope_sin, seed, scale, causal,
                  window, qpg, d, rot, rate):
    o, _ = _flash_packed_fwd_impl(qkv, kv_lengths, rope_cos, rope_sin,
                                  seed, scale, causal, window, qpg, d, rot,
                                  rate)
    return o


def _packed_geom_of(qkv, qpg, d):
    s, b, W = qkv.shape
    g = W // ((qpg + 2) * d)
    gpc, in_w, out_w = packed_geometry(g, qpg, d)
    return s, b, W, g, (gpc, in_w, out_w), g * qpg


def _rope_tuple(rope_cos, rope_sin, rot):
    return None if rot == 0 else (rope_cos, rope_sin, rot)


def _drop_tuple(seed, rate):
    return None if rate == 0.0 else (seed, rate)


@nvtx_range(SCOPE_FLASH_FWD)
def _flash_packed_fwd_impl(qkv, kv_lengths, rope_cos, rope_sin, seed,
                           scale, causal, window, qpg, d, rot, rate):
    s, b, W, g, geom, heads = _packed_geom_of(qkv, qpg, d)
    o2, lse = _run_fwd_packed(
        qkv.reshape(s, b * W), kv_lengths, _rope_tuple(rope_cos, rope_sin,
                                                       rot),
        _drop_tuple(seed, rate),
        scale=scale, s=s, batch=b, W=W,
        d=d, qpg=qpg, geom=geom, heads=heads, causal=causal, window=window)
    return o2.reshape(s, b, heads * d), lse


def _flash_packed_vjp_fwd(qkv, kv_lengths, rope_cos, rope_sin, seed, scale,
                          causal, window, qpg, d, rot, rate):
    o, lse = _flash_packed_fwd_impl(qkv, kv_lengths, rope_cos, rope_sin,
                                    seed, scale, causal, window, qpg, d,
                                    rot, rate)
    return o, (qkv, kv_lengths, rope_cos, rope_sin, seed, o, lse)


@nvtx_range(SCOPE_FLASH_BWD)
def _flash_packed_vjp_bwd(scale, causal, window, qpg, d, rot, rate, res,
                          do):
    qkv, kv_lengths, rope_cos, rope_sin, seed, o, lse = res
    s, b, W, g, geom, heads = _packed_geom_of(qkv, qpg, d)
    dqkv = _run_bwd_packed(
        qkv.reshape(s, b * W), do.reshape(s, b * heads * d),
        o.reshape(s, b * heads * d), lse,
        kv_lengths, _rope_tuple(rope_cos, rope_sin, rot),
        _drop_tuple(seed, rate),
        scale=scale, s=s, batch=b, W=W, d=d, qpg=qpg, geom=geom,
        heads=heads, causal=causal, window=window)
    dkvl = (None if kv_lengths is None
            else np.zeros(kv_lengths.shape, dtype=jax.dtypes.float0))
    # rope tables / dropout seed are constants (zero cotangent)
    dcos = None if rope_cos is None else jnp.zeros_like(rope_cos)
    dsin = None if rope_sin is None else jnp.zeros_like(rope_sin)
    dseed = (None if seed is None
             else np.zeros(seed.shape, dtype=jax.dtypes.float0))
    return dqkv.reshape(s, b, W), dkvl, dcos, dsin, dseed


_flash_packed.defvjp(_flash_packed_vjp_fwd, _flash_packed_vjp_bwd)


def flash_attention_packed(
    qkv: jax.Array,
    *,
    queries_per_group: int,
    head_dim: int,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    kv_lengths: Optional[jax.Array] = None,
    sliding_window: Optional[int] = None,
    rope_freqs: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jax.Array] = None,
) -> jax.Array:
    """Self-attention over a packed QKV projection, layout-native.

    Args:
      qkv: ``[s, b, G*(qpg+2)*head_dim]`` — the fused QKV projection output,
        each group's columns ordered ``q_0..q_{qpg-1} | k | v`` (the
        ``ParallelAttention`` convention). GQA/MQA falls out of ``G``/
        ``qpg``; MHA is ``qpg == 1``.
      queries_per_group: query heads per K/V group (``qpg``).

    Returns ``[s, b, G*qpg*head_dim]`` context in model layout — no
    [b,h,s,d] transposes on either side of the kernel, and the VJP emits
    the packed ``dqkv`` cotangent directly (see the section comment).
    Callers must pre-check :func:`packed_attention_supported`.

    ``rope_freqs``: optional RoPE angles for positions 0..s-1 (shape
    ``[s, rot_dim]`` or the ``[s, 1, 1, rot_dim]``
    :func:`~apex_tpu.ops.fused_rope` layout, Megatron concat(f, f)
    convention, rot_dim even): q and k are rotated IN-KERNEL — the packed
    layout never materializes a pre-kernel [s,b,h,d] view to rotate — and
    the VJP un-rotates dq/dk so the cotangent matches the raw projection.

    ``dropout_rate``/``dropout_seed``: attention dropout on the softmax
    probabilities (torch semantics; the reference fmha capability),
    applied in-kernel from a position-deterministic integer hash mask
    (:func:`_hash_keep`) that the backward REGENERATES from the same
    (seed, batch, head, position) coordinates — no s^2 mask bytes are
    stored, and the Pallas kernels, interpret mode and the pure-XLA
    fallback all drop the SAME positions for a given seed.
    ``dropout_seed`` is an int32 ``[1]`` array; the caller derives it
    from its PRNG key (distinct per layer/step as desired).
    """
    s, b, W = qkv.shape
    qpg, d = queries_per_group, head_dim
    g = W // ((qpg + 2) * d)
    if W != g * (qpg + 2) * d:
        raise ValueError(f"packed width {W} is not a multiple of the group "
                         f"block {(qpg + 2) * d}")
    if sliding_window is not None and not causal:
        raise ValueError("sliding_window requires causal attention")
    scale = float(softmax_scale if softmax_scale is not None
                  else 1.0 / np.sqrt(d))
    rot = 0
    cos = sin = None
    if rope_freqs is not None:
        f = rope_freqs.reshape(s, -1).astype(jnp.float32)
        rot = f.shape[-1]
        if rot % 2 or rot > d:
            raise ValueError(f"rotary dim {rot} must be even and <= "
                             f"head_dim {d}")
        pad = ((0, 0), (0, d - rot))
        cos = jnp.pad(jnp.cos(f), pad, constant_values=1.0)
        sin = jnp.pad(jnp.sin(f), pad)
    if dropout_rate < 0.0 or dropout_rate >= 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 needs a dropout_seed")
    if not use_pallas():
        q, k, v = _packed_unpack(qkv, qpg, d)
        if rot:
            from apex_tpu.ops.rope import fused_rope
            f4 = rope_freqs.reshape(s, 1, 1, rot)
            # rope expects [s, b, h, d]
            q = fused_rope(q.transpose(2, 0, 1, 3), f4).transpose(1, 2, 0, 3)
            k = fused_rope(k.transpose(2, 0, 1, 3), f4).transpose(1, 2, 0, 3)
        ctx = _mha_reference(q, k, v, kv_lengths, scale, causal,
                             sliding_window,
                             dropout_rate=dropout_rate,
                             dropout_seed=dropout_seed)
        return ctx.transpose(2, 0, 1, 3).reshape(s, b, g * qpg * d)
    if not _packed_supported(s, g, qpg, d):
        raise ValueError(
            f"packed attention unsupported for s={s}, groups={g}, "
            f"qpg={qpg}, d={d} — gate on packed_attention_supported()")
    sp = round_up(s, 8)
    if sp != s:
        # pad rows to the sublane multiple; padded KEY slots are masked
        # via kv_lengths (a padded QUERY row then holds a real softmax
        # over the true keys — harmless: its rows are sliced off, and in
        # the VJP its do rows are zero so it contributes nothing)
        qkv = jnp.pad(qkv, ((0, sp - s), (0, 0), (0, 0)))
        kv_lengths = (jnp.full((b,), s, jnp.int32) if kv_lengths is None
                      else kv_lengths)
        if cos is not None:
            cos = jnp.pad(cos, ((0, sp - s), (0, 0)), constant_values=1.0)
            sin = jnp.pad(sin, ((0, sp - s), (0, 0)))
    seed = (None if dropout_rate == 0.0
            else dropout_seed.reshape((1,)).astype(jnp.int32))
    out = _flash_packed(qkv, kv_lengths, cos, sin, seed, scale, causal,
                        sliding_window, qpg, d, rot, float(dropout_rate))
    return out[:s] if sp != s else out


def packed_attention_supported(s: int, num_groups: int,
                               queries_per_group: int,
                               head_dim: int) -> bool:
    """Whether :func:`flash_attention_packed` has a kernel for this shape
    (callers fall back to the [b,h,s,d] path otherwise). The pure-XLA
    reference path accepts anything; this predicate is about the Pallas
    geometry: 128-lane-aligned cells, one (s, s) block in VMEM."""
    if not use_pallas():
        return True
    return _packed_supported(s, num_groups, queries_per_group, head_dim)


def _wrap_kernel(fn, kv_lengths, **kw):
    """Bind kernel keywords; with no kv_lengths operand, slot None into the
    kernel's ``kvl_ref`` position (shared by all backward dispatches)."""
    if kv_lengths is not None:
        return functools.partial(fn, **kw)
    return functools.partial(
        lambda offs, *r, **k2: fn(offs, None, *r, **k2), **kw)


def _run_bwd_single(q, k, v, do, lse, delta, kv_lengths, scale, causal,
                    sq, sk, bq, bk, group, window, q_off, k_off):
    """Single-block fused dq/dk/dv dispatch — see _dqkv_single_kernel."""
    batch, _, sqp, dp = q.shape
    kv_heads = k.shape[1]
    need_mask = _single_need_mask(causal, window, kv_lengths, k.shape[2], sk)
    kvl_spec = []
    args = [_offsets(q_off, k_off, sq, sk)]
    if kv_lengths is not None:
        kvl_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args.append(kv_lengths.astype(jnp.int32))
    dq, dk, dv = pl.pallas_call(
        _wrap_kernel(_dqkv_single_kernel, kv_lengths, scale=scale, bq=bq,
                     bk=bk, sk=sk, causal=causal, window=window,
                     need_mask=need_mask),
        grid=(batch, kv_heads, group),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + kvl_spec + [
            pl.BlockSpec((1, 1, bq, dp),
                         lambda b, h, t: (b, h * group + t, 0, 0)),  # q
            pl.BlockSpec((1, 1, bk, dp), lambda b, h, t: (b, h, 0, 0)),  # k
            pl.BlockSpec((1, 1, bk, dp), lambda b, h, t: (b, h, 0, 0)),  # v
            pl.BlockSpec((1, 1, bq, dp),
                         lambda b, h, t: (b, h * group + t, 0, 0)),  # do
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b, h, t: (b, h * group + t, 0, 0)),  # lse
            pl.BlockSpec((1, 1, 1, bq),
                         lambda b, h, t: (b, h * group + t, 0, 0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, dp),
                         lambda b, h, t: (b, h * group + t, 0, 0)),
            pl.BlockSpec((1, 1, bk, dp), lambda b, h, t: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, dp), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, dp), jnp.float32),
                        pltpu.VMEM((bk, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name="flash_attention_bwd_single",
    )(*args, q, k, v, do, lse, delta)
    return dq, dk, dv


def _run_bwd(q, k, v, do, lse, delta, kv_lengths, scale, causal,
             sq, sk, bq, bk, group=1, window=None, q_off=None, k_off=None):
    batch, heads, sqp, dp = q.shape
    kv_heads, skp = k.shape[1], k.shape[2]
    nq, nk = sqp // bq, skp // bk
    if nq == 1 and nk == 1:
        # whole problem fits one (bq, bk) tile: fused one-pass backward
        return _run_bwd_single(q, k, v, do, lse, delta, kv_lengths, scale,
                               causal, sq, sk, bq, bk, group, window,
                               q_off, k_off)
    # banded window grids (see _run_fwd)
    win_grid = None
    nk_grid, nq_grid = nk, nq
    if window is not None and q_off is None and k_off is None:
        win_grid = sk - sq
        nk_grid = min(nk, (bq + window - 2) // bk + 2)
        nq_grid = min(nq, (bk + window - 2) // bq + 2)
    if win_grid is None and nq >= 2 and not pallas_interpret():
        # fused one-pass dq/dk/dv (see _dqkv_fused_kernel); the banded
        # window grid and nq == 1 keep the two-kernel path — their block
        # revisit patterns break the aliased dq accumulation's
        # distinct-consecutive-windows requirement. Interpret mode also
        # keeps the two-kernel path: the interpreter reads inputs
        # functionally, so input_output_aliases does not feed a step's
        # dq write back to later steps (the accumulation is a compiled
        # Mosaic window-DMA mechanism); hardware parity is pinned by
        # TestFusedMultiblockBackward under APEX_TPU_TEST_TPU=1.
        return _run_bwd_fused(q, k, v, do, lse, delta, kv_lengths, scale,
                              causal, sq, sk, bq, bk, group, window,
                              q_off, k_off)

    def _kj(i, j):
        if win_grid is None:
            return j
        return jnp.minimum(j + _win_j_base(i, bq, bk, win_grid, window),
                           nk - 1)

    def _qi(j, t):
        if win_grid is None:
            return t % nq
        return jnp.minimum(
            t % nq_grid + _win_i_base(j, bq, bk, win_grid, window), nq - 1)

    def _qh(h, t):
        return h * group + t // (nq if win_grid is None else nq_grid)

    kvl_spec = []
    args = [_offsets(q_off, k_off, sq, sk)]
    if kv_lengths is not None:
        kvl_spec = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args.append(kv_lengths.astype(jnp.int32))

    row_specs = [
        pl.BlockSpec((1, 1, bq, dp), lambda b, h, i, j: (b, h, i, 0)),   # q
        pl.BlockSpec((1, 1, bk, dp),
                     lambda b, h, i, j: (b, h // group, _kj(i, j), 0)),  # k
        pl.BlockSpec((1, 1, bk, dp),
                     lambda b, h, i, j: (b, h // group, _kj(i, j), 0)),  # v
        pl.BlockSpec((1, 1, bq, dp), lambda b, h, i, j: (b, h, i, 0)),   # do
        pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),    # lse
        pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)),    # delta
    ]
    dq = pl.pallas_call(
        _wrap_kernel(_dq_kernel, kv_lengths, scale=scale, bq=bq, bk=bk, nk=nk, sk=sk,
             causal=causal, window=window, win_grid=win_grid),
        grid=(batch, heads, nq, nk_grid),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + kvl_spec
        + row_specs,
        out_specs=pl.BlockSpec((1, 1, bq, dp), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=pallas_interpret(),
        name="flash_attention_bwd_dq",
    )(*args, q, k, v, do, lse, delta)

    # trailing grid dim walks (q head in group, q block) pairs:
    # t = g*nq_grid + i_local
    col_specs = [
        pl.BlockSpec((1, 1, bq, dp),
                     lambda b, h, j, t: (b, _qh(h, t), _qi(j, t), 0)),   # q
        pl.BlockSpec((1, 1, bk, dp), lambda b, h, j, t: (b, h, j, 0)),   # k
        pl.BlockSpec((1, 1, bk, dp), lambda b, h, j, t: (b, h, j, 0)),   # v
        pl.BlockSpec((1, 1, bq, dp),
                     lambda b, h, j, t: (b, _qh(h, t), _qi(j, t), 0)),   # do
        pl.BlockSpec((1, 1, 1, bq),
                     lambda b, h, j, t: (b, _qh(h, t), 0, _qi(j, t))),   # lse
        pl.BlockSpec((1, 1, 1, bq),
                     lambda b, h, j, t: (b, _qh(h, t), 0, _qi(j, t))),   # delta
    ]
    dk, dv = pl.pallas_call(
        _wrap_kernel(_dkv_kernel, kv_lengths, scale=scale, bq=bq, bk=bk, nq=nq, sk=sk,
             causal=causal, group=group, window=window,
             win_grid=win_grid, nq_grid=nq_grid),
        grid=(batch, kv_heads, nk, group * nq_grid),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + kvl_spec
        + col_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk, dp), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, dp), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, dp), jnp.float32),
                        pltpu.VMEM((bk, dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=pallas_interpret(),
        name="flash_attention_bwd_dkv",
    )(*args, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# padding helpers + custom_vjp plumbing
# ---------------------------------------------------------------------------

def _pad_qkv(q, k, v, bq, bk):
    sq, sk = q.shape[2], k.shape[2]
    # head dim pads to a multiple of 64, not 128: Mosaic handles 64-lane
    # blocks, and the common head_dim=64 case halves kernel HBM traffic and
    # QK^T/PV FLOPs vs padding to 128 (measured ~20% faster fwd+bwd on v5e)
    # v keeps a head size of its own (latent attention: qk 192, v 128);
    # the forward kernels read it from v, the backward ones want one size
    sqp, skp = round_up(sq, bq), round_up(sk, bk)

    def pad(x, sp):
        return jnp.pad(x, ((0, 0), (0, 0), (0, sp - x.shape[2]),
                           (0, round_up(x.shape[3], 64) - x.shape[3])))
    return pad(q, sqp), pad(k, skp), pad(v, skp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, kv_lengths, scale, causal, bq, bk, window):
    o, _ = _flash_fwd_impl(q, k, v, kv_lengths, scale, causal, bq, bk,
                           window)
    return o


@nvtx_range(SCOPE_FLASH_FWD)
def _flash_fwd_impl(q, k, v, kv_lengths, scale, causal, bq, bk, window):
    sq, d = q.shape[2], q.shape[3]
    sk = k.shape[2]
    group = q.shape[1] // k.shape[1]
    qp, kp, vp = _pad_qkv(q, k, v, bq, bk)
    o, lse = _run_fwd(qp, kp, vp, kv_lengths, scale, causal, sq, sk, bq, bk,
                      group=group, window=window)
    return o[:, :, :sq, :d], lse[:, :, :sq]


def _flash_vjp_fwd(q, k, v, kv_lengths, scale, causal, bq, bk, window):
    o, lse = _flash_fwd_impl(q, k, v, kv_lengths, scale, causal, bq, bk,
                             window)
    return o, (q, k, v, kv_lengths, o, lse)


@nvtx_range(SCOPE_FLASH_BWD)
def _flash_vjp_bwd(scale, causal, bq, bk, window, res, do):
    q, k, v, kv_lengths, o, lse = res
    sq, d = q.shape[2], q.shape[3]
    sk = k.shape[2]
    sqp = round_up(sq, bq)
    qp, kp, vp = _pad_qkv(q, k, v, bq, bk)
    dop = jnp.pad(do, ((0, 0), (0, 0), (0, sqp - sq),
                       (0, qp.shape[3] - d)))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.pad(delta, ((0, 0), (0, 0), (0, sqp - sq)))
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, sqp - sq)),
                   constant_values=_LSE_PAD)
    # reshape row-vectors to (B, H, 1, sqp) for the (1,1,1,bq) block specs
    dq, dk, dv = _run_bwd(qp, kp, vp, dop, lsep[:, :, None, :],
                          delta[:, :, None, :], kv_lengths, scale, causal,
                          sq, sk, bq, bk, group=q.shape[1] // k.shape[1],
                          window=window)
    dq = dq[:, :, :sq, :d]
    dk = dk[:, :, :sk, :d]
    dv = dv[:, :, :sk, :d]
    if kv_lengths is None:
        dkvl = None
    else:
        dkvl = np.zeros(kv_lengths.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dkvl


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# chunk-level API (ring attention building blocks)
# ---------------------------------------------------------------------------
# Non-differentiable raw kernels over one (q chunk, kv chunk) pair with
# GLOBAL position offsets: ring attention composes these per hop and defines
# its own vjp (apex_tpu/ops/ring_attention.py). The lse convention matches
# the flash kernel: fp32 ``m + log(l)`` per row, ``_LSE_PAD`` for rows with
# no visible keys.

def _chunk_valid(sq, sk, q_start, k_start, kv_lengths, causal, window):
    row_g = q_start + jnp.arange(sq)[:, None]
    col_g = k_start + jnp.arange(sk)[None, :]
    valid = jnp.ones((sq, sk), bool)
    if causal:
        valid = jnp.logical_and(valid, col_g <= row_g)
    if window is not None:
        valid = jnp.logical_and(valid, col_g > row_g - window)
    valid = valid[None, None]                            # [1, 1, sq, sk]
    if kv_lengths is not None:
        valid = jnp.logical_and(
            valid, (col_g[None] < kv_lengths[:, None, None])[:, None])
    return valid


def _chunk_reference_fwd(q, k, v, kv_lengths, scale, causal, window,
                         q_start, k_start):
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    sq, sk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    valid = _chunk_valid(sq, sk, q_start, k_start, kv_lengths, causal,
                         window)
    s = jnp.where(valid, s, _NEG_INF)
    any_valid = jnp.any(valid, axis=-1)
    m = jnp.max(s, axis=-1)
    l = jnp.sum(jnp.exp(s - m[..., None]), axis=-1)
    lse = jnp.where(any_valid, m + jnp.log(l), _LSE_PAD)
    p = jnp.where(any_valid[..., None], jnp.exp(s - lse[..., None]), 0.0)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), lse


def _chunk_reference_bwd(q, k, v, do, lse, delta, kv_lengths, scale,
                         causal, window, q_start, k_start):
    group = q.shape[1] // k.shape[1]
    kf = jnp.repeat(k, group, axis=1) if group > 1 else k
    vf = jnp.repeat(v, group, axis=1) if group > 1 else v
    sq, sk = q.shape[2], kf.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    valid = _chunk_valid(sq, sk, q_start, k_start, kv_lengths, causal,
                         window)
    s = jnp.where(valid, s, _NEG_INF)
    p = jnp.exp(s - lse[..., None].astype(jnp.float32))
    do32 = do.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, do32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do32, vf.astype(jnp.float32))
    ds = p * (dp - delta[..., None].astype(jnp.float32))
    dq = scale * jnp.einsum("bhqk,bhkd->bhqd", ds, kf.astype(jnp.float32))
    dk = scale * jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    if group > 1:
        b, _, skc, d = k.shape
        dk = dk.reshape(b, k.shape[1], group, skc, d).sum(2)
        dv = dv.reshape(b, k.shape[1], group, skc, d).sum(2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@nvtx_range(SCOPE_FLASH_FWD)
def flash_chunk_fwd(q, k, v, *, q_start, k_start, causal=False, window=None,
                    kv_lengths=None, softmax_scale=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    name: Optional[str] = None):
    """One flash forward over a (q chunk, kv chunk) pair -> ``(o, lse)``.

    ``q_start``/``k_start`` (traced OK) place the chunks in GLOBAL sequence
    positions, so causal masks, sliding windows, and ``kv_lengths`` (global
    valid length) are exact across chunk boundaries; a chunk that is
    entirely in the causal future costs only grid overhead (every k-block
    is skipped) and returns ``lse = _LSE_PAD`` rows that merge with weight
    zero. ``v`` may have a head size of its own (``o`` then has it too);
    ``name`` names the Pallas call in a device trace."""
    scale = float(softmax_scale if softmax_scale is not None
                  else 1.0 / np.sqrt(q.shape[-1]))
    if not use_pallas():
        return _chunk_reference_fwd(q, k, v, kv_lengths, scale, causal,
                                    window, q_start, k_start)
    sq, d = q.shape[2], q.shape[3]
    sk = k.shape[2]
    block_q, block_k = _auto_blocks(block_q, block_k, sk)
    bq = min(block_q, round_up(sq, 8))
    bk = min(block_k, round_up(sk, 128))
    group = q.shape[1] // k.shape[1]
    qp, kp, vp = _pad_qkv(q, k, v, bq, bk)
    o, lse = _run_fwd(qp, kp, vp, kv_lengths, scale, causal, sq, sk, bq, bk,
                      group=group, window=window, q_off=q_start,
                      k_off=k_start, name=name)
    return o[:, :, :sq, :v.shape[3]], lse[:, :, :sq]


@nvtx_range(SCOPE_FLASH_BWD)
def flash_chunk_bwd(q, k, v, do, lse, delta, *, q_start, k_start,
                    causal=False, window=None, kv_lengths=None,
                    softmax_scale=None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """Flash backward over one chunk pair with the GLOBAL ``lse``/``delta``
    residuals -> ``(dq, dk, dv)``. Exactness rests on the flash-backward
    decomposition: with the global log-sum-exp, per-chunk contributions sum
    to the full-sequence gradients."""
    scale = float(softmax_scale if softmax_scale is not None
                  else 1.0 / np.sqrt(q.shape[-1]))
    if not use_pallas():
        return _chunk_reference_bwd(q, k, v, do, lse, delta, kv_lengths,
                                    scale, causal, window, q_start, k_start)
    sq, d = q.shape[2], q.shape[3]
    sk = k.shape[2]
    block_q, block_k = _auto_blocks(block_q, block_k, sk)
    bq = min(block_q, round_up(sq, 8))
    bk = min(block_k, round_up(sk, 128))
    group = q.shape[1] // k.shape[1]
    sqp = round_up(sq, bq)
    qp, kp, vp = _pad_qkv(q, k, v, bq, bk)
    dop = jnp.pad(do, ((0, 0), (0, 0), (0, sqp - sq),
                       (0, qp.shape[3] - d)))
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, sqp - sq)))
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, sqp - sq)),
                   constant_values=_LSE_PAD)
    dq, dk, dv = _run_bwd(qp, kp, vp, dop, lsep[:, :, None, :],
                          deltap[:, :, None, :], kv_lengths, scale, causal,
                          sq, sk, bq, bk, group=group, window=window,
                          q_off=q_start, k_off=k_start)
    return (dq[:, :, :sq, :d], dk[:, :, :k.shape[2], :d],
            dv[:, :, :k.shape[2], :d])


# ---------------------------------------------------------------------------
# reference (XLA) path
# ---------------------------------------------------------------------------

def _mha_reference(q, k, v, kv_lengths, scale, causal, window=None,
                   dropout_rate=0.0, dropout_seed=None):
    sq, sk = q.shape[2], k.shape[2]
    if k.shape[1] != q.shape[1]:     # GQA/MQA: broadcast the K/V heads
        group = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    col = jnp.arange(sk)[None, None, None, :]
    row = jnp.arange(sq)[None, None, :, None]
    valid = jnp.ones(s.shape, dtype=bool)
    if kv_lengths is not None:
        valid = jnp.logical_and(valid, col < kv_lengths[:, None, None, None])
    if causal:
        valid = jnp.logical_and(valid, col <= row + (sk - sq))
    if window is not None:
        valid = jnp.logical_and(valid, col > row + (sk - sq) - window)
    s = jnp.where(valid, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (empty batch elements / kv_lengths == 0) get zero
    # output + zero grads, matching the Pallas path's l == 0 guard
    p = jnp.where(jnp.any(valid, axis=-1, keepdims=True), p, 0.0)
    if dropout_rate > 0.0:
        b, h = p.shape[0], p.shape[1]
        combo = _drop_combo(
            jnp.arange(b, dtype=jnp.uint32)[:, None, None, None],
            jnp.arange(h, dtype=jnp.uint32)[None, :, None, None])
        keep = _hash_keep(jnp.asarray(dropout_seed).reshape(()), combo,
                          p.shape, dropout_rate)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    softmax_scale: Optional[float] = None,
    kv_lengths: Optional[jax.Array] = None,
    sliding_window: Optional[int] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Multi-head attention ``softmax(scale * q @ k^T + mask) @ v``.

    Args:
      q: ``[batch, heads, seq_q, head_dim]``.
      k, v: ``[batch, kv_heads, seq_k, head_dim]`` — ``kv_heads`` may divide
        ``heads`` (GQA; ``kv_heads == 1`` is MQA): grouped query heads read
        the same K/V blocks inside the kernel, so no broadcast copy of K/V
        ever lands in HBM, and dK/dV accumulate over the group in one
        scratch pass.
      causal: upper-triangular mask with the standard ``seq_k - seq_q`` offset
        (reference ``scaled_upper_triang_masked_softmax`` semantics).
      softmax_scale: defaults to ``1/sqrt(head_dim)``.
      kv_lengths: optional int32 ``[batch]`` valid key/value lengths (the
        fmha padded-batch capability, ``apex/contrib/fmha/fmha.py:41-56``).
      sliding_window: keep only the last ``sliding_window`` keys per query
        (incl. self; requires ``causal``) — Mistral-class local attention.
        Far-past K blocks are skipped entirely, so cost is O(seq * window)
        rather than O(seq^2).
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("flash_attention expects [batch, heads, seq, dim]")
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        raise ValueError(
            f"kv_heads ({k.shape[1]}) must divide query heads "
            f"({q.shape[1]}) for GQA/MQA")
    if sliding_window is not None:
        if not causal:
            raise ValueError("sliding_window requires causal attention")
        if sliding_window < 1:
            raise ValueError(f"sliding_window must be >= 1, got "
                             f"{sliding_window}")
    scale = float(softmax_scale if softmax_scale is not None
                  else 1.0 / np.sqrt(q.shape[-1]))
    if not use_pallas():
        return _mha_reference(q, k, v, kv_lengths, scale, causal,
                              sliding_window)
    block_q, block_k = _auto_blocks(block_q, block_k, k.shape[2])
    bq = min(block_q, round_up(q.shape[2], 8))
    bk = min(block_k, round_up(k.shape[2], 128))
    return _flash(q, k, v, kv_lengths, scale, causal, bq, bk,
                  sliding_window)
