"""Fused LayerNorm / RMSNorm — Pallas TPU kernels with custom VJP.

Capability parity with ``fused_layer_norm_cuda``
(``csrc/layer_norm_cuda.cpp:445-459``, kernels ``csrc/layer_norm_cuda_kernel.cu``):
forward returns normalized output with per-row mean/invvar statistics; backward
produces dx and (for affine) dweight/dbias; RMSNorm shares the machinery; a
``memory_efficient`` variant recomputes x̂ from the output instead of saving
the input (reference: ``apex/normalization/fused_layer_norm.py:32-191``).

TPU design: rows are tiled onto the grid, each block normalizes ``(BM, H)`` in
VMEM with fp32 accumulation (the CUDA warp-shuffle Welford reduction,
``layer_norm_cuda_kernel.cu:353-426``, becomes a VPU row reduction); dweight /
dbias are accumulated as per-block partials then summed by XLA, replacing the
two-stage cross-CTA reduction of ``cuComputeGradInput``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.observability.tracing import SCOPE_LAYER_NORM
from apex_tpu.ops._support import block_rows, cdiv, min_sublane, pallas_interpret, round_up, use_pallas
from apex_tpu.utils.profiling import nvtx_range

_VMEM_BUDGET = 4 * 1024 * 1024  # per-operand block budget, bytes


def _norm_shapes(x, normalized_shape):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    h = int(np.prod(normalized_shape))
    m = x.size // h
    return m, h, tuple(normalized_shape)


def _block_rows(h_pad: int, dtype) -> int:
    # cap tuning history + constraints documented in the shared helper
    return block_rows(h_pad, dtype, vmem_budget=_VMEM_BUDGET)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, invvar_ref, *, h, eps,
                is_rms, has_affine, out_dtype):
    xf = x_ref[:].astype(jnp.float32)
    bm, hp = xf.shape
    mask = jax.lax.broadcasted_iota(jnp.int32, (bm, hp), 1) < h
    xf = jnp.where(mask, xf, 0.0)
    if is_rms:
        mean = jnp.zeros((bm, 1), jnp.float32)
        var = jnp.sum(xf * xf, axis=1, keepdims=True) / h
    else:
        mean = jnp.sum(xf, axis=1, keepdims=True) / h
        cent = jnp.where(mask, xf - mean, 0.0)
        var = jnp.sum(cent * cent, axis=1, keepdims=True) / h
    invvar = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean) * invvar
    if has_affine:
        y = xhat * w_ref[:].astype(jnp.float32)
        if b_ref is not None:
            y = y + b_ref[:].astype(jnp.float32)
    else:
        y = xhat
    y_ref[:] = y.astype(out_dtype)
    mean_ref[:] = mean
    invvar_ref[:] = invvar


def _fwd_pallas(x2, w, b, h, eps, is_rms, out_dtype):
    m = x2.shape[0]
    hp = round_up(h, 128)
    bm = _block_rows(hp, x2.dtype)
    grid = (cdiv(m, bm),)
    has_affine = w is not None
    xp = jnp.pad(x2, ((0, 0), (0, hp - h))) if hp != h else x2
    pad_row = lambda a: (jnp.pad(a.reshape(1, -1).astype(jnp.float32),
                                 ((0, 0), (0, hp - h))) if hp != h
                         else a.reshape(1, -1).astype(jnp.float32))
    args = [xp]
    in_specs = [pl.BlockSpec((bm, hp), lambda i: (i, 0), memory_space=pltpu.VMEM)]
    if has_affine:
        args.append(pad_row(w))
        in_specs.append(pl.BlockSpec((1, hp), lambda i: (0, 0), memory_space=pltpu.VMEM))
    if b is not None:
        args.append(pad_row(b))
        in_specs.append(pl.BlockSpec((1, hp), lambda i: (0, 0), memory_space=pltpu.VMEM))

    def kernel(*refs):
        if has_affine and b is not None:
            x_ref, w_ref, b_ref, y_ref, mean_ref, iv_ref = refs
        elif has_affine:
            x_ref, w_ref, y_ref, mean_ref, iv_ref = refs
            b_ref = None
        else:
            x_ref, y_ref, mean_ref, iv_ref = refs
            w_ref = b_ref = None
        _fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, iv_ref,
                    h=h, eps=eps, is_rms=is_rms, has_affine=has_affine,
                    out_dtype=out_dtype)

    y, mean, invvar = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, hp), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, hp), out_dtype),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
            jax.ShapeDtypeStruct((m, 1), jnp.float32),
        ],
        interpret=pallas_interpret(),
        name="layer_norm_fwd",
    )(*args)
    if hp != h:
        y = y[:, :h]
    return y, mean[:, 0], invvar[:, 0]


def _fwd_jnp(x2, w, b, h, eps, is_rms, out_dtype):
    xf = x2.astype(jnp.float32)
    if is_rms:
        mean = jnp.zeros((x2.shape[0],), jnp.float32)
        var = jnp.mean(xf * xf, axis=1)
    else:
        mean = jnp.mean(xf, axis=1)
        var = jnp.mean(jnp.square(xf - mean[:, None]), axis=1)
    invvar = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean[:, None]) * invvar[:, None]
    y = xhat
    if w is not None:
        y = y * w.reshape(1, -1).astype(jnp.float32)
    if b is not None:
        y = y + b.reshape(1, -1).astype(jnp.float32)
    return y.astype(out_dtype), mean, invvar


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(dy_ref, x_ref, mean_ref, iv_ref, w_ref,
                dx_ref, dw_ref, db_ref, *, h, m_total, is_rms, has_affine, x_dtype):
    dy = dy_ref[:].astype(jnp.float32)
    xf = x_ref[:].astype(jnp.float32)
    bm, hp = dy.shape
    # mask padded columns AND out-of-range tail rows: dw/db reduce over the
    # row axis, so garbage rows in the last block would pollute them
    row_offset = pl.program_id(0) * bm
    row_ok = (jax.lax.broadcasted_iota(jnp.int32, (bm, hp), 0) + row_offset) < m_total
    mask = (jax.lax.broadcasted_iota(jnp.int32, (bm, hp), 1) < h) & row_ok
    dy = jnp.where(mask, dy, 0.0)
    xf = jnp.where(mask, xf, 0.0)
    mean = mean_ref[:]
    invvar = iv_ref[:]
    xhat = (xf - mean) * invvar
    xhat = jnp.where(mask, xhat, 0.0)
    if has_affine:
        wf = w_ref[:].astype(jnp.float32)
        dyw = dy * wf
    else:
        dyw = dy
    c2 = jnp.sum(dyw * xhat, axis=1, keepdims=True) / h
    if is_rms:
        dx = invvar * (dyw - xhat * c2)
    else:
        c1 = jnp.sum(dyw, axis=1, keepdims=True) / h
        dx = invvar * (dyw - c1 - xhat * c2)
    dx_ref[:] = jnp.where(mask, dx, 0.0).astype(x_dtype)
    if has_affine:
        # dweight/dbias: reduce the block's rows down to 8 sublanes and
        # accumulate into a single (8, hp) output revisited by every grid
        # step (TPU grid steps run sequentially); caller sums the 8 rows.
        first = pl.program_id(0) == 0

        @pl.when(first)
        def _():
            dw_ref[:] = jnp.zeros_like(dw_ref)
            if db_ref is not None:
                db_ref[:] = jnp.zeros_like(db_ref)

        contrib = (dy * xhat).reshape(bm // 8, 8, hp)
        dw_ref[:] += jnp.sum(contrib, axis=0)
        if db_ref is not None:
            db_ref[:] += jnp.sum(dy.reshape(bm // 8, 8, hp), axis=0)


def _bwd_pallas(dy2, x2, mean, invvar, w, h, is_rms, has_bias):
    m = x2.shape[0]
    hp = round_up(h, 128)
    bm = _block_rows(hp, x2.dtype)
    grid = (cdiv(m, bm),)
    has_affine = w is not None
    pad = lambda a: jnp.pad(a, ((0, 0), (0, hp - h))) if hp != h else a
    args = [pad(dy2), pad(x2), mean.reshape(-1, 1), invvar.reshape(-1, 1)]
    in_specs = [
        pl.BlockSpec((bm, hp), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bm, hp), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((bm, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
    ]
    if has_affine:
        wp = w.reshape(1, -1).astype(jnp.float32)
        if hp != h:
            wp = jnp.pad(wp, ((0, 0), (0, hp - h)))
        args.append(wp)
        in_specs.append(pl.BlockSpec((1, hp), lambda i: (0, 0), memory_space=pltpu.VMEM))

    out_specs = [pl.BlockSpec((bm, hp), lambda i: (i, 0), memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct((m, hp), x2.dtype)]
    if has_affine:
        out_specs.append(pl.BlockSpec((8, hp), lambda i: (0, 0), memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((8, hp), jnp.float32))
        if has_bias:
            out_specs.append(pl.BlockSpec((8, hp), lambda i: (0, 0), memory_space=pltpu.VMEM))
            out_shape.append(jax.ShapeDtypeStruct((8, hp), jnp.float32))

    def kernel(*refs):
        n_in = len(args)
        ins, outs = refs[:n_in], refs[n_in:]
        dy_ref, x_ref, mean_ref, iv_ref = ins[:4]
        w_ref = ins[4] if has_affine else None
        dx_ref = outs[0]
        dw_ref = outs[1] if has_affine else None
        db_ref = outs[2] if (has_affine and has_bias) else None
        _bwd_kernel(dy_ref, x_ref, mean_ref, iv_ref, w_ref, dx_ref, dw_ref, db_ref,
                    h=h, m_total=m, is_rms=is_rms, has_affine=has_affine,
                    x_dtype=x2.dtype)

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=pallas_interpret(),
        name="layer_norm_bwd",
    )(*args)
    dx = outs[0][:, :h]
    dw = db = None
    if has_affine:
        dw = jnp.sum(outs[1], axis=0)[:h]
        if has_bias:
            db = jnp.sum(outs[2], axis=0)[:h]
    return dx, dw, db


def _bwd_jnp(dy2, x2, mean, invvar, w, h, is_rms, has_bias):
    dy = dy2.astype(jnp.float32)
    xf = x2.astype(jnp.float32)
    xhat = (xf - mean[:, None]) * invvar[:, None]
    dyw = dy * w.reshape(1, -1).astype(jnp.float32) if w is not None else dy
    c2 = jnp.mean(dyw * xhat, axis=1, keepdims=True)
    if is_rms:
        dx = invvar[:, None] * (dyw - xhat * c2)
    else:
        c1 = jnp.mean(dyw, axis=1, keepdims=True)
        dx = invvar[:, None] * (dyw - c1 - xhat * c2)
    dw = jnp.sum(dy * xhat, axis=0) if w is not None else None
    db = jnp.sum(dy, axis=0) if (w is not None and has_bias) else None
    return dx.astype(x2.dtype), dw, db


# ---------------------------------------------------------------------------
# public functional API (mirrors apex/normalization/fused_layer_norm.py)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _norm(x, weight, bias, normalized_shape, eps, is_rms, memory_efficient,
          out_dtype):
    y, _, _ = _norm_fwd_impl(x, weight, bias, normalized_shape, eps, is_rms,
                             out_dtype)
    return y


@nvtx_range(SCOPE_LAYER_NORM)
def _norm_fwd_impl(x, weight, bias, normalized_shape, eps, is_rms,
                   out_dtype=None):
    m, h, _ = _norm_shapes(x, normalized_shape)
    x2 = x.reshape(m, h)
    if out_dtype is None:
        # default: promote semantics (bf16 x + fp32 weight -> fp32 out);
        # callers that immediately consume the output in the compute dtype
        # pass out_dtype=x.dtype so the kernel writes half the bytes and
        # no downstream convert materializes (round 5: each transformer
        # LN wrote a 25 MB fp32 tensor a GEMM then re-cast to bf16)
        out_dtype = (x.dtype if weight is None
                     else jnp.promote_types(x.dtype, weight.dtype))
        if out_dtype == jnp.float64:
            out_dtype = jnp.float32
    fwd = _fwd_pallas if use_pallas() else _fwd_jnp
    y, mean, invvar = fwd(x2, weight, bias, h, eps, is_rms, out_dtype)
    return y.reshape(x.shape), mean, invvar


def _norm_vjp_fwd(x, weight, bias, normalized_shape, eps, is_rms,
                  memory_efficient, out_dtype):
    y, mean, invvar = _norm_fwd_impl(x, weight, bias, normalized_shape, eps,
                                     is_rms, out_dtype)
    # zero-size marker carrying x's dtype (x itself may not be saved)
    x_dtype_marker = jnp.zeros((0,), x.dtype)
    if memory_efficient:
        # save output instead of input; x̂ is recomputed in bwd
        # (reference memory-efficient variant, fused_layer_norm.py:43-77)
        return y, (None, y, mean, invvar, weight, bias, x_dtype_marker)
    return y, (x, y, mean, invvar, weight, bias, x_dtype_marker)


@nvtx_range(SCOPE_LAYER_NORM)
def _norm_vjp_bwd(normalized_shape, eps, is_rms, memory_efficient,
                  out_dtype, res, dy):
    x_dtype = res[-1].dtype
    res = res[:-1]
    if memory_efficient:
        _, y, mean, invvar, weight, bias = res
        m, h, _ = _norm_shapes(y, normalized_shape)
        y2 = y.reshape(m, h).astype(jnp.float32)
        if weight is not None:
            wf = weight.reshape(1, -1).astype(jnp.float32)
            safe_w = jnp.where(jnp.abs(wf) < 1e-12, 1.0, wf)
            y2 = y2 - (bias.reshape(1, -1).astype(jnp.float32) if bias is not None else 0.0)
            xhat = y2 / safe_w
        else:
            xhat = y2
        x2 = xhat / invvar[:, None] + mean[:, None]
        x2 = x2.astype(y.dtype)
    else:
        x, y, mean, invvar, weight, bias = res
        m, h, _ = _norm_shapes(x, normalized_shape)
        x2 = x.reshape(m, h)
    dy2 = dy.reshape(m, h)
    has_bias = bias is not None
    bwd = _bwd_pallas if use_pallas() else _bwd_jnp
    dx, dw, db = bwd(dy2, x2, mean, invvar, weight, h, is_rms, has_bias)
    dx = dx.reshape(dy.shape).astype(x_dtype)
    dwo = dw.reshape(weight.shape).astype(weight.dtype) if weight is not None else None
    dbo = db.reshape(bias.shape).astype(bias.dtype) if has_bias else None
    return dx, dwo, dbo


_norm.defvjp(_norm_vjp_fwd, _norm_vjp_bwd)


def fused_layer_norm_affine(x, weight, bias, normalized_shape, eps: float = 1e-5,
                            memory_efficient: bool = False, out_dtype=None):
    """Reference: ``fused_layer_norm_affine`` (``fused_layer_norm.py:194-204``).
    ``out_dtype=None`` keeps promote semantics; pass ``x.dtype`` when the
    consumer runs in the compute dtype anyway (halves the kernel's write
    bytes under mixed precision — see _norm_fwd_impl)."""
    return _norm(x, weight, bias, _as_shape(normalized_shape), eps, False,
                 memory_efficient, out_dtype)


def fused_layer_norm(x, normalized_shape, eps: float = 1e-5,
                     memory_efficient: bool = False, out_dtype=None):
    """Non-affine variant (``fused_layer_norm.py:207-214``)."""
    return _norm(x, None, None, _as_shape(normalized_shape), eps, False,
                 memory_efficient, out_dtype)


def fused_rms_norm_affine(x, weight, normalized_shape, eps: float = 1e-5,
                          memory_efficient: bool = False, out_dtype=None):
    """Reference: ``fused_rms_norm_affine`` (``fused_layer_norm.py:217-227``)."""
    return _norm(x, weight, None, _as_shape(normalized_shape), eps, True,
                 memory_efficient, out_dtype)


def fused_rms_norm(x, normalized_shape, eps: float = 1e-5,
                   memory_efficient: bool = False, out_dtype=None):
    return _norm(x, None, None, _as_shape(normalized_shape), eps, True,
                 memory_efficient, out_dtype)


def _as_shape(s) -> Tuple[int, ...]:
    return (s,) if isinstance(s, int) else tuple(s)
