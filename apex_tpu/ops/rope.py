"""Fused rotary positional embedding.

Capability parity with ``fused_rotary_positional_embedding``
(``csrc/megatron/fused_rotary_positional_embedding.cpp:223-243``): plain,
cached sin/cos, THD (packed variable-length), and 2D-image variants, each with
an exact custom VJP (rotate by -θ), mirroring the functional wrappers in
``apex/transformer/functional/fused_rope.py:19-303``.

RoPE is pure elementwise math; under XLA it fuses into the surrounding
matmuls' prologue, so a handwritten Pallas kernel adds nothing — the fusion
the CUDA build needed a kernel for is the compiler's default here.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class YarnScaling:
    """YaRN context extension of rotary positions (arXiv 2309.00071), under
    the published ``rope_scaling`` key names of the DeepSeek-V3 family."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(rot_dim: int, theta: float, yarn: YarnScaling):
    """``(inv_freq [rot_dim / 2] float32, cos/sin scale)``: the rotary
    frequencies blended between ``theta^(-2k/rot_dim)`` (extrapolated: the
    fast dims, kept) and the same over ``factor`` (interpolated: the slow
    dims), by a linear ramp between the dims whose wavelength makes
    ``beta_fast`` and ``beta_slow`` turns in the original context."""
    base = theta ** (np.arange(0, rot_dim, 2, dtype=np.float64) / rot_dim)

    def turns_dim(turns):
        return (rot_dim * math.log(yarn.original_max_position_embeddings
                                   / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_dim(yarn.beta_slow)), rot_dim - 1)
    ramp = np.clip((np.arange(rot_dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv = (1.0 / (yarn.factor * base)) * ramp + (1.0 / base) * (1.0 - ramp)
    scale = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    return inv.astype(np.float32), scale


def _rotate_half(t: jax.Array) -> jax.Array:
    half = t.shape[-1] // 2
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([-t2, t1], axis=-1)


def _apply(t: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    rot_dim = cos.shape[-1]
    t_rot, t_pass = t[..., :rot_dim], t[..., rot_dim:]
    out = t_rot * cos + _rotate_half(t_rot) * sin
    if t_pass.shape[-1]:
        out = jnp.concatenate([out, t_pass], axis=-1)
    return out.astype(t.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def fused_rope(t: jax.Array, freqs: jax.Array) -> jax.Array:
    """Apply RoPE. ``t``: (s, b, h, d); ``freqs``: (s, 1, 1, d_rot)
    (reference: ``fused_rope.py:19-98``)."""
    f = freqs.astype(jnp.float32)
    return _apply(t, jnp.cos(f), jnp.sin(f))


def _rope_fwd(t, freqs):
    return fused_rope(t, freqs), freqs


def _rope_bwd(freqs, g):
    f = freqs.astype(jnp.float32)
    # inverse rotation: cos(θ) unchanged, sin(−θ) = −sin(θ)
    return _apply(g, jnp.cos(f), -jnp.sin(f)), None


fused_rope.defvjp(_rope_fwd, _rope_bwd)


def fused_rope_cached(t: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Cached-sin/cos variant (reference: ``fused_rope.py:99-178``)."""
    return _rope_cached(t, cos, sin)


@jax.custom_vjp
def _rope_cached(t, cos, sin):
    return _apply(t, cos.astype(jnp.float32), sin.astype(jnp.float32))


def _rc_fwd(t, cos, sin):
    return _rope_cached(t, cos, sin), (cos, sin)


def _rc_bwd(res, g):
    cos, sin = res
    return _apply(g, cos.astype(jnp.float32), -sin.astype(jnp.float32)), None, None


_rope_cached.defvjp(_rc_fwd, _rc_bwd)


def fused_rope_thd(t: jax.Array, cu_seqlens: jax.Array, freqs: jax.Array) -> jax.Array:
    """Packed variable-length (THD) variant (reference: ``fused_rope.py:179-246``).

    ``t``: (total_tokens, h, d); ``cu_seqlens``: (batch+1,) cumulative lengths;
    ``freqs``: (max_seq, 1, 1, d_rot). Each token uses the frequency of its
    position within its own sequence.
    """
    total = t.shape[0]
    token_idx = jnp.arange(total)
    # position within sequence: idx - cu_seqlens[seq_id]
    seq_id = jnp.searchsorted(cu_seqlens, token_idx, side="right") - 1
    pos = token_idx - cu_seqlens[seq_id]
    f = freqs[pos, 0, 0, :].astype(jnp.float32)  # (total, d_rot)
    cos = jnp.cos(f)[:, None, :]
    sin = jnp.sin(f)[:, None, :]
    return _rope_cached(t, cos, sin)


def fused_rope_2d(t: jax.Array, img_h: int, img_w: int,
                  freqs_h: jax.Array, freqs_w: jax.Array) -> jax.Array:
    """2D image variant (reference: ``fused_rope.py:247-303``).

    ``t``: (b, img_h*img_w, h, d); first half of d rotated by row frequencies,
    second half by column frequencies.
    """
    d = t.shape[-1]
    half = d // 2
    fh = jnp.broadcast_to(freqs_h[:img_h, 0, 0, :], (img_h, half))
    fw = jnp.broadcast_to(freqs_w[:img_w, 0, 0, :], (img_w, half))
    fh2 = jnp.repeat(fh[:, None, :], img_w, axis=1).reshape(img_h * img_w, half)
    fw2 = jnp.repeat(fw[None, :, :], img_h, axis=0).reshape(img_h * img_w, half)
    f = jnp.concatenate([fh2, fw2], axis=-1)[None, :, None, :].astype(jnp.float32)
    return _rope_cached(t, jnp.cos(f), jnp.sin(f))
