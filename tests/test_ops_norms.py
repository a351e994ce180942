"""Fused LayerNorm/RMSNorm parity (tier-L0 analog of
``tests/L0/run_fused_layer_norm``): values and grads vs pure-jnp references,
plus kernel validation in Pallas interpreter mode."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import (
    fused_layer_norm,
    fused_layer_norm_affine,
    fused_rms_norm,
    fused_rms_norm_affine,
)
from apex_tpu.ops import _support


def ref_layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w
    if b is not None:
        y = y + b
    return y


def ref_rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    if w is not None:
        y = y * w
    return y


@pytest.fixture(params=[
    (4, 8, 96), pytest.param((2, 384), marks=pytest.mark.slow)])
def shapes(request):
    return request.param


def test_layer_norm_affine_fwd_bwd(shapes):
    key = jax.random.PRNGKey(0)
    h = shapes[-1]
    x = jax.random.normal(key, shapes, jnp.float32) * 2 + 1
    w = jax.random.normal(jax.random.PRNGKey(1), (h,), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(2), (h,), jnp.float32)

    y = fused_layer_norm_affine(x, w, b, h, 1e-5)
    np.testing.assert_allclose(y, ref_layer_norm(x, w, b, 1e-5), atol=1e-5)

    def loss_fused(x, w, b):
        return jnp.sum(jnp.sin(fused_layer_norm_affine(x, w, b, h, 1e-5)))

    def loss_ref(x, w, b):
        return jnp.sum(jnp.sin(ref_layer_norm(x, w, b, 1e-5)))

    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(x, w, b)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
    for gf, gr in zip(g_fused, g_ref):
        np.testing.assert_allclose(gf, gr, atol=2e-4, rtol=1e-3)


def test_layer_norm_no_affine(shapes):
    h = shapes[-1]
    x = jax.random.normal(jax.random.PRNGKey(0), shapes, jnp.float32)
    y = fused_layer_norm(x, h)
    np.testing.assert_allclose(y, ref_layer_norm(x, None, None, 1e-5), atol=1e-5)
    gf = jax.grad(lambda x: jnp.sum(fused_layer_norm(x, h) ** 2))(x)
    gr = jax.grad(lambda x: jnp.sum(ref_layer_norm(x, None, None, 1e-5) ** 2))(x)
    np.testing.assert_allclose(gf, gr, atol=2e-4, rtol=1e-3)


def test_rms_norm(shapes):
    h = shapes[-1]
    x = jax.random.normal(jax.random.PRNGKey(0), shapes, jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (h,), jnp.float32) + 1.0
    y = fused_rms_norm_affine(x, w, h, 1e-6)
    np.testing.assert_allclose(y, ref_rms_norm(x, w, 1e-6), atol=3e-5)
    g_fused = jax.grad(
        lambda x, w: jnp.sum(jnp.cos(fused_rms_norm_affine(x, w, h, 1e-6))),
        argnums=(0, 1))(x, w)
    g_ref = jax.grad(
        lambda x, w: jnp.sum(jnp.cos(ref_rms_norm(x, w, 1e-6))),
        argnums=(0, 1))(x, w)
    for gf, gr in zip(g_fused, g_ref):
        np.testing.assert_allclose(gf, gr, atol=2e-4, rtol=1e-3)
    yn = fused_rms_norm(x, h)
    np.testing.assert_allclose(yn, ref_rms_norm(x, None, 1e-6), atol=3e-5)


def test_memory_efficient_matches():
    h = 64
    x = jax.random.normal(jax.random.PRNGKey(0), (8, h), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (h,), jnp.float32) + 1.0
    b = jax.random.normal(jax.random.PRNGKey(2), (h,), jnp.float32)

    def g(me):
        return jax.grad(
            lambda x, w, b: jnp.sum(
                fused_layer_norm_affine(x, w, b, h, 1e-5, memory_efficient=me) ** 2),
            argnums=(0, 1, 2))(x, w, b)

    for a, bb in zip(g(False), g(True)):
        np.testing.assert_allclose(a, bb, atol=1e-3, rtol=1e-3)


def test_bf16_io():
    h = 128
    x = jax.random.normal(jax.random.PRNGKey(0), (16, h), jnp.bfloat16)
    w = jnp.ones((h,), jnp.bfloat16)
    b = jnp.zeros((h,), jnp.bfloat16)
    y = fused_layer_norm_affine(x, w, b, h)
    assert y.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(y, np.float32),
        np.asarray(ref_layer_norm(x, w, b, 1e-5), np.float32), atol=0.05)


def test_pallas_interpret_kernel(monkeypatch):
    """Validate the actual Pallas kernel logic via interpreter mode."""
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "interpret")
    _support.pallas_mode.cache_clear()
    try:
        h = 96  # exercises padding to 128
        x = jax.random.normal(jax.random.PRNGKey(0), (16, h), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (h,), jnp.float32) + 1.0
        b = jax.random.normal(jax.random.PRNGKey(2), (h,), jnp.float32)
        y = fused_layer_norm_affine(x, w, b, h, 1e-5)
        np.testing.assert_allclose(y, ref_layer_norm(x, w, b, 1e-5), atol=1e-5)
        g_fused = jax.grad(
            lambda x, w, b: jnp.sum(fused_layer_norm_affine(x, w, b, h, 1e-5) ** 2),
            argnums=(0, 1, 2))(x, w, b)
        g_ref = jax.grad(
            lambda x, w, b: jnp.sum(ref_layer_norm(x, w, b, 1e-5) ** 2),
            argnums=(0, 1, 2))(x, w, b)
        for gf, gr in zip(g_fused, g_ref):
            np.testing.assert_allclose(gf, gr, atol=2e-4, rtol=1e-3)
        # rms norm kernel path too
        yr = fused_rms_norm_affine(x, w, h, 1e-6)
        np.testing.assert_allclose(yr, ref_rms_norm(x, w, 1e-6), atol=1e-5)
    finally:
        _support.pallas_mode.cache_clear()


def test_pallas_interpret_multiblock_grid(monkeypatch):
    """m > block_rows forces grid > 1, exercising the dw/db revisited-block
    accumulator and the tail-row masking (a past TPU bug lived here)."""
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "interpret")
    _support.pallas_mode.cache_clear()
    try:
        h = 96
        m = 600  # bm=256 -> grid=(3,), last block partially filled
        x = jax.random.normal(jax.random.PRNGKey(0), (m, h), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (h,), jnp.float32) + 1.0
        b = jax.random.normal(jax.random.PRNGKey(2), (h,), jnp.float32)
        g_fused = jax.grad(
            lambda x, w, b: jnp.sum(fused_layer_norm_affine(x, w, b, h, 1e-5) ** 2),
            argnums=(0, 1, 2))(x, w, b)
        g_ref = jax.grad(
            lambda x, w, b: jnp.sum(ref_layer_norm(x, w, b, 1e-5) ** 2),
            argnums=(0, 1, 2))(x, w, b)
        for gf, gr in zip(g_fused, g_ref):
            np.testing.assert_allclose(gf, gr, atol=1e-3, rtol=1e-3)
    finally:
        _support.pallas_mode.cache_clear()
