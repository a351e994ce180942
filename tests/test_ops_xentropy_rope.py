"""Cross-entropy + RoPE parity tests (analogs of ``apex/contrib/test/xentropy``
and the fused_rope functional tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import (
    softmax_cross_entropy_loss,
    fused_rope,
    fused_rope_cached,
    fused_rope_thd,
    fused_rope_2d,
)


def ref_xent(logits, labels, smoothing=0.0):
    lf = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(lf, axis=-1)
    v = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, v)
    target = (1 - smoothing) * onehot + smoothing / v
    return -jnp.sum(target * logp, axis=-1)


def test_xentropy_values_and_grads():
    logits = jax.random.normal(jax.random.PRNGKey(0), (16, 50))
    labels = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 50)
    for sm in (0.0, 0.1):
        loss = softmax_cross_entropy_loss(logits, labels, sm)
        np.testing.assert_allclose(loss, ref_xent(logits, labels, sm), atol=1e-5)
        g = jax.grad(lambda l: jnp.sum(softmax_cross_entropy_loss(l, labels, sm)))(logits)
        gr = jax.grad(lambda l: jnp.sum(ref_xent(l, labels, sm)))(logits)
        np.testing.assert_allclose(g, gr, atol=1e-5)


def test_xentropy_ignore_index():
    logits = jax.random.normal(jax.random.PRNGKey(0), (8, 10))
    labels = jnp.array([0, 1, -100, 3, -100, 5, 6, 7])
    loss = softmax_cross_entropy_loss(logits, labels, 0.0)
    assert float(loss[2]) == 0.0 and float(loss[4]) == 0.0
    g = jax.grad(lambda l: jnp.sum(softmax_cross_entropy_loss(l, labels, 0.0)))(logits)
    np.testing.assert_allclose(g[2], 0.0, atol=1e-7)


def _freqs(s, d):
    inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2) / d))
    t = jnp.arange(s)[:, None] * inv[None, :]
    return jnp.concatenate([t, t], axis=-1)[:, None, None, :]


def ref_rope(t, freqs):
    f = freqs.astype(jnp.float32)
    cos, sin = jnp.cos(f), jnp.sin(f)
    rot = t[..., : f.shape[-1]]
    half = rot.shape[-1] // 2
    rot_half = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    out = rot * cos + rot_half * sin
    return jnp.concatenate([out, t[..., f.shape[-1]:]], axis=-1).astype(t.dtype)


def test_rope_matches_reference_math():
    s, b, h, d = 12, 2, 4, 16
    t = jax.random.normal(jax.random.PRNGKey(0), (s, b, h, d))
    freqs = _freqs(s, d)
    np.testing.assert_allclose(fused_rope(t, freqs), ref_rope(t, freqs), atol=1e-5)
    # partial rotation
    freqs_half = _freqs(s, d // 2)
    np.testing.assert_allclose(
        fused_rope(t, freqs_half), ref_rope(t, freqs_half), atol=1e-5)


def test_rope_grad_is_inverse_rotation():
    s, b, h, d = 8, 2, 2, 8
    t = jax.random.normal(jax.random.PRNGKey(0), (s, b, h, d))
    freqs = _freqs(s, d)
    g = jax.grad(lambda t: jnp.sum(fused_rope(t, freqs) * jnp.sin(t)))(t)
    gr = jax.grad(lambda t: jnp.sum(ref_rope(t, freqs) * jnp.sin(t)))(t)
    np.testing.assert_allclose(g, gr, atol=1e-5)


def test_rope_cached():
    s, b, h, d = 8, 2, 2, 8
    t = jax.random.normal(jax.random.PRNGKey(0), (s, b, h, d))
    f = _freqs(s, d).astype(jnp.float32)
    y = fused_rope_cached(t, jnp.cos(f), jnp.sin(f))
    np.testing.assert_allclose(y, fused_rope(t, f), atol=1e-6)


@pytest.mark.slow
def test_rope_thd():
    d, h = 8, 2
    lens = [3, 5, 2]
    cu = jnp.array([0, 3, 8, 10])
    total = 10
    t = jax.random.normal(jax.random.PRNGKey(0), (total, h, d))
    freqs = _freqs(8, d)
    y = fused_rope_thd(t, cu, freqs)
    # manual: each sequence restarts positions
    off = 0
    for L in lens:
        seg = t[off:off + L][:, None]          # (L, 1, h, d) as (s, b, h, d)
        seg = jnp.transpose(seg, (0, 1, 2, 3))
        expect = ref_rope(seg, freqs[:L])
        np.testing.assert_allclose(y[off:off + L], expect[:, 0], atol=1e-5)
        off += L


def test_rope_2d_shapes():
    b, H, W, h, d = 2, 4, 4, 2, 8
    t = jax.random.normal(jax.random.PRNGKey(0), (b, H * W, h, d))
    fh = _freqs(H, d // 2)
    fw = _freqs(W, d // 2)
    y = fused_rope_2d(t, H, W, fh, fw)
    assert y.shape == t.shape
    assert jnp.isfinite(y).all()
