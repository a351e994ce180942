"""Fused 1x1-conv + BN + stats kernel tests (ops/conv_fused.py).

Capability counterpart of the reference's fused conv-epilogue tests
(``apex/contrib/test/conv_bias_relu``, ``apex/contrib/test/bottleneck``):
kernel-vs-composition parity for forward, gradients (including the
statistics cotangent — the BN backward-through-stats path), multi-block
grids with tail masking, and full bottleneck-block / ResNet-50 parity
between the fused and unfused training paths.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.ops._support as _support
from apex_tpu.ops.conv_fused import _ref_impl, conv1x1_bn_act


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "interpret")
    _support.pallas_mode.cache_clear()
    yield
    _support.pallas_mode.cache_clear()


def _ref(x, w, a=None, b=None, *, relu=False, shift=None):
    k, n = w.shape
    x2 = x.reshape(-1, k)
    if shift is None:
        shift = jnp.zeros((n,), jnp.float32)
    if a is None:
        y, s = _ref_impl(x2, None, None, w, shift, affine=False, relu=False)
    else:
        y, s = _ref_impl(x2, a.astype(jnp.float32), b.astype(jnp.float32),
                         w, shift, affine=True, relu=relu)
    return y.reshape(*x.shape[:-1], n), s


class TestOpParity:
    @pytest.mark.parametrize("affine,relu", [(False, False), (True, False),
                                             (True, True)])
    def test_forward(self, interpret, affine, relu):
        k, n, m = 64, 96, 200
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (m, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
        a = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (k,))) + 0.5 \
            if affine else None
        b = jax.random.normal(jax.random.PRNGKey(3), (k,)) if affine else None
        c = jax.random.normal(jax.random.PRNGKey(4), (n,))
        y, s = conv1x1_bn_act(x, w, a, b, relu=relu, stats_shift=c)
        yr, sr = _ref(x, w, a, b, relu=relu, shift=c)
        np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(s, sr, atol=1e-2, rtol=1e-4)

    def test_forward_bf16(self, interpret):
        k, n, m = 64, 64, 128
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.bfloat16)
        y, s = conv1x1_bn_act(x, w)
        yr, sr = _ref(x, w)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32),
                                   atol=0.1, rtol=0.05)
        np.testing.assert_allclose(s, sr, atol=2.0, rtol=0.02)

    def test_gradients_with_stats_cotangent(self, interpret):
        """Statistics cotangent flows through the kernel backward — the
        fused equivalent of BN's backward-through-batch-stats terms."""
        k, n, m = 32, 48, 96
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
        a = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (k,))) + 0.5
        b = jax.random.normal(jax.random.PRNGKey(3), (k,))
        c = jax.random.normal(jax.random.PRNGKey(4), (n,))
        r1 = jax.random.normal(jax.random.PRNGKey(5), (m, n))
        r2 = jax.random.normal(jax.random.PRNGKey(6), (2, n))

        def loss(fn):
            def f(x, a, b, w):
                y, s = fn(x, w, a, b, relu=True, shift_kw=c)
                return jnp.sum(y * r1) + jnp.sum(s * r2)
            return f

        fused = loss(lambda x, w, a, b, relu, shift_kw:
                     conv1x1_bn_act(x, w, a, b, relu=relu,
                                    stats_shift=shift_kw))
        ref = loss(lambda x, w, a, b, relu, shift_kw:
                   _ref(x, w, a, b, relu=relu, shift=shift_kw))
        gf = jax.grad(fused, argnums=(0, 1, 2, 3))(x, a, b, w)
        gr = jax.grad(ref, argnums=(0, 1, 2, 3))(x, a, b, w)
        for f_, r_ in zip(gf, gr):
            np.testing.assert_allclose(f_, r_, atol=1e-3, rtol=1e-3)

    def test_multiblock_tail_masking(self, interpret):
        """m not divisible by the block size: tail rows must not leak into
        the statistics or the dW/da/db accumulators."""
        k, n = 16, 16
        m = 40  # bm >= 16 -> last block partial
        import apex_tpu.ops.conv_fused as cf
        orig = cf._pick_bm
        cf._pick_bm = lambda *a, **kw: 16
        try:
            x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
            w = jax.random.normal(jax.random.PRNGKey(1), (k, n), jnp.float32)
            a = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (k,))) + 0.5
            b = jax.random.normal(jax.random.PRNGKey(3), (k,))

            def f(fn):
                def g(x, a, b, w):
                    y, s = fn(x, w, a, b)
                    return jnp.sum(y ** 2) + jnp.sum(s ** 2)
                return g

            fused = f(lambda x, w, a, b: conv1x1_bn_act(x, w, a, b,
                                                        relu=True))
            ref = f(lambda x, w, a, b: _ref(x, w, a, b, relu=True))
            np.testing.assert_allclose(fused(x, a, b, w), ref(x, a, b, w),
                                       rtol=1e-5)
            gf = jax.grad(fused, argnums=(0, 1, 2, 3))(x, a, b, w)
            gr = jax.grad(ref, argnums=(0, 1, 2, 3))(x, a, b, w)
            for f_, r_ in zip(gf, gr):
                np.testing.assert_allclose(f_, r_, atol=1e-3, rtol=1e-3)
        finally:
            cf._pick_bm = orig


class TestResNetFusedParity:
    """Fused bottleneck path == unfused XLA path, forward + grads + state."""

    def _build(self, fused):
        from apex_tpu.models import ResNet, ResNetConfig
        cfg = ResNetConfig(depth=50, num_classes=8, fused_conv=fused)
        return ResNet(cfg)

    @pytest.mark.slow
    def test_model_parity(self, interpret):
        m_f, m_u = self._build(True), self._build(False)
        params, state = m_u.init(jax.random.PRNGKey(0))
        # batch 4 @ 64px keeps the deepest stage's per-channel sample count
        # non-degenerate (var >> eps), so 1/sqrt(var+eps) does not amplify
        # fp32 reassociation noise between the two compute paths
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 64, 3))
        y = jax.random.randint(jax.random.PRNGKey(2), (4,), 0, 8)

        def loss(model):
            def f(p):
                logits, new_s = model.apply(p, state, x, train=True)
                logp = jax.nn.log_softmax(logits)
                return -jnp.mean(logp[jnp.arange(4), y]), new_s
            return f

        (lf, sf), gf = jax.value_and_grad(loss(m_f), has_aux=True)(params)
        (lu, su), gu = jax.value_and_grad(loss(m_u), has_aux=True)(params)
        np.testing.assert_allclose(lf, lu, rtol=2e-4)
        jax.tree.map(lambda a_, b_: np.testing.assert_allclose(
            a_, b_, atol=5e-3, rtol=5e-3), sf, su)
        jax.tree.map(lambda a_, b_: np.testing.assert_allclose(
            a_, b_, atol=1e-2, rtol=5e-2), gf, gu)

    @pytest.mark.slow
    def test_eval_uses_unfused_path(self, interpret):
        """Eval mode must not require the training-stats kernel."""
        m_f = self._build(True)
        params, state = m_f.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        logits, new_s = m_f.apply(params, state, x, train=False)
        assert logits.shape == (2, 8)
        jax.tree.map(np.testing.assert_allclose, new_s, state)


@pytest.mark.slow   # opt-in-off path (ROADMAP D9); 1x1 op parity stays tier-1
class TestConv3x3Parity:
    """Fused 3x3 kernel (full-image blocks, 9-tap shifted GEMMs) vs the
    XLA composition oracle — forward, stats, and all gradients including
    the statistics cotangent."""

    def _args(self, nimg=4, H=8, W=8, k=16, n=32, affine=True):
        x = jax.random.normal(jax.random.PRNGKey(0), (nimg, H, W, k),
                              jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, k, n),
                              jnp.float32) * 0.2
        a = (jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (k,))) + 0.5
             if affine else None)
        b = (jax.random.normal(jax.random.PRNGKey(3), (k,)) if affine
             else None)
        c = jax.random.normal(jax.random.PRNGKey(4), (n,))
        return x, w, a, b, c

    @pytest.mark.parametrize("affine,relu", [(False, False), (True, True)])
    def test_forward(self, interpret, affine, relu):
        from apex_tpu.ops.conv_fused import _c3_ref_impl, conv3x3_bn_act

        x, w, a, b, c = self._args(affine=affine)
        y, s = conv3x3_bn_act(x, w, a, b, relu=relu, stats_shift=c)
        if affine:
            yr, sr = _c3_ref_impl(x, a, b, w, c, affine=True, relu=relu)
        else:
            yr, sr = _c3_ref_impl(x, None, None, w, c, affine=False,
                                  relu=False)
        np.testing.assert_allclose(y, yr, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(s, sr, atol=1e-2, rtol=1e-4)

    def test_gradients_with_stats_cotangent(self, interpret):
        from apex_tpu.ops.conv_fused import _c3_ref_impl, conv3x3_bn_act

        x, w, a, b, c = self._args(nimg=2, H=6, W=6, k=8, n=16)
        r1 = jax.random.normal(jax.random.PRNGKey(5), (2, 6, 6, 16))
        r2 = jax.random.normal(jax.random.PRNGKey(6), (2, 16))

        def loss(fn):
            def f(x, a, b, w):
                y, s = fn(x, a, b, w)
                return jnp.sum(y * r1) + jnp.sum(s * r2)
            return f

        gf = jax.grad(loss(lambda x, a, b, w: conv3x3_bn_act(
            x, w, a, b, relu=True, stats_shift=c)),
            argnums=(0, 1, 2, 3))(x, a, b, w)
        gr = jax.grad(loss(lambda x, a, b, w: _c3_ref_impl(
            x, a, b, w, c, affine=True, relu=True)),
            argnums=(0, 1, 2, 3))(x, a, b, w)
        for f_, r_ in zip(gf, gr):
            np.testing.assert_allclose(f_, r_, atol=2e-3, rtol=2e-3)

    def test_multi_image_grid(self, interpret):
        """nimg > images-per-block exercises the revisited dW/da/db
        accumulators across grid steps."""
        import apex_tpu.ops.conv_fused as cf

        orig = cf._c3_pick_bn
        cf._c3_pick_bn = lambda *a, **kw: 2
        try:
            from apex_tpu.ops.conv_fused import (_c3_ref_impl,
                                                 conv3x3_bn_act)

            x, w, a, b, c = self._args(nimg=6, H=4, W=4, k=8, n=8)

            def f(fn):
                def g(x, a, b, w):
                    y, s = fn(x, a, b, w)
                    return jnp.sum(y ** 2) + jnp.sum(s ** 2)
                return g

            fused = f(lambda x, a, b, w: conv3x3_bn_act(
                x, w, a, b, relu=True, stats_shift=c))
            ref = f(lambda x, a, b, w: _c3_ref_impl(
                x, a, b, w, c, affine=True, relu=True))
            np.testing.assert_allclose(fused(x, a, b, w), ref(x, a, b, w),
                                       rtol=1e-5)
            gf = jax.grad(fused, argnums=(0, 1, 2, 3))(x, a, b, w)
            gr = jax.grad(ref, argnums=(0, 1, 2, 3))(x, a, b, w)
            for f_, r_ in zip(gf, gr):
                np.testing.assert_allclose(f_, r_, atol=2e-3, rtol=2e-3)
        finally:
            cf._c3_pick_bn = orig

    def test_forward_backward_bf16(self, interpret):
        """bf16 is the production amp-O2 dtype and the 3x3 kernel has more
        dtype-sensitive cast points (zb, dy_c, out_dtype, fp32 dzp)."""
        from apex_tpu.ops.conv_fused import _c3_ref_impl, conv3x3_bn_act

        x, w, a, b, c = self._args(nimg=2, H=6, W=6, k=8, n=16)
        x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)

        def f(fn):
            def g(x, a, b, w):
                y, s = fn(x, a, b, w)
                return (jnp.sum(y.astype(jnp.float32) ** 2)
                        + jnp.sum(s * 1e-3))
            return g

        fused = f(lambda x, a, b, w: conv3x3_bn_act(
            x, w, a, b, relu=True, stats_shift=c))
        ref = f(lambda x, a, b, w: _c3_ref_impl(
            x, a, b, w, c, affine=True, relu=True))
        np.testing.assert_allclose(float(fused(x, a, b, w)),
                                   float(ref(x, a, b, w)), rtol=2e-2)
        gf = jax.grad(fused, argnums=(0, 1, 2, 3))(x, a, b, w)
        gr = jax.grad(ref, argnums=(0, 1, 2, 3))(x, a, b, w)
        for f_, r_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(f_, np.float32),
                                       np.asarray(r_, np.float32),
                                       atol=0.15, rtol=0.1)
