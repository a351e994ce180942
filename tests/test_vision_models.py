"""Vision model tests: ResNet, DCGAN, ViT.

Mirrors the reference's example-level coverage (``examples/imagenet``,
``examples/dcgan`` drive RN50/DCGAN through amp + DDP; SyncBN numerics in
``tests/distributed/synced_batchnorm/``): shape/dtype contracts, a train
step that actually descends, and SyncBN-inside-ResNet parity between a
sharded run and the equivalent unsharded batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

from apex_tpu.models import (
    DCGANConfig,
    Discriminator,
    Generator,
    ResNet,
    ResNetConfig,
    resnet18,
    resnet50,
    vit_b16,
)
from apex_tpu.optimizers import FusedSGD


class TestResNet:
    @pytest.mark.slow  # full RN50 build+forward is compile-bound (ROADMAP tiers)
    def test_resnet50_shapes(self):
        model = resnet50(num_classes=10)
        params, state = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, 3))
        logits, new_state = jax.jit(
            lambda p, s, x: model.apply(p, s, x, train=True))(params, state, x)
        assert logits.shape == (2, 10)
        assert logits.dtype == jnp.float32
        # running stats updated
        old = state["stem"]["bn"]["mean"]
        new = new_state["stem"]["bn"]["mean"]
        assert not np.allclose(old, new)

    @pytest.mark.slow  # compile-bound eval sweep: slow tier (ROADMAP)

    def test_resnet18_eval_deterministic(self):
        model = resnet18(num_classes=4)
        params, state = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        l1, s1 = model.apply(params, state, x, train=False)
        l2, s2 = model.apply(params, state, x, train=False)
        np.testing.assert_allclose(l1, l2)
        # eval does not touch stats
        jax.tree.map(np.testing.assert_allclose, s1, state)

    @pytest.mark.slow
    def test_bf16_compute(self):
        model = ResNet(ResNetConfig(depth=18, num_classes=4,
                                    compute_dtype=jnp.bfloat16))
        params, state = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        logits, _ = jax.jit(
            lambda p, s, x: model.apply(p, s, x, train=True))(params, state, x)
        assert logits.dtype == jnp.float32
        assert np.isfinite(np.asarray(logits)).all()

    @pytest.mark.slow
    def test_train_step_descends(self):
        model = resnet18(num_classes=4)
        params, state = model.init(jax.random.PRNGKey(0))
        opt = FusedSGD(lr=0.05, momentum=0.9)
        opt_state = opt.init(params)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
        y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 4)

        @jax.jit
        def step(params, state, opt_state):
            def loss_fn(p):
                logits, new_s = model.apply(p, state, x, train=True)
                logp = jax.nn.log_softmax(logits)
                return -jnp.mean(logp[jnp.arange(8), y]), new_s
            (loss, new_s), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            params, opt_state = opt.step(grads, params, opt_state)
            return params, new_s, opt_state, loss

        losses = []
        for _ in range(8):
            params, state, opt_state, loss = step(params, state, opt_state)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    @pytest.mark.slow
    def test_syncbn_matches_global_batch(self):
        """Sharded ResNet (BN psum over 'data') == unsharded on full batch —
        the property the reference tests in
        tests/distributed/synced_batchnorm/."""
        devices = jax.devices()
        if len(devices) < 4:
            pytest.skip("needs >=4 devices")
        mesh = Mesh(np.array(devices[:4]), ("data",))
        cfg_sync = ResNetConfig(depth=18, num_classes=4, axis_name="data")
        cfg_ref = ResNetConfig(depth=18, num_classes=4, axis_name=None)
        m_sync, m_ref = ResNet(cfg_sync), ResNet(cfg_ref)
        params, state = m_ref.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))

        ref_logits, ref_state = m_ref.apply(params, state, x, train=True)

        sharded = shard_map(
            lambda p, s, x: m_sync.apply(p, s, x, train=True),
            mesh=mesh,
            in_specs=(P(), P(), P("data")),
            out_specs=(P("data"), P()))
        logits, new_state = sharded(params, state, x)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(new_state["stem"]["bn"]["mean"]),
            np.asarray(ref_state["stem"]["bn"]["mean"]), rtol=1e-5,
            atol=1e-6)


class TestDCGAN:
    def test_generator_shapes(self):
        cfg = DCGANConfig(latent_dim=32, gen_features=16, disc_features=16)
        gen = Generator(cfg)
        params, state = gen.init(jax.random.PRNGKey(0))
        z = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
        img, _ = jax.jit(
            lambda p, s, z: gen.apply(p, s, z, train=True))(params, state, z)
        assert img.shape == (4, 64, 64, 3)
        assert float(jnp.max(jnp.abs(img))) <= 1.0

    @pytest.mark.slow
    def test_discriminator_shapes(self):
        cfg = DCGANConfig(latent_dim=32, gen_features=16, disc_features=16)
        disc = Discriminator(cfg)
        params, state = disc.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 64, 3))
        logit, _ = jax.jit(
            lambda p, s, x: disc.apply(p, s, x, train=True))(params, state, x)
        assert logit.shape == (4,)

    @pytest.mark.slow
    def test_adversarial_step(self):
        """One G/D update each with separate optimizers — the multi-model,
        multi-optimizer capability of examples/dcgan/main_amp.py."""
        cfg = DCGANConfig(latent_dim=16, gen_features=8, disc_features=8)
        gen, disc = Generator(cfg), Discriminator(cfg)
        gp, gs = gen.init(jax.random.PRNGKey(0))
        dp, ds = disc.init(jax.random.PRNGKey(1))
        g_opt = FusedSGD(lr=0.01)
        d_opt = FusedSGD(lr=0.01)
        g_os, d_os = g_opt.init(gp), d_opt.init(dp)
        z = jax.random.normal(jax.random.PRNGKey(2), (4, 16))
        real = jax.random.normal(jax.random.PRNGKey(3), (4, 64, 64, 3))

        def bce(logit, target):
            return jnp.mean(jnp.maximum(logit, 0) - logit * target
                            + jnp.log1p(jnp.exp(-jnp.abs(logit))))

        @jax.jit
        def step(gp, gs, dp, ds, g_os, d_os):
            def d_loss(dp):
                fake, _ = gen.apply(gp, gs, z, train=True)
                rl, _ = disc.apply(dp, ds, real, train=True)
                fl, _ = disc.apply(dp, ds, fake, train=True)
                return bce(rl, jnp.ones(4)) + bce(fl, jnp.zeros(4))
            dl, dg = jax.value_and_grad(d_loss)(dp)
            dp, d_os = d_opt.step(dg, dp, d_os)

            def g_loss(gp):
                fake, _ = gen.apply(gp, gs, z, train=True)
                fl, _ = disc.apply(dp, ds, fake, train=True)
                return bce(fl, jnp.ones(4))
            gl, gg = jax.value_and_grad(g_loss)(gp)
            gp, g_os = g_opt.step(gg, gp, g_os)
            return gp, dp, g_os, d_os, dl, gl

        gp, dp, g_os, d_os, dl, gl = step(gp, gs, dp, ds, g_os, d_os)
        assert np.isfinite(float(dl)) and np.isfinite(float(gl))


class TestViT:
    def test_vit_ctor(self):
        model = vit_b16(image_size=224, num_classes=10)
        assert model.config.num_patches == 196
        assert model.config.transformer.hidden_size == 768

    def test_vit_shapes(self):
        from apex_tpu.models.vit import ViTConfig, ViTModel, _encoder_config
        enc = _encoder_config(2, 64, 4, ffn_hidden_size=128)
        model = ViTModel(ViTConfig(image_size=32, patch_size=16,
                                   num_classes=10, transformer=enc))
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        logits = jax.jit(model.apply)(params, x)
        assert logits.shape == (2, 10)
        assert np.isfinite(np.asarray(logits)).all()

    @pytest.mark.slow
    def test_vit_grad_flows(self):
        from apex_tpu.models.vit import ViTConfig, ViTModel, _encoder_config
        enc = _encoder_config(2, 64, 4, ffn_hidden_size=128)
        model = ViTModel(ViTConfig(image_size=32, patch_size=16,
                                   num_classes=10, transformer=enc))
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        y = jnp.array([1, 3])

        def loss_fn(p):
            logits = model.apply(p, x)
            return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(2), y])

        grads = jax.grad(loss_fn)(params)
        gnorm = jax.tree.reduce(
            lambda a, b: a + b,
            jax.tree.map(lambda g: float(jnp.sum(jnp.abs(g))), grads))
        assert np.isfinite(gnorm) and gnorm > 0
