"""DCGAN mixed-precision training — parity with the reference's second
example (``/root/reference/examples/dcgan/main_amp.py``).

The apex capability exercised there is *multiple models/optimizers/losses
under one amp context* (``amp.initialize(num_losses=3)``, one ``scale_loss``
per loss with its own scaler). Here: two functional nets, two FusedAdam
optimizers, three dynamic loss-scaler states (errD_real, errD_fake, errG)
from one ``amp.initialize(num_losses=3)`` call, trained on synthetic images.

Usage: ``PYTHONPATH=. python examples/dcgan_amp.py``
"""

import argparse
import time

import jax
import jax.numpy as jnp

from apex_tpu import amp
from apex_tpu.models import DCGANConfig, Discriminator, Generator
from apex_tpu.optimizers import FusedAdam


def bce_with_logits(logit, target):
    return jnp.mean(jnp.maximum(logit, 0) - logit * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--nz", type=int, default=100)
    ap.add_argument("--lr", type=float, default=2e-4)
    args = ap.parse_args()

    # fp16-style dynamic scaling exercised on all three losses (the bf16
    # default wouldn't need it; the flow is the capability under test)
    amp_state = amp.initialize("O2", num_losses=3)
    scaler = amp_state.scaler
    sstates = amp_state.scaler_states
    cfg = DCGANConfig(latent_dim=args.nz, compute_dtype=jnp.bfloat16)
    gen, disc = Generator(cfg), Discriminator(cfg)
    gp, gs = gen.init(jax.random.PRNGKey(0))
    dp_, ds = disc.init(jax.random.PRNGKey(1))
    g_opt = FusedAdam(lr=args.lr, betas=(0.5, 0.999), master_weights=True)
    d_opt = FusedAdam(lr=args.lr, betas=(0.5, 0.999), master_weights=True)
    g_os, d_os = g_opt.init(gp), d_opt.init(dp_)

    @jax.jit
    def train_step(gp, gs, dp_, ds, g_os, d_os, sstates, rng):
        k_z, k_data, k_z2 = jax.random.split(rng, 3)
        real = jnp.tanh(jax.random.normal(
            k_data, (args.batch, 64, 64, 3)))          # synthetic "images"
        z = jax.random.normal(k_z, (args.batch, args.nz))
        s_real, s_fake, s_g = sstates

        # --- D step: two separately-scaled losses (reference lines
        # `with amp.scale_loss(errD_real, optimizerD, loss_id=0)` etc.)
        def d_loss_real(dp_):
            logit, _ = disc.apply(dp_, ds, real, train=True)
            return bce_with_logits(logit, jnp.ones(args.batch))

        def d_loss_fake(dp_):
            fake, _ = gen.apply(gp, gs, z, train=True)
            logit, new_ds = disc.apply(dp_, ds, fake, train=True)
            return bce_with_logits(logit, jnp.zeros(args.batch)), new_ds

        lr_scaled, g_real = jax.value_and_grad(
            lambda p: scaler.scale(d_loss_real(p), s_real))(dp_)
        lr_raw = lr_scaled / s_real.loss_scale

        def d_fake_scaled(p):
            loss, new_ds = d_loss_fake(p)
            return scaler.scale(loss, s_fake), new_ds

        (lf_scaled, new_ds), g_fake = jax.value_and_grad(
            d_fake_scaled, has_aux=True)(dp_)
        lf_raw = lf_scaled / s_fake.loss_scale

        g_real, inf_real = scaler.unscale(g_real, s_real)
        g_fake, inf_fake = scaler.unscale(g_fake, s_fake)
        d_grads = jax.tree.map(lambda a, b: a + b, g_real, g_fake)
        d_inf = jnp.logical_or(inf_real, inf_fake)
        new_dp, new_d_os = d_opt.step(d_grads, dp_, d_os, found_inf=d_inf)
        s_real = scaler.update(s_real, inf_real)
        s_fake = scaler.update(s_fake, inf_fake)

        # --- G step (loss_id=2)
        def g_loss(gp):
            fake, new_gs = gen.apply(gp, gs, z, train=True)
            logit, _ = disc.apply(new_dp, ds, fake, train=True)
            return bce_with_logits(logit, jnp.ones(args.batch)), new_gs

        def g_loss_scaled(p):
            loss, new_gs = g_loss(p)
            return scaler.scale(loss, s_g), new_gs

        (lg_scaled, new_gs), g_g = jax.value_and_grad(
            g_loss_scaled, has_aux=True)(gp)
        lg_raw = lg_scaled / s_g.loss_scale
        g_g, inf_g = scaler.unscale(g_g, s_g)
        new_gp, new_g_os = g_opt.step(g_g, gp, g_os, found_inf=inf_g)
        s_g = scaler.update(s_g, inf_g)

        errD = lr_raw + lf_raw
        return (new_gp, new_gs, new_dp, new_ds, new_g_os, new_d_os,
                [s_real, s_fake, s_g], errD, lg_raw)

    rng = jax.random.PRNGKey(42)
    t0 = time.perf_counter()
    for it in range(args.iters):
        rng, sub = jax.random.split(rng)
        (gp, gs, dp_, ds, g_os, d_os, sstates, errD, errG) = train_step(
            gp, gs, dp_, ds, g_os, d_os, sstates, sub)
        if it % 5 == 0:
            print(f"[{it:3d}/{args.iters}] Loss_D {float(errD):7.4f} "
                  f"Loss_G {float(errG):7.4f} "
                  f"scales {[int(s.loss_scale) for s in sstates]}")
    dt = time.perf_counter() - t0
    print(f"done: {args.iters * args.batch / dt:.1f} imgs/sec; "
          f"finite: D={bool(jnp.isfinite(errD))} G={bool(jnp.isfinite(errG))}")


if __name__ == "__main__":
    main()
