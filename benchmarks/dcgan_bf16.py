"""BASELINE config 2: DCGAN bf16 mixed-precision G+D train step; imgs/sec.

The capability under test is the reference's second example — multiple
models/optimizers/losses with per-loss dynamic scaling
(``/root/reference/examples/dcgan/main_amp.py``); the full flow lives in
``examples/dcgan_amp.py``. This benchmark times the combined D+G step.
Usage: ``python benchmarks/dcgan_bf16.py``
"""

import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks._harness import run, start
from apex_tpu.models import DCGANConfig, Discriminator, Generator
from apex_tpu.optimizers import FusedAdam


def _bce(logit, target):
    return jnp.mean(jnp.maximum(logit, 0) - logit * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logit))))


def main(batch=256, nz=100):
    start()
    cfg = DCGANConfig(latent_dim=nz, compute_dtype=jnp.bfloat16)
    gen, disc = Generator(cfg), Discriminator(cfg)
    gp, gs = gen.init(jax.random.PRNGKey(0))
    dp_, ds = disc.init(jax.random.PRNGKey(1))
    g_opt = FusedAdam(lr=2e-4, betas=(0.5, 0.999), master_weights=True)
    d_opt = FusedAdam(lr=2e-4, betas=(0.5, 0.999), master_weights=True)
    g_os, d_os = g_opt.init(gp), d_opt.init(dp_)
    real = jnp.tanh(jax.random.normal(jax.random.PRNGKey(2),
                                      (batch, 64, 64, 3)))
    z = jax.random.normal(jax.random.PRNGKey(3), (batch, nz))

    @jax.jit
    def step(gp, gs, dp_, ds, g_os, d_os):
        def d_loss(p):
            lr_, _ = disc.apply(p, ds, real, train=True)
            fake, _ = gen.apply(gp, gs, z, train=True)
            lf, new_ds = disc.apply(p, ds, fake, train=True)
            return (_bce(lr_, jnp.ones(batch))
                    + _bce(lf, jnp.zeros(batch))), new_ds

        (errD, new_ds), d_g = jax.value_and_grad(d_loss, has_aux=True)(dp_)
        new_dp, new_d_os = d_opt.step(d_g, dp_, d_os)

        def g_loss(p):
            fake, new_gs = gen.apply(p, gs, z, train=True)
            logit, _ = disc.apply(new_dp, ds, fake, train=True)
            return _bce(logit, jnp.ones(batch)), new_gs

        (errG, new_gs), g_g = jax.value_and_grad(g_loss, has_aux=True)(gp)
        new_gp, new_g_os = g_opt.step(g_g, gp, g_os)
        return new_gp, new_gs, new_dp, new_ds, new_g_os, new_d_os, errD + errG

    # model flops from the compiled program (G/D conv stacks have no simple
    # closed form); cost_analysis counts executed flops ~= model flops here
    # (no activation recompute in this step)
    flops = float(step.lower(gp, gs, dp_, ds, g_os, d_os).compile()
                  .cost_analysis()["flops"])
    # the G+D step is short (~17 ms); longer windows + more of them pin
    # the run-to-run spread (was 12-21% MFU in round 2)
    return run("dcgan_bf16_imgs_per_sec_per_chip", "imgs/sec",
               step, gp, gs, dp_, ds, g_os, d_os, work_per_step=batch,
               steps=40, windows=5, model_flops_per_step=flops)


if __name__ == "__main__":
    main()
