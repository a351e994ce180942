"""BASELINE config 5: ViT-L/16 + FusedAdam train step; imgs/sec/chip.

Usage: ``python benchmarks/vit_adam.py``
"""

import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks._harness import run, start, transformer_train_flops
from apex_tpu.models import vit_l16
from apex_tpu.optimizers import FusedAdam


def main(batch=32, image=224):
    start()
    model = vit_l16(image_size=image, num_classes=1000,
                    # r3 tuning: no recompute + unrolled scan + donation
                    recompute=False, scan_unroll=24,
                    compute_dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=3e-4, weight_decay=0.05)
    opt_state = opt.init(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, image, image, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 1000)

    from functools import partial

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        def loss_fn(p):
            logits = model.apply(p, x)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(logp[jnp.arange(batch), y])
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.step(grads, params, opt_state)
        return params, opt_state, loss

    n_params = sum(x.size for x in jax.tree.leaves(params))
    tokens = batch * ((image // 16) ** 2 + 1)
    return run("vit_l16_adam_train_imgs_per_sec_per_chip", "imgs/sec",
               step, params, opt_state, work_per_step=batch,
               consume_state=True,
               model_flops_per_step=transformer_train_flops(
                   n_params, tokens, 24, 1024, (image // 16) ** 2 + 1,
                   causal=False))


if __name__ == "__main__":
    main()
