"""BASELINE config 1: ResNet-50 "ImageNet", amp O2-equivalent + DP.

Measures imgs/sec/chip on whatever devices exist (the north-star config;
reference ``examples/imagenet/main_amp.py`` Speed printout).
Usage: ``python benchmarks/rn50_dp.py``
"""

import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from benchmarks._harness import resnet50_train_flops, run, start
from apex_tpu.models import ResNet, ResNetConfig
from apex_tpu.optimizers import FusedSGD


def main(batch=256, image=224):
    start()
    devices = jax.devices()
    ndev = len(devices)
    model = ResNet(ResNetConfig(
        depth=50, num_classes=1000, compute_dtype=jnp.bfloat16,
        axis_name="data" if ndev > 1 else None))
    params, bn_state = model.init(jax.random.PRNGKey(0))
    opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4,
                   master_weights=True)
    opt_state = opt.init(params)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, image, image, 3))
    y = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 1000)

    def per_rank(params, bn_state, opt_state, x, y):
        def loss_fn(p):
            logits, new_bn = model.apply(p, bn_state, x, train=True)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(logp[jnp.arange(x.shape[0]), y]), new_bn
        (loss, new_bn), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if ndev > 1:
            grads = jax.lax.pmean(grads, "data")
            loss = jax.lax.pmean(loss, "data")
        params, opt_state = opt.step(grads, params, opt_state)
        return params, new_bn, opt_state, loss

    if ndev > 1:
        mesh = Mesh(np.array(devices), ("data",))
        fn = jax.jit(jax.shard_map(
            per_rank, mesh=mesh,
            in_specs=(P(), P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P(), P()), check_vma=False))
    else:
        fn = jax.jit(per_rank)

    def step(params, bn_state, opt_state):
        p, b, o, loss = fn(params, bn_state, opt_state, x, y)
        return p, b, o, loss

    return run(f"rn50_{image}px_amp_o2_dp_imgs_per_sec_per_chip", "imgs/sec",
               step, params, bn_state, opt_state,
               work_per_step=batch / ndev,
               model_flops_per_step=resnet50_train_flops(batch / ndev, image))


if __name__ == "__main__":
    main()
