"""ResNet-50 "ImageNet" training — the reference's flagship example.

Capability parity with ``/root/reference/examples/imagenet/main_amp.py``:
amp O2 (bf16 compute, fp32 master weights in the optimizer, dynamic loss
scaling for fp16), data parallelism (the apex-DDP role is one ``shard_map``
over the ``data`` mesh axis with SyncBN statistics ``psum``-merged), fused
SGD with momentum, and the per-interval ``Speed`` (imgs/sec) printout of
``main_amp.py:386-400``.

Runs on whatever devices exist: the real TPU chip (DP=1) or a virtual CPU
mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` + env
``JAX_PLATFORMS=cpu``).

Data flows through the real input pipeline
(:mod:`apex_tpu.data.pipeline`): ``--data-dir`` points at an on-disk
uint8-shard dataset (materialized synthetically on first run when absent —
swap in real ImageNet by replacing the shard reader), worker threads
augment/normalize, the C++ token queue stages batches, and ``device_put``
runs one batch ahead — the DALI/DataLoader prefetch role of the reference
example.

Usage: ``PYTHONPATH=. python examples/imagenet_amp.py
[--iters N] [--batch B] [--image-size S] [--data-dir DIR]``
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

from apex_tpu import amp
from apex_tpu.models import ResNet, ResNetConfig
from apex_tpu.optimizers import FusedSGD


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--batch", type=int, default=64, help="global batch")
    ap.add_argument("--image-size", type=int, default=128)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--print-freq", type=int, default=10)
    ap.add_argument("--data-dir", type=str, default="/tmp/apex_tpu_imagenet",
                    help="on-disk dataset root (synthesized when absent)")
    ap.add_argument("--num-workers", type=int, default=2)
    ap.add_argument("--prefetch", type=int, default=2)
    args = ap.parse_args()

    devices = jax.devices()
    ndev = len(devices)
    assert args.batch % ndev == 0, \
        "global batch must be a multiple of the device count"
    mesh = Mesh(np.array(devices), ("data",))
    print(f"devices: {ndev} x {devices[0].device_kind} | "
          f"global batch {args.batch}")

    amp_state = amp.initialize("O2")  # bf16 compute, fp32 master, no scaling
    model = ResNet(ResNetConfig(
        depth=50, num_classes=args.num_classes,
        axis_name="data" if ndev > 1 else None,
        compute_dtype=jnp.bfloat16))
    params, bn_state = model.init(jax.random.PRNGKey(0))
    opt = FusedSGD(lr=args.lr, momentum=0.9, weight_decay=1e-4,
                   master_weights=True)
    opt_state = opt.init(params)

    def per_rank_step(params, bn_state, opt_state, images, labels):
        def loss_fn(p):
            logits, new_bn = model.apply(p, bn_state, images, train=True)
            logp = jax.nn.log_softmax(logits)
            n = labels.shape[0]
            loss = -jnp.mean(logp[jnp.arange(n), labels])
            return loss, (new_bn, logits)

        (loss, (new_bn, logits)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if ndev > 1:
            grads = jax.lax.pmean(grads, "data")
            loss = jax.lax.pmean(loss, "data")
        params, opt_state = opt.step(grads, params, opt_state)
        n = labels.shape[0]
        acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
        if ndev > 1:
            acc = jax.lax.pmean(acc, "data")
        return params, new_bn, opt_state, loss, acc

    if ndev > 1:
        step = jax.jit(shard_map(
            per_rank_step, mesh=mesh,
            in_specs=(P(), P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P(), P(), P())),
            donate_argnums=(0, 1, 2))
    else:
        step = jax.jit(per_rank_step, donate_argnums=(0, 1, 2))

    # real input pipeline: on-disk shards -> worker-thread augment -> C++
    # token queue -> device_put one batch ahead (apex_tpu.data.pipeline)
    from apex_tpu.data import make_input_pipeline, write_synthetic_imagenet

    stored = max(args.image_size, int(args.image_size * 1.15))
    per_shard = max(args.batch, 256)
    # key the scratch dataset dir by its shape config so flag changes
    # regenerate instead of tripping the meta-mismatch guard
    data_dir = (f"{args.data_dir}-{stored}px-{per_shard}x4"
                f"-c{args.num_classes}")
    write_synthetic_imagenet(
        data_dir, num_shards=4, per_shard=per_shard, image_size=stored,
        num_classes=args.num_classes)
    loader = make_input_pipeline(
        data_dir, args.batch, mesh=mesh if ndev > 1 else None,
        crop=args.image_size, prefetch=args.prefetch,
        num_workers=args.num_workers)
    batches = iter(loader)

    # warmup/compile
    images, labels = next(batches)
    params, bn_state, opt_state, loss, acc = step(
        params, bn_state, opt_state, images, labels)
    jax.block_until_ready(loss)
    print(f"compiled; initial loss {float(loss):.4f}")

    t0 = time.perf_counter()
    tlast, seen = t0, 0
    for it in range(1, args.iters + 1):
        images, labels = next(batches)
        params, bn_state, opt_state, loss, acc = step(
            params, bn_state, opt_state, images, labels)
        seen += args.batch
        if it % args.print_freq == 0:
            jax.block_until_ready(loss)
            now = time.perf_counter()
            speed = seen / (now - tlast)
            print(f"iter {it:4d}  loss {float(loss):7.4f}  "
                  f"prec@1 {float(acc) * 100:5.2f}  "
                  f"Speed {speed:9.1f} imgs/sec "
                  f"({speed / ndev:.1f}/chip)")
            tlast, seen = now, 0
    jax.block_until_ready(loss)
    total = args.iters * args.batch / (time.perf_counter() - t0)
    print(f"mean throughput: {total:.1f} imgs/sec over {args.iters} iters")


if __name__ == "__main__":
    main()
