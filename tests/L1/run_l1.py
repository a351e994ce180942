"""L1 integration runner: ResNet-50 amp opt-level convergence at depth.

The reference's L1 tier trains full ResNet-50 sweeps of opt-level x
loss-scale x keep-batchnorm against an O0 baseline and diffs the loss /
grad-norm traces (``tests/L1/common/run_test.sh:29-48``, ``main_amp.py``,
``compare.py``). This runner is that harness for TPU: real ResNet-50
(depth 50, 224px), >=500 iterations per configuration on synthetic data
(fixed random images, random labels — memorization gives a real descending
objective with deterministic data), traces recorded to
``tests/L1/traces/<config>.json`` and compared with
:func:`compare_traces`.

Run on hardware:
    PYTHONPATH=. python tests/L1/run_l1.py \
        [--iters 500] [--batch 64] [--configs all]

The pytest wrapper (`test_l1_traces.py`) validates whatever traces are
recorded in-tree, so the hardware evidence is versioned.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "traces")

# the run_test.sh-style matrix: name -> (opt_level, loss_scale, keep_bn)
CONFIGS = {
    "o0_fp32": ("O0", None, None),
    "o2_bf16_dynamic": ("O2", "dynamic", None),
    "o2_bf16_static128": ("O2", 128.0, None),
    "o2_bf16_keepbn_false": ("O2", "dynamic", False),
    "o2_bf16_static1": ("O2", 1.0, True),
}


def _cast_bn_params(params, dtype):
    from jax.tree_util import tree_map_with_path

    def f(path, x):
        keys = "/".join(str(getattr(p, "key", p)) for p in path)
        return x.astype(dtype) if "bn" in keys else x

    return tree_map_with_path(f, params)


def train_one(name, opt_level, loss_scale, keep_bn, *, iters, batch,
              image=224, classes=100, n_images=512, log_every=25):
    from apex_tpu import amp
    from apex_tpu.models import ResNet, ResNetConfig
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.utils.tree import global_norm

    amp_state = amp.initialize(opt_level, loss_scale=loss_scale,
                               keep_batchnorm_fp32=keep_bn,
                               half_dtype=jnp.bfloat16)
    props = amp_state.properties
    compute = jnp.float32 if opt_level == "O0" else jnp.bfloat16
    model = ResNet(ResNetConfig(depth=50, num_classes=classes,
                                compute_dtype=compute))
    params, state = model.init(jax.random.PRNGKey(0))
    opt = FusedSGD(lr=0.02, momentum=0.9, weight_decay=1e-4,
                   master_weights=bool(props.master_weights))
    opt_state = opt.init(params)
    scaler = amp_state.scaler
    sstate = amp_state.scaler_states[0]

    # deterministic synthetic dataset: fixed images + labels, memorizable
    xs = jax.random.normal(jax.random.PRNGKey(1),
                           (n_images, image, image, 3))
    ys = jax.random.randint(jax.random.PRNGKey(2), (n_images,), 0, classes)
    n_batches = n_images // batch
    half_bn = props.keep_batchnorm_fp32 is False and opt_level != "O0"

    @jax.jit
    def step(params, state, opt_state, sstate, x, y):
        def loss_fn(p):
            if half_bn:
                p = _cast_bn_params(p, jnp.bfloat16)
            logits, new_s = model.apply(p, state, x, train=True)
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(logp[jnp.arange(x.shape[0]), y]), new_s

        def scaled(p):
            loss, new_s = loss_fn(p)
            return scaler.scale(loss, sstate), (loss, new_s)

        (_, (loss, new_s)), grads = jax.value_and_grad(
            scaled, has_aux=True)(params)
        grads, found_inf = scaler.unscale(grads, sstate)
        gnorm = global_norm(grads)
        params, opt_state = opt.step(grads, params, opt_state,
                                     found_inf=found_inf)
        new_sstate = scaler.update(sstate, found_inf)
        return (params, new_s, opt_state, new_sstate, loss, gnorm,
                new_sstate.loss_scale)

    losses, gnorms, scales = [], [], []
    t0 = time.time()
    for i in range(iters):
        b = i % n_batches
        x = xs[b * batch:(b + 1) * batch]
        y = ys[b * batch:(b + 1) * batch]
        params, state, opt_state, sstate, loss, gnorm, scale = step(
            params, state, opt_state, sstate, x, y)
        losses.append(float(loss))
        gnorms.append(float(gnorm))
        scales.append(float(scale))
        if i % log_every == 0 or i == iters - 1:
            print(f"[{name}] iter {i:4d} loss {losses[-1]:.4f} "
                  f"gnorm {gnorms[-1]:.3f} scale {scales[-1]:.0f}",
                  flush=True)
    trace = {
        "config": {"name": name, "opt_level": opt_level,
                   "loss_scale": loss_scale, "keep_batchnorm_fp32": keep_bn,
                   "iters": iters, "batch": batch, "image": image,
                   "depth": 50, "device": str(jax.devices()[0])},
        "wall_seconds": round(time.time() - t0, 1),
        "loss": losses, "grad_norm": gnorms, "loss_scale": scales,
    }
    os.makedirs(TRACE_DIR, exist_ok=True)
    with open(os.path.join(TRACE_DIR, f"{name}.json"), "w") as f:
        json.dump(trace, f)
    return trace


def compare_traces(trace, baseline, *, early=50, early_rtol=0.2,
                   loss_floor=1e-3):
    """The compare.py contract: finite traces, early-trajectory agreement
    with O0, end-state convergence, sane scaler behavior. Returns a list
    of failure strings (empty = pass).

    ``loss_floor``: relative deviation is only judged while the baseline
    loss is above this — once both runs have collapsed to ~0 (small
    memorization tasks do this within a few iterations), the ratio of two
    near-zero numbers measures noise, not tracking.
    """
    fails = []
    L = np.asarray(trace["loss"])
    G = np.asarray(trace["grad_norm"])
    B = np.asarray(baseline["loss"])
    if not np.isfinite(L).all():
        fails.append("non-finite loss")
    if not np.isfinite(G).all():
        fails.append("non-finite grad norm")
    # early trajectory must track the fp32 baseline (precision-level drift
    # only); later iterations diverge chaotically for ANY precision change
    n = min(early, len(L), len(B))
    meaningful = np.abs(B[:n]) > loss_floor
    dev = np.where(meaningful,
                   np.abs(L[:n] - B[:n]) / np.maximum(np.abs(B[:n]),
                                                      loss_floor), 0.0)
    if dev.max() > early_rtol:
        fails.append(f"early loss deviates from O0 by {dev.max():.3f} "
                     f"(> {early_rtol})")
    # both must actually converge (memorization objective)
    if not (L[-25:].mean() < 0.5 * L[:25].mean()):
        fails.append(f"did not converge: start {L[:25].mean():.3f} "
                     f"end {L[-25:].mean():.3f}")
    S = np.asarray(trace["loss_scale"])
    if (S <= 0).any() or not np.isfinite(S).all():
        fails.append("loss scale left the sane range")
    return fails


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=500)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--configs", type=str, default="all")
    args = ap.parse_args()
    names = (list(CONFIGS) if args.configs == "all"
             else args.configs.split(","))
    traces = {}
    for name in names:
        ol, ls, kb = CONFIGS[name]
        traces[name] = train_one(name, ol, ls, kb, iters=args.iters,
                                 batch=args.batch)
    base = traces.get("o0_fp32")
    if base is None:
        base_path = os.path.join(TRACE_DIR, "o0_fp32.json")
        with open(base_path) as f:
            base = json.load(f)
    ok = True
    for name, tr in traces.items():
        if name == "o0_fp32":
            continue
        fails = compare_traces(tr, base)
        status = "OK" if not fails else f"FAIL: {fails}"
        ok = ok and not fails
        print(f"[compare] {name}: {status}", flush=True)
    print("L1 SWEEP", "PASSED" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
