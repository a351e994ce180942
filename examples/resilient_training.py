"""User-style demo: the resilience layer end to end — dynamic loss scaling
+ FusedAdam under `run_training`, with a scripted NaN-gradient burst that
trips the watchdog, rolls training back to the last good checkpoint at a
decayed loss scale, and still converges. Ctrl-free: faults come from the
deterministic injector, so the run behaves identically everywhere.

The run also measures itself: a MetricsRegistry with a JSONL sink rides
along (ResilienceConfig.metrics), and at the end the same report that
`python -m apex_tpu.monitor <run.jsonl>` prints — counters reconciling
with TrainingResult.telemetry, step-time p50/p95, throughput/MFU,
incident timeline — is rendered from the log."""
import os
import tempfile

import jax
import jax.numpy as jnp

import apex_tpu
from apex_tpu.amp.scaler import LossScaler
from apex_tpu.observability import (JsonlSink, MetricsRegistry,
                                    build_report, render_report)
from apex_tpu.optimizers import FusedAdam
from apex_tpu.resilience import (ResilienceConfig, make_train_state,
                                 make_resilient_train_step, run_training)
from apex_tpu.testing_faults import FaultInjector
from apex_tpu.utils.flops import peak_flops_per_chip

print("devices:", jax.devices(), "| apex_tpu", apex_tpu.__version__)

H = 128
key = jax.random.PRNGKey(0)
k1, k2 = jax.random.split(key)
params = {
    "w1": jax.random.normal(k1, (64, H)) * 0.1,
    "w2": jax.random.normal(k2, (H, 1)) * 0.1,
}
opt = FusedAdam(lr=1e-2, master_weights=True)
scaler = LossScaler("dynamic", init_scale=2.0 ** 12, scale_window=500)


def loss_fn(p, batch, rng):
    pred = batch["x"] @ p["w1"] @ p["w2"]
    return jnp.mean((pred - batch["y"]) ** 2)


TEACHER = jax.random.normal(jax.random.PRNGKey(7), (64, 1)) * 0.3


def batch_fn(step):  # pure function of step -> replayable after rollback
    k = jax.random.PRNGKey(step)
    x = jax.random.normal(k, (256, 64))
    return {"x": x, "y": x @ TEACHER}


step_fn = make_resilient_train_step(loss_fn, opt, scaler)
state = make_train_state(params, opt.init(params), scaler.init())

# a transient fault: train-step calls 30..35 produce NaN gradients
injector = FaultInjector(nan_grad_calls=range(30, 36))

with tempfile.TemporaryDirectory() as tmp:
    run_log = os.path.join(tmp, "run.jsonl")
    registry = MetricsRegistry([JsonlSink(run_log)])
    cfg = ResilienceConfig(
        save_interval_steps=20,       # checkpoint cadence (sharded, atomic commit)
        poll_interval_steps=5,        # watchdog device->host sync cadence
        max_consecutive_skips=4,      # divergence = 4 skipped steps in a row
        max_rollbacks=2,              # retry budget before TrainingDiverged
        rollback_scale_decay=4.0,     # retry at loss_scale/4
        save_backoff_base=0.2,        # checkpoint-save retry backoff
        metrics=registry,             # step metrics + incident events
        tokens_per_step=256,          # enables tokens/s
        model_flops_per_step=6.0 * (64 * 128 + 128),  # 6N for the 2-layer MLP
    )                                 # MFU: the chip table's peak; none on CPU
    result = run_training(
        step_fn, state, batch_fn, num_steps=300,
        rng=jax.random.PRNGKey(42),
        checkpoint_dir=os.path.join(tmp, "ckpts"),
        config=cfg, fault_injector=injector)
    registry.close()
    # same output as `python -m apex_tpu.monitor <run.jsonl>`
    report = build_report(run_log)
    print(render_report(report))
    assert report["counters"] == result.telemetry  # two ledgers, one truth
    assert report["step_time_s"]["p50"] > 0
    # MFU exists exactly where a peak does: on a listed TPU, not on CPU
    assert (report["mfu"] is not None) == (peak_flops_per_chip() is not None)

print(f"status={result.status} steps={result.steps_completed} "
      f"rollbacks={result.rollbacks}")
print("telemetry:", result.telemetry)
final = [h for h in result.history if not h["skipped"]][-1]
print(f"final loss {final['loss']:.5f} at step {final['step']}, "
      f"loss_scale {float(result.state['scaler'].loss_scale):.0f}")
assert result.status == "completed"
assert result.rollbacks == 1          # the NaN burst cost one rollback
assert final["loss"] < 0.08, "did not converge"
print("RECOVERED + CONVERGED OK")
