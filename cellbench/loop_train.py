"""A training cell: one compiled step, fed a fresh batch every step.

Set-up builds the step and its state from the seed, drives them through
the three steps that ``correct`` compares (the window's own call and
feed), reads the few norms the comparison needs, and hands the same
object to the window. The window runs whole optimizer steps until
``seconds`` have passed and ends on the last step's loss.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from cellbench import check, spans, traffic as T, weights as W, work

CHECK_STEPS = 3
SETTLE_STEPS = 2      # more warm-up steps: none may be skipped by the scaler
IN_FLIGHT = 2         # steps dispatched ahead of the one being waited for


def run(cell, seed: int, seconds: float, trace_dir, t_process: float,
        compiles, control: bool = False) -> dict:
    from cellbench.program import Trainer

    tr = cell.traffic
    batch, seq = tr["batch"], tr["seq"]
    trainer = Trainer(cell.config, tr, seed)
    stream = T.train_stream(seed, batch, seq, cell.config["vocab_size"])

    # -- the checked first steps, through the window's own call and feed
    rows, losses, skipped = [], [], []
    for i in range(CHECK_STEPS):
        r = next(stream)
        rows.append(r)
        m = trainer.advance(trainer.feed(r))
        losses.append(m["loss"])
        skipped.append(m["skipped"])
        if i == 0:
            grad_norms = trainer.first_grad_norms(trainer.state)
    update_norms = trainer.read_update_norms()
    for _ in range(SETTLE_STEPS):
        m = trainer.advance(trainer.feed(next(stream)))
        skipped.append(m["skipped"])
    got = jax.device_get({"loss": losses, "grad": grad_norms,
                          "update": update_norms, "skipped": skipped})
    if any(bool(s) for s in got["skipped"]):
        raise RuntimeError("the loss scaler skipped a warm-up step: the "
                           "scale has not settled")
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process

    # -- the window
    rec = spans.Recorder(trace_dir, seconds, tr.get("trace_seconds", 4.0))
    pending, done_losses, done_skips = [], [], []
    compiles.reset()
    t0 = time.perf_counter()
    steps = 0
    while True:
        rec.poll(time.perf_counter() - t0)
        with rec.span("cb.input"):
            b = trainer.feed(next(stream))
        with rec.span("cb.step"):
            m = trainer.advance(b)
            pending.append(m)
            if len(pending) > IN_FLIGHT:
                old = pending.pop(0)
                jax.block_until_ready(old["loss"])
                done_losses.append(old["loss"])
                done_skips.append(old["skipped"])
        steps += 1
        if time.perf_counter() - t0 >= seconds:
            break
    for old in pending:
        done_losses.append(old["loss"])
        done_skips.append(old["skipped"])
    jax.block_until_ready(done_losses[-1])
    window_s = time.perf_counter() - t0
    compiled = compiles.report()
    rec.stop()

    w_losses = np.asarray(jax.device_get(done_losses), np.float64)
    w_skips = np.asarray(jax.device_get(done_skips), bool)
    failed = int(np.sum(~np.isfinite(w_losses) | w_skips))
    peak = spans.memory_peak_bytes(cell.chips)
    trainer.release()
    del pending, done_losses, done_skips, m, b
    spans.free_device()

    ref = reference_run(cell, seed, rows)
    readings = compare(got, ref)
    if control:
        # calibration only: the control (the reference one precision
        # down) and a planted fault, each put in the program's place
        from cellbench.reference import gpt2

        readings["_control"] = compare(
            reference_run(cell, seed, rows, quant=gpt2.fp8), ref)
        readings["_half_batch"] = compare(
            reference_run(cell, seed, rows, half_batch=True), ref)
    readings["compiles_in_window"] = compiled
    readings["failed_steps"] = failed
    tokens = steps * batch * seq
    sz = trainer.sz
    traced = len(rec.host.get("cb.step", [])) - rec.traced_from("cb.step")
    mesh = tr.get("mesh", {})
    fl, by = work.flash_train_work(sz, batch, seq)
    local_qkv = (batch // mesh.get("data", 1)) * 3 * sz["h"] \
        // mesh.get("tensor", 1)
    return {
        "attempted": steps, "failed": failed, "setup_s": setup_s,
        "window_s": window_s, "memory_peak_bytes": int(peak),
        "readings": readings,
        "end_to_end": {
            "train_tok_per_s_per_chip": tokens / window_s / cell.chips},
        "facts": {
            "sz": sz, "recorder": rec,
            "shapes": {"seq": seq, "b3h": local_qkv},
            "train_flops_traced": traced * work.train_flops(sz, batch, seq),
            # one chip's share of the attention work
            "flash_train_work": (traced * fl / cell.chips,
                                 traced * by / cell.chips)},
    }


def reference_run(cell, seed: int, rows, quant=None,
                  half_batch: bool = False) -> dict:
    """The plain reference over the same three batches: its losses, the
    per-leaf norms of its first gradient and of its parameters' change.
    ``quant`` (the control) and ``half_batch`` (a planted fault: half of
    the batch left out, the mean taken over the rest) are for the
    calibration and the tests, never for a run's own reference."""
    import jax.numpy as jnp

    from cellbench.reference import gpt2

    sz = W.sizes(cell.config)
    t = cell.config["training"]
    make = jax.jit(lambda k: W.canonical(k, sz, round_to=jnp.bfloat16))
    w = _place(make(W.key_from_seed(seed)), cell.chips)
    w0 = jax.tree.map(jnp.copy, w)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad = [], None
    for i, r in enumerate(rows):
        if half_batch:
            r = r[: len(r) // 2]
        loss, g = gpt2.loss_and_grads(
            w, jnp.asarray(r[:, :-1]), jnp.asarray(r[:, 1:]),
            heads=sz["heads"], eps=sz["eps"], quant=quant,
            rows=cell.limits.get("reference_rows", 1))
        losses.append(float(loss))
        if i == 0:
            grad = jax.device_get(gpt2.layer_norms(g))
        w, m, v = gpt2.adam(w, m, v, g, float(i + 1), lr=t["lr"],
                            b1=t["betas"][0], b2=t["betas"][1],
                            eps=t["adam_eps"])
        del g
    update = jax.device_get(gpt2.layer_norms(
        jax.tree.map(jnp.subtract, w, w0)))
    return {"loss": losses, "grad": grad, "update": update}


def compare(got: dict, ref: dict) -> dict:
    """The numbers ``correct`` compares, ``got`` against the reference."""
    out = {}
    # the losses are read and printed but not compared: on seeded random
    # weights and tokens the loss sits at ln(vocabulary) whatever the
    # precision, and neither the control nor a fault moves it (PERF.md)
    out["_loss_rel"] = [abs(float(a) - b) / abs(b)
                        for a, b in zip(got["loss"], ref["loss"])]
    out["first_grad_norm_gap"], gleaf = check.worst_leaf_gap(
        got["grad"], ref["grad"])
    out["update_norm_gap"], uleaf = check.worst_leaf_gap(
        got["update"], ref["update"], skip=check.still_leaves(ref["grad"]))
    out["_worst_leaf"] = {"first_grad_norm_gap": gleaf,
                          "update_norm_gap": uleaf}
    return out


def _place(tree, chips: int):
    """On several chips the float32 reference is spread over them (each
    leaf split along its longest axis that divides), so that it fits."""
    if chips == 1:
        return tree
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("r",))

    def one(x):
        axes = [None] * x.ndim
        for ax in sorted(range(x.ndim), key=lambda a: -x.shape[a]):
            if x.shape[ax] % chips == 0:
                axes[ax] = "r"
                break
        return jax.device_put(x, NamedSharding(mesh, P(*axes)))

    return jax.tree.map(one, tree)
