"""Per-op device profiler — the measurement tool behind PERF.md's profiles.

Runs a jitted step a few times under ``jax.profiler.trace`` and aggregates
per-op device time from the captured xplane proto (the same data the
TensorBoard profiler renders). This is the TPU counterpart of profiling a
CUDA step with Nsight and reading the kernel summary: op names carry the
HLO metadata (which includes the ``jax.named_scope``/source annotations),
so Pallas kernels, fusions, copies and convert/transpose traffic are
separable.

Usage (as a library — the round-5 profiles in PERF.md were taken this way):

    from benchmarks.profile_step import profile_op_table
    rows = profile_op_table(lambda: step(params, opt_state))
    # rows: [(total_us_across_steps, count, op_name), ...] sorted desc

or standalone against the 355M trainer:

    python benchmarks/profile_step.py
"""

from __future__ import annotations

import glob
import os
import re
import tempfile
from collections import defaultdict

import jax

__all__ = ["profile_op_table", "print_op_table", "group_rows"]


def _load_xplanes(log_dir):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spaces = []
    for p in paths:
        xs = xplane_pb2.XSpace()
        with open(p, "rb") as f:
            xs.ParseFromString(f.read())
        spaces.append(xs)
    return spaces


def profile_op_table(run_once, *, iters=3, device_substr="TPU",
                     line_name="XLA Ops"):
    """Run ``run_once()`` ``iters`` times under the profiler; return
    ``[(total_us, count, name), ...]`` (device-time sum over all iters,
    descending). ``run_once`` must block (e.g. end with
    ``jax.block_until_ready``)."""
    run_once()                                   # compile outside the trace
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(iters):
                run_once()
        acc = defaultdict(lambda: [0.0, 0])
        for xs in _load_xplanes(d):
            for plane in xs.planes:
                if device_substr not in plane.name:
                    continue
                meta = plane.event_metadata
                for line in plane.lines:
                    if line_name and line.name != line_name:
                        continue
                    for ev in line.events:
                        name = meta[ev.metadata_id].name
                        acc[name][0] += ev.duration_ps / 1e6
                        acc[name][1] += 1
    return sorted(((v[0], v[1], k) for k, v in acc.items()), reverse=True)


# Buckets keyed on the HLO INSTRUCTION NAME (the `%name =` token — XLA
# names instructions after their opcode / fused pattern) plus the
# custom_call_target marker for Pallas: the xplane op text is the FULL
# instruction, where `%` prefixes instruction and operand NAMES, not
# opcodes, so matching the whole text would hit operand names like
# `%copy` inside unrelated instructions. The Python kernel function name
# never appears — per-kernel attribution needs output-shape signatures,
# as the PERF.md round-5 analyses do.
_GROUPS = [
    ("gemm+epilogue", re.compile(r"^(convolution|dot)|"
                                 r"(convolution|dot)[a-z_]*_fusion",
                                 re.I)),
    ("fusion", re.compile(r"fusion", re.I)),
    ("copy/transpose/reshape", re.compile(
        r"^(copy|transpose|bitcast|reshape|slice)", re.I)),
    ("other", re.compile(r".")),
]


def group_rows(rows):
    """Bucket an op table into coarse classes -> {class: total_us}."""
    out = defaultdict(float)
    for us, _, name in rows:
        iname = name.split(" = ")[0].lstrip("%")
        if ('custom_call_target="tpu_custom_call"' in name
                or " custom-call(" in name
                or iname.startswith("closed_call")):
            out["pallas-kernel"] += us
            continue
        for gname, pat in _GROUPS:
            if pat.search(iname):
                out[gname] += us
                break
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def print_op_table(rows, *, iters=3, top=40):
    total = sum(r[0] for r in rows)
    print(f"total device time: {total / iters / 1000:.2f} ms/step "
          f"({iters} steps)")
    for us, n, name in rows[:top]:
        print(f"{us / iters / 1000:9.3f} ms  x{n:<4d} {name[:110]}")
    print("-- grouped --")
    for g, us in group_rows(rows).items():
        print(f"{us / iters / 1000:9.3f} ms  {g}")


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from functools import partial

    import jax.numpy as jnp

    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.optimizers import FusedAdam
    from benchmarks._harness import start

    start()
    cfg = TransformerConfig(
        num_layers=24, hidden_size=1024, num_attention_heads=16,
        vocab_size=50304, max_position_embeddings=1024,
        hidden_dropout=0.0, attention_dropout=0.0,
        recompute=False, scan_unroll=24, compute_dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 1024), 0, 50304)
    labels = jax.random.randint(jax.random.PRNGKey(2), (8, 1024), 0, 50304)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(p, s):
        loss, grads = jax.value_and_grad(
            lambda q: model.apply(q, tokens, labels))(p)
        p, s = opt.step(grads, p, s)
        return p, s, loss

    state = [params, opt_state]

    def once():
        p, s, loss = step(state[0], state[1])
        state[0], state[1] = p, s
        jax.block_until_ready(loss)

    rows = profile_op_table(once)
    print_op_table(rows)
