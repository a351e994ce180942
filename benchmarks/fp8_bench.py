"""fp8 dense benchmark: native-fp8 dot vs the bf16 MXU path.

VERDICT r3 item 8: record the platform verdict with a measured row.
``native_fp8_dot_supported()`` returns True on this v5e — fp8 operands
compile and run — but v5e's MXU has no fp8 execution units (those arrive
with v6e/Trillium), so the interesting question is whether native-fp8
storage costs or saves time vs bf16. One delayed-scaling ``fp8_dense``
fwd+bwd over a GPT-355M-sized GEMM, chained in-jit (the dispatch-overhead
methodology of PERF.md), against the same matmul in bf16.

Usage: ``python benchmarks/fp8_bench.py``
"""

import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.amp import fp8
from benchmarks._harness import emit, start

M, K, N = 8192, 1024, 4096
ITERS = 150   # ~400 ms/chain: per-call dispatch amortizes to <5% per
              # endpoint; interleaved windows tighten the RATIO to ~3%
              # (round 4's 50-iter chain had +-15% noise and a verdict
              # range that excluded the driver's own capture — VERDICT r4)


def main():
    start()
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.bfloat16)
    state = fp8.init_fp8_state(("x", "w"))
    # probe, don't assume (ADVICE r4: the row hardcoded True; on a backend
    # without the native dot the bench just crashed and the recorded claim
    # would be wrong if copied to another platform)
    native = bool(fp8.native_fp8_dot_supported())

    # sum(y^2): the cotangent is 2y, a real data-dependent matrix — a
    # plain sum(y) makes dL/dy all-ones, which XLA folds into reductions
    # and the "GEMM" backward vanishes. BOTH grads and the fp8 state feed
    # the scan carry so nothing is dead-code-eliminated or hoisted: dw
    # stays live (all 3 GEMMs execute), w changes per step (weights are
    # re-quantized each iteration, as in real training), and the
    # delayed-scaling amax updates remain in the timed program.
    def fp8_loss(x, w, state):
        y, state = fp8.fp8_dense(x, w, state, native=native)
        y32 = y.astype(jnp.float32)
        return jnp.sum(y32 * y32), state

    g8 = jax.value_and_grad(fp8_loss, argnums=(0, 1), has_aux=True)

    @jax.jit
    def run_fp8(x, w, state):
        def body(carry, _):
            c, w, state = carry
            (_, state), (dx, dw) = g8(c, w, state)
            return (c + (1e-6 * dx).astype(c.dtype),
                    w + (1e-6 * dw).astype(w.dtype), state), None
        carry, _ = jax.lax.scan(body, (x, w, state), None, length=ITERS)
        return carry[0]

    def bf16_loss(x, w):
        y = (x @ w).astype(jnp.float32)
        return jnp.sum(y * y)

    gb = jax.grad(bf16_loss, argnums=(0, 1))

    @jax.jit
    def run_bf16(x, w):
        def body(carry, _):
            c, w = carry
            dx, dw = gb(c, w)
            return (c + (1e-6 * dx).astype(c.dtype),
                    w + (1e-6 * dw).astype(w.dtype)), None
        carry, _ = jax.lax.scan(body, (x, w), None, length=ITERS)
        return carry[0]

    def _one(run, *args):
        t0 = time.perf_counter()
        out = run(*args)
        np.asarray(jax.tree.leaves(out)[0]).ravel()[0]
        return (time.perf_counter() - t0) / ITERS

    # warmup both, then INTERLEAVE the timing windows (A,B,A,B...): slow
    # drift hits both configs equally, so the best-of ratio is pinned
    # far tighter than two separate best-of-3 blocks
    _one(run_fp8, x, w, state)
    _one(run_bf16, x, w)
    t8 = tb = float("inf")
    for _ in range(4):
        t8 = min(t8, _one(run_fp8, x, w, state))
        tb = min(tb, _one(run_bf16, x, w))
    flops = 3 * 2 * M * K * N            # fwd + dx + dw matmuls
    emit({
        "metric": ("fp8_dense_native_fwd_bwd_tflops" if native
                   else "fp8_dense_qdq_fwd_bwd_tflops"),
        "value": round(flops / t8 / 1e12, 1), "unit": "TFLOP/s",
        "vs_baseline": round(tb / t8, 3),
        "config": {"shape": [M, K, N], "iters": ITERS,
                   "native_fp8_dot_supported": native,
                   "baseline": "same GEMM chain in bf16 (interleaved "
                               "windows)",
                   "note": "v5e MXU executes fp8 operands without fp8 "
                           "units; vs_baseline < 1 means fp8 costs time "
                           "on this generation"}})


if __name__ == "__main__":
    main()
