"""Benchmark driver: the full BASELINE.md config matrix on one TPU chip.

Prints one JSON line per config ({"metric", "value", "unit", "vs_baseline",
"mfu", "model_tflops"}), finishing with the headline flagship line (GPT-2
124M training throughput, ``vs_baseline`` = fused/Pallas vs the repo's own
unfused-XLA path, each at its best feasible config: the fused path skips
activation recompute because flash attention's O(seq) memory permits it,
the unfused path cannot — so the ratio measures the kernels AND the memory
headroom they buy. The reference publishes no absolute numbers,
BASELINE.md).

Configs (BASELINE.md / BASELINE.json):
  1. ResNet-50 224px, amp-O2-equivalent bf16 + FusedSGD (north-star config)
  2. DCGAN bf16 G+D step
  3. BERT-base + FusedLAMB
  4. GPT-2 Megatron TP path (tp=1 on a single chip)
  5. GPT-2 355M (large-GEMM MFU row: bs8, no recompute, unrolled scan)
  6. ViT-L/16 + FusedAdam
  7. long-context: GPT at 32k tokens full-causal + 32k/64k sliding-window
     — the reference caps at 16k
  8. generation: prefill + decode-ONLY tokens/sec (bs 1 / 8 / 32, each
     with its share of the weight+KV read-bandwidth bound)
  9. fp8: native-fp8 dense fwd+bwd vs the same GEMM in bf16 (platform
     verdict row — v5e runs fp8 operands without fp8 MXU units)
 10. headline: GPT-2 124M fused-vs-unfused (printed LAST; the driver
     records the tail line)

MFU is model-FLOPs utilization against the chip's bf16 peak
(benchmarks/_harness.py). Every row is stamped with the device it ran on;
without a TPU the driver fails at start, and a config that raises makes
the process exit non-zero after the remaining configs have run. One
process holds the chip: every config runs in this one.
"""

from __future__ import annotations

import sys
import time
import traceback

import jax
import jax.numpy as jnp

from benchmarks._harness import (
    emit,
    peak_flops_per_chip,
    start,
    transformer_train_flops,
)

STEPS = 30


def _build(recompute: bool):
    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.optimizers import FusedAdam

    cfg = TransformerConfig(
        num_layers=12, hidden_size=768, num_attention_heads=12,
        vocab_size=50304, max_position_embeddings=1024,
        hidden_dropout=0.0, attention_dropout=0.0,
        recompute=recompute, scan_unroll=12, compute_dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)

    bs, seq = 8, 1024
    tokens = jax.random.randint(jax.random.PRNGKey(1), (bs, seq), 0, 50304)
    labels = jax.random.randint(jax.random.PRNGKey(2), (bs, seq), 0, 50304)

    def loss_fn(p):
        return model.apply(p, tokens, labels)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.step(grads, params, opt_state)
        return params, opt_state, loss

    n_params = sum(x.size for x in jax.tree.leaves(params))
    return step, params, opt_state, bs * seq, n_params, seq


def _run(flash: bool):
    import apex_tpu.ops._support as support
    import os

    # kernel dispatch is keyed on APEX_TPU_FORCE_PALLAS (ops/_support.py);
    # 'off' turns every fused op into its plain-XLA fallback = the baseline
    prev = os.environ.get("APEX_TPU_FORCE_PALLAS")
    os.environ["APEX_TPU_FORCE_PALLAS"] = "tpu" if flash else "off"
    support.pallas_mode.cache_clear()
    # each path runs its best feasible config: the flash kernel's O(seq)
    # memory lets the fused path skip activation recompute (~+4%); the
    # unfused path materializes per-layer score tensors and OOMs without it.
    step, params, opt_state, tokens_per_step, n_params, seq = _build(
        recompute=not flash)
    params, opt_state, loss = step(params, opt_state)          # compile
    _ = float(loss)
    # best-of-3 windows (ROADMAP S0 replaces this with median and
    # quartiles over repeated windows)
    best = float("inf")
    for _w in range(3):
        t0 = time.perf_counter()
        for _i in range(STEPS):
            params, opt_state, loss = step(params, opt_state)
        _ = float(loss)                                        # host sync
        best = min(best, (time.perf_counter() - t0) / STEPS)
    dt = best
    if prev is None:
        os.environ.pop("APEX_TPU_FORCE_PALLAS", None)
    else:
        os.environ["APEX_TPU_FORCE_PALLAS"] = prev
    support.pallas_mode.cache_clear()
    return (tokens_per_step / dt, float(loss), n_params, seq, dt,
            tokens_per_step)


def _config_matrix() -> list:
    """Run every BASELINE config, each printing its own JSON line.
    Returns the names of the configs that raised: a failure does not stop
    the remaining configs, but ``main`` exits non-zero on any."""
    import benchmarks.bert_lamb as bert
    import benchmarks.dcgan_bf16 as dcgan
    import benchmarks.fp8_bench as fp8_bench
    import benchmarks.generation_bench as generation
    import benchmarks.gpt_large as gpt_large
    import benchmarks.gpt_tp as gpt_tp
    import benchmarks.long_context as long_context
    import benchmarks.rn50_dp as rn50
    import benchmarks.vit_adam as vit

    configs = [
        ("rn50", lambda: rn50.main(batch=256, image=224)),
        ("dcgan", lambda: dcgan.main()),
        ("bert", lambda: bert.main()),
        ("gpt_tp", lambda: gpt_tp.main()),
        ("gpt2_355m", lambda: gpt_large.main()),
        ("vit", lambda: vit.main()),
        ("long_context_32k", lambda: long_context.main()),
        ("long_context_32k_window", lambda: long_context.main(window=1024)),
        ("long_context_64k_window",
         lambda: long_context.main(seq=65536, window=1024)),
        ("generation", lambda: generation.main()),
        ("fp8_dense", lambda: fp8_bench.main()),
    ]
    failed = []
    for name, fn in configs:
        try:
            fn()
        except Exception as e:   # boundary: the other configs still run
            failed.append(name)
            emit({"metric": f"{name}_FAILED", "value": 0.0, "unit": "error",
                  "vs_baseline": 0.0,
                  "error": f"{type(e).__name__}: {str(e)[:200]}"})
            traceback.print_exc()
    return failed


def main() -> int:
    start()
    failed = _config_matrix()
    fused_tps, loss, n_params, seq, dt, tokens_per_step = _run(flash=True)
    baseline_tps, _, _, _, _, _ = _run(flash=False)
    line = {
        "metric": "gpt2_124m_train_tokens_per_sec_per_chip",
        "value": round(fused_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(fused_tps / baseline_tps, 3),
        # each side's best feasible config (ADVICE r2): the ratio measures
        # kernels AND the recompute headroom flash attention buys — it is
        # NOT a matched-config pure-kernel ratio
        "config": {"fused": "pallas kernels, no recompute",
                   "baseline": "plain XLA, full recompute (OOMs without)"},
    }
    mf = transformer_train_flops(n_params, tokens_per_step, 12, 768, seq,
                                 causal=True)
    line["mfu"] = round(mf / dt / peak_flops_per_chip(), 4)
    line["model_tflops"] = round(mf / dt / 1e12, 1)
    emit(line)
    if failed:
        print(f"bench: {len(failed)} config(s) failed: {failed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
