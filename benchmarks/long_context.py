"""Long-context benchmark: GPT training at 32k/64k tokens on one chip.

The reference's attention kernels hard-cap at 16k
(``/root/reference/csrc/megatron/scaled_masked_softmax.h:460``); these
configs run full GPT-2-size training steps at 2x and 4x that length through
the Pallas flash kernel (O(seq) memory): 32k full-causal, 32k
sliding-window, and 64k sliding-window. Context-parallel ring/Ulysses
extend the same kernels across chips (``tests/test_context_parallel.py``
pins parity and per-rank memory; a 128k ring phase runs in
``__graft_entry__.dryrun_multichip``).

Tuning (measured on v5e, PERF.md round 3): long-seq flash blocks
(1024, 1024) auto-selected by the kernel; no activation recompute — flash's
O(seq) residuals fit, and skipping the backward's attention re-run is worth
1.27x at 32k; unrolled layer scan; donated buffers.

Usage: ``python benchmarks/long_context.py``
"""

import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks._harness import run, start, transformer_train_flops
from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.optimizers import FusedAdam

LAYERS, HIDDEN, HEADS = 12, 768, 12


def main(seq=32768, window=None):
    start()
    # recompute-free fits through 32k (flash O(seq) residuals); at 64k the
    # saved activations + vocab logits exceed 16 GB, and with a sliding
    # window the re-run attention is cheap anyway
    cfg = TransformerConfig(
        num_layers=LAYERS, hidden_size=HIDDEN, num_attention_heads=HEADS,
        vocab_size=50304, max_position_embeddings=seq,
        position_embedding_type="rope",
        hidden_dropout=0.0, attention_dropout=0.0,
        sliding_window=window,
        recompute=(seq > 32768),
        # unrolled layers win at 32k; at 64k the unrolled graph lets every
        # layer's recompute buffers coexist and blows the 16 GB budget
        scan_unroll=(LAYERS if seq <= 32768 else 1),
        loss_seq_chunks=max(seq // 16384, 1),
        compute_dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, seq), 0, 50304)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(
            lambda p: model.apply(p, tokens, tokens))(params)
        params, opt_state = opt.step(grads, params, opt_state)
        return params, opt_state, loss

    n_params = sum(x.size for x in jax.tree.leaves(params))
    # attention term reflects the true window span when sliding
    eff_span = min(window, seq) if window else seq
    kt = f"{seq // 1024}k"
    name = (f"gpt2_124m_seq{kt}_window{window}" if window
            else f"gpt2_124m_seq{kt}")
    # full causal attention averages s/2 keys per query; a sliding window
    # averages ~window keys (no halving)
    return run(f"{name}_train_tokens_per_sec_per_chip", "tokens/sec",
               step, params, opt_state, work_per_step=seq, steps=5,
               consume_state=True,
               model_flops_per_step=transformer_train_flops(
                   n_params, seq, LAYERS, HIDDEN, eff_span,
                   causal=(window is None)))


if __name__ == "__main__":
    main()
    main(window=1024)
    main(seq=65536, window=1024)
