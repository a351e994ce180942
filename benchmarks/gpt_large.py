"""BASELINE large-GEMM config: GPT-2 355M (Megatron 'medium') training MFU.

The largest standard GPT-2 config that fits one 16 GB v5e chip with Adam
state. hidden 1024 puts the MXU on [8192, 1024] x [1024, 4096]-class GEMMs
— the evidence that the framework's transformer MFU scales with model
size rather than stopping at the 124M small-GEMM regime (VERDICT r2 item
2). Tuned settings measured on-chip (PERF.md): no activation recompute
(fits at bs8), fully unrolled layer scan (kills while-loop + stacked-save
overhead), donated buffers.

Usage: ``python benchmarks/gpt_large.py``
"""

import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from functools import partial

import jax
import jax.numpy as jnp

from benchmarks._harness import run, start, transformer_train_flops
from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.optimizers import FusedAdam

LAYERS, HIDDEN, HEADS = 24, 1024, 16


def main(batch=8, seq=1024):
    start()
    cfg = TransformerConfig(
        num_layers=LAYERS, hidden_size=HIDDEN, num_attention_heads=HEADS,
        vocab_size=50304, max_position_embeddings=seq,
        hidden_dropout=0.0, attention_dropout=0.0,
        recompute=False, scan_unroll=LAYERS,
        compute_dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                50304)
    labels = jax.random.randint(jax.random.PRNGKey(2), (batch, seq), 0,
                                50304)
    n_params = sum(x.size for x in jax.tree.leaves(params))

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(
            lambda p: model.apply(p, tokens, labels))(params)
        params, opt_state = opt.step(grads, params, opt_state)
        return params, opt_state, loss

    return run("gpt2_355m_train_tokens_per_sec_per_chip", "tokens/sec",
               step, params, opt_state,
               work_per_step=batch * seq, consume_state=True,
               model_flops_per_step=transformer_train_flops(
                   n_params, batch * seq, LAYERS, HIDDEN, seq, causal=True))


if __name__ == "__main__":
    main()
