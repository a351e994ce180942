"""BASELINE config 3: BERT-base pretrain step, FusedLAMB + Pallas LayerNorm.

Measures tokens/sec/chip.
Usage: ``python benchmarks/bert_lamb.py``
"""

import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks._harness import run, start, transformer_train_flops
from apex_tpu.models import BertModel, TransformerConfig
from apex_tpu.optimizers import FusedLAMB
from apex_tpu.transformer.enums import AttnMaskType


def main(batch=16, seq=512):
    start()
    cfg = TransformerConfig(
        num_layers=12, hidden_size=768, num_attention_heads=12,
        vocab_size=30528, max_position_embeddings=512,
        hidden_dropout=0.0, attention_dropout=0.0,
        attn_mask_type=AttnMaskType.padding,
        # r3 tuning: activations fit without recompute at this size; the
        # unrolled layer scan removes while-loop + stacked-save overhead
        recompute=False, scan_unroll=12, compute_dtype=jnp.bfloat16)
    model = BertModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedLAMB(lr=1e-3, weight_decay=0.01)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, 30528)
    labels = jax.random.randint(jax.random.PRNGKey(2), (batch, seq), 0, 30528)

    from functools import partial

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state):
        def loss_fn(p):
            lm_loss, _ = model.apply(p, tokens, lm_labels=labels)
            return lm_loss
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.step(grads, params, opt_state)
        return params, opt_state, loss

    n_params = sum(x.size for x in jax.tree.leaves(params))
    return run("bert_base_lamb_train_tokens_per_sec_per_chip", "tokens/sec",
               step, params, opt_state, work_per_step=batch * seq,
               consume_state=True,
               model_flops_per_step=transformer_train_flops(
                   n_params, batch * seq, 12, 768, seq, causal=False))


if __name__ == "__main__":
    main()
