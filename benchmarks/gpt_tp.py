"""BASELINE config 4: GPT Megatron-style TP train step.

With one real chip this measures the TP=1 path; on a mesh (or the virtual
CPU mesh: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
env ``JAX_PLATFORMS=cpu``) it shards TP over all devices and reports
tokens/sec/chip.
Usage: ``python benchmarks/gpt_tp.py``
"""

import sys, os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from benchmarks._harness import run, start, transformer_train_flops
from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.optimizers import FusedAdam
from apex_tpu.training import make_train_step
from apex_tpu.transformer import parallel_state
from jax.sharding import PartitionSpec as P


def main(batch=8, seq=1024):
    start()
    ndev = len(jax.devices())
    tp = ndev  # all devices on the tensor axis
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=tp)
    cfg = TransformerConfig(
        num_layers=12, hidden_size=768, num_attention_heads=12,
        vocab_size=50304, max_position_embeddings=1024,
        hidden_dropout=0.0, attention_dropout=0.0,
        sequence_parallel=(tp > 1),
        # r3 tuning: recompute-free + unrolled scan (memory fits at bs8)
        recompute=False, scan_unroll=12,
        compute_dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                50304)
    labels = jax.random.randint(jax.random.PRNGKey(2), (batch, seq), 0,
                                50304)

    def loss_fn(p, b, rng):
        return model.apply(p, b["tokens"], b["labels"], rng=rng)

    step_fn = make_train_step(
        loss_fn, opt, mesh, model.spec(),
        {"tokens": P("data"), "labels": P("data")},
        opt_state_spec=opt.state_spec(params, model.spec()))
    batch_dict = {"tokens": tokens, "labels": labels}

    def step(params, opt_state):
        p, o, loss = step_fn(params, opt_state, batch_dict, None)
        return p, o, loss

    n_params = sum(x.size for x in jax.tree.leaves(params))
    out = run(f"gpt2_124m_tp{tp}_train_tokens_per_sec_per_chip", "tokens/sec",
              step, params, opt_state, work_per_step=batch * seq / ndev,
              model_flops_per_step=transformer_train_flops(
                  n_params, batch * seq, 12, 768, seq, causal=True) / ndev)
    parallel_state.destroy_model_parallel()
    return out


if __name__ == "__main__":
    main()
