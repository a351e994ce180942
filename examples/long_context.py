"""Long-context training demo: 32k tokens on one chip, ring attention on a
mesh.

The reference's attention kernels cap at 16k tokens
(``csrc/megatron/scaled_masked_softmax.h:460``); this example trains
GPT-2-size models beyond that, two ways:

- single device: the Pallas flash kernel's O(seq) memory at ``--seq 32768``
  (optionally ``--window`` for Mistral-style local attention — banded-grid
  kernels make it O(seq x window));
- multi device (``--cp N``): ring-attention context parallelism — the
  sequence is sharded over the ``context`` mesh axis, K/V chunks rotate
  over ICI, and the loss/grads match the unsharded model exactly.

Usage:
  PYTHONPATH=. python examples/long_context.py \
      [--seq 32768] [--window 1024] [--iters 5]
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/long_context.py --cp 4 --seq 2048 --force-cpu
"""

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=32768)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--cp", type=int, default=1,
                    help="context-parallel size (ring attention)")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--force-cpu", action="store_true")
    args = ap.parse_args()

    if args.force_cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models import GPTModel, TransformerConfig
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step
    from apex_tpu.transformer import parallel_state

    cp = args.cp
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(context_parallel_size=cp)
    cfg = TransformerConfig(
        num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.hidden // 64, vocab_size=50304,
        max_position_embeddings=args.seq,
        position_embedding_type="rope",
        hidden_dropout=0.0, attention_dropout=0.0,
        sliding_window=args.window,
        context_parallel_method="ring" if cp > 1 else None,
        recompute=True, compute_dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    layout = f"cp {cp} (ring)" if cp > 1 else "single device"
    print(f"{n_params/1e6:.0f}M params | seq {args.seq} | "
          f"window {args.window} | {layout}")

    tokens = jax.random.randint(jax.random.PRNGKey(1), (cp, args.seq),
                                0, 50304)
    # next-token objective: position t predicts token t+1 (lm_head_loss
    # does not shift internally); the final position has no target
    labels = jnp.roll(tokens, -1, axis=1)
    loss_mask = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)
    # sequence positions shard over the context axis; each rank computes
    # its local loss, averaged over data+context by the train step
    step = make_train_step(
        lambda p, b, rng: model.apply(p, b["tokens"], b["labels"],
                                      loss_mask=b["loss_mask"]),
        opt, mesh, model.spec(),
        {"tokens": P(None, "context"), "labels": P(None, "context"),
         "loss_mask": P(None, "context")},
        opt_state_spec=opt.state_spec(params, model.spec()),
        data_axes=("data", "context"))
    batch = {"tokens": tokens, "labels": labels, "loss_mask": loss_mask}

    params, opt_state, loss = step(params, opt_state, batch, None)
    print(f"compiled; initial loss {float(loss):.4f}")
    t0 = time.perf_counter()
    for it in range(args.iters):
        params, opt_state, loss = step(params, opt_state, batch, None)
    loss = float(loss)
    dt = (time.perf_counter() - t0) / args.iters
    tput = tokens.size / dt
    print(f"loss {loss:.4f} | {dt*1e3:.0f} ms/step | "
          f"{tput:,.0f} tokens/sec total")


if __name__ == "__main__":
    main()
