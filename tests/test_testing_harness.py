"""Tests for the transformer testing toolkit, memory arenas, and launcher
helper (reference: ``apex/transformer/testing/*``,
``tensor_parallel/memory.py``, ``apex/parallel/multiproc.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel.memory import (
    MemoryBuffer,
    RingMemBuffer,
    allocate_mem_buff,
)
from apex_tpu.transformer.testing import (
    DistributedTestBase,
    IdentityLayer,
    initialize_distributed,
    parse_args,
    set_random_seed,
)
from apex_tpu.transformer.testing import global_vars


class TestArguments:
    def test_defaults_and_derived(self):
        args = parse_args(args=[])
        assert args.ffn_hidden_size == 4 * args.hidden_size
        assert args.data_parallel_size == args.world_size
        assert args.global_batch_size == (args.micro_batch_size
                                          * args.data_parallel_size)

    def test_parallel_divisibility_enforced(self):
        with pytest.raises(ValueError):
            parse_args(args=["--tensor-model-parallel-size", "3",
                             "--world-size", "8"])

    def test_fp16_bf16_exclusive(self):
        with pytest.raises(ValueError):
            parse_args(args=["--fp16", "--bf16"])

    def test_defaults_override(self):
        args = parse_args(args=[], defaults={"hidden-size": 64})
        # explicit CLI value survives, unset one takes the default
        assert args.hidden_size == 128  # argparse default wins (set)
        args2 = parse_args(args=[], defaults={"save": "/tmp/x"})
        assert args2.save == "/tmp/x"

    def test_config_from_args(self):
        from apex_tpu.transformer.testing.arguments import (
            core_transformer_config_from_args,
        )

        args = parse_args(args=["--num-layers", "3", "--bf16"])
        cfg = core_transformer_config_from_args(args)
        assert cfg.num_layers == 3
        assert cfg.compute_dtype == jnp.bfloat16


class TestGlobalVars:
    def test_singleton_lifecycle(self):
        global_vars.destroy_global_vars()
        with pytest.raises(RuntimeError):
            global_vars.get_args()
        args = global_vars.set_global_variables(parse_args(args=[]))
        assert global_vars.get_args() is args
        with pytest.raises(RuntimeError):
            global_vars.set_global_variables(args)
        global_vars.destroy_global_vars()


class TestCommons:
    def test_identity_layer_grad(self):
        layer = IdentityLayer((4, 4), scale=0.5)
        params = layer.init()
        g = jax.grad(lambda p: jnp.sum(layer.apply(p) ** 2))(params)
        np.testing.assert_allclose(np.asarray(g["weight"]),
                                   2 * np.asarray(params["weight"]),
                                   rtol=1e-6)

    def test_set_random_seed(self):
        k1 = set_random_seed(7)
        k2 = set_random_seed(7)
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))

    def test_initialize_distributed(self):
        mesh = initialize_distributed(tensor_model_parallel_size=2)
        assert parallel_state.get_tensor_model_parallel_world_size() == 2
        parallel_state.destroy_model_parallel()


class TestDistributedTestBase:
    def test_mesh_lifecycle(self):
        class _T(DistributedTestBase):
            def runTest(self):
                pass

        t = _T()
        t.setUp()
        assert t.world_size == len(jax.devices())
        mesh = t.initialize_model_parallel(tensor_model_parallel_size=2)
        assert parallel_state.model_parallel_is_initialized()
        t.tearDown()
        assert not parallel_state.model_parallel_is_initialized()

    def test_world_size_cap(self):
        class _T(DistributedTestBase):
            MAX_WORLD_SIZE = 2

            def runTest(self):
                pass

        assert _T().world_size == 2


class TestMemoryBuffer:
    def test_get_and_reset(self):
        buf = MemoryBuffer("test", 64, jnp.float32)
        a = buf.get((4, 4))
        b = buf.get((8,))
        assert a.shape == (4, 4) and b.shape == (8,)
        assert buf.numel_in_use() == 24
        buf.reset()
        assert not buf.is_in_use()

    def test_overflow_raises(self):
        buf = MemoryBuffer("small", 8, jnp.float32)
        buf.get((8,))
        with pytest.raises(MemoryError):
            buf.get((1,))

    def test_dtype_mismatch_raises(self):
        buf = allocate_mem_buff("t", 8, jnp.bfloat16)
        with pytest.raises(ValueError):
            buf.get((2,), jnp.float32)

    def test_ring_rotates_and_resets(self):
        ring = RingMemBuffer("ring", 2, 16, jnp.float32)
        b0 = ring.get_next_buffer()
        b0.get((16,))
        b1 = ring.get_next_buffer()
        assert b1 is not b0
        b0_again = ring.get_next_buffer()
        assert b0_again is b0
        assert not b0_again.is_in_use()   # reset on reacquisition


class TestMultiproc:
    def test_init_distributed_single_process(self):
        from apex_tpu.parallel.multiproc import init_distributed

        # single-process: jax.distributed init either succeeds trivially or
        # is already initialized; either way process_count is 1 here
        try:
            n = init_distributed()
        except Exception:
            pytest.skip("jax.distributed unavailable in this environment")
        assert n == 1


class TestModelParallelGradScaler:
    """transformer.amp.GradScaler: one rank's overflow must skip everywhere
    (reference apex/transformer/amp/grad_scaler.py:21-125)."""

    def test_overflow_on_one_tp_rank_seen_by_all(self):
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        from apex_tpu.transformer.amp import GradScaler

        mesh = initialize_distributed(tensor_model_parallel_size=8)
        scaler = GradScaler("dynamic")
        state = scaler.init()

        # grads sharded over tensor ranks; rank 3's shard holds an inf
        g = np.ones((8, 4), np.float32)
        g[3, 1] = np.inf

        def per_rank(g_local, state):
            scaled = jax.tree.map(lambda x: x * state.loss_scale, g_local)
            _, found_inf = scaler.unscale(scaled, state)
            return found_inf.reshape(1)

        found = shard_map(per_rank, mesh=mesh,
                          in_specs=(P("tensor"), P()),
                          out_specs=P("tensor"))(g, state)
        # every rank agrees: all True
        assert np.asarray(found).all()
        parallel_state.destroy_model_parallel()

    def test_no_overflow_plain(self):
        from apex_tpu.transformer.amp import GradScaler

        parallel_state.destroy_model_parallel()
        scaler = GradScaler("dynamic")
        state = scaler.init()
        grads = {"w": jnp.ones((3,)) * state.loss_scale}
        un, found = scaler.unscale(grads, state)
        assert not bool(found)
        np.testing.assert_allclose(np.asarray(un["w"]), np.ones(3), rtol=1e-6)


class TestProfiling:
    def test_nvtx_range_names_eager_work(self):
        from apex_tpu.utils import nvtx_range

        with nvtx_range("block"):
            y = jnp.sum(jnp.ones(4))
        assert float(y) == 4.0

    def test_named_scope_in_jit(self):
        from apex_tpu.utils import nvtx_range

        @jax.jit
        def f(x):
            with nvtx_range("inner"):
                return x + 1

        assert float(f(jnp.zeros(()))) == 1.0

    def test_device_memory_stats_shape(self):
        from apex_tpu.utils import device_memory_stats

        stats = device_memory_stats()
        assert isinstance(stats, dict)

    @pytest.mark.slow
    def test_trace_writes_profile(self, tmp_path):
        from apex_tpu.utils import profiler_start, profiler_stop

        profiler_start(str(tmp_path))
        try:
            jax.block_until_ready(jnp.dot(jnp.ones((8, 8)), jnp.ones((8, 8))))
        finally:
            profiler_stop()
        import os
        found = any("trace" in f or f.endswith(".pb") or "plugins" in r
                    for r, _, fs in os.walk(tmp_path) for f in fs + [r])
        assert found


class TestExtendedArgSurface:
    """Round-2 arg-surface growth: every model knob added to the framework
    (GQA, rope, rmsnorm, swiglu, sliding window, MoE, CP method, fp8,
    optimizer selection) parses and reaches TransformerConfig."""

    def test_modern_llm_config(self):
        from apex_tpu.transformer.testing.arguments import (
            core_transformer_config_from_args,
        )

        args = parse_args(args=[
            "--num-layers", "4", "--hidden-size", "256",
            "--num-attention-heads", "8", "--num-query-groups", "2",
            "--position-embedding-type", "rope", "--rotary-percent", "0.5",
            "--normalization", "rmsnorm", "--swiglu",
            "--sliding-window", "64", "--bf16"])
        cfg = core_transformer_config_from_args(args)
        assert cfg.num_query_groups == 2
        assert cfg.position_embedding_type == "rope"
        assert cfg.rotary_percent == 0.5
        assert cfg.normalization == "rmsnorm"
        assert cfg.activation == "swiglu"
        assert cfg.sliding_window == 64

    def test_moe_and_cp_args(self):
        from apex_tpu.transformer.testing.arguments import (
            core_transformer_config_from_args,
        )

        args = parse_args(args=[
            "--num-experts", "4", "--moe-router-topk", "2",
            "--moe-expert-axis", "data", "--world-size", "4",
            "--context-parallel-size", "1"])
        cfg = core_transformer_config_from_args(args)
        assert cfg.num_moe_experts == 4
        assert cfg.moe_top_k == 2
        # cp size 1 -> no CP method regardless of flag default
        assert cfg.context_parallel_method is None

    def test_cp_method_defaults_to_ring(self):
        args = parse_args(args=["--context-parallel-size", "2",
                                "--world-size", "2"])
        assert args.context_parallel_method == "ring"

    def test_gqa_divisibility_enforced(self):
        import pytest

        with pytest.raises(ValueError, match="num_query_groups"):
            parse_args(args=["--num-attention-heads", "8",
                             "--num-query-groups", "3"])

    def test_optimizer_and_fp8_groups(self):
        args = parse_args(args=["--optimizer", "lamb", "--fp8",
                                "--fp8-amax-history-len", "8",
                                "--use-distributed-optimizer"])
        assert args.optimizer == "lamb"
        assert args.fp8 and args.fp8_amax_history_len == 8
        assert args.use_distributed_optimizer

    def test_global_vars_build_microbatch_calculator(self):
        from apex_tpu.transformer.testing import global_vars
        from apex_tpu.transformer.pipeline_parallel import utils as pp_utils

        global_vars.destroy_global_vars()
        global_vars.set_global_variables(parse_args(args=[
            "--micro-batch-size", "2", "--global-batch-size", "8",
            "--world-size", "1"]))
        assert global_vars.get_num_microbatches() == 4
        assert global_vars.get_current_global_batch_size() == 8
        assert global_vars.get_timers() is not None
        assert global_vars.get_adlr_autoresume() is None
        assert global_vars.get_tensorboard_writer() is None
        global_vars.destroy_global_vars()


# The reference's complete flag surface (``apex/transformer/testing/
# arguments.py``), frozen here as the parity checklist: every flag must be
# accepted by parse_args and carry an explicit disposition.
REFERENCE_FLAGS = [
    "--accumulate-allreduce-grads-in-fp32", "--adam-beta1", "--adam-beta2",
    "--adam-eps", "--adlr-autoresume", "--adlr-autoresume-interval",
    "--apply-residual-connection-post-layernorm", "--attention-dropout",
    "--attention-softmax-in-fp32", "--batch-size", "--bert-load",
    "--bert-no-binary-head", "--bf16", "--biencoder-projection-dim",
    "--biencoder-shared-query-context-model", "--block-data-path",
    "--checkpoint-activations", "--classes-fraction", "--clip-grad",
    "--cpu-offload", "--data-impl", "--data-path",
    "--data-per-class-fraction", "--dataloader-type",
    "--decoder-seq-length", "--dino-bottleneck-size",
    "--dino-freeze-last-layer", "--dino-head-hidden-size",
    "--dino-local-crops-number", "--dino-local-img-size",
    "--dino-norm-last-layer", "--dino-teacher-temp",
    "--dino-warmup-teacher-temp", "--dino-warmup-teacher-temp-epochs",
    "--distribute-saved-activations", "--distributed-backend",
    "--embedding-path", "--empty-unused-memory-level",
    "--encoder-seq-length", "--end-weight-decay", "--eod-mask-loss",
    "--eval-interval", "--eval-iters", "--evidence-data-path",
    "--exit-duration-in-mins", "--exit-interval", "--ffn-hidden-size",
    "--finetune", "--fp16", "--fp16-lm-cross-entropy",
    "--fp32-residual-connection", "--global-batch-size", "--head-lr-mult",
    "--hidden-dropout", "--hidden-size", "--hysteresis", "--ict-head-size",
    "--ict-load", "--img-h", "--img-w", "--indexer-batch-size",
    "--indexer-log-interval", "--inference-batch-times-seqlen-threshold",
    "--init-method-std", "--init-method-xavier-uniform",
    "--initial-loss-scale", "--iter-per-epoch", "--kv-channels",
    "--layernorm-epsilon", "--lazy-mpu-init", "--load",
    "--log-batch-size-to-tensorboard", "--log-interval",
    "--log-memory-to-tensorboard", "--log-num-zeros-in-grad",
    "--log-params-norm", "--log-timers-to-tensorboard",
    "--log-validation-ppl-to-tensorboard",
    "--log-world-size-to-tensorboard", "--loss-scale",
    "--loss-scale-window", "--lr", "--lr-decay-iters", "--lr-decay-samples",
    "--lr-decay-style", "--lr-warmup-fraction", "--lr-warmup-iters",
    "--lr-warmup-samples", "--make-vocab-size-divisible-by",
    "--mask-factor", "--mask-prob", "--mask-type",
    "--max-position-embeddings", "--merge-file", "--micro-batch-size",
    "--min-loss-scale", "--min-lr", "--mmap-warmup",
    "--model-parallel-size", "--no-async-tensor-model-parallel-allreduce",
    "--no-bias-dropout-fusion", "--no-bias-gelu-fusion",
    "--no-contiguous-buffers-in-local-ddp", "--no-data-sharding",
    "--no-gradient-accumulation-fusion", "--no-load-optim", "--no-load-rng",
    "--no-log-learnig-rate-to-tensorboard",
    "--no-log-loss-scale-to-tensorboard", "--no-masked-softmax-fusion",
    "--no-persist-layer-norm", "--no-query-key-layer-scaling",
    "--no-save-optim", "--no-save-rng",
    "--no-scatter-gather-tensors-in-pipeline", "--num-attention-heads",
    "--num-channels", "--num-classes", "--num-experts", "--num-layers",
    "--num-layers-per-virtual-pipeline-stage", "--num-workers",
    "--onnx-safe", "--openai-gelu", "--optimizer",
    "--override-lr-scheduler", "--patch-dim",
    "--pipeline-model-parallel-size",
    "--pipeline-model-parallel-split-rank", "--query-in-block-prob",
    "--rampup-batch-size", "--recompute-activations",
    "--recompute-granularity", "--recompute-method",
    "--recompute-num-layers", "--reset-attention-mask",
    "--reset-position-ids", "--retriever-report-topk-accuracies",
    "--retriever-score-scaling", "--retriever-seq-length", "--sample-rate",
    "--save", "--save-interval", "--seed", "--seq-length",
    "--sequence-parallel", "--sgd-momentum", "--short-seq-prob", "--split",
    "--standalone-embedding-stage", "--start-weight-decay",
    "--swin-backbone-type", "--tensor-model-parallel-size",
    "--tensorboard-dir", "--tensorboard-log-interval",
    "--tensorboard-queue-size", "--titles-data-path", "--tokenizer-type",
    "--train-iters", "--train-samples", "--use-checkpoint-lr-scheduler",
    "--use-cpu-initialization", "--use-one-sent-docs",
    "--vision-backbone-type", "--vision-pretraining",
    "--vision-pretraining-type", "--vocab-extra-ids", "--vocab-file",
    "--warmup", "--weight-decay", "--weight-decay-incr-style",
]


class TestFullReferenceArgsContract:
    def test_disposition_registry_is_exhaustive_and_exact(self):
        from apex_tpu.transformer.testing.arguments import (
            REFERENCE_DISPOSITIONS,
        )

        assert set(REFERENCE_DISPOSITIONS) == set(REFERENCE_FLAGS)
        for flag, (status, note) in REFERENCE_DISPOSITIONS.items():
            assert status in ("wired", "inert"), flag
            assert note, flag

    def test_every_reference_flag_parses(self):
        import warnings as _w

        needs_value = {
            "--batch-size": "4", "--bert-load": "/tmp/x",
            "--data-path": "/tmp/d", "--lr": "1e-4",
            "--hidden-size": "64", "--num-layers": "2",
            "--num-attention-heads": "4", "--dataloader-type": "single",
            "--lr-decay-style": "cosine", "--optimizer": "sgd",
            "--recompute-granularity": "full",
            "--recompute-method": "uniform",
            "--weight-decay-incr-style": "linear",
            "--rampup-batch-size": None,     # nargs=3, handled below
        }
        # parse in one invocation per flag so store_true/value flags both
        # work; every flag must be ACCEPTED (no argparse error)
        for flag in REFERENCE_FLAGS:
            argv = [flag]
            from apex_tpu.transformer.testing.arguments import parse_args
            import argparse as _ap
            # value-taking flags need a value: introspect via a dry parse
            with _w.catch_warnings():
                _w.simplefilter("ignore")
                try:
                    parse_args(args=argv)
                    continue
                except ValueError:
                    continue    # parsed; post-validation fired = wired
                except SystemExit:
                    pass
                # needs a value (or conflicts); retry with a plausible one
                if flag == "--rampup-batch-size":
                    argv2 = [flag, "4", "4", "64",
                             "--global-batch-size", "16"]
                elif flag == "--start-weight-decay":
                    argv2 = [flag, "0.0", "--end-weight-decay", "0.1"]
                elif flag == "--end-weight-decay":
                    argv2 = [flag, "0.1", "--start-weight-decay", "0.0"]
                else:
                    argv2 = [flag, needs_value.get(flag, "1")]
                try:
                    parse_args(args=argv2)
                except ValueError:
                    pass        # parsed; post-validation fired = wired
                except SystemExit as e:      # pragma: no cover
                    raise AssertionError(
                        f"reference flag {flag} rejected") from e

    def test_inert_flags_warn_and_record(self):
        import warnings as _w

        from apex_tpu.transformer.testing.arguments import parse_args

        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter("always")
            args = parse_args(args=["--tensorboard-dir", "/tmp/tb"])
        assert args.inert_flags_set == ["--tensorboard-dir"]
        assert any("--tensorboard-dir" in str(m.message) for m in rec)

    def test_deprecated_aliases(self):
        from apex_tpu.transformer.testing.arguments import parse_args

        a = parse_args(args=["--model-parallel-size", "2",
                             "--world-size", "4"])
        assert a.tensor_model_parallel_size == 2
        a = parse_args(args=["--batch-size", "8"])
        assert a.micro_batch_size == 8
        a = parse_args(args=["--warmup", "5"])
        assert a.lr_warmup_fraction == 0.05
        a = parse_args(args=["--checkpoint-activations"])
        assert a.recompute is True
        a = parse_args(args=["--recompute-activations"])
        assert a.recompute == "selective"

    def test_derivations(self):
        from apex_tpu.transformer.testing.arguments import parse_args

        a = parse_args(args=["--num-layers", "8",
                             "--pipeline-model-parallel-size", "2",
                             "--num-layers-per-virtual-pipeline-stage", "2",
                             "--world-size", "2"])
        assert a.virtual_pipeline_model_parallel_size == 2
        a = parse_args(args=["--vocab-size", "50257",
                             "--tensor-model-parallel-size", "2",
                             "--world-size", "2"])
        assert a.padded_vocab_size == 50432       # ceil to 256
        import pytest as _pt
        with _pt.raises(ValueError):
            parse_args(args=["--kv-channels", "999"])
        with _pt.raises(ValueError):
            parse_args(args=["--seq-length", "256",
                             "--max-position-embeddings", "128"])
        a = parse_args(args=["--start-weight-decay", "0.0",
                             "--end-weight-decay", "0.1"])
        assert a.start_weight_decay == 0.0 and a.end_weight_decay == 0.1
