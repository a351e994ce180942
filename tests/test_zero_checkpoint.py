"""ZeRO-sharded optimizer + distributed checkpoint suite.

Mirrors the reference's ``apex/contrib/test/optimizers/test_dist_adam.py``
(DistributedFusedAdam vs plain Adam parity) and the checkpoint round-trip
flows of ``apex/amp`` state_dict + ``DistributedFusedAdam`` sharded
state_dict (SURVEY.md §5 checkpoint/resume).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

os.environ.setdefault("APEX_TPU_FORCE_PALLAS", "interpret")

from apex_tpu.optimizers import DistributedFusedAdam, FusedAdam  # noqa: E402
from apex_tpu.training import make_train_step  # noqa: E402
from apex_tpu.transformer import parallel_state  # noqa: E402


def _params(key=0):
    k = jax.random.PRNGKey(key)
    k1, k2, k3 = jax.random.split(k, 3)
    return {
        "w1": jax.random.normal(k1, (16, 33)),   # odd sizes force padding
        "b1": jax.random.normal(k2, (33,)),
        "w2": jax.random.normal(k3, (33, 4)),
    }


def _grads(key=9):
    return jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(
            jax.random.PRNGKey(key), x.size), x.shape), _params())


class TestDistributedFusedAdamSingle:
    def test_matches_fused_adam_unsharded(self):
        parallel_state.destroy_model_parallel()
        params = _params()
        grads = _grads()
        ref = FusedAdam(lr=1e-2, weight_decay=0.01)
        dist = DistributedFusedAdam(lr=1e-2, weight_decay=0.01, num_shards=1)
        rstate, dstate = ref.init(params), dist.init(params)
        p_ref, p_dist = params, params
        for _ in range(3):
            p_ref, rstate = ref.step(grads, p_ref, rstate)
            p_dist, dstate = dist.step(grads, p_dist, dstate)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-6),
            p_ref, p_dist)

    def test_found_inf_skips_update(self):
        params = _params()
        grads = _grads()
        dist = DistributedFusedAdam(lr=1e-2, num_shards=1)
        state = dist.init(params)
        new_p, new_state = dist.step(grads, params, state,
                                     found_inf=jnp.asarray(True))
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b),
                     new_p, params)
        assert int(new_state["step"]) == 0

    def test_grad_scale_unscales(self):
        params = _params()
        grads = _grads()
        dist = DistributedFusedAdam(lr=1e-2, num_shards=1)
        s1 = dist.init(params)
        p1, _ = dist.step(grads, params, s1)
        scaled = jax.tree.map(lambda g: g * 512.0, grads)
        s2 = dist.init(params)
        p2, _ = dist.step(scaled, params, s2,
                          grad_scale=jnp.asarray(512.0))
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-6), p1, p2)


class TestDistributedFusedAdamSharded:
    """ZeRO path on an 8-device mesh must match replicated FusedAdam."""

    def _train(self, optimizer, tp=1, steps=4):
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=tp)
        params = _params()
        # simple per-rank model: tp shards w2 columns
        param_spec = {"w1": P(), "b1": P(),
                      "w2": P(None, "tensor") if tp > 1 else P()}

        def loss_fn(p, batch, rng):
            h = jnp.tanh(batch["x"] @ p["w1"] + p["b1"])
            out = h @ p["w2"]
            if tp > 1:
                out = jax.lax.all_gather(out, "tensor", axis=1, tiled=True)
            return jnp.mean((out - batch["y"]) ** 2)

        if isinstance(optimizer, DistributedFusedAdam):
            opt_state = optimizer.init(params, param_spec)
        else:
            opt_state = optimizer.init(params)
        step = make_train_step(
            loss_fn, optimizer, mesh, param_spec,
            {"x": P("data"), "y": P("data")},
            opt_state_spec=optimizer.state_spec(params, param_spec))
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
        y = jax.random.normal(jax.random.PRNGKey(2), (16, 4))
        p, s = params, opt_state
        losses = []
        for _ in range(steps):
            p, s, loss = step(p, s, {"x": x, "y": y}, None)
            losses.append(float(loss))
        parallel_state.destroy_model_parallel()
        return losses, jax.device_get(p), s

    def test_zero_matches_replicated_adam(self):
        ref_losses, ref_p, _ = self._train(FusedAdam(lr=1e-2))
        z_losses, z_p, z_s = self._train(
            DistributedFusedAdam(lr=1e-2, num_shards=8))
        np.testing.assert_allclose(z_losses, ref_losses, rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-6),
            z_p, ref_p)
        # state is genuinely sharded: leading dim = dp shards
        assert z_s["master"].shape[0] == 8

    def test_zero_with_tensor_parallel(self):
        ref_losses, _, _ = self._train(FusedAdam(lr=1e-2), tp=2)
        z_losses, _, z_s = self._train(
            DistributedFusedAdam(lr=1e-2, num_shards=4), tp=2)
        np.testing.assert_allclose(z_losses, ref_losses, rtol=1e-5)
        assert z_s["master"].shape[0] == 4  # dp shards

    def test_weight_decay_mask_matches_per_leaf(self):
        # biases excluded from decay, exactly as torch param-groups would
        mask = {"w1": True, "b1": False, "w2": True}
        ref_losses, ref_p, _ = self._train(
            FusedAdam(lr=1e-2, weight_decay=0.1, weight_decay_mask=mask))
        z_losses, z_p, _ = self._train(
            DistributedFusedAdam(lr=1e-2, weight_decay=0.1,
                                 weight_decay_mask=mask, num_shards=8))
        np.testing.assert_allclose(z_losses, ref_losses, rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-6),
            z_p, ref_p)


class TestGatherDtypeAndRemainders:
    """Reduced-precision param all-gather + bf16-remainder master storage
    (reference ``distributed_fused_lamb.py:105,340`` fp16/e5m2 gather,
    ``distributed_fused_adam.py:251-267`` store_param_remainders)."""

    def _train_bf16(self, optimizer, steps=100):
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), _params())
        spec = {"w1": P(), "b1": P(), "w2": P()}

        def loss_fn(p, batch, rng):
            p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p)
            h = jnp.tanh(batch["x"] @ p32["w1"] + p32["b1"])
            return jnp.mean((h @ p32["w2"] - batch["y"]) ** 2)

        opt_state = optimizer.init(params, spec)
        step = make_train_step(
            loss_fn, optimizer, mesh, spec,
            {"x": P("data"), "y": P("data")},
            opt_state_spec=optimizer.state_spec(params, spec))
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
        y = jax.random.normal(jax.random.PRNGKey(2), (16, 4))
        p, s = params, opt_state
        losses = []
        for _ in range(steps):
            p, s, loss = step(p, s, {"x": x, "y": y}, None)
            losses.append(float(loss))
        parallel_state.destroy_model_parallel()
        return losses, jax.device_get(p), s

    def test_bf16_gather_matches_fp32_gather(self):
        """Auto gather dtype (bf16 for all-bf16 params) is LOSSLESS vs an
        explicit fp32 gather: the gathered values are cast to the leaf
        dtype anyway, and the cast commutes with all_gather."""
        a_losses, a_p, _ = self._train_bf16(
            DistributedFusedAdam(lr=1e-2, num_shards=8))
        b_losses, b_p, _ = self._train_bf16(
            DistributedFusedAdam(lr=1e-2, num_shards=8,
                                 gather_dtype=jnp.float32))
        np.testing.assert_allclose(a_losses, b_losses, rtol=1e-6)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)), a_p, b_p)

    def test_fp32_params_default_to_fp32_gather(self):
        opt = DistributedFusedAdam(lr=1e-2, num_shards=8)
        assert opt._resolve_gather_dtype(_params()) == jnp.float32
        bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), _params())
        assert opt._resolve_gather_dtype(bf16) == jnp.bfloat16
        mixed = dict(bf16, w1=_params()["w1"])
        assert opt._resolve_gather_dtype(mixed) == jnp.float32

    @pytest.mark.slow
    def test_store_param_remainders_matches_master_mode(self):
        """(bf16 image + int16 remainder) storage follows the fp32-master
        trajectory; differences are bounded by round-half-up vs
        round-nearest-even 1-ulp ties in the gathered image."""
        a_losses, _, a_s = self._train_bf16(
            DistributedFusedAdam(lr=1e-2, num_shards=8))
        b_losses, _, b_s = self._train_bf16(
            DistributedFusedAdam(lr=1e-2, num_shards=8,
                                 store_param_remainders=True))
        np.testing.assert_allclose(a_losses, b_losses, rtol=2e-2, atol=1e-4)
        assert "master" not in b_s
        assert b_s["master_rem"].dtype == jnp.int16
        # reconstruction is exact: master == image<<16 + remainder
        opt = DistributedFusedAdam(lr=1e-2, num_shards=1)
        m = jnp.asarray([1.0000123, -3.5e-4, 2.75, 0.0, 1e30], jnp.float32)
        img, rem = opt._remainder_split(m)
        np.testing.assert_array_equal(
            np.asarray(opt._master_from_remainder(
                img.astype(jnp.float32), rem)), np.asarray(m))

    def test_remainders_reject_non_bf16(self):
        opt = DistributedFusedAdam(lr=1e-2, num_shards=1,
                                   store_param_remainders=True)
        with pytest.raises(ValueError, match="bfloat16"):
            opt.init(_params())

    def test_e5m2_gather_converges(self):
        """The reference's e5m2_allgather analog: lossy, but training still
        converges on the toy problem."""
        losses, _, _ = self._train_bf16(
            DistributedFusedAdam(lr=1e-2, num_shards=8,
                                 gather_dtype=jnp.float8_e5m2), steps=60)
        assert losses[-1] < losses[0] * 0.5


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        from apex_tpu.checkpoint import load_checkpoint, save_checkpoint

        state = {
            "params": _params(),
            "opt": {"step": jnp.asarray(7, jnp.int32),
                    "m": jax.tree.map(jnp.zeros_like, _params())},
            "scaler": {"loss_scale": jnp.asarray(2.0 ** 16)},
        }
        path = tmp_path / "ckpt1"
        save_checkpoint(str(path), state)
        restored = load_checkpoint(str(path), template=state)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                     state, restored)

    def test_roundtrip_sharded(self, tmp_path):
        from apex_tpu.checkpoint import load_checkpoint, save_checkpoint

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()
        from jax.sharding import NamedSharding

        sharding = NamedSharding(mesh, P("data"))
        arr = jax.device_put(jnp.arange(64, dtype=jnp.float32), sharding)
        state = {"master": arr, "step": jnp.asarray(3)}
        path = tmp_path / "ckpt2"
        save_checkpoint(str(path), state)
        restored = load_checkpoint(str(path), template=state)
        np.testing.assert_array_equal(
            np.asarray(restored["master"]), np.arange(64, dtype=np.float32))
        assert restored["master"].sharding == sharding
        parallel_state.destroy_model_parallel()

    def test_manager_rotation_and_resume(self, tmp_path):
        from apex_tpu.checkpoint import CheckpointManager

        state = {"w": jnp.zeros((4,))}
        mgr = CheckpointManager(str(tmp_path / "run"), max_to_keep=2)
        for step in range(3):
            mgr.save(step, {"w": jnp.full((4,), float(step))})
        mgr.wait_until_finished()
        assert mgr.latest_step() == 2
        step, restored = mgr.restore(state)
        assert step == 2
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.full((4,), 2.0))
        mgr.close()


class TestBatchSamplers:
    def test_pretraining_sampler_shards_and_resumes(self):
        from apex_tpu.transformer._data import MegatronPretrainingSampler

        s0 = list(MegatronPretrainingSampler(
            total_samples=32, consumed_samples=0, micro_batch_size=2,
            data_parallel_rank=0, data_parallel_size=2))
        s1 = list(MegatronPretrainingSampler(
            total_samples=32, consumed_samples=0, micro_batch_size=2,
            data_parallel_rank=1, data_parallel_size=2))
        assert s0[0] == [0, 1] and s1[0] == [2, 3]
        # disjoint coverage
        flat = sorted(i for b in s0 + s1 for i in b)
        assert flat == list(range(32))
        # resume at consumed_samples=8 continues exactly
        resumed = list(MegatronPretrainingSampler(
            total_samples=32, consumed_samples=8, micro_batch_size=2,
            data_parallel_rank=0, data_parallel_size=2))
        assert resumed == s0[2:]

    def test_random_sampler_resumable(self):
        from apex_tpu.transformer._data import (
            MegatronPretrainingRandomSampler,
        )

        full = list(MegatronPretrainingRandomSampler(
            total_samples=32, consumed_samples=0, micro_batch_size=2,
            data_parallel_rank=0, data_parallel_size=2))
        resumed = list(MegatronPretrainingRandomSampler(
            total_samples=32, consumed_samples=8, micro_batch_size=2,
            data_parallel_rank=0, data_parallel_size=2))
        # resuming skips exactly consumed/dp per-rank samples
        assert resumed == full[2:]
        # ranks see disjoint index ranges
        r1 = list(MegatronPretrainingRandomSampler(
            total_samples=32, consumed_samples=0, micro_batch_size=2,
            data_parallel_rank=1, data_parallel_size=2))
        flat0 = {i for b in full for i in b}
        flat1 = {i for b in r1 for i in b}
        assert not (flat0 & flat1)

    def test_random_sampler_multi_epoch_and_dropped_tail(self):
        from apex_tpu.transformer._data import (
            MegatronPretrainingRandomSampler,
        )

        # total=34, global batch 4: tail of 2 dropped, active epoch = 32.
        # Resume exactly at the epoch boundary must start epoch 1, not an
        # empty iterator; resume past one epoch must also work.
        for consumed in (32, 40):
            resumed = list(MegatronPretrainingRandomSampler(
                total_samples=34, consumed_samples=consumed,
                micro_batch_size=2, data_parallel_rank=0,
                data_parallel_size=2))
            assert len(resumed) == (32 - consumed % 32) // 4
            assert all(len(b) == 2 for b in resumed)


class TestDistributedFusedLAMB:
    """ZeRO LAMB: trust ratios computed from cross-shard segment norms must
    reproduce the unsharded FusedLAMB exactly (reference
    ``apex/contrib/test/optimizers/test_dist_lamb.py`` strategy)."""

    def test_matches_fused_lamb_unsharded(self):
        from apex_tpu.optimizers import DistributedFusedLAMB, FusedLAMB

        parallel_state.destroy_model_parallel()
        params = _params()
        grads = _grads()
        ref = FusedLAMB(lr=1e-2, weight_decay=0.01)
        dist = DistributedFusedLAMB(lr=1e-2, weight_decay=0.01, num_shards=1)
        rstate, dstate = ref.init(params), dist.init(params)
        p_ref, p_dist = params, params
        for _ in range(3):
            p_ref, rstate = ref.step(grads, p_ref, rstate)
            p_dist, dstate = dist.step(grads, p_dist, dstate)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-6),
            p_ref, p_dist)

    def test_zero_lamb_matches_replicated(self):
        from apex_tpu.optimizers import DistributedFusedLAMB, FusedLAMB

        harness = TestDistributedFusedAdamSharded()
        ref_losses, ref_p, _ = harness._train(FusedLAMB(lr=1e-2))
        z_losses, z_p, z_s = harness._train(
            DistributedFusedLAMB(lr=1e-2, num_shards=8))
        np.testing.assert_allclose(z_losses, ref_losses, rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-6),
            z_p, ref_p)
        assert z_s["master"].shape[0] == 8

    def test_weight_decay_mask_matches_per_leaf(self):
        from apex_tpu.optimizers import DistributedFusedLAMB, FusedLAMB

        mask = {"w1": True, "b1": False, "w2": True}
        harness = TestDistributedFusedAdamSharded()
        ref_losses, ref_p, _ = harness._train(
            FusedLAMB(lr=1e-2, weight_decay=0.1, weight_decay_mask=mask))
        z_losses, z_p, _ = harness._train(
            DistributedFusedLAMB(lr=1e-2, weight_decay=0.1,
                                 weight_decay_mask=mask, num_shards=8))
        np.testing.assert_allclose(z_losses, ref_losses, rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                    atol=1e-6),
            z_p, ref_p)

    def test_no_decay_no_adapt_matches_adam_shape(self):
        from apex_tpu.optimizers import DistributedFusedLAMB

        parallel_state.destroy_model_parallel()
        params = _params()
        grads = _grads()
        opt = DistributedFusedLAMB(lr=1e-2, weight_decay=0.0, num_shards=1)
        state = opt.init(params)
        new_p, new_state = opt.step(grads, params, state)
        assert int(new_state["step"]) == 1
        changed = jax.tree.map(
            lambda a, b: bool(np.any(np.asarray(a) != np.asarray(b))),
            new_p, params)
        assert all(jax.tree.leaves(changed))

    def test_found_inf_skips(self):
        from apex_tpu.optimizers import DistributedFusedLAMB

        parallel_state.destroy_model_parallel()
        params = _params()
        grads = _grads()
        opt = DistributedFusedLAMB(lr=1e-2, num_shards=1)
        state = opt.init(params)
        new_p, new_state = opt.step(grads, params, state,
                                    found_inf=jnp.asarray(True))
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b),
                     new_p, params)
        assert int(new_state["step"]) == 0
