"""Tensor-parallel layer/mapping tests.

Mirrors the reference suite ``tests/L0/run_transformer/`` (``test_layers.py``,
``test_mapping.py``, ``test_cross_entropy.py``, ``test_random.py``,
``test_data.py``): sharded results computed under ``shard_map`` on the 8-way
virtual CPU mesh must match a single-rank reference computed from the same
global parameters.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    vocab_parallel_cross_entropy,
    broadcast_data,
    divide,
    gather_from_sequence_parallel_region,
    gather_from_tensor_model_parallel_region,
    copy_to_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    reduce_scatter_to_sequence_parallel_region,
    scatter_to_sequence_parallel_region,
    scatter_to_tensor_model_parallel_region,
    get_rng_tracker,
    model_parallel_rng_key,
)
from apex_tpu.utils.sharding import shard_map

TENSOR = parallel_state.TENSOR_AXIS


@pytest.fixture
def tp8_mesh():
    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(tensor_model_parallel_size=8)
    yield mesh
    parallel_state.destroy_model_parallel()


def shmap(fn, mesh, in_specs, out_specs):
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


# ---------------------------------------------------------------------------
# mappings (reference tests/L0/run_transformer/test_mapping.py)
# ---------------------------------------------------------------------------

class TestMappings:
    def test_copy_identity_fwd_psum_bwd(self, tp8_mesh):
        # Per-rank autodiff (the canonical torch-style usage, grad computed
        # *inside* shard_map): each rank scales the copied activation by
        # (rank+1); the copy region's backward all-reduce must therefore give
        # every rank grad sum(1..8) = 36.
        x = jnp.ones((4,))

        def per_rank(v):
            scale = jax.lax.axis_index(TENSOR).astype(jnp.float32) + 1.0
            return jax.grad(
                lambda u: (copy_to_tensor_model_parallel_region(u) * scale).sum()
            )(v)

        g = shmap(per_rank, tp8_mesh, P(), P())(x)
        np.testing.assert_allclose(g, 36.0 * np.ones(4))

    def test_scatter_gather_roundtrip(self, tp8_mesh):
        x = jnp.arange(32.0).reshape(4, 8)

        def f(v):
            # v arrives replicated [4, 8]; scatter keeps the local last-dim
            # chunk [4, 1]; gather restores [4, 8]
            s = scatter_to_tensor_model_parallel_region(v)
            assert s.shape == (4, 1)
            return gather_from_tensor_model_parallel_region(s)

        out = shmap(f, tp8_mesh, P(), P())(x)
        np.testing.assert_allclose(out, x)

    def test_reduce(self, tp8_mesh):
        x = jnp.ones((8, 4))

        def f(v):
            return reduce_from_tensor_model_parallel_region(v)

        out = shmap(f, tp8_mesh, P(TENSOR, None), P(TENSOR, None))(x)
        np.testing.assert_allclose(out, 8 * np.ones((8, 4)))

    def test_sequence_parallel_roundtrip(self, tp8_mesh):
        x = jnp.arange(16.0).reshape(16, 1)

        def f(v):
            s = scatter_to_sequence_parallel_region(v)
            assert s.shape == (2, 1)
            return gather_from_sequence_parallel_region(s, False)

        out = shmap(f, tp8_mesh, P(), P())(x)
        np.testing.assert_allclose(out, x)

    def test_reduce_scatter_then_gather_is_psum(self, tp8_mesh):
        x = jnp.ones((16, 2))

        def f(v):
            rs = reduce_scatter_to_sequence_parallel_region(v)
            assert rs.shape == (2, 2)
            return gather_from_sequence_parallel_region(rs, False)

        out = shmap(f, tp8_mesh, P(), P())(x)
        np.testing.assert_allclose(out, 8 * np.ones((16, 2)))

    def test_unsharded_identity(self):
        # outside shard_map every region is the identity (world size 1)
        x = jnp.arange(6.0).reshape(2, 3)
        for fn in (copy_to_tensor_model_parallel_region,
                   reduce_from_tensor_model_parallel_region,
                   scatter_to_tensor_model_parallel_region,
                   gather_from_tensor_model_parallel_region,
                   scatter_to_sequence_parallel_region,
                   reduce_scatter_to_sequence_parallel_region):
            np.testing.assert_allclose(fn(x), x)


# ---------------------------------------------------------------------------
# layers (reference tests/L0/run_transformer/test_layers.py)
# ---------------------------------------------------------------------------

class TestColumnParallelLinear:
    def test_matches_unsharded(self, tp8_mesh):
        layer = ColumnParallelLinear(16, 32, gather_output=True)
        params = layer.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))

        ref = layer.apply(params, x)  # unsharded path
        out = shmap(layer.apply, tp8_mesh,
                    (layer.spec(), P()), P())(params, x)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_grads_match_unsharded(self, tp8_mesh):
        # Canonical usage: per-rank autodiff *inside* shard_map (torch-style),
        # param grads exit through the same sharded specs as the params.
        layer = ColumnParallelLinear(16, 32, gather_output=True)
        params = layer.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))

        def per_rank(p, v):
            return jax.grad(lambda pp: (layer.apply(pp, v) ** 2).sum())(p)

        g_ref = jax.grad(lambda p: (layer.apply(p, x) ** 2).sum())(params)
        g_sh = shmap(per_rank, tp8_mesh,
                     (layer.spec(), P()), layer.spec())(params, x)
        for k in g_ref:
            np.testing.assert_allclose(g_sh[k], g_ref[k], rtol=1e-4, atol=1e-5)

    def test_no_gather_output_shape(self, tp8_mesh):
        layer = ColumnParallelLinear(16, 32, gather_output=False)
        params = layer.init(jax.random.PRNGKey(0))
        x = jnp.ones((4, 16))
        out = shmap(layer.apply, tp8_mesh,
                    (layer.spec(), P()), P(None, TENSOR))(params, x)
        ref = layer.apply(params, x)
        np.testing.assert_allclose(out, ref, rtol=1e-5)

    def test_skip_bias_add(self):
        layer = ColumnParallelLinear(8, 8, skip_bias_add=True)
        params = layer.init(jax.random.PRNGKey(0))
        out, bias = layer.apply(params, jnp.ones((2, 8)))
        assert out.shape == (2, 8) and bias.shape == (8,)

    def test_sp_incompatible_with_gather(self):
        with pytest.raises(ValueError):
            ColumnParallelLinear(8, 8, gather_output=True,
                                 sequence_parallel_enabled=True)


class TestRowParallelLinear:
    def test_matches_unsharded(self, tp8_mesh):
        layer = RowParallelLinear(32, 16, input_is_parallel=False)
        params = layer.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))

        ref = layer.apply(params, x)
        out = shmap(layer.apply, tp8_mesh,
                    (layer.spec(), P()), P())(params, x)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_grads_match_unsharded(self, tp8_mesh):
        layer = RowParallelLinear(32, 16, input_is_parallel=False)
        params = layer.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))

        def per_rank(p, v):
            return jax.grad(lambda pp: (layer.apply(pp, v) ** 2).sum())(p)

        g_ref = jax.grad(lambda p: (layer.apply(p, x) ** 2).sum())(params)
        g_sh = shmap(per_rank, tp8_mesh,
                     (layer.spec(), P()), layer.spec())(params, x)
        for k in g_ref:
            np.testing.assert_allclose(g_sh[k], g_ref[k], rtol=1e-4, atol=1e-5)


class TestColumnRowSequenceParallel:
    """Megatron SP: sequence-sharded activations through Column→Row pair
    (reference layers.py:310-325,797 + test_layers.py SP cases)."""

    def test_column_row_pair_sp(self, tp8_mesh):
        col = ColumnParallelLinear(16, 64, gather_output=False,
                                   sequence_parallel_enabled=True)
        row = RowParallelLinear(64, 16, input_is_parallel=True,
                                sequence_parallel_enabled=True)
        cp = col.init(jax.random.PRNGKey(0))
        rp = row.init(jax.random.PRNGKey(1))
        # [s, b, h] with s sharded over tensor axis
        x = jax.random.normal(jax.random.PRNGKey(2), (16, 2, 16))

        def fwd(cparams, rparams, v):
            h = col.apply(cparams, v)
            return row.apply(rparams, h)

        out = shmap(fwd, tp8_mesh,
                    (col.spec(), row.spec(), P(TENSOR)), P(TENSOR))(cp, rp, x)

        # reference: same math without sharding
        col_ref = ColumnParallelLinear(16, 64, gather_output=False)
        row_ref = RowParallelLinear(64, 16, input_is_parallel=True)
        ref = row_ref.apply(rp, col_ref.apply(cp, x))
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

    def test_sp_grads_match(self, tp8_mesh):
        col = ColumnParallelLinear(8, 32, gather_output=False,
                                   sequence_parallel_enabled=True)
        row = RowParallelLinear(32, 8, input_is_parallel=True,
                                sequence_parallel_enabled=True)
        cp = col.init(jax.random.PRNGKey(0))
        rp = row.init(jax.random.PRNGKey(1))
        x = jax.random.normal(jax.random.PRNGKey(2), (8, 2, 8))

        # Canonical usage (see mappings.py docstring): per-rank autodiff
        # *inside* shard_map — the global loss is the sum of per-rank local
        # losses over the sequence shards, and the region backwards
        # (all-gather / psum) assemble full grads on every rank.
        def per_rank(cparams, rparams, v):
            def local_loss(c, r):
                return (row.apply(r, col.apply(c, v)) ** 2).sum()
            return jax.grad(local_loss, argnums=(0, 1))(cparams, rparams)

        g_sh = shmap(per_rank, tp8_mesh,
                     (col.spec(), row.spec(), P(TENSOR)),
                     (col.spec(), row.spec()))(cp, rp, x)

        col_ref = ColumnParallelLinear(8, 32, gather_output=False)
        row_ref = RowParallelLinear(32, 8, input_is_parallel=True)

        def loss_ref(cparams, rparams):
            return (row_ref.apply(rparams, col_ref.apply(cparams, x)) ** 2).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1))(cp, rp)
        for a, b in zip(jax.tree.leaves(g_sh), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestVocabParallelEmbedding:
    def test_matches_take(self, tp8_mesh):
        emb = VocabParallelEmbedding(64, 16)
        params = emb.init(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0, 64)

        ref = jnp.take(params["weight"], ids, axis=0)
        out = shmap(emb.apply, tp8_mesh,
                    (emb.spec(), P()), P())(params, ids)
        np.testing.assert_allclose(out, ref, rtol=1e-6)

    def test_grad_matches(self, tp8_mesh):
        emb = VocabParallelEmbedding(64, 16)
        params = emb.init(jax.random.PRNGKey(0))
        ids = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0, 64)

        def per_rank(p, t):
            return jax.grad(lambda pp: emb.apply(pp, t).sum())(p)

        g_ref = jax.grad(lambda p: jnp.take(p["weight"], ids, axis=0).sum())(params)
        g_sh = shmap(per_rank, tp8_mesh,
                     (emb.spec(), P()), emb.spec())(params, ids)
        np.testing.assert_allclose(g_sh["weight"], g_ref["weight"], rtol=1e-6)


# ---------------------------------------------------------------------------
# cross entropy (reference tests/L0/run_transformer/test_cross_entropy.py)
# ---------------------------------------------------------------------------

class TestVocabParallelCrossEntropy:
    def _ref_ce(self, logits, target, smoothing=0.0):
        V = logits.shape[-1]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]
        if smoothing > 0:
            s = smoothing * V / (V - 1)
            return (1 - s) * nll - s * logp.mean(axis=-1)
        return nll

    @pytest.mark.parametrize("smoothing", [
        0.0, pytest.param(0.1, marks=pytest.mark.slow)])
    def test_matches_full_softmax(self, tp8_mesh, smoothing):
        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 64))
        target = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0, 64)

        ref = self._ref_ce(logits, target, smoothing)
        out = shmap(
            lambda l, t: vocab_parallel_cross_entropy(l, t, smoothing),
            tp8_mesh, (P(None, None, TENSOR), P()), P())(logits, target)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("smoothing", [
        0.0, pytest.param(0.1, marks=pytest.mark.slow)])
    def test_grads_match(self, tp8_mesh, smoothing):
        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 6, 64))
        target = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0, 64)

        def per_rank(l, t):
            return jax.grad(lambda ll: vocab_parallel_cross_entropy(
                ll, t, smoothing).sum())(l)

        g_ref = jax.grad(
            lambda l: self._ref_ce(l, target, smoothing).sum())(logits)
        g_sh = shmap(per_rank, tp8_mesh,
                     (P(None, None, TENSOR), P()),
                     P(None, None, TENSOR))(logits, target)
        np.testing.assert_allclose(g_sh, g_ref, rtol=1e-4, atol=1e-6)

    def test_unsharded_path(self):
        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
        target = jax.random.randint(jax.random.PRNGKey(1), (4,), 0, 32)
        out = vocab_parallel_cross_entropy(logits, target)
        np.testing.assert_allclose(out, self._ref_ce(logits, target), rtol=1e-5)


# ---------------------------------------------------------------------------
# random / data / utils
# ---------------------------------------------------------------------------

class TestRandom:
    def test_model_parallel_keys_distinct_per_rank(self, tp8_mesh):
        key = jax.random.PRNGKey(0)

        def draw(k):
            k = model_parallel_rng_key(k)
            return jax.random.normal(k, (1, 4))

        out = shmap(draw, tp8_mesh, P(), P(TENSOR))(key)
        # 8 ranks → 8 distinct rows
        assert len({tuple(np.asarray(r)) for r in out}) == 8

    def test_default_region_identical_across_ranks(self, tp8_mesh):
        key = jax.random.PRNGKey(0)

        def draw(k):
            return jax.random.normal(k, (1, 4))

        out = shmap(draw, tp8_mesh, P(), P(TENSOR))(key)
        assert len({tuple(np.asarray(r)) for r in out}) == 1

    def test_tracker_fork_advances(self):
        tracker = get_rng_tracker()
        tracker.reset()
        with tracker.fork() as k1:
            pass
        with tracker.fork() as k2:
            pass
        assert not np.array_equal(np.asarray(k1), np.asarray(k2))


class TestDataUtils:
    def test_divide(self):
        assert divide(8, 2) == 4
        with pytest.raises(ValueError):
            divide(7, 2)

    def test_broadcast_data(self, tp8_mesh):
        data = {"text": jnp.ones((4, 8), jnp.int32),
                "types": jnp.zeros((4, 8), jnp.int32)}
        out = broadcast_data(["text", "types"], data, jnp.int32)
        np.testing.assert_array_equal(out["text"], data["text"])
        with pytest.raises(ValueError):
            broadcast_data(["text"], {"text": jnp.ones((2,), jnp.float32)}, jnp.int32)


class TestZLoss:
    """z-loss logit regularization on the vocab-parallel CE (PaLM-style,
    exceeds the reference): loss += z * log(Z)^2, grads via the custom vjp
    must match autodiff through an explicit reference."""

    def _ref(self, logits, target, z):
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        nll = lse - jnp.take_along_axis(
            logits.astype(jnp.float32), target[..., None], -1)[..., 0]
        return nll + z * lse * lse

    def test_forward_matches_reference(self):
        from apex_tpu.transformer.tensor_parallel import (
            vocab_parallel_cross_entropy,
        )

        logits = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 32))
        target = jax.random.randint(jax.random.PRNGKey(1), (4, 3), 0, 32)
        out = vocab_parallel_cross_entropy(logits, target, z_loss=1e-2)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref(logits, target, 1e-2)),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.slow
    def test_grads_match_autodiff_reference(self):
        from apex_tpu.transformer.tensor_parallel import (
            vocab_parallel_cross_entropy,
        )

        logits = jax.random.normal(jax.random.PRNGKey(2), (2, 5, 16))
        target = jax.random.randint(jax.random.PRNGKey(3), (2, 5), 0, 16)
        g = jax.grad(lambda l: jnp.sum(vocab_parallel_cross_entropy(
            l, target, z_loss=1e-2)))(logits)
        gr = jax.grad(lambda l: jnp.sum(self._ref(l, target, 1e-2)))(logits)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=1e-5, atol=1e-5)

    def test_zero_coef_is_plain_ce(self):
        from apex_tpu.transformer.tensor_parallel import (
            vocab_parallel_cross_entropy,
        )

        logits = jax.random.normal(jax.random.PRNGKey(4), (3, 4, 8))
        target = jax.random.randint(jax.random.PRNGKey(5), (3, 4), 0, 8)
        a = vocab_parallel_cross_entropy(logits, target)
        b = vocab_parallel_cross_entropy(logits, target, z_loss=0.0)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    @pytest.mark.slow
    def test_with_label_smoothing_grads_consistent(self):
        """Regression: z-loss must be added AFTER the smoothing rescale so
        the custom vjp matches autodiff of the returned value."""
        from apex_tpu.transformer.tensor_parallel import (
            vocab_parallel_cross_entropy,
        )

        logits = jax.random.normal(jax.random.PRNGKey(6), (2, 4, 16))
        target = jax.random.randint(jax.random.PRNGKey(7), (2, 4), 0, 16)

        def ref(l):
            l32 = l.astype(jnp.float32)
            lse = jax.nn.logsumexp(l32, axis=-1)
            nll = lse - jnp.take_along_axis(l32, target[..., None],
                                            -1)[..., 0]
            sp = 0.1 * 16 / 15
            mean_lp = jnp.mean(l32 - lse[..., None], axis=-1)
            sm = (1.0 - sp) * nll - sp * mean_lp
            return jnp.sum(sm + 1e-3 * lse * lse)

        val = jnp.sum(vocab_parallel_cross_entropy(
            logits, target, 0.1, z_loss=1e-3))
        np.testing.assert_allclose(float(val), float(ref(logits)),
                                   rtol=1e-5)
        g = jax.grad(lambda l: jnp.sum(vocab_parallel_cross_entropy(
            l, target, 0.1, z_loss=1e-3)))(logits)
        gr = jax.grad(ref)(logits)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=1e-4, atol=1e-5)

    def test_sharded_matches_unsharded(self, mesh8):
        """z-loss under a bound tensor axis: logZ must use the psum'd
        denominator + pmax'd max — sharded loss/grads == unsharded."""
        from apex_tpu.transformer.tensor_parallel import (
            vocab_parallel_cross_entropy,
        )

        logits = jax.random.normal(jax.random.PRNGKey(8), (3, 4, 32))
        target = jax.random.randint(jax.random.PRNGKey(9), (3, 4), 0, 32)
        ref_loss = vocab_parallel_cross_entropy(logits, target, z_loss=1e-2)
        ref_grad = jax.grad(lambda l: jnp.sum(
            vocab_parallel_cross_entropy(l, target, z_loss=1e-2)))(logits)

        def body(l, t):
            loss = vocab_parallel_cross_entropy(l, t, z_loss=1e-2)
            grad = jax.grad(lambda ll: jnp.sum(
                vocab_parallel_cross_entropy(ll, t, z_loss=1e-2)))(l)
            return loss, grad

        loss, grad = jax.jit(shard_map(
            body, mesh=mesh8,
            in_specs=(P(None, None, "tensor"), P()),
            out_specs=(P(), P(None, None, "tensor")),
            check_vma=False))(logits, target)
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref_loss),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad),
                                   rtol=1e-5, atol=1e-5)
