"""Data-parallel collectives + SyncBN on the 8-device CPU mesh (analog of
``tests/distributed/`` in the reference)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.parallel import (
    DistributedDataParallel,
    Reducer,
    SyncBatchNorm,
    all_reduce_gradients,
)
from apex_tpu.transformer import parallel_state
from apex_tpu.utils.sharding import axis_size, shard_map


def test_all_reduce_gradients_mean(data_mesh):
    mesh = data_mesh
    n = mesh.shape["data"]
    grads = jnp.arange(n * 4, dtype=jnp.float32).reshape(n, 4)

    @shard_map(mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def reduce(g):
        return all_reduce_gradients({"g": g}, "data")["g"]

    out = reduce(grads)
    expect = np.broadcast_to(np.asarray(grads).reshape(n, 1, 4).mean(axis=0), (n, 1, 4)).reshape(n, 4)
    np.testing.assert_allclose(out, expect, rtol=1e-6)


def test_ddp_options(data_mesh):
    mesh = data_mesh
    n = mesh.shape["data"]
    ddp = DistributedDataParallel(
        allreduce_always_fp32=True, gradient_predivide_factor=2.0)
    grads = jnp.ones((n, 8), jnp.bfloat16)

    @shard_map(mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def reduce(g):
        out = ddp.reduce_gradients({"g": g})["g"]
        return out

    out = reduce(grads)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), 1.0)  # mean of ones


def test_reducer(data_mesh):
    mesh = data_mesh
    n = mesh.shape["data"]
    vals = jnp.arange(n, dtype=jnp.float32).reshape(n, 1)

    @shard_map(mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    def rd(v):
        return Reducer().reduce({"v": v})["v"]

    out = rd(vals)
    np.testing.assert_allclose(out, np.full((n, 1), (n - 1) / 2.0), rtol=1e-6)


def test_syncbn_matches_global_bn(data_mesh):
    """Per-shard SyncBN stats == full-batch BN stats (the key invariant the
    reference tests in tests/distributed/synced_batchnorm)."""
    mesh = data_mesh
    n = mesh.shape["data"]
    batch, feat = 4 * n, 6
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, feat)) * 3 + 1

    bn = SyncBatchNorm(num_features=feat, axis_name="data", momentum=1.0)
    variables = bn.init(jax.random.PRNGKey(1), x[:4])

    @shard_map(mesh=mesh, in_specs=(P(), P("data")), out_specs=(P("data"), P()))
    def run(vars_, xs):
        y, updated = bn.apply(vars_, xs, mutable=["batch_stats"])
        return y, updated["batch_stats"]

    y, stats = run(variables, x)
    # reference: plain full-batch normalization
    mean = x.mean(axis=0)
    var = x.var(axis=0)
    expect = (x - mean) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect), atol=1e-4)
    np.testing.assert_allclose(np.asarray(stats["mean"]), np.asarray(mean), atol=1e-5)
    unbiased = x.var(axis=0, ddof=1)
    np.testing.assert_allclose(np.asarray(stats["var"]), np.asarray(unbiased), atol=1e-4)


def test_syncbn_channel_first_and_relu():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4, 4))  # NCHW
    bn = SyncBatchNorm(num_features=3, channel_last=False, fuse_relu=True)
    variables = bn.init(jax.random.PRNGKey(1), x)
    y = bn.apply(variables, x, mutable=["batch_stats"])[0]
    assert y.shape == x.shape
    assert float(jnp.min(y)) >= 0.0  # relu fused


def test_syncbn_eval_mode_uses_running_stats():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 4))
    bn = SyncBatchNorm(num_features=4, momentum=1.0)
    variables = bn.init(jax.random.PRNGKey(1), x)
    _, updated = bn.apply(variables, x, mutable=["batch_stats"])
    variables = {**variables, "batch_stats": updated["batch_stats"]}
    y = bn.apply(variables, x, use_running_stats=True)
    mean = np.asarray(x).mean(axis=0)
    var = np.asarray(x).var(axis=0, ddof=1)
    np.testing.assert_allclose(
        np.asarray(y), (np.asarray(x) - mean) / np.sqrt(var + 1e-5), atol=1e-4)


def test_syncbn_process_groups_sub_axis():
    """Reference ``tests/distributed/synced_batchnorm/test_groups.py``:
    BN synchronized within *groups* of ranks, not globally. Here groups =
    a sub-axis of a 2D data mesh: stats psum over ``group`` only, so each
    group of shards normalizes with its own statistics."""
    from jax.sharding import Mesh

    from apex_tpu.parallel import SyncBatchNorm

    if len(jax.devices()) < 8:
        pytest.skip("needs an 8-device mesh (2x4 group layout)")
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("group", "member"))
    bn = SyncBatchNorm(num_features=3, axis_name="member",
                       momentum=1.0, channel_last=True)

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 4, 4, 3))
    # make the two groups statistically different
    x = x.at[8:].add(5.0)
    variables = bn.init(jax.random.PRNGKey(1), x[:2])

    def body(v, xs):
        out, updates = bn.apply(v, xs, mutable=["batch_stats"])
        return out, updates["batch_stats"]

    y, stats = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(("group", "member"))),
        out_specs=(P(("group", "member")), P("group")),
        check_vma=False))(variables, x)

    # per-group running means differ (group 1 saw the +5 shift);
    # out_spec P("group") concatenates the two [C] vectors along dim 0
    m0 = np.asarray(stats["mean"][:3])
    m1 = np.asarray(stats["mean"][3:])
    assert abs(float(np.mean(m1 - m0)) - 5.0) < 0.5
    # ...and each group's output is normalized with its own stats: both
    # halves come out ~zero-mean despite the shift
    y = np.asarray(y, np.float32)
    assert abs(float(y[:8].mean())) < 0.1
    assert abs(float(y[8:].mean())) < 0.1
    # global BN (sync over both axes) would instead leave opposite-signed
    # group means ~ +-2.5/std; assert we did NOT do that
    assert abs(float(y[:8].mean() - y[8:].mean())) < 0.2


class TestSpecAwareGradSync:
    """sync_data_parallel_grads with param_spec: prefix pytrees (the same
    prefix semantics shard_map in_specs accept) and data-sharded leaves."""

    def test_prefix_spec_accepted(self):
        from apex_tpu.training import sync_data_parallel_grads

        if len(jax.devices()) < 8:
            pytest.skip("assertions assume an 8-rank data axis")

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()   # data = 8
        grads = {"block": {"w": jnp.ones((8, 4)), "b": jnp.ones((4,))},
                 "head": jnp.ones((4, 2))}
        # prefix spec: one entry covers the whole nested "block" subtree
        spec = {"block": P(), "head": P()}

        def per_rank(g):
            g = jax.tree.map(
                lambda x: x * (1.0 + jax.lax.axis_index("data")), g)
            return sync_data_parallel_grads(g, ("data",), spec)

        out = jax.jit(shard_map(
            per_rank, mesh=mesh, in_specs=(jax.tree.map(lambda _: P(), grads),),
            out_specs=jax.tree.map(lambda _: P(), grads),
            check_vma=False))(grads)
        # pmean of (1..8) = 4.5 for every replicated leaf
        jax.tree.map(
            lambda x: np.testing.assert_allclose(np.asarray(x), 4.5),
            out)
        parallel_state.destroy_model_parallel()

    def test_data_sharded_leaf_divided_not_averaged(self):
        from apex_tpu.training import sync_data_parallel_grads

        if len(jax.devices()) < 8:
            pytest.skip("assertions assume an 8-rank data axis")

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()
        grads = {"expert": jnp.ones((8, 4)), "shared": jnp.ones((8, 4))}
        spec = {"expert": P("data", None), "shared": P()}

        def per_rank(g):
            g = jax.tree.map(
                lambda x: x * (1.0 + jax.lax.axis_index("data")), g)
            return sync_data_parallel_grads(g, ("data",), spec)

        out = jax.jit(shard_map(
            per_rank, mesh=mesh,
            in_specs=({"expert": P("data", None), "shared": P()},),
            out_specs={"expert": P("data", None), "shared": P()},
            check_vma=False))(grads)
        # sharded leaf: rank r's rows scaled by (1+r)/8, no cross-rank mixing
        expert = np.asarray(out["expert"])
        for r in range(8):
            np.testing.assert_allclose(expert[r], (1.0 + r) / 8.0)
        np.testing.assert_allclose(np.asarray(out["shared"]), 4.5)
        parallel_state.destroy_model_parallel()


@pytest.mark.slow
def test_syncbn_unequal_per_rank_batches(data_mesh):
    """Count-weighted merge with unequal REAL batch sizes per rank
    (reference ``tests/distributed/synced_batchnorm/
    two_gpu_test_different_batch_size.py``): under SPMD every rank's shapes
    match, so short ranks pad and pass ``sample_mask``; statistics must
    equal full-batch BN over only the real rows."""
    mesh = data_mesh
    n = mesh.shape["data"]
    per_rank, feat = 4, 6
    # rank r has (4 - r % 3) real samples: e.g. 4,3,2,4,3,2,... over 8 ranks
    counts = np.array([per_rank - (r % 3) for r in range(n)])
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (n * per_rank, feat)) * 3 + 1
    mask = np.zeros((n * per_rank,), bool)
    for r in range(n):
        mask[r * per_rank: r * per_rank + counts[r]] = True
    mask_j = jnp.asarray(mask)

    bn = SyncBatchNorm(num_features=feat, axis_name="data", momentum=1.0)
    variables = bn.init(jax.random.PRNGKey(1), x[:4])

    @shard_map(mesh=mesh, in_specs=(P(), P("data"), P("data")),
                   out_specs=(P("data"), P()))
    def run(vars_, xs, m):
        y, updated = bn.apply(vars_, xs, sample_mask=m,
                              mutable=["batch_stats"])
        return y, updated["batch_stats"]

    y, stats = run(variables, x, mask_j)
    real = np.asarray(x)[mask]
    mean = real.mean(axis=0)
    var = real.var(axis=0)
    # real rows normalized by the count-weighted global stats
    expect = (real - mean) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(np.asarray(y)[mask], expect, atol=1e-4)
    np.testing.assert_allclose(np.asarray(stats["mean"]), mean, atol=1e-5)
    np.testing.assert_allclose(np.asarray(stats["var"]),
                               real.var(axis=0, ddof=1), atol=1e-4)


@pytest.mark.slow
def test_syncbn_unequal_batches_grads(data_mesh):
    """Gradients through the count-weighted masked SyncBN match the
    reference computation on only-the-real rows (the grad-parity half of
    the reference's different-batch-size test)."""
    mesh = data_mesh
    n = mesh.shape["data"]
    per_rank, feat = 2, 4
    counts = np.array([per_rank if r % 2 == 0 else 1 for r in range(n)])
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (n * per_rank, feat)) * 2 - 1
    mask = np.zeros((n * per_rank,), bool)
    for r in range(n):
        mask[r * per_rank: r * per_rank + counts[r]] = True
    mask_j = jnp.asarray(mask)

    bn = SyncBatchNorm(num_features=feat, axis_name="data", momentum=1.0)
    variables = bn.init(jax.random.PRNGKey(1), x[:2])
    tgt = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    @shard_map(mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
                   out_specs=P("data"), check_vma=False)
    def grad_x(xs, m, t):
        def loss(xs):
            y = bn.apply(variables, xs, sample_mask=m,
                         mutable=["batch_stats"])[0]
            # loss over real rows only (masked rows are padding); the /n
            # compensates psum's transpose summing every rank's unit
            # cotangent (each rank differentiates the same replicated loss)
            w = m.astype(jnp.float32)[:, None]
            return jax.lax.psum(
                jnp.sum(w * (y - t) ** 2), "data") / axis_size("data")
        return jax.grad(loss)(xs)

    g = np.asarray(grad_x(x, mask_j, tgt))

    # reference: same loss with only real rows through unmasked global BN
    real_idx = np.where(mask)[0]
    xr = jnp.asarray(np.asarray(x)[real_idx])
    tr = jnp.asarray(np.asarray(tgt)[real_idx])

    def ref_loss(xr):
        m_ = jnp.mean(xr, axis=0)
        v_ = jnp.mean((xr - m_) ** 2, axis=0)
        y = (xr - m_) / jnp.sqrt(v_ + 1e-5)
        return jnp.sum((y - tr) ** 2)

    g_ref = np.asarray(jax.grad(ref_loss)(xr))
    np.testing.assert_allclose(g[real_idx], g_ref, atol=1e-4)
    # padded rows contribute nothing and receive no gradient
    np.testing.assert_allclose(g[~mask], 0.0, atol=1e-6)


def test_syncbn_all_masked_batch_is_noop_on_running_stats():
    """A fully-padded global batch must leave batch_stats untouched —
    unguarded, the momentum blend decays them toward the count-guard's
    zero mean/var (ADVICE r4)."""
    from apex_tpu.parallel import SyncBatchNorm

    bn = SyncBatchNorm(num_features=3, axis_name=None, momentum=0.5)
    x = jnp.ones((4, 3)) * 2.0
    variables = bn.init(jax.random.PRNGKey(0), x)
    # one real step moves the stats off their init values
    _, v1 = bn.apply(variables, x, sample_mask=jnp.ones((4,), bool),
                     mutable=["batch_stats"])
    stats1 = jax.tree.map(np.asarray, v1["batch_stats"])
    assert stats1["mean"][0] != 0.0
    # an all-masked step is a no-op
    _, v2 = bn.apply({"params": variables["params"],
                      "batch_stats": v1["batch_stats"]}, x,
                     sample_mask=jnp.zeros((4,), bool),
                     mutable=["batch_stats"])
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        stats1, jax.tree.map(np.asarray, v2["batch_stats"]))


def test_bn_apply_sample_mask():
    """Functional bn_apply counterpart (the vision-model path): masked NHWC
    rows drop out of the count-weighted stats."""
    from apex_tpu.utils.batch_norm import bn_apply, bn_init

    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (4, 3, 3, 5)),
                   np.float32) * 2 + 3
    mask = np.array([True, True, True, False])
    p, s = bn_init(5)
    y, new_s = bn_apply(jax.tree.map(jnp.asarray, p),
                        jax.tree.map(jnp.asarray, s), jnp.asarray(x),
                        train=True, momentum=1.0, eps=1e-5, axis_name=None,
                        sample_mask=jnp.asarray(mask))
    real = x[mask].reshape(-1, 5)
    mean = real.mean(axis=0)
    var = real.var(axis=0)
    np.testing.assert_allclose(np.asarray(new_s["mean"]), mean, atol=1e-5)
    expect = (x[mask] - mean) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(np.asarray(y)[mask], expect, atol=1e-4)


def test_syncbn_mask_robust_to_garbage_padding():
    """Padded rows may hold ANYTHING (uninitialized buffers): NaN/Inf in a
    masked-out row must not leak into statistics or outputs (where-masking,
    not multiply — 0*NaN is NaN), and an all-padded batch must degrade to
    finite stats rather than 0/0."""
    from apex_tpu.utils.batch_norm import bn_apply, bn_init

    x = np.ones((4, 2, 2, 3), np.float32)
    x[2:] = np.nan
    x[3, 0, 0, 0] = np.inf
    mask = np.array([True, True, False, False])
    p, s = bn_init(3)
    p = jax.tree.map(jnp.asarray, p)
    s = jax.tree.map(jnp.asarray, s)
    y, new_s = bn_apply(p, s, jnp.asarray(x), train=True, momentum=1.0,
                        eps=1e-5, axis_name=None,
                        sample_mask=jnp.asarray(mask))
    assert np.isfinite(np.asarray(new_s["mean"])).all()
    assert np.isfinite(np.asarray(new_s["var"])).all()
    assert np.isfinite(np.asarray(y)[mask]).all()

    # flax module path
    bn = SyncBatchNorm(num_features=3, momentum=1.0)
    variables = bn.init(jax.random.PRNGKey(0), jnp.asarray(x))
    y2, upd = bn.apply(variables, jnp.asarray(x),
                       sample_mask=jnp.asarray(mask),
                       mutable=["batch_stats"])
    assert np.isfinite(np.asarray(upd["batch_stats"]["mean"])).all()
    assert np.isfinite(np.asarray(y2)[mask]).all()

    # all-padded: finite (degraded) stats, not NaN
    none = jnp.zeros((4,), bool)
    y3, new_s3 = bn_apply(p, s, jnp.asarray(x), train=True, momentum=1.0,
                          eps=1e-5, axis_name=None, sample_mask=none)
    assert np.isfinite(np.asarray(new_s3["mean"])).all()
    assert np.isfinite(np.asarray(new_s3["var"])).all()


@pytest.mark.slow
def test_convert_syncbn_model(data_mesh):
    """The functional convert_syncbn_model analog (reference
    apex/parallel/__init__.py:21-77): flax BatchNorm modules in the
    dataclass tree become SyncBatchNorm with the SAME param/collection
    layout (params transfer), training-mode outputs match flax BN on a
    single device, and the converted model's statistics synchronize
    across the data axis."""
    import flax.linen as fnn
    from apex_tpu.parallel import SyncBatchNorm, convert_syncbn_model

    model = fnn.Sequential([
        fnn.Dense(8),
        fnn.BatchNorm(use_running_average=False, momentum=0.9),
        fnn.Dense(4),
        fnn.BatchNorm(use_running_average=False, momentum=0.9),
    ])
    conv = convert_syncbn_model(model, axis_name="data")
    assert isinstance(conv.layers[1], SyncBatchNorm)
    assert conv.layers[1].momentum == pytest.approx(0.1)
    assert isinstance(conv.layers[3], SyncBatchNorm)

    x = jnp.asarray(np.random.default_rng(0).standard_normal((8, 16)),
                    jnp.float32)
    vars_flax = model.init(jax.random.PRNGKey(0), x)
    # identical param/collection tree -> flax-initialized variables drive
    # the converted model directly
    vars_conv = jax.tree.map(lambda a: a, vars_flax)
    y_flax, st_flax = model.apply(vars_flax, x, mutable=["batch_stats"])
    y_conv, st_conv = conv.apply(vars_conv, x, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(y_flax), np.asarray(y_conv),
                               rtol=2e-5, atol=2e-5)
    # running stats track the SOURCE module's (biased-variance, flax)
    # semantics so eval-mode behavior is preserved across conversion
    for a, b in zip(jax.tree.leaves(st_flax), jax.tree.leaves(st_conv)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    # cross-rank sync: per-rank batches with different statistics must
    # normalize with the GLOBAL moments (parity vs running the unsharded
    # batch through one device)
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    xg = jnp.asarray(np.random.default_rng(1).standard_normal((8, 16)),
                     jnp.float32) * 3.0 + 1.0

    def fwd(xs):
        y, _ = conv.apply(vars_conv, xs, mutable=["batch_stats"])
        return y

    y_sharded = shard_map(fwd, mesh=data_mesh, in_specs=P("data"),
                          out_specs=P("data"))(xg)
    # global reference: the ORIGINAL flax model over the unsharded batch
    # (training-mode BN over the full batch == synced per-shard BN)
    y_global, _ = model.apply(vars_flax, xg, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(y_sharded), np.asarray(y_global),
                               rtol=2e-5, atol=2e-5)


def test_convert_syncbn_model_guards():
    import flax.linen as fnn
    from apex_tpu.parallel import convert_syncbn_model

    with pytest.raises(NotImplementedError, match="axis"):
        convert_syncbn_model(fnn.Sequential(
            [fnn.BatchNorm(use_running_average=False, axis=1)]))
    with pytest.raises(NotImplementedError, match="eval-mode"):
        convert_syncbn_model(fnn.Sequential(
            [fnn.BatchNorm(use_running_average=True)]))
    # a compute/output dtype override has no SyncBatchNorm equivalent
    with pytest.raises(NotImplementedError, match="dtype"):
        convert_syncbn_model(fnn.Sequential(
            [fnn.BatchNorm(use_running_average=False,
                           dtype=jnp.bfloat16)]))
    with pytest.raises(NotImplementedError, match="use_fast_variance"):
        convert_syncbn_model(fnn.Sequential(
            [fnn.BatchNorm(use_running_average=False,
                           use_fast_variance=False)]))


def test_convert_syncbn_model_transfers_param_dtype():
    """A BN with non-default param_dtype must convert to a SyncBatchNorm
    initializing scale/bias in that dtype, not silently fp32."""
    import flax.linen as fnn
    from apex_tpu.parallel import convert_syncbn_model

    model = fnn.Sequential([
        fnn.BatchNorm(use_running_average=False,
                      param_dtype=jnp.bfloat16),
    ])
    conv = convert_syncbn_model(model)
    assert conv.layers[0].param_dtype == jnp.bfloat16
    x = jnp.ones((4, 8), jnp.float32)
    variables = conv.init(jax.random.PRNGKey(0), x)
    bn_params = variables["params"]["layers_0"]
    assert bn_params["scale"].dtype == jnp.bfloat16
    assert bn_params["bias"].dtype == jnp.bfloat16
    # running stats stay fp32 (flax BatchNorm keeps them fp32 too)
    assert variables["batch_stats"]["layers_0"]["mean"].dtype == jnp.float32
