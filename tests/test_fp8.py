"""fp8 delayed-scaling scaffolding tests.

The reference's fp8 story is the AMAX reduction process group
(``apex/transformer/parallel_state.py:280-292``, TP x DP per pipeline
stage); here that group is a set of mesh axes and the reduction is a
``lax.pmax``. These tests pin (a) the mesh-axis translation — every rank in
the amax group computes the identical scale, pipeline stages stay
independent — and (b) the delayed-scaling recipe math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.amp import fp8
from apex_tpu.transformer import parallel_state
from apex_tpu.utils.sharding import shard_map


class TestRecipe:
    def test_scale_from_history_max(self):
        state = fp8.init_fp8_state(["w"], fp8.Fp8Recipe(amax_history_len=4))
        r = fp8.Fp8Recipe(amax_history_len=4)
        for amax in (2.0, 8.0, 4.0):
            state = fp8.update_fp8_state(
                state, {"w": jnp.asarray(amax)}, r, axis_names=())
        # window max = 8 -> scale = 448 / 8
        np.testing.assert_allclose(float(state["w"]["scale"]), 448.0 / 8.0)
        np.testing.assert_allclose(
            np.asarray(state["w"]["amax_history"])[:3], [4.0, 8.0, 2.0])

    def test_most_recent_and_margin(self):
        r = fp8.Fp8Recipe(amax_history_len=4, amax_compute_algo="most_recent",
                          margin=1)
        state = fp8.init_fp8_state(["w"], r)
        for amax in (8.0, 2.0):
            state = fp8.update_fp8_state(state, {"w": jnp.asarray(amax)}, r,
                                         axis_names=())
        np.testing.assert_allclose(float(state["w"]["scale"]),
                                   448.0 / (2.0 * 2.0))

    def test_zero_amax_keeps_scale(self):
        r = fp8.Fp8Recipe(amax_history_len=2)
        state = fp8.init_fp8_state(["w"], r)
        state = fp8.update_fp8_state(state, {"w": jnp.asarray(0.0)}, r,
                                     axis_names=())
        np.testing.assert_allclose(float(state["w"]["scale"]), 1.0)

    def test_inf_amax_keeps_scale_and_recovers(self):
        # an overflow step (amax = inf) must neither zero the scale (NaN
        # dequantize) nor pin the window at inf
        r = fp8.Fp8Recipe(amax_history_len=2)
        state = fp8.init_fp8_state(["w"], r)
        state = fp8.update_fp8_state(state, {"w": jnp.asarray(4.0)}, r,
                                     axis_names=())
        s_before = float(state["w"]["scale"])
        state = fp8.update_fp8_state(state, {"w": jnp.asarray(jnp.inf)}, r,
                                     axis_names=())
        assert float(state["w"]["scale"]) == s_before
        y = fp8.dequantize(fp8.quantize(jnp.ones(4), state["w"]["scale"]),
                           state["w"]["scale"])
        assert np.isfinite(np.asarray(y)).all()
        # window rolls the sanitized 0 out; next finite amax takes over
        state = fp8.update_fp8_state(state, {"w": jnp.asarray(2.0)}, r,
                                     axis_names=())
        state = fp8.update_fp8_state(state, {"w": jnp.asarray(2.0)}, r,
                                     axis_names=())
        np.testing.assert_allclose(float(state["w"]["scale"]), 448.0 / 2.0)

    def test_bwd_dtype_range(self):
        r = fp8.Fp8Recipe(amax_history_len=1)
        state = fp8.init_fp8_state(["g"], r)
        state = fp8.update_fp8_state(state, {"g": jnp.asarray(2.0)}, r,
                                     axis_names=(),
                                     dtypes={"g": r.bwd_dtype})
        np.testing.assert_allclose(float(state["g"]["scale"]), 57344.0 / 2.0)

    def test_qdq_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 64)) * 3.0
        scale = jnp.asarray(448.0 / float(jnp.max(jnp.abs(x))))
        y = fp8.qdq(x, scale, fp8.E4M3)
        assert y.dtype == x.dtype
        # e4m3 has 3 mantissa bits -> relative step 2^-3; scaled to amax
        err = np.max(np.abs(np.asarray(y - x)))
        assert err <= float(jnp.max(jnp.abs(x))) / 8.0
        # and fp8 rounding genuinely happened
        assert not np.allclose(np.asarray(y), np.asarray(x), atol=1e-6)


class TestAmaxReductionMesh:
    def test_axes_exclude_pipeline(self):
        axes = parallel_state.amax_reduction_axes()
        assert "pipeline" not in axes
        assert set(axes) == {"data", "context", "tensor"}
        assert "pipeline" in parallel_state.amax_reduction_axes(
            include_pipeline=True)

    def test_scales_agree_within_group_and_differ_across_stages(self):
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2, pipeline_model_parallel_size=2)
        r = fp8.Fp8Recipe(amax_history_len=2)

        def per_rank(x):
            # per-rank distinct activations; stages see different tensors
            state = fp8.init_fp8_state(["h"], r)
            state = fp8.update_fp8_state(state, {"h": fp8.compute_amax(x)}, r)
            return state["h"]["scale"].reshape(1, 1, 1)

        # amax on (dp, pp, tp) rank = crafted so the group max differs per
        # pipeline stage: stage 0 sees max 4, stage 1 sees max 16
        x = jnp.asarray([[[1.0, 4.0], [2.0, 16.0]],
                         [[3.0, 2.0], [8.0, 1.0]]])   # [dp, pp, tp]
        scales = jax.jit(shard_map(
            per_rank, mesh=mesh,
            in_specs=P("data", "pipeline", "tensor"),
            out_specs=P("data", "pipeline", "tensor"),
            check_vma=False))(x[..., None])
        scales = np.asarray(scales).reshape(2, 2, 2)
        # within each pipeline stage: all dp x tp ranks agree
        np.testing.assert_allclose(scales[:, 0, :], 448.0 / 4.0)
        np.testing.assert_allclose(scales[:, 1, :], 448.0 / 16.0)
        parallel_state.destroy_model_parallel()

    def test_unsharded_is_identity(self):
        a = {"w": jnp.asarray(3.0)}
        out = fp8.reduce_amaxes(a, ("data", "tensor"))
        np.testing.assert_allclose(float(out["w"]), 3.0)


class TestMultiSliceMesh:
    def test_dcn_major_data_axis(self):
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2, num_slices=2)
        assert parallel_state.get_num_slices() == 2
        assert parallel_state.get_data_parallel_world_size() == 4
        assert parallel_state.get_data_parallel_dcn_size() == 2
        assert parallel_state.get_data_parallel_ici_size() == 2
        # model-axis groups never cross the slice boundary: with 8 devices
        # in enumeration order, slice = id // 4
        devs = mesh.devices          # [dp, pp, cp, tp]
        per_slice = 4
        for d in range(devs.shape[0]):
            block = devs[d].reshape(-1)
            slices = {dev.id // per_slice for dev in block}
            assert len(slices) == 1, (
                f"data coord {d} spans slices {slices}")
        # DCN-major: data coords 0,1 on slice 0; 2,3 on slice 1
        slice_of = [devs[d, 0, 0, 0].id // per_slice
                    for d in range(devs.shape[0])]
        assert slice_of == [0, 0, 1, 1]
        parallel_state.destroy_model_parallel()

    def test_model_axes_cannot_cross_dcn(self):
        import pytest

        if len(jax.devices()) < 8:
            pytest.skip("the divisibility check fires before the DCN guard "
                        "on small device counts")
        parallel_state.destroy_model_parallel()
        with pytest.raises(RuntimeError, match="DCN"):
            parallel_state.initialize_model_parallel(
                tensor_model_parallel_size=8, num_slices=2)
        parallel_state.destroy_model_parallel()

    def test_indivisible_slices_rejected(self):
        import pytest

        parallel_state.destroy_model_parallel()
        with pytest.raises(RuntimeError, match="num_slices"):
            parallel_state.initialize_model_parallel(num_slices=3)
        parallel_state.destroy_model_parallel()


class TestFp8Dense:
    """The delayed-scaling matmul hook: scales trail the data one step,
    gradients pass straight-through the quantizer."""

    def test_trains_and_scales_adapt(self):
        r = fp8.Fp8Recipe(amax_history_len=4)
        w = jax.random.normal(jax.random.PRNGKey(0), (32, 16)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32)) * 2.0
        y_t = jax.random.normal(jax.random.PRNGKey(2), (64, 16))
        state = fp8.init_fp8_state(["x", "w"], r)

        @jax.jit
        def step(w, state):
            def loss_fn(w):
                y, new_state = fp8.fp8_dense(x, w, state, recipe=r,
                                             axis_names=())
                return jnp.mean((y - y_t) ** 2), new_state
            (loss, new_state), g = jax.value_and_grad(
                loss_fn, has_aux=True)(w)
            return w - 0.05 * g, new_state, loss

        losses = []
        for _ in range(25):
            w, state, loss = step(w, state)
            losses.append(float(loss))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.7
        # scales adapted to the observed amaxes (no longer the init 1.0)
        assert float(state["x"]["scale"]) != 1.0
        assert float(state["w"]["scale"]) != 1.0

    def test_matches_unquantized_within_fp8_tolerance(self):
        r = fp8.Fp8Recipe(amax_history_len=1)
        x = jax.random.normal(jax.random.PRNGKey(3), (32, 64))
        w = jax.random.normal(jax.random.PRNGKey(4), (64, 32)) * 0.1
        state = fp8.init_fp8_state(["x", "w"], r)
        # one warmup call installs data-driven scales
        _, state = fp8.fp8_dense(x, w, state, recipe=r, axis_names=())
        y, _ = fp8.fp8_dense(x, w, state, recipe=r, axis_names=())
        ref = x @ w
        rel = float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref)))
        assert rel < 0.1          # e4m3 rounding, not garbage

    def test_straight_through_gradient(self):
        r = fp8.Fp8Recipe(amax_history_len=1)
        x = jnp.ones((4, 8))
        w = jnp.full((8, 2), 0.5)
        state = fp8.init_fp8_state(["x", "w"], r)
        _, state = fp8.fp8_dense(x, w, state, recipe=r, axis_names=())

        def loss_fn(w):
            y, _ = fp8.fp8_dense(x, w, state, recipe=r, axis_names=())
            return jnp.sum(y)

        g = jax.grad(loss_fn)(w)
        # d(sum(xq @ wq))/dw ~= x^T @ ones through the straight-through path
        ref = jnp.ones((4, 8)).T @ jnp.ones((4, 2))
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref),
                                   rtol=0.1)

    @pytest.mark.slow
    def test_backward_e5m2_rounding_applied(self):
        # the cotangent path must show e5m2 quantization effects (current
        # scaling): grads through fp8_dense differ from exact bf16 grads
        # by bounded rounding, and disabling bwd_dtype recovers exactness
        x = jax.random.normal(jax.random.PRNGKey(5), (16, 32))
        w = jax.random.normal(jax.random.PRNGKey(6), (32, 8)) * 0.1
        r = fp8.Fp8Recipe(amax_history_len=1)
        state = fp8.init_fp8_state(["x", "w"], r)
        _, state = fp8.fp8_dense(x, w, state, recipe=r, axis_names=())
        ct = jax.random.normal(jax.random.PRNGKey(7), (16, 8))

        def loss_fn(w):
            y, _ = fp8.fp8_dense(x, w, state, recipe=r, axis_names=())
            return jnp.sum(y * ct)

        g = jax.grad(loss_fn)(w)
        # reference: same fwd qdq operands, exact backward
        xq = fp8.qdq(x, state["x"]["scale"])
        ref = jax.grad(lambda w: jnp.sum(
            (xq @ fp8.qdq(w, state["w"]["scale"])) * ct))(w)
        rel = float(jnp.max(jnp.abs(g - ref)) / jnp.max(jnp.abs(ref)))
        assert rel < 0.4             # e5m2 (2 mantissa bits), not garbage
        assert rel > 0.0             # and genuinely quantized


class TestNativeFp8Dispatch:
    """Native fp8 dot_general path (round 3): same delayed-scaling state,
    the dot runs ON fp8 storage dtypes instead of the qdq simulation.
    Parity bounds reflect only accumulation-dtype differences (the native
    path accumulates in fp32; the qdq path matmuls dequantized values in
    the input dtype)."""

    def test_probe_and_forward_parity(self):
        assert fp8.native_fp8_dot_supported() in (True, False)
        if not fp8.native_fp8_dot_supported():
            pytest.skip("backend cannot run fp8 dot_general")
        r = fp8.Fp8Recipe(amax_history_len=1)
        x = jax.random.normal(jax.random.PRNGKey(0), (32, 64))
        w = jax.random.normal(jax.random.PRNGKey(1), (64, 32)) * 0.1
        state = fp8.init_fp8_state(["x", "w"], r)
        _, state = fp8.fp8_dense(x, w, state, recipe=r, axis_names=())
        y_n, st_n = fp8.fp8_dense(x, w, state, recipe=r, axis_names=(),
                                  native=True)
        y_q, st_q = fp8.fp8_dense(x, w, state, recipe=r, axis_names=(),
                                  native=False)
        np.testing.assert_allclose(np.asarray(y_n), np.asarray(y_q),
                                   rtol=2e-3, atol=2e-3)
        # the state machinery is shared: identical updates
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b)), st_n, st_q)

    @pytest.mark.slow
    def test_gradient_parity_vs_unquantized(self):
        """The two backwards round in different places (native quantizes
        the cotangent BEFORE its GEMMs — the TE order; qdq rounds the
        already-computed grads), so they are not bitwise-comparable: both
        must instead sit within e5m2-level error of the unquantized
        reference gradients."""
        if not fp8.native_fp8_dot_supported():
            pytest.skip("backend cannot run fp8 dot_general")
        r = fp8.Fp8Recipe(amax_history_len=1)
        x = jax.random.normal(jax.random.PRNGKey(2), (16, 32))
        w = jax.random.normal(jax.random.PRNGKey(3), (32, 16)) * 0.2
        state = fp8.init_fp8_state(["x", "w"], r)
        _, state = fp8.fp8_dense(x, w, state, recipe=r, axis_names=())

        def loss(native):
            def f(x, w):
                y, _ = fp8.fp8_dense(x, w, state, recipe=r, axis_names=(),
                                     native=native)
                return jnp.sum(y ** 2)
            return f

        g_ref = jax.grad(lambda x, w: jnp.sum((x @ w) ** 2),
                         argnums=(0, 1))(x, w)
        for native in (True, False):
            for g, ref in zip(jax.grad(loss(native), argnums=(0, 1))(x, w),
                              g_ref):
                rel = float(jnp.max(jnp.abs(g - ref))
                            / jnp.max(jnp.abs(ref)))
                assert rel < 0.4, (native, rel)   # e5m2, not garbage

    def test_native_trains(self):
        if not fp8.native_fp8_dot_supported():
            pytest.skip("backend cannot run fp8 dot_general")
        r = fp8.Fp8Recipe(amax_history_len=4)
        x = jax.random.normal(jax.random.PRNGKey(5), (64, 32))
        w0 = jax.random.normal(jax.random.PRNGKey(6), (32, 8)) * 0.3
        y_t = jnp.tanh(x @ w0)
        w = jax.random.normal(jax.random.PRNGKey(7), (32, 8)) * 0.3
        state = fp8.init_fp8_state(["x", "w"], r)

        @jax.jit
        def step(w, state):
            def loss_fn(w):
                y, new_state = fp8.fp8_dense(x, w, state, recipe=r,
                                             axis_names=(), native=True)
                return jnp.mean((y - y_t) ** 2), new_state
            (loss, new_state), g = jax.value_and_grad(
                loss_fn, has_aux=True)(w)
            return w - 0.05 * g, new_state, loss

        losses = []
        for _ in range(25):
            w, state, loss = step(w, state)
            losses.append(float(loss))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.7
