"""Contrib extension suite.

Mirrors the per-extension tests under ``apex/contrib/test/`` (focal_loss vs
torchvision's sigmoid_focal_loss, index_mul_2d vs composed ops, group_norm
vs torch GroupNorm, transducer vs torchaudio-style reference DP, multihead
attn vs torch.nn.MultiheadAttention, groupbn vs torch BatchNorm, spatial
bottleneck vs its unsharded self, ASP mask invariants).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
from jax.sharding import PartitionSpec as P

os.environ.setdefault("APEX_TPU_FORCE_PALLAS", "interpret")

from apex_tpu.transformer import parallel_state  # noqa: E402
from apex_tpu.utils.sharding import shard_map  # noqa: E402


def _t(x):
    return torch.tensor(np.asarray(x))


class TestFocalLoss:
    def test_matches_torchvision_formula(self):
        from apex_tpu.contrib.focal_loss import focal_loss

        N, K, alpha, gamma = 12, 8, 0.24, 2.0
        x = jax.random.normal(jax.random.PRNGKey(0), (N, K))
        classes = jax.random.randint(jax.random.PRNGKey(1), (N,), 0, K)

        # torchvision sigmoid_focal_loss reimplemented as ground truth
        xt = _t(x).requires_grad_()
        y = torch.nn.functional.one_hot(_t(classes).long(), K).float()
        p = torch.sigmoid(xt)
        ce = torch.nn.functional.binary_cross_entropy_with_logits(
            xt, y, reduction="none")
        p_t = p * y + (1 - p) * (1 - y)
        ref = (ce * ((1 - p_t) ** gamma) * (alpha * y + (1 - alpha) * (1 - y))
               ).sum()
        ref.backward()

        loss, grads = jax.value_and_grad(
            lambda x: focal_loss(x, classes, jnp.ones(()), K, alpha, gamma))(x)
        np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
        # atol 1e-5: TPU sigmoid/pow transcendentals drift ~4e-6 vs torch
        np.testing.assert_allclose(np.asarray(grads), xt.grad.numpy(),
                                   rtol=2e-4, atol=1e-5)

    def test_label_smoothing_and_background(self):
        from apex_tpu.contrib.focal_loss import focal_loss

        x = jax.random.normal(jax.random.PRNGKey(0), (6, 4))
        classes = jnp.array([0, 1, -1, 3, -1, 2])  # -1 = background
        loss = focal_loss(x, classes, jnp.asarray(2.0), 4, 0.25, 2.0,
                          label_smoothing=0.1)
        assert np.isfinite(float(loss))


class TestIndexMul2d:
    def test_matches_composition_and_grads(self):
        from apex_tpu.contrib.index_mul_2d import index_mul_2d

        in1 = jax.random.normal(jax.random.PRNGKey(0), (10, 7))
        in2 = jax.random.normal(jax.random.PRNGKey(1), (16, 7))
        idx = jax.random.randint(jax.random.PRNGKey(2), (16,), 0, 10)
        out = index_mul_2d(in1, in2, idx)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(in1)[np.asarray(idx)]
                                   * np.asarray(in2), rtol=1e-6)
        # grad of in1 is a scatter-add over duplicate indices
        g1 = jax.grad(lambda a: jnp.sum(index_mul_2d(a, in2, idx)))(in1)
        ref = np.zeros_like(np.asarray(in1))
        np.add.at(ref, np.asarray(idx), np.asarray(in2))
        np.testing.assert_allclose(np.asarray(g1), ref, rtol=1e-5, atol=1e-6)


class TestGroupNorm:
    @pytest.mark.parametrize("act", ["", "swish"])
    def test_matches_torch_group_norm(self, act):
        from apex_tpu.contrib.group_norm import GroupNorm

        N, H, W, C, G = 2, 5, 6, 16, 4
        gn = GroupNorm(G, C, act=act)
        params = gn.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (N, H, W, C))
        out = gn.apply(params, x)

        tgn = torch.nn.GroupNorm(G, C)
        ref = tgn(_t(x).permute(0, 3, 1, 2)).permute(0, 2, 3, 1)
        if act:
            ref = ref * torch.sigmoid(ref)
        np.testing.assert_allclose(np.asarray(out), ref.detach().numpy(),
                                   rtol=1e-4, atol=1e-5)

    def test_bad_activation(self):
        from apex_tpu.contrib.group_norm import group_norm_nhwc

        with pytest.raises(ValueError):
            group_norm_nhwc(jnp.zeros((1, 2, 2, 4)), 2, None, None,
                            act="relu")


class TestTransducer:
    def test_joint_shapes_and_relu(self):
        from apex_tpu.contrib.transducer import TransducerJoint

        B, T, U, H = 2, 5, 4, 8
        f = jax.random.normal(jax.random.PRNGKey(0), (B, T, H))
        g = jax.random.normal(jax.random.PRNGKey(1), (B, U, H))
        out = TransducerJoint()(f, g)
        np.testing.assert_allclose(
            np.asarray(out[0, 1, 2]), np.asarray(f[0, 1] + g[0, 2]),
            rtol=1e-6)
        out_relu = TransducerJoint(relu=True)(f, g)
        assert float(jnp.min(out_relu)) >= 0.0

    def test_loss_matches_brute_force(self):
        from apex_tpu.contrib.transducer import transducer_loss

        # brute-force DP in numpy over log-probs
        B, T, U, K, blank = 2, 4, 3, 5, 0
        rng = np.random.default_rng(0)
        x = rng.normal(size=(B, T, U, K)).astype(np.float32)
        label = rng.integers(1, K, size=(B, U - 1))
        f_len = np.array([4, 3])
        y_len = np.array([2, 1])

        logp = np.asarray(jax.nn.log_softmax(jnp.asarray(x), axis=-1))

        def brute(b):
            T_, U_ = f_len[b], y_len[b] + 1
            alpha = np.full((T_, U_), -np.inf)
            alpha[0, 0] = 0.0
            for t in range(T_):
                for u in range(U_):
                    terms = []
                    if t > 0:
                        terms.append(alpha[t - 1, u]
                                     + logp[b, t - 1, u, blank])
                    if u > 0:
                        terms.append(alpha[t, u - 1]
                                     + logp[b, t, u - 1, label[b, u - 1]])
                    if terms:
                        alpha[t, u] = np.logaddexp.reduce(terms)
            return -(alpha[T_ - 1, U_ - 1] + logp[b, T_ - 1, U_ - 1, blank])

        ref = np.array([brute(b) for b in range(B)])
        loss = transducer_loss(jnp.asarray(x), jnp.asarray(label),
                               jnp.asarray(f_len), jnp.asarray(y_len), blank)
        np.testing.assert_allclose(np.asarray(loss), ref, rtol=1e-5,
                                   atol=1e-5)

    @pytest.mark.slow
    def test_loss_grads_finite(self):
        from apex_tpu.contrib.transducer import transducer_loss

        x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 3, 5))
        g = jax.grad(lambda x: jnp.sum(transducer_loss(
            x, jnp.array([[1, 2], [3, 4]]), jnp.array([4, 3]),
            jnp.array([2, 1]), 0)))(x)
        assert np.isfinite(np.asarray(g)).all()


class TestMultiheadAttn:
    def test_self_attn_matches_torch(self):
        from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn

        T, B, E, H = 6, 2, 16, 4
        attn = SelfMultiheadAttn(E, H, bias=True)
        params = attn.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (T, B, E))

        ref = torch.nn.MultiheadAttention(E, H, bias=True)
        with torch.no_grad():
            ref.in_proj_weight.copy_(_t(params["in_proj_weight"]))
            ref.in_proj_bias.copy_(_t(params["in_proj_bias"]))
            ref.out_proj.weight.copy_(_t(params["out_proj_weight"]))
            ref.out_proj.bias.copy_(_t(params["out_proj_bias"]))
        ref_out, _ = ref(_t(x), _t(x), _t(x), need_weights=False)

        out = attn.apply(params, x, is_training=False)
        np.testing.assert_allclose(np.asarray(out),
                                   ref_out.detach().numpy(),
                                   rtol=1e-4, atol=1e-5)

    def test_self_attn_key_padding_mask(self):
        from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn

        T, B, E, H = 5, 2, 8, 2
        attn = SelfMultiheadAttn(E, H, bias=True)
        params = attn.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (T, B, E))
        mask = jnp.zeros((B, T), bool).at[:, -2:].set(True)
        out_m = attn.apply(params, x, key_padding_mask=mask,
                           is_training=False)
        # masking the padded keys must equal attention over the prefix only
        out_prefix = attn.apply(params, x[:3], is_training=False)
        np.testing.assert_allclose(np.asarray(out_m[:3]),
                                   np.asarray(out_prefix),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.slow
    def test_encdec_and_norm_add(self):
        from apex_tpu.contrib.multihead_attn import EncdecMultiheadAttn

        Tq, Tk, B, E, H = 4, 6, 2, 8, 2
        attn = EncdecMultiheadAttn(E, H, bias=True, include_norm_add=True)
        params = attn.init(jax.random.PRNGKey(0))
        q = jax.random.normal(jax.random.PRNGKey(1), (Tq, B, E))
        k = jax.random.normal(jax.random.PRNGKey(2), (Tk, B, E))
        out = attn.apply(params, q, k, is_training=False)
        assert out.shape == (Tq, B, E)
        # residual add: zero attention output would return query unchanged;
        # with real params the difference from query must be bounded but
        # nonzero
        assert float(jnp.max(jnp.abs(out - q))) > 0


class TestGroupBN:
    def test_matches_torch_bn_training_and_eval(self):
        from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC

        N, H, W, C = 4, 5, 6, 8
        bn = BatchNorm2d_NHWC(C)
        params, state = bn.init(), bn.init_state()
        x = jax.random.normal(jax.random.PRNGKey(0), (N, H, W, C))

        tbn = torch.nn.BatchNorm2d(C)
        xt = _t(x).permute(0, 3, 1, 2)
        ref = tbn(xt).permute(0, 2, 3, 1)
        y, state = bn.apply(params, state, x, training=True)
        np.testing.assert_allclose(np.asarray(y), ref.detach().numpy(),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(state["running_mean"]),
                                   tbn.running_mean.numpy(), rtol=1e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(state["running_var"]),
                                   tbn.running_var.numpy(), rtol=1e-4,
                                   atol=1e-5)
        # eval mode uses running stats
        tbn.eval()
        ref_e = tbn(xt).permute(0, 2, 3, 1)
        y_e, _ = bn.apply(params, state, x, training=False)
        np.testing.assert_allclose(np.asarray(y_e), ref_e.detach().numpy(),
                                   rtol=1e-4, atol=1e-5)

    def test_fused_add_relu(self):
        from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC

        bn = BatchNorm2d_NHWC(4, fuse_relu=True)
        params, state = bn.init(), bn.init_state()
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 3, 4))
        z = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 3, 4))
        y, _ = bn.apply(params, state, x, z)
        assert float(jnp.min(y)) >= 0.0

    def test_group_stats_sync(self):
        from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()
        C = 4
        bn = BatchNorm2d_NHWC(C, bn_group=8, bn_group_axis="data")
        params, state = bn.init(), bn.init_state()
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 3, 3, C))

        def per_rank(x):
            y, _ = bn.apply(params, state, x, training=True)
            return y

        y = jax.jit(shard_map(per_rank, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"),
                                  check_vma=False))(x)
        # group-synced stats == full-batch BN
        y_ref, _ = bn.apply(params, state, x, training=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-5)
        parallel_state.destroy_model_parallel()


class TestBottleneck:
    @pytest.mark.slow
    def test_spatial_matches_unsharded(self):
        from apex_tpu.contrib.bottleneck import Bottleneck, SpatialBottleneck

        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel(
            context_parallel_size=4)
        N, H, W, C = 2, 16, 8, 8
        ref_block = Bottleneck(C, 4, C)
        params = ref_block.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (N, H, W, C))
        ref = ref_block.apply(params, x)

        sp = SpatialBottleneck(C, 4, C, spatial_axis="context")
        out = jax.jit(shard_map(
            lambda p, x: sp.apply(p, x), mesh=mesh,
            in_specs=(ref_block.spec(), P(None, "context")),
            out_specs=P(None, "context"),
            check_vma=False))(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
        parallel_state.destroy_model_parallel()

    def test_downsample_path(self):
        from apex_tpu.contrib.bottleneck import Bottleneck

        block = Bottleneck(8, 4, 16, stride=2)
        params = block.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 8))
        out = block.apply(params, x)
        assert out.shape == (2, 4, 4, 16)


class TestASP:
    def test_mask_2to4_invariants(self):
        from apex_tpu.contrib.sparsity import compute_sparse_mask_2to4

        w = jax.random.normal(jax.random.PRNGKey(0), (32, 64))
        mask = compute_sparse_mask_2to4(w)
        groups = np.asarray(mask).reshape(32, 16, 4)
        assert (groups.sum(-1) == 2).all()
        # kept entries are the 2 largest magnitudes per group
        wg = np.abs(np.asarray(w)).reshape(32, 16, 4)
        kept = np.where(groups, wg, -1.0)
        dropped = np.where(~groups, wg, np.inf)
        assert (kept.max(-1) >= dropped.min(-1) - 1e-12).all()

    def test_asp_workflow(self):
        from apex_tpu.contrib.sparsity import ASP

        params = {
            "dense": {"weight": jax.random.normal(jax.random.PRNGKey(0),
                                                  (64, 64)),
                      "bias": jnp.ones((64,))},
        }
        asp = ASP()
        asp.init_model_for_pruning(params)
        masks = asp.compute_sparse_masks(params)
        pruned = asp.apply_masks(params, masks)
        # weight pruned to 50%, bias untouched
        assert float(jnp.mean((pruned["dense"]["weight"] != 0))) == 0.5
        np.testing.assert_array_equal(np.asarray(pruned["dense"]["bias"]),
                                      np.ones(64))


class TestFMHA:
    def test_varlen_matches_per_sample(self):
        from apex_tpu.contrib.fmha import FMHA

        B, S, H, E = 3, 8, 2, 8
        fmha = FMHA(num_attention_heads=H, hidden_size=E)
        qkv = jax.random.normal(jax.random.PRNGKey(0), (B, S, 3 * E))
        seqlens = jnp.array([8, 5, 3])
        out = fmha(qkv, seqlens)
        # each sample equals dense attention over its true length
        for b, L in enumerate([8, 5, 3]):
            sub = fmha(qkv[b:b + 1, :L], jnp.array([L]))
            np.testing.assert_allclose(np.asarray(out[b, :L]),
                                       np.asarray(sub[0]),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(out[b, L:]), 0.0)


class TestConvBiasReLU:
    """apex/contrib/conv_bias_relu parity: epilogue math vs unfused ops."""

    def _data(self):
        k = jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(k, 3)
        x = jax.random.normal(k1, (2, 8, 8, 4))
        w = jax.random.normal(k2, (3, 3, 4, 6)) * 0.1
        b = jax.random.normal(k3, (6,))
        return x, w, b

    def test_conv_bias_relu(self):
        from apex_tpu.contrib.conv_bias_relu import ConvBias, ConvBiasReLU
        from apex_tpu.utils.conv import conv_nhwc

        x, w, b = self._data()
        ref = conv_nhwc(x, w) + b
        np.testing.assert_allclose(np.asarray(ConvBias(x, w, b)),
                                   np.asarray(ref), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(ConvBiasReLU(x, w, b)),
                                   np.maximum(np.asarray(ref), 0), rtol=1e-6)

    def test_conv_bias_mask_relu(self):
        from apex_tpu.contrib.conv_bias_relu import ConvBiasMaskReLU
        from apex_tpu.utils.conv import conv_nhwc

        x, w, b = self._data()
        mask = (jax.random.uniform(jax.random.PRNGKey(7),
                                   (2, 8, 8, 6)) > 0.5).astype(x.dtype)
        ref = np.maximum(np.asarray((conv_nhwc(x, w) + b) * mask), 0)
        np.testing.assert_allclose(
            np.asarray(ConvBiasMaskReLU(x, w, b, mask)), ref, rtol=1e-6)

    def test_frozen_scale_bias(self):
        from apex_tpu.contrib.conv_bias_relu import ConvFrozenScaleBiasReLU
        from apex_tpu.utils.conv import conv_nhwc

        x, w, _ = self._data()
        scale = jnp.full((6,), 1.5)
        bias = jnp.full((6,), -0.25)
        ref = np.maximum(np.asarray(conv_nhwc(x, w) * scale + bias), 0)
        np.testing.assert_allclose(
            np.asarray(ConvFrozenScaleBiasReLU(x, w, scale, bias)), ref,
            rtol=1e-6)

    def test_grad_flows(self):
        from apex_tpu.contrib.conv_bias_relu import ConvBiasReLU

        x, w, b = self._data()
        g = jax.grad(lambda w: jnp.sum(ConvBiasReLU(x, w, b)))(w)
        assert np.isfinite(np.asarray(g)).all()


class TestFusedAdamSWA:
    """apex/contrib/openfold_triton FusedAdamSWA semantics
    (fused_adam_swa.py:102-112)."""

    def _setup(self):
        params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
        grads = {"w": jnp.full((4, 4), 0.1), "b": jnp.full((4,), 0.2)}
        return params, grads

    def test_first_step_copies_params(self):
        from apex_tpu.contrib.openfold import FusedAdamSWA

        params, grads = self._setup()
        opt = FusedAdamSWA(lr=1e-2, swa_decay_rate=0.9)
        state = opt.init(params)
        new_p, new_s = opt.step(grads, params, state)
        assert int(new_s["n_averaged"]) == 1
        # n_averaged was 0 -> SWA buffer = stepped params exactly
        jax.tree.map(lambda s, p: np.testing.assert_allclose(s, p),
                     new_s["swa_params"], new_p)

    def test_ema_after_first(self):
        from apex_tpu.contrib.openfold import FusedAdamSWA

        params, grads = self._setup()
        decay = 0.8
        opt = FusedAdamSWA(lr=1e-2, swa_decay_rate=decay)
        state = opt.init(params)
        p1, s1 = opt.step(grads, params, state)
        p2, s2 = opt.step(grads, p1, s1)
        expect = jax.tree.map(
            lambda swa, p: swa + (1 - decay) * (p - swa),
            s1["swa_params"], p2)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
            s2["swa_params"], expect)
        assert int(s2["n_averaged"]) == 2

    def test_adam_math_matches_fused_adam(self):
        from apex_tpu.contrib.openfold import FusedAdamSWA
        from apex_tpu.optimizers import FusedAdam

        params, grads = self._setup()
        swa = FusedAdamSWA(lr=1e-2, swa_decay_rate=0.9, weight_decay=0.01)
        ref = FusedAdam(lr=1e-2, weight_decay=0.01)
        ps, ss = params, swa.init(params)
        pr, sr = params, ref.init(params)
        for _ in range(3):
            ps, ss = swa.step(grads, ps, ss)
            pr, sr = ref.step(grads, pr, sr)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
                     ps, pr)

    def test_found_inf_freezes_swa(self):
        from apex_tpu.contrib.openfold import FusedAdamSWA

        params, grads = self._setup()
        opt = FusedAdamSWA(lr=1e-2)
        state = opt.init(params)
        new_p, new_s = opt.step(grads, params, state,
                                found_inf=jnp.asarray(True))
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b),
                     new_p, params)
        assert int(new_s["n_averaged"]) == 0
        jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b),
                     new_s["swa_params"], state["swa_params"])
