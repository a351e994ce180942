"""Mixture-of-Experts / expert-parallelism tests.

The reference has no MoE (SURVEY.md §2.2 EP: absent) — this is
exceeds-reference capability, so correctness is established internally:
expert-parallel dispatch over the mesh must match the dense (unsharded)
dispatch bit-for-bit given the same params, and the routing machinery must
satisfy its contracts (capacity drops, weight normalization, aux-loss
sensitivity to imbalance).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.shard_map import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.moe import MoEConfig, SwitchMLP
from apex_tpu.utils.sharding import shard_map


def _cfg(**kw):
    d = dict(hidden_size=16, ffn_hidden_size=32, num_experts=8,
             capacity_factor=2.0, expert_axis=None)
    d.update(kw)
    return MoEConfig(**d)


def _x(s=6, b=4, h=16, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (s, b, h))


class TestDense:
    def test_shapes_and_finite(self):
        moe = SwitchMLP(_cfg())
        params = moe.init(jax.random.PRNGKey(0))
        y, aux = jax.jit(lambda p, x: moe.apply(p, x))(params, _x())
        assert y.shape == (6, 4, 16)
        assert np.isfinite(np.asarray(y)).all()
        assert float(aux) > 0

    def test_top1_output_is_single_expert_ffn(self):
        """With huge capacity, each token's output equals its top-1 expert's
        FFN applied to it (weight 1.0 after top-1 renorm)."""
        cfg = _cfg(capacity_factor=8.0, top_k=1)
        moe = SwitchMLP(cfg)
        params = moe.init(jax.random.PRNGKey(0))
        x = _x()
        y, _ = moe.apply(params, x)
        x2d = x.reshape(-1, 16)
        logits = x2d @ params["router"]
        top = jnp.argmax(logits, axis=-1)
        for t in range(x2d.shape[0]):
            e = int(top[t])
            hmid = jax.nn.gelu(x2d[t] @ params["w_in"][e] + params["b_in"][e])
            ref = hmid @ params["w_out"][e] + params["b_out"][e]
            w = jax.nn.softmax(logits[t])[e]  # top-1 prob used as scale
            np.testing.assert_allclose(
                np.asarray(y.reshape(-1, 16)[t]), np.asarray(ref * w),
                rtol=1e-4, atol=1e-5)

    def test_top2_weights_normalized(self):
        cfg = _cfg(top_k=2, capacity_factor=8.0)
        moe = SwitchMLP(cfg)
        params = moe.init(jax.random.PRNGKey(0))
        y, _ = moe.apply(params, _x())
        assert np.isfinite(np.asarray(y)).all()

    def test_capacity_drops_tokens(self):
        """capacity_factor tiny -> most tokens dropped -> output mostly 0."""
        cfg = _cfg(capacity_factor=0.01)
        moe = SwitchMLP(cfg)
        params = moe.init(jax.random.PRNGKey(0))
        y, _ = moe.apply(params, _x(s=16, b=8))
        zero_rows = np.mean(
            np.all(np.asarray(y.reshape(-1, 16)) == 0.0, axis=1))
        assert zero_rows > 0.5

    def test_aux_loss_prefers_balance(self):
        """A router forced to one expert must have higher aux loss than the
        learned (roughly uniform at init) router."""
        cfg = _cfg()
        moe = SwitchMLP(cfg)
        params = moe.init(jax.random.PRNGKey(0))
        x = _x(s=16, b=8)
        _, aux_uniform = moe.apply(params, x)
        biased = dict(params)
        bias = jnp.zeros((16, 8)).at[:, 0].set(50.0)
        biased["router"] = params["router"] + bias
        _, aux_collapsed = moe.apply(biased, x)
        assert float(aux_collapsed) > float(aux_uniform) * 2

    def test_grads_flow_to_experts_and_router(self):
        moe = SwitchMLP(_cfg())
        params = moe.init(jax.random.PRNGKey(0))
        x = _x()

        def loss(p):
            y, aux = moe.apply(p, x)
            return jnp.mean(y ** 2) + 0.01 * aux

        g = jax.grad(loss)(params)
        assert float(jnp.sum(jnp.abs(g["router"]))) > 0
        assert float(jnp.sum(jnp.abs(g["w_in"]))) > 0


class TestDenseDropFree:
    """The >512-token drop-free path (round 5): dense per-expert scan with
    O(T*ffn) memory instead of the [T, E, cap] one-hots (quadratic at
    cap = tokens — the review's 32k/64-expert prefill example is ~275 GB)."""

    def test_matches_dropless_capacity_path(self):
        # capacity_factor = E/top_k => cap = tokens on the factor path too,
        # so both paths are drop-free and must agree
        cfg = _cfg(num_experts=4, top_k=2, capacity_factor=2.0)
        moe = SwitchMLP(cfg)
        params = moe.init(jax.random.PRNGKey(0))
        x = _x(s=48, b=16)                      # 768 tokens > 512 gate
        y_dense, aux_d = jax.jit(
            lambda p, x: moe.apply(p, x, drop_free=True))(params, x)
        y_cap, aux_c = jax.jit(lambda p, x: moe.apply(p, x))(params, x)
        np.testing.assert_allclose(np.asarray(y_dense), np.asarray(y_cap),
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(float(aux_d), float(aux_c), rtol=1e-5)

    def test_ep_dense_drop_free_matches_unsharded(self):
        """Tokens SHARDED over the expert axis (EP rides DP — each rank
        holds different tokens): the dense path must gather tokens before
        its expert scan and slice its shard back after the psum; a
        shard-local psum would silently sum different ranks' tokens (r5
        review). Per-rank tokens exceed the 512 dense gate."""
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()   # data = 8
        dense = SwitchMLP(_cfg(top_k=2, expert_axis=None))
        ep = SwitchMLP(_cfg(top_k=2, expert_axis="data"))
        params = dense.init(jax.random.PRNGKey(0))
        x = _x(s=80, b=64)                # 5120 tokens = 640/rank > 512
        y_ref, _ = dense.apply(params, x, drop_free=True)
        y, _ = jax.jit(shard_map(
            lambda p, x: ep.apply(p, x, drop_free=True), mesh=mesh,
            in_specs=(ep.spec(), P(None, "data")),
            out_specs=(P(None, "data"), P()), check_vma=False))(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-6)
        parallel_state.destroy_model_parallel()

    def test_gated_activation_dense_path(self):
        cfg = _cfg(num_experts=4, top_k=1, activation="swiglu")
        moe = SwitchMLP(cfg)
        params = moe.init(jax.random.PRNGKey(0))
        assert "b_in" not in params             # gated experts bias-free
        x = _x(s=48, b=16)
        y, aux = jax.jit(
            lambda p, x: moe.apply(p, x, drop_free=True))(params, x)
        assert np.isfinite(np.asarray(y)).all()


class TestExpertParallel:
    def test_ep_matches_dense(self):
        """EP over the data axis == dense dispatch, same params/inputs."""
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()   # data = 8
        dense = SwitchMLP(_cfg(expert_axis=None))
        ep = SwitchMLP(_cfg(expert_axis="data"))
        params = dense.init(jax.random.PRNGKey(0))
        x = _x(s=6, b=4)

        y_ref, aux_ref = dense.apply(params, x)

        def per_rank(p, x):
            y, aux = ep.apply(p, x)
            return y, aux.reshape(1)

        y, aux = jax.jit(shard_map(
            per_rank, mesh=mesh,
            in_specs=(ep.spec(), P()),
            out_specs=(P(), P("data")), check_vma=False))(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(float(aux[0]), float(aux_ref), rtol=1e-5)
        parallel_state.destroy_model_parallel()

    @pytest.mark.slow
    def test_ep_top2_matches_dense(self):
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()
        dense = SwitchMLP(_cfg(expert_axis=None, top_k=2))
        ep = SwitchMLP(_cfg(expert_axis="data", top_k=2))
        params = dense.init(jax.random.PRNGKey(3))
        x = _x(s=4, b=4, seed=7)
        y_ref, _ = dense.apply(params, x)
        y, _ = jax.jit(shard_map(
            lambda p, x: ep.apply(p, x),
            mesh=mesh, in_specs=(ep.spec(), P()),
            out_specs=(P(), P()), check_vma=False))(params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-6)
        parallel_state.destroy_model_parallel()

    def test_ep_requires_divisible_experts(self):
        parallel_state.destroy_model_parallel()
        mesh = parallel_state.initialize_model_parallel()
        ep = SwitchMLP(_cfg(expert_axis="data", num_experts=6))
        params = SwitchMLP(_cfg(expert_axis=None, num_experts=6)).init(
            jax.random.PRNGKey(0))
        with pytest.raises(Exception):
            jax.jit(shard_map(
                lambda p, x: ep.apply(p, x), mesh=mesh,
                in_specs=(ep.spec(), P()), out_specs=(P(), P()),
                check_vma=False))(params, _x())
        parallel_state.destroy_model_parallel()


class TestExpertParallelTraining:
    """Whole-model EP-over-DP training: expert params sharded over the data
    axis must train identically to the dense unsharded model — pins the
    spec-aware gradient sync (expert grads divided by the data-axis size
    instead of pmean'd, which would mix different experts)."""

    @pytest.mark.slow  # whole-model EP-vs-dense parity: slow-tier class
    def test_ep_training_matches_dense(self):
        from apex_tpu.models import GPTModel, TransformerConfig
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.training import make_train_step

        cfg = dict(num_layers=2, hidden_size=32, num_attention_heads=4,
                   vocab_size=64, max_position_embeddings=32,
                   hidden_dropout=0.0, attention_dropout=0.0,
                   num_moe_experts=8,       # divisible by the dp=8 axis
                   moe_capacity_factor=8.0,   # = num_experts -> no drops
                   # the aux loss is a nonlinear function of per-shard token
                   # statistics, so its pmean differs from the global-batch
                   # value; zero it for exact loss parity
                   moe_aux_loss_weight=0.0)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 64)

        # dense reference, unsharded
        parallel_state.destroy_model_parallel()
        ref_model = GPTModel(TransformerConfig(**cfg))
        params = ref_model.init(jax.random.PRNGKey(0))
        opt = FusedAdam(lr=1e-2)
        p_ref, s_ref = params, opt.init(params)
        ref_losses = []

        @jax.jit
        def ref_step(p, s):
            loss, g = jax.value_and_grad(
                lambda p: ref_model.apply(p, tokens, labels))(p)
            p, s = opt.step(g, p, s)
            return p, s, loss

        for _ in range(3):
            p_ref, s_ref, loss = ref_step(p_ref, s_ref)
            ref_losses.append(float(loss))

        # EP over the data axis on the 8-device mesh
        mesh = parallel_state.initialize_model_parallel()   # dp = 8
        ep_model = GPTModel(TransformerConfig(**cfg, moe_expert_axis="data"))
        opt2 = FusedAdam(lr=1e-2)
        p_ep, s_ep = params, opt2.init(params)
        step = make_train_step(
            lambda p, b, rng: ep_model.apply(p, b["tokens"], b["labels"]),
            opt2, mesh, ep_model.spec(),
            {"tokens": P("data"), "labels": P("data")},
            opt_state_spec=opt2.state_spec(params, ep_model.spec()))
        ep_losses = []
        for _ in range(3):
            p_ep, s_ep, loss = step(p_ep, s_ep,
                                    {"tokens": tokens, "labels": labels},
                                    None)
            ep_losses.append(float(loss))
        np.testing.assert_allclose(ep_losses, ref_losses, rtol=2e-5)
        parallel_state.destroy_model_parallel()

    def test_zero_rejects_data_sharded_params(self):
        from apex_tpu.optimizers import DistributedFusedAdam

        parallel_state.destroy_model_parallel()
        parallel_state.initialize_model_parallel()
        params = {"w": jnp.zeros((8, 4))}
        opt = DistributedFusedAdam(lr=1e-3, num_shards=8)
        with pytest.raises(NotImplementedError, match="ZeRO axis"):
            opt.init(params, {"w": P("data", None)})
        parallel_state.destroy_model_parallel()


class TestMoETransformer:
    """MoE wired into the transformer stack (TransformerConfig.num_moe_experts)."""

    def _model(self, **kw):
        from apex_tpu.models import GPTModel, TransformerConfig

        cfg = TransformerConfig(
            num_layers=2, hidden_size=32, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=32,
            hidden_dropout=0.0, attention_dropout=0.0,
            num_moe_experts=4, moe_capacity_factor=2.0, **kw)
        return GPTModel(cfg)

    def test_moe_gpt_trains(self):
        from apex_tpu.optimizers import FusedAdam

        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        assert "w_in" in params["transformer"]["layers"]["mlp"]
        opt = FusedAdam(lr=1e-2)
        opt_state = opt.init(params)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 64)

        @jax.jit
        def step(params, opt_state):
            loss, grads = jax.value_and_grad(
                lambda p: model.apply(p, tokens, labels))(params)
            params, opt_state = opt.step(grads, params, opt_state)
            return params, opt_state, loss

        losses = []
        for _ in range(6):
            params, opt_state, loss = step(params, opt_state)
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    @pytest.mark.slow
    def test_moe_router_gets_gradient(self):
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        labels = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 64)
        g = jax.grad(lambda p: model.apply(p, tokens, labels))(params)
        router_g = g["transformer"]["layers"]["mlp"]["router"]
        assert float(jnp.sum(jnp.abs(router_g))) > 0

    def test_moe_logits_mode(self):
        model = self._model()
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
        logits = model.apply(params, tokens)
        assert logits.shape == (8, 2, 64)

    @pytest.mark.slow
    def test_moe_in_bert_adds_aux_to_lm_loss(self):
        """MoE composes with BERT (round 3): the pre-scaled aux joins the
        masked-LM loss, and router grads flow."""
        from apex_tpu.models import BertModel, TransformerConfig

        cfg = TransformerConfig(
            num_layers=2, hidden_size=32, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=16,
            hidden_dropout=0.0, attention_dropout=0.0,
            num_moe_experts=4, moe_capacity_factor=4.0)
        model = BertModel(cfg, add_binary_head=False)
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)

        def loss(p):
            lm_loss, _ = model.apply(p, tokens, lm_labels=tokens)
            return lm_loss

        l, g = jax.value_and_grad(loss)(params)
        assert np.isfinite(float(l))
        router_g = g["transformer"]["layers"]["mlp"]["router"]
        assert float(jnp.sum(jnp.abs(router_g))) > 0

    @pytest.mark.slow
    def test_moe_in_vit_returns_logits_and_aux(self):
        from apex_tpu.models import TransformerConfig, ViTConfig, ViTModel

        cfg = TransformerConfig(
            num_layers=2, hidden_size=32, num_attention_heads=4,
            hidden_dropout=0.0, attention_dropout=0.0,
            num_moe_experts=4, moe_capacity_factor=4.0)
        model = ViTModel(ViTConfig(image_size=32, patch_size=16,
                                   num_classes=4, transformer=cfg))
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        logits, aux = model.apply(params, x)
        assert logits.shape == (2, 4)
        assert np.isfinite(float(aux))
