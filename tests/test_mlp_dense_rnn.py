"""MLP / FusedDense / RNN parity suite.

Mirrors the reference's ``tests/L0/run_mlp/`` (MLP vs an ``nn.Sequential``
of Linears) and the torch-cell semantics of ``apex/RNN``: weights are copied
into torch modules and outputs/grads must agree.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

os.environ.setdefault("APEX_TPU_FORCE_PALLAS", "interpret")

from apex_tpu.fused_dense import FusedDense, FusedDenseGeluDense  # noqa: E402
from apex_tpu.mlp import MLP  # noqa: E402
from apex_tpu.rnn import GRU, LSTM, ReLU, Tanh, mLSTM  # noqa: E402


def _t(x):
    return torch.tensor(np.asarray(x))


class TestMLP:
    @pytest.mark.parametrize("activation", ["none", "relu", "sigmoid"])
    def test_matches_torch_sequential(self, activation):
        sizes = [13, 27, 11]
        mlp = MLP(sizes, bias=True, activation=activation)
        params = mlp.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 13))

        layers = []
        for i in range(2):
            lin = torch.nn.Linear(sizes[i], sizes[i + 1])
            with torch.no_grad():
                lin.weight.copy_(_t(params[f"weight_{i}"]))
                lin.bias.copy_(_t(params[f"bias_{i}"]))
            layers.append(lin)
            if activation == "relu":
                layers.append(torch.nn.ReLU())
            elif activation == "sigmoid":
                layers.append(torch.nn.Sigmoid())
        ref = torch.nn.Sequential(*layers)

        out = mlp.apply(params, x)
        ref_out = ref(_t(x)).detach().numpy()
        np.testing.assert_allclose(np.asarray(out), ref_out, rtol=1e-5,
                                   atol=1e-6)

    def test_no_bias_and_bad_activation(self):
        mlp = MLP([4, 4], bias=False)
        params = mlp.init(jax.random.PRNGKey(0))
        assert "bias_0" not in params
        with pytest.raises(TypeError):
            MLP([4, 4], activation="gelu")

    def test_grads_flow(self):
        mlp = MLP([8, 16, 4])
        params = mlp.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (3, 8))
        g = jax.grad(lambda p: jnp.sum(mlp.apply(p, x) ** 2))(params)
        assert all(bool(jnp.any(v != 0)) for v in jax.tree.leaves(g))


class TestFusedDense:
    def test_matches_torch_linear(self):
        fd = FusedDense(9, 17)
        params = fd.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 9))
        lin = torch.nn.Linear(9, 17)
        with torch.no_grad():
            lin.weight.copy_(_t(params["weight"]))
            lin.bias.copy_(_t(params["bias"]))
        np.testing.assert_allclose(
            np.asarray(fd.apply(params, x)),
            lin(_t(x)).detach().numpy(), rtol=1e-5, atol=1e-6)

    def test_gelu_dense_matches_torch(self):
        fdg = FusedDenseGeluDense(8, 32, 6)
        params = fdg.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
        l1, l2 = torch.nn.Linear(8, 32), torch.nn.Linear(32, 6)
        with torch.no_grad():
            l1.weight.copy_(_t(params["weight1"]))
            l1.bias.copy_(_t(params["bias1"]))
            l2.weight.copy_(_t(params["weight2"]))
            l2.bias.copy_(_t(params["bias2"]))
        ref = l2(torch.nn.functional.gelu(l1(_t(x)), approximate="tanh"))
        np.testing.assert_allclose(
            np.asarray(fdg.apply(params, x)),
            ref.detach().numpy(), rtol=1e-4, atol=1e-5)

    def test_no_bias_gelu_raises(self):
        with pytest.raises(AssertionError):
            FusedDenseGeluDense(4, 8, 4, bias=False)


def _copy_rnn_weights_to_torch(trnn, params, bidirectional=False):
    with torch.no_grad():
        for layer, p in enumerate(params):
            dirs = p if bidirectional else [p]
            for d, pd in enumerate(dirs):
                sfx = f"_l{layer}" + ("_reverse" if d == 1 else "")
                getattr(trnn, f"weight_ih{sfx}").copy_(_t(pd["w_ih"]))
                getattr(trnn, f"weight_hh{sfx}").copy_(_t(pd["w_hh"]))
                getattr(trnn, f"bias_ih{sfx}").copy_(_t(pd["b_ih"]))
                getattr(trnn, f"bias_hh{sfx}").copy_(_t(pd["b_hh"]))


class TestRNN:
    @pytest.mark.parametrize("bidirectional", [
        False, pytest.param(True, marks=pytest.mark.slow)])
    def test_lstm_matches_torch(self, bidirectional):
        T, B, I, H, L = 6, 3, 5, 7, 2
        model = LSTM(I, H, L, bias=True, bidirectional=bidirectional)
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (T, B, I))

        trnn = torch.nn.LSTM(I, H, L, bidirectional=bidirectional)
        _copy_rnn_weights_to_torch(trnn, params, bidirectional)
        ref_out, _ = trnn(_t(x))

        out, finals = model.apply(params, x)
        # atol 1e-4: TPU transcendental units (tanh/sigmoid) differ from
        # torch CPU at ~3e-5 over recurrent accumulation
        np.testing.assert_allclose(np.asarray(out),
                                   ref_out.detach().numpy(),
                                   rtol=1e-4, atol=1e-4)
        assert len(finals) == L

    def test_gru_matches_torch(self):
        T, B, I, H = 5, 2, 4, 6
        model = GRU(I, H, 1, bias=True)
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (T, B, I))
        trnn = torch.nn.GRU(I, H, 1)
        _copy_rnn_weights_to_torch(trnn, params)
        ref_out, _ = trnn(_t(x))
        out, _ = model.apply(params, x)
        # atol 1e-4: TPU transcendental units (tanh/sigmoid) differ from
        # torch CPU at ~3e-5 over recurrent accumulation
        np.testing.assert_allclose(np.asarray(out),
                                   ref_out.detach().numpy(),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("factory,mode", [(ReLU, "relu"), (Tanh, "tanh")])
    def test_elman_matches_torch(self, factory, mode):
        T, B, I, H = 4, 2, 3, 5
        model = factory(I, H, 1, bias=True)
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (T, B, I))
        trnn = torch.nn.RNN(I, H, 1, nonlinearity=mode)
        _copy_rnn_weights_to_torch(trnn, params)
        ref_out, _ = trnn(_t(x))
        out, _ = model.apply(params, x)
        # atol 1e-4: TPU transcendental units (tanh/sigmoid) differ from
        # torch CPU at ~3e-5 over recurrent accumulation
        np.testing.assert_allclose(np.asarray(out),
                                   ref_out.detach().numpy(),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.slow
    def test_mlstm_shapes_and_grads(self):
        T, B, I, H = 4, 2, 3, 5
        model = mLSTM(I, H, 1, bias=True)
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (T, B, I))
        out, finals = model.apply(params, x)
        assert out.shape == (T, B, H)
        g = jax.grad(lambda p: jnp.sum(model.apply(p, x)[0] ** 2))(params)
        assert all(np.isfinite(np.asarray(v)).all()
                   for v in jax.tree.leaves(g))
        assert any(bool(jnp.any(v != 0)) for v in jax.tree.leaves(g))

    def test_output_projection(self):
        T, B, I, H, O = 4, 2, 3, 8, 5
        model = LSTM(I, H, 1, output_size=O)
        params = model.init(jax.random.PRNGKey(0))
        assert params[0]["w_ho"].shape == (O, H)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, B, I))
        out, finals = model.apply(params, x)
        assert out.shape == (T, B, O)
        # recurrent state: h is output-sized, c is hidden-sized
        h, c = finals[0]
        assert h.shape == (B, O) and c.shape == (B, H)

    def test_batch_first(self):
        model = Tanh(3, 4, 1, batch_first=True)
        params = model.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 3))  # [B, T, I]
        out, _ = model.apply(params, x)
        assert out.shape == (2, 6, 4)
