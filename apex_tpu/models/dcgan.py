"""DCGAN generator/discriminator, TPU-native NHWC.

Capability counterpart of the reference's mixed-precision GAN example
(``/root/reference/examples/dcgan/main_amp.py``: 64x64 DCGAN trained with two
optimizers and two loss scalers through ``amp.initialize(num_losses=3)``).
The interesting apex capability it
exercises is *multiple models/optimizers/losses under one amp context*;
here both nets are plain functional modules, and the multi-loss-scaler story
is :class:`apex_tpu.amp.DynamicLossScaler` instances carried per loss.

Design: transposed convs via ``lax.conv_transpose`` (generator) and strided
convs (discriminator), NHWC, BN with carried state as in
:mod:`apex_tpu.models.resnet`.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.utils.batch_norm import bn_apply as _bn_apply, bn_init as _bn_init

__all__ = ["DCGANConfig", "Generator", "Discriminator"]


@dataclass(frozen=True)
class DCGANConfig:
    latent_dim: int = 100        # nz
    gen_features: int = 64       # ngf
    disc_features: int = 64      # ndf
    channels: int = 3            # nc
    bn_eps: float = 1e-5
    bn_momentum: float = 0.1
    compute_dtype: Any = jnp.float32


def _winit(key, shape):
    # DCGAN recipe: N(0, 0.02) conv weights (examples/dcgan weights_init)
    return jax.random.normal(key, shape, jnp.float32) * 0.02


class _Net:
    def __init__(self, config: DCGANConfig):
        self.config = config

    def _bn(self, p, s, x, train):
        cfg = self.config
        return _bn_apply(p, s, x, train=train, momentum=cfg.bn_momentum,
                         eps=cfg.bn_eps, axis_name=None)


class Generator(_Net):
    """z [N, latent] -> image [N, 64, 64, C] in [-1, 1]."""

    def init(self, key: jax.Array):
        cfg = self.config
        f = cfg.gen_features
        chans = [(cfg.latent_dim, f * 8), (f * 8, f * 4), (f * 4, f * 2),
                 (f * 2, f), (f, cfg.channels)]
        keys = jax.random.split(key, len(chans))
        params: Dict[str, Any] = {}
        state: Dict[str, Any] = {}
        for i, (cin, cout) in enumerate(chans):
            params[f"deconv{i}"] = _winit(keys[i], (4, 4, cin, cout))
            if i < len(chans) - 1:
                params[f"bn{i}"], state[f"bn{i}"] = _bn_init(cout)
        return params, state

    def apply(self, params, state, z, *, train: bool = False):
        cfg = self.config
        x = z.reshape(z.shape[0], 1, 1, cfg.latent_dim)
        x = x.astype(cfg.compute_dtype)
        new_state: Dict[str, Any] = {}
        n_layers = 5
        for i in range(n_layers):
            w = params[f"deconv{i}"].astype(cfg.compute_dtype)
            first, last = i == 0, i == n_layers - 1
            x = lax.conv_transpose(
                x, w, strides=(1, 1) if first else (2, 2),
                padding="VALID" if first else "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            if last:
                return jnp.tanh(x), new_state
            x, new_state[f"bn{i}"] = self._bn(
                params[f"bn{i}"], state[f"bn{i}"], x, train)
            x = jax.nn.relu(x)


class Discriminator(_Net):
    """image [N, 64, 64, C] -> logit [N] (no sigmoid; pair with BCE-with-
    logits, numerically safer than the example's Sigmoid+BCELoss which amp
    must blacklist — ``examples/dcgan/main_amp.py`` notes this exact issue)."""

    def init(self, key: jax.Array):
        cfg = self.config
        f = cfg.disc_features
        chans = [(cfg.channels, f), (f, f * 2), (f * 2, f * 4),
                 (f * 4, f * 8), (f * 8, 1)]
        keys = jax.random.split(key, len(chans))
        params: Dict[str, Any] = {}
        state: Dict[str, Any] = {}
        for i, (cin, cout) in enumerate(chans):
            params[f"conv{i}"] = _winit(keys[i], (4, 4, cin, cout))
            if 0 < i < len(chans) - 1:
                params[f"bn{i}"], state[f"bn{i}"] = _bn_init(cout)
        return params, state

    def apply(self, params, state, x, *, train: bool = False):
        cfg = self.config
        x = x.astype(cfg.compute_dtype)
        new_state: Dict[str, Any] = {}
        n_layers = 5
        for i in range(n_layers):
            w = params[f"conv{i}"].astype(cfg.compute_dtype)
            last = i == n_layers - 1
            x = lax.conv_general_dilated(
                x, w, window_strides=(1, 1) if last else (2, 2),
                padding="VALID" if last else "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            if last:
                return x.reshape(x.shape[0]).astype(jnp.float32), new_state
            if i > 0:
                x, new_state[f"bn{i}"] = self._bn(
                    params[f"bn{i}"], state[f"bn{i}"], x, train)
            x = jax.nn.leaky_relu(x, 0.2)
