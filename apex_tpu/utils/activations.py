"""MLP activation helpers shared by the dense and MoE FFN paths.

One home for the activation whitelist and the gated unit-interleaved layout
convention (output column ``2i`` = gate_i, ``2i+1`` = up_i) so
``ParallelMLP`` and ``SwitchMLP`` cannot drift apart.

The interleaved layout is what ``init``, training, checkpoints and every
caller's parameter tree hold. The serving side re-lays a gated
``ParallelMLP``'s weight once, at intake, to halves apart (``[2, ffn, h]``:
plane 0 = gate, plane 1 = up; ``models.generation.split_gated_mlp_params``)
and combines the two halves with :func:`gated_product`, which never makes
the lane dim of 2 that :func:`apply_activation` slices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ACTIVATIONS = ("gelu", "relu", "swiglu", "geglu")
GATED = ("swiglu", "geglu")

__all__ = ["ACTIVATIONS", "GATED", "is_gated", "validate_activation",
           "apply_activation", "gated_product"]


def is_gated(activation: str) -> bool:
    return activation in GATED


def validate_activation(activation: str) -> None:
    if activation not in ACTIVATIONS:
        raise ValueError(
            f"activation must be one of {ACTIVATIONS}, got {activation!r}")


def gated_product(gate: jax.Array, up: jax.Array,
                  activation: str) -> jax.Array:
    """``act(gate) * up`` of a gated activation, the halves given apart
    (the serving side's form, see the module docstring)."""
    act = (jax.nn.silu if activation == "swiglu"
           else lambda t: jax.nn.gelu(t, approximate=True))
    return act(gate) * up


def apply_activation(x: jax.Array, activation: str) -> jax.Array:
    """Apply ``activation`` to an FFN pre-activation.

    Gated variants expect the unit-interleaved ``2*ffn`` layout
    (``x[..., 2i]`` = gate_i, ``x[..., 2i+1]`` = up_i; any TP slice of even
    width holds matched pairs) and halve the last dim:
    ``act(gate) * up``. Gated projections are bias-free by convention
    (LLaMA-style) — callers construct their linears accordingly.
    """
    if is_gated(activation):
        x = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
        return gated_product(x[..., 0], x[..., 1], activation)
    if activation == "relu":
        return jax.nn.relu(x)
    return jax.nn.gelu(x, approximate=True)
