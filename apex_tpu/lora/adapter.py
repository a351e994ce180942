"""Adapter format + device-resident adapter bank for Multi-LoRA.

An *adapter* is a set of low-rank factor pairs, one per target matrix:
for a target weight ``W [out, in]`` the factors are ``A [in, r]`` and
``B [r, out]`` and the adapted projection is ``y = x @ W.T + (x @ A) @ B``
— LoRA with the delta kept factored (LoRAFusion, PAPERS.md 2510.00206).
Targets are the two ColumnParallelLinear projections every layer owns:
the fused QKV (``query_key_value``) and the MLP up-projection
(``dense_h_to_4h``); both shard their OUTPUT dim over the tensor axis, so
the ``B`` factor shards with the heads while ``A`` stays replicated.

:class:`AdapterStore` registers adapters host-side and materializes them
as a stacked device bank ``[num_layers, max_adapters + 1, ...]`` per
factor. Index ``max_adapters`` is the reserved all-zeros NULL adapter:
requests without an ``adapter_id`` gather it and their delta is exactly
zero, so base traffic shares the one batched program with tenant traffic.
``load``/``unload`` rewrite one bank row in place (same shapes, no
retrace) — the hot-load hook the ROADMAP's live-update item needs.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.utils.activations import is_gated

__all__ = [
    "LORA_TARGETS",
    "AdapterStore",
    "UnknownAdapterError",
    "init_adapter",
    "random_adapter",
    "merge_adapter",
    "target_dims",
]

#: layer-local projections that take a low-rank delta, in bank order
LORA_TARGETS = ("query_key_value", "dense_h_to_4h")


class UnknownAdapterError(KeyError):
    """``adapter_id`` is not (or no longer) loaded in the AdapterStore."""


def target_dims(config) -> Dict[str, Tuple[int, int]]:
    """``{target: (in_dim, out_dim)}`` — FULL (unsharded) dims; the sharded
    engine's shard_map slices the ``B`` bank with the heads. MoE models
    carry no ``dense_h_to_4h`` (expert weights are routed, not adapted)."""
    c = config
    if getattr(c, "mixed_layers", False) or getattr(
            c, "num_routed_experts", None):
        raise NotImplementedError(
            "LoRA adapters are stacked [num_layers, ...] banks over layers "
            "of one kind with a dense MLP; a model with "
            "attention_layer_types / num_dense_layers / num_routed_experts "
            "is not supported")
    qpg = c.num_attention_heads // c.kv_heads
    dims = {"query_key_value": (c.hidden_size,
                                c.kv_heads * (qpg + 2) * c.head_dim)}
    if not c.num_moe_experts:
        gated = 2 if is_gated(c.activation) else 1
        dims["dense_h_to_4h"] = (c.hidden_size, gated * c.ffn_size)
    return dims


def init_adapter(config, rank: int, key) -> Dict[str, Dict[str, jax.Array]]:
    """Fresh trainable factors ``{target: {"A": [L, in, r], "B": [L, r,
    out]}}`` — A gaussian, B zeros, so the initial delta is exactly zero
    (the standard LoRA init: fine-tuning starts from the base model)."""
    c = config
    factors = {}
    for t, (din, dout) in target_dims(config).items():
        key, ka = jax.random.split(key)
        factors[t] = {
            "A": (0.02 * jax.random.normal(
                ka, (c.num_layers, din, rank))).astype(jnp.float32),
            "B": jnp.zeros((c.num_layers, rank, dout), jnp.float32),
        }
    return factors


def random_adapter(config, rank: int, key,
                   scale: float = 0.02) -> Dict[str, Dict[str, jax.Array]]:
    """Factors with BOTH halves nonzero (delta != 0) — the shape traffic
    generators and parity tests want; ``init_adapter`` is a zero delta."""
    c = config
    factors = {}
    for t, (din, dout) in target_dims(config).items():
        key, ka, kb = jax.random.split(key, 3)
        factors[t] = {
            "A": (scale * jax.random.normal(
                ka, (c.num_layers, din, rank))).astype(jnp.float32),
            "B": (scale * jax.random.normal(
                kb, (c.num_layers, rank, dout))).astype(jnp.float32),
        }
    return factors


def _check_factors(config, rank: int, factors) -> None:
    dims = target_dims(config)
    if set(factors) != set(dims):
        raise ValueError(
            f"adapter targets {sorted(factors)} != expected "
            f"{sorted(dims)} for this config")
    L = config.num_layers
    for t, (din, dout) in dims.items():
        a = factors[t]["A"]
        b = factors[t]["B"]
        if tuple(a.shape) != (L, din, rank):
            raise ValueError(
                f"{t}.A shape {tuple(a.shape)} != {(L, din, rank)}")
        if tuple(b.shape) != (L, rank, dout):
            raise ValueError(
                f"{t}.B shape {tuple(b.shape)} != {(L, rank, dout)}")


def merge_adapter(params, factors):
    """Fold an adapter into full weights: per layer/target
    ``W' = W + (A @ B).T`` (``W`` is ``[out, in]``, Megatron layout). The
    merged-reference engine the parity tests compare against runs these
    params with NO lora arguments — the ground truth for token-exactness.
    Handles both the stacked ``[L, ...]`` layer leaves and the per-layer
    list form; returns a new params pytree (input untouched)."""
    params = dict(params)
    params["transformer"] = dict(params["transformer"])
    layers = params["transformer"]["layers"]
    paths = {"query_key_value": ("self_attention", "query_key_value"),
             "dense_h_to_4h": ("mlp", "dense_h_to_4h")}

    def folded(w, a, b):
        # delta in fp32, cast back: params may be bf16
        delta = jnp.einsum("...ir,...ro->...oi", a.astype(jnp.float32),
                           b.astype(jnp.float32))
        return (w.astype(jnp.float32) + delta).astype(w.dtype)

    def set_weight(layer_params, sub, name, w):
        lp = dict(layer_params)
        lp[sub] = dict(lp[sub])
        lp[sub][name] = dict(lp[sub][name])
        lp[sub][name]["weight"] = w
        return lp

    if isinstance(layers, (list, tuple)):
        layers_new: Any = list(layers)
        for t, f in factors.items():
            sub, name = paths[t]
            for idx in range(len(layers_new)):
                w = layers_new[idx][sub][name]["weight"]
                layers_new[idx] = set_weight(
                    layers_new[idx], sub, name,
                    folded(w, f["A"][idx], f["B"][idx]))
    else:
        layers_new = layers
        for t, f in factors.items():
            sub, name = paths[t]
            w = layers_new[sub][name]["weight"]          # [L, out, in]
            layers_new = set_weight(layers_new, sub, name,
                                    folded(w, f["A"], f["B"]))
    params["transformer"]["layers"] = layers_new
    return params


class AdapterStore:
    """Host-side registry + device-resident stacked adapter bank.

    The bank is a pytree ``{target: {"A": [L, n+1, in, r], "B":
    [L, n+1, r, out]}}`` (``n = max_adapters``); engine step programs
    close over nothing — the bank is a runtime argument, gathered per
    slot in-jit, so ``load``/``unload`` between ticks never retrace.
    """

    def __init__(self, config, rank: int, max_adapters: int = 8):
        if rank < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        if max_adapters < 1:
            raise ValueError(
                f"max_adapters must be >= 1, got {max_adapters}")
        self.config = config
        self.rank = int(rank)
        self.max_adapters = int(max_adapters)
        self._ids: Dict[str, int] = {}
        self._free = list(range(max_adapters))
        L = config.num_layers
        self._bank = {
            t: {"A": jnp.zeros((L, max_adapters + 1, din, rank),
                               jnp.float32),
                "B": jnp.zeros((L, max_adapters + 1, rank, dout),
                               jnp.float32)}
            for t, (din, dout) in target_dims(config).items()
        }

    # -- identity ---------------------------------------------------------
    @property
    def null_index(self) -> int:
        """Bank row of the reserved all-zeros adapter (base traffic)."""
        return self.max_adapters

    @property
    def bank(self):
        """The device bank pytree — pass straight into the step programs."""
        return self._bank

    def ids(self):
        return sorted(self._ids)

    def __contains__(self, adapter_id: str) -> bool:
        return adapter_id in self._ids

    def __len__(self) -> int:
        return len(self._ids)

    def index_of(self, adapter_id: Optional[str]) -> int:
        """Bank row for a request's ``adapter_id`` (None -> null row)."""
        if adapter_id is None:
            return self.null_index
        try:
            return self._ids[adapter_id]
        except KeyError:
            raise UnknownAdapterError(
                f"adapter {adapter_id!r} is not loaded "
                f"(loaded: {self.ids()})") from None

    # -- lifecycle --------------------------------------------------------
    def load(self, adapter_id: str, factors) -> int:
        """Register ``factors`` under ``adapter_id`` and write its bank
        row (re-loading an existing id overwrites in place). Returns the
        bank index. Raises when full or on a shape/target mismatch."""
        if not isinstance(adapter_id, str) or not adapter_id:
            raise ValueError("adapter_id must be a non-empty string")
        _check_factors(self.config, self.rank, factors)
        if adapter_id in self._ids:
            ix = self._ids[adapter_id]
        else:
            if not self._free:
                raise ValueError(
                    f"adapter bank full ({self.max_adapters} slots); "
                    f"unload one of {self.ids()}")
            ix = self._free.pop(0)
            self._ids[adapter_id] = ix
        for t, f in factors.items():
            self._bank[t] = {
                "A": self._bank[t]["A"].at[:, ix].set(
                    jnp.asarray(f["A"], jnp.float32)),
                "B": self._bank[t]["B"].at[:, ix].set(
                    jnp.asarray(f["B"], jnp.float32)),
            }
        return ix

    def unload(self, adapter_id: str) -> None:
        """Drop an adapter: zero its bank row and free the index. Requests
        already decoding against the row keep running — against a zero
        delta from the next step on (they degrade to base-model output);
        NEW submits with this id fail :class:`UnknownAdapterError`."""
        if adapter_id not in self._ids:
            raise UnknownAdapterError(
                f"adapter {adapter_id!r} is not loaded "
                f"(loaded: {self.ids()})")
        ix = self._ids.pop(adapter_id)
        for t in list(self._bank):
            self._bank[t] = {
                "A": self._bank[t]["A"].at[:, ix].set(0.0),
                "B": self._bank[t]["B"].at[:, ix].set(0.0),
            }
        self._free.append(ix)
        self._free.sort()
