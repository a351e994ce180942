"""Vision Transformer (ViT-B/L/H) on the parallel transformer toolkit.

ViT-L/16 (24 layers, hidden 1024, 16 heads, 16x16 patches) under
SyncBatchNorm + FusedAdam is a configuration apex users train; the
reference itself has no ViT, but its Megatron blocks are
the obvious substrate (the same way NeMo builds ViT on apex's
``apex/transformer``). Patch embedding is a single strided conv (an MXU
matmul after im2col — XLA does this folding), then the standard
:class:`~apex_tpu.models.transformer.ParallelTransformer` encoder stack in
Megatron ``[seq, batch, hidden]`` layout with bidirectional (padding-free)
attention, CLS token, and a linear head.

Tensor parallelism, sequence parallelism, recompute, and bf16 compute all
come along for free from :class:`TransformerConfig`.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from apex_tpu.models.transformer import ParallelTransformer, TransformerConfig
from apex_tpu.transformer.enums import AttnMaskType

__all__ = ["ViTConfig", "ViTModel", "vit_b16", "vit_l16", "vit_h14"]


@dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    num_classes: int = 1000
    channels: int = 3
    transformer: TransformerConfig = None  # required

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2


def _encoder_config(num_layers, hidden, heads, **kw) -> TransformerConfig:
    return TransformerConfig(
        num_layers=num_layers, hidden_size=hidden, num_attention_heads=heads,
        attn_mask_type=AttnMaskType.padding, hidden_dropout=0.0,
        attention_dropout=0.0, **kw)


class ViTModel:
    """Functional ViT: ``init(key) -> params``;
    ``apply(params, images_nhwc) -> logits``."""

    def __init__(self, config: ViTConfig):
        self.config = config
        self.encoder = ParallelTransformer(config.transformer)

    def init(self, key: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        t = cfg.transformer
        h = t.hidden_size
        k_patch, k_cls, k_pos, k_head, k_enc = jax.random.split(key, 5)
        fan_in = cfg.patch_size * cfg.patch_size * cfg.channels
        return {
            "patch_embed": jax.random.normal(
                k_patch, (cfg.patch_size, cfg.patch_size, cfg.channels, h),
                jnp.float32) * fan_in ** -0.5,
            "cls_token": jax.random.normal(k_cls, (1, 1, h)) * 0.02,
            "pos_embed": jax.random.normal(
                k_pos, (cfg.num_patches + 1, 1, h)) * 0.02,
            "encoder": self.encoder.init(k_enc),
            "head": {
                "kernel": jax.random.normal(k_head, (h, cfg.num_classes),
                                            jnp.float32) * h ** -0.5,
                "bias": jnp.zeros((cfg.num_classes,), jnp.float32),
            },
        }

    def spec(self):
        return {
            "patch_embed": PartitionSpec(),
            "cls_token": PartitionSpec(),
            "pos_embed": PartitionSpec(),
            "encoder": self.encoder.spec(),
            "head": {"kernel": PartitionSpec(), "bias": PartitionSpec()},
        }

    def apply(self, params, images, *, rng=None, deterministic=True):
        """images: [N, H, W, C] NHWC -> logits [N, num_classes] — or
        ``(logits, moe_aux_loss)`` when the transformer config enables MoE
        (``num_moe_experts``): the pre-scaled load-balancing term belongs
        in the caller's training loss (ViT computes no loss in-model)."""
        cfg = self.config
        t = cfg.transformer
        x = images.astype(t.compute_dtype)
        w = params["patch_embed"].astype(t.compute_dtype)
        patches = lax.conv_general_dilated(
            x, w, window_strides=(cfg.patch_size, cfg.patch_size),
            padding="VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        n = patches.shape[0]
        # [N, h/p, w/p, H] -> Megatron [seq, batch, hidden]
        hidden = patches.reshape(n, cfg.num_patches, t.hidden_size)
        hidden = jnp.transpose(hidden, (1, 0, 2))
        cls = jnp.broadcast_to(
            params["cls_token"].astype(t.compute_dtype),
            (1, n, t.hidden_size))
        hidden = jnp.concatenate([cls, hidden], axis=0)
        hidden = hidden + params["pos_embed"].astype(t.compute_dtype)
        hidden = self.encoder.apply(
            params["encoder"], hidden, rng=rng, deterministic=deterministic)
        moe_aux = None
        if t.num_moe_experts:
            hidden, moe_aux = hidden
        cls_out = hidden[0].astype(jnp.float32)          # [batch, hidden]
        logits = cls_out @ params["head"]["kernel"] + params["head"]["bias"]
        return logits if moe_aux is None else (logits, moe_aux)


def _make(name, layers, hidden, heads, patch):
    def ctor(image_size: int = 224, num_classes: int = 1000,
             **tkw) -> ViTModel:
        enc = _encoder_config(layers, hidden, heads, **tkw)
        return ViTModel(ViTConfig(image_size=image_size, patch_size=patch,
                                  num_classes=num_classes, transformer=enc))
    ctor.__name__ = name
    ctor.__doc__ = f"ViT {name}: {layers}L/{hidden}H/{heads}A, patch {patch}."
    return ctor


vit_b16 = _make("vit_b16", 12, 768, 12, 16)
vit_l16 = _make("vit_l16", 24, 1024, 16, 16)
vit_h14 = _make("vit_h14", 32, 1280, 16, 14)
