"""Pallas TPU kernels + XLA fallbacks (the ``csrc/`` capability layer)."""

from apex_tpu.ops.layer_norm import (
    fused_layer_norm,
    fused_layer_norm_affine,
    fused_rms_norm,
    fused_rms_norm_affine,
)
from apex_tpu.ops.softmax import (
    scaled_softmax,
    scaled_masked_softmax,
    scaled_upper_triang_masked_softmax,
    generic_scaled_masked_softmax,
)
from apex_tpu.ops.cross_entropy import (
    softmax_cross_entropy_loss,
    SoftmaxCrossEntropyLoss,
)
from apex_tpu.ops.attention import (
    flash_attention,
    flash_attention_packed,
    packed_attention_supported,
)
from apex_tpu.ops.ring_attention import ring_attention, ulysses_attention
from apex_tpu.ops.decode_attention import (
    fused_paged_decode_attention,
    paged_pages_for,
)
from apex_tpu.ops.grouped_matmul import grouped_gated_ffn, routed_layout
from apex_tpu.ops.rope import (
    fused_rope,
    fused_rope_cached,
    fused_rope_thd,
    fused_rope_2d,
)

__all__ = [
    "fused_layer_norm",
    "fused_layer_norm_affine",
    "fused_rms_norm",
    "fused_rms_norm_affine",
    "scaled_softmax",
    "scaled_masked_softmax",
    "scaled_upper_triang_masked_softmax",
    "generic_scaled_masked_softmax",
    "softmax_cross_entropy_loss",
    "SoftmaxCrossEntropyLoss",
    "fused_rope",
    "fused_rope_cached",
    "fused_rope_thd",
    "fused_rope_2d",
    "flash_attention",
    "flash_attention_packed",
    "packed_attention_supported",
    "ring_attention",
    "ulysses_attention",
    "fused_paged_decode_attention",
    "paged_pages_for",
    "grouped_gated_ffn",
    "routed_layout",
]
