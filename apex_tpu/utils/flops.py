"""FLOP accounting: chip peak FLOP/s table + model-FLOP estimators.

One source of truth for the MFU math of the library's observability
layer (:mod:`apex_tpu.observability` — per-step MFU against the chip's
bf16 peak).
MFU here is *model*-FLOPs utilization (PaLM-style: the FLOPs the math
requires, not the FLOPs the compiler executes), so numbers are comparable
across implementations.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["peak_flops_per_chip", "transformer_train_flops",
           "resnet50_train_flops"]

# bf16 peak TFLOP/s per chip by device kind (public Cloud TPU specs); MFU is
# model-FLOPs utilization against this number
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops_per_chip(device=None) -> Optional[float]:
    """bf16 peak FLOP/s of ``device`` (default: the first visible device).

    ``None`` only on the CPU platform, where MFU has no meaning. On any
    other platform the ``device_kind`` must be in the table EXACTLY — an
    unlisted kind raises, because a guessed peak is a wrong MFU under a
    real name (a prefix match would hand an unknown "TPU v5x" the v5p
    number)."""
    import jax

    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return None
    try:
        return _PEAK_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak for device kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add it to _PEAK_FLOPS with "
            f"its source rather than assuming one") from None


def transformer_train_flops(n_params: int, tokens: int, num_layers: int,
                            hidden: int, seq: int, causal: bool) -> float:
    """Model FLOPs for one training step over ``tokens`` tokens: the
    standard ``6N`` matmul term plus the attention score/value term
    ``12 * L * s * d`` per token (halved for causal masking)."""
    attn = 12 * num_layers * seq * hidden * (0.5 if causal else 1.0)
    return float(tokens) * (6.0 * n_params + attn)


def resnet50_train_flops(images: int, image_size: int) -> float:
    """Model FLOPs for one RN50 training step: 4.09 GFLOP forward per
    224px image (torchvision profile), scaled by area, x3 for fwd+bwd."""
    return images * 3.0 * 4.09e9 * (image_size / 224.0) ** 2
