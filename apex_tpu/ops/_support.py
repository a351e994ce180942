"""Kernel dispatch support.

The reference gates CUDA kernels on availability predicates (e.g.
``FusedScaleMaskSoftmax.is_kernel_available``, ``apex/transformer/functional/
fused_softmax.py:222-248``) and falls back to eager torch. Here the analog:
Pallas TPU kernels when running on TPU, pure-``jnp`` fallbacks elsewhere
(interpret mode is available for kernel debugging via
``APEX_TPU_FORCE_PALLAS=interpret``).
"""

from __future__ import annotations

import functools
import os

import jax


@functools.lru_cache(maxsize=None)
def pallas_mode() -> str:
    """Return 'tpu' (compiled pallas), 'interpret', or 'off'."""
    forced = os.environ.get("APEX_TPU_FORCE_PALLAS", "").lower()
    if forced in ("interpret", "tpu", "off"):
        return forced
    # a backend that cannot initialise raises here — it must, or a lost
    # chip would silently turn every kernel into its jnp fallback
    return "tpu" if jax.default_backend() == "tpu" else "off"


def use_pallas() -> bool:
    return pallas_mode() != "off"


def pallas_interpret() -> bool:
    return pallas_mode() == "interpret"


def round_up(x: int, multiple: int) -> int:
    return ((x + multiple - 1) // multiple) * multiple


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def min_sublane(dtype) -> int:
    """Minimum second-to-last tile dim for a dtype on TPU."""
    import jax.numpy as jnp

    if dtype in (jnp.bfloat16, jnp.float16):
        return 16
    if dtype in (jnp.int8, jnp.uint8):
        return 32
    return 8


def block_rows(h_pad: int, dtype, *, vmem_budget: int = 4 * 1024 * 1024,
               cap: int = 256) -> int:
    """Row-block size for row-wise kernels (layer norm, softmax): as many
    rows as a ``vmem_budget``-byte fp32 block allows, capped at ``cap``,
    rounded to the dtype's sublane. Cap tuning (v5e, round 4): an
    interleaved same-process A/B on the BERT step measured 256 vs 512 at
    77.8 vs 78.4 ms — equal within noise (an apparent +5% for 512 across
    separate processes did not survive the same-process comparison); 1024
    exceeds Mosaic's 16 MB
    scoped-vmem stack in the LN backward (18.9 MB of live fp32
    intermediates at (1024, 768)). 256 stays."""
    sub = min_sublane(dtype)
    bm = max(sub, min(cap, vmem_budget // (h_pad * 4)))
    return round_up(bm, sub)
