"""Operations and bytes the *algorithm* needs, from shapes alone.

These are the numerators of every ``mfu`` and roofline metric. They count
what the mathematics asks for: no block-masked redundant MACs, no padding
to a bucket or a page, no recomputation. Whatever implements the function
later is measured against the same work.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of a chip; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"cellbench/peaks.json with its source")
    return table[device_kind]


def matmul_params(sz: dict) -> int:
    """Weights that take part in a matrix product per token: the blocks'
    four projections and the tied output head (the embedding lookup and
    the position table multiply nothing)."""
    return sz["L"] * 12 * sz["h"] ** 2 + sz["V"] * sz["h"]


def attn_flops(sz: dict, kv_pairs: float) -> float:
    """Forward FLOPs of softmax attention over ``kv_pairs`` (query, key)
    pairs in all layers: QK^T and PV, 2*h each per pair."""
    return 4.0 * sz["L"] * sz["h"] * kv_pairs


def causal_pairs(n: int) -> float:
    """(query, key) pairs of one causal sequence of ``n`` tokens."""
    return n * (n + 1) / 2.0


def train_flops(sz: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one optimizer step: forward + backward = 3x forward
    for the products with weights (6N per token) and for attention."""
    tokens = batch * seq
    fwd_attn = attn_flops(sz, batch * causal_pairs(seq))
    return 6.0 * matmul_params(sz) * tokens + 3.0 * fwd_attn


def serve_flops(sz: dict, tokens: float, kv_pairs: float) -> float:
    """Model FLOPs of serving ``tokens`` token positions (prompt or
    generated) that attended to ``kv_pairs`` cached positions in all."""
    return 2.0 * matmul_params(sz) * tokens + attn_flops(sz, kv_pairs)


def flash_train_work(sz: dict, batch: int, seq: int) -> tuple:
    """(FLOPs, bytes) of causal attention forward + backward in one step.
    Backward is five products against the forward's two (2.5x); bytes are
    one read of q, k, v and one write of the context forward, and reads of
    q, k, v, o, do with writes of dq, dk, dv backward, in bf16."""
    fwd = attn_flops(sz, batch * causal_pairs(seq))
    tensor = batch * seq * sz["h"] * 2 * sz["L"]
    return 3.5 * fwd, (4 + 8) * tensor


def flash_prefill_work(sz: dict, prompt_lens) -> tuple:
    """(FLOPs, bytes) of causal attention over each prompt, unpadded."""
    pairs = sum(causal_pairs(n) for n in prompt_lens)
    tokens = sum(prompt_lens)
    return (attn_flops(sz, pairs),
            4 * tokens * sz["h"] * 2 * sz["L"])


def paged_decode_bytes(sz: dict, context_tokens: float, rows: float) -> float:
    """Bytes one decode step must move for attention: K and V (bf16) of
    every cached position the active slots attend to, in all layers, plus
    the new K and V rows written."""
    return (context_tokens + rows) * 2 * sz["h"] * 2 * sz["L"]


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
