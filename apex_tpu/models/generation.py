"""Autoregressive generation with KV caching for :class:`GPTModel`.

The reference ships no inference utilities (its `get_ltor_masks...` helper
is training-side), so this exceeds parity: jit-compiled incremental decoding
— one token per step, K/V written into preallocated caches, greedy or
temperature/top-k sampling — the standard TPU decode shape (static shapes,
``lax.scan`` over steps, no host round-trips inside the loop).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.utils.sharding import axis_size

__all__ = ["init_kv_caches", "init_paged_kv_caches", "decode_step",
           "generate", "cast_decode_params", "flatten_decode_caches",
           "preslice_layer_params", "split_gated_mlp_params",
           "is_gated_mlp_weight"]


def cast_decode_params(params, compute_dtype):
    """Cast fp32 params to the compute dtype ONCE for decoding — except
    MoE router weights, which stay fp32 (the router matmul reads fp32;
    rounding them would let decode pick different experts than the full
    forward near top-k boundaries). Inside a decode scan every layer's
    f32->bf16 weight cast is loop-invariant, but XLA re-materializes it
    per step (~0.3 GB/step at GPT-2 124M — the 154 MB tied embedding
    alone re-cast every token)."""
    from jax.tree_util import tree_map_with_path

    def cast(path, x):
        if any("router" in str(getattr(p, "key", p)) for p in path):
            return x
        return x.astype(compute_dtype) if x.dtype == jnp.float32 else x

    return tree_map_with_path(cast, params)


def is_gated_mlp_weight(path) -> bool:
    """Whether a ``tree_map_with_path`` path ends at a ``ParallelMLP``'s
    in-projection weight (``.../dense_h_to_4h/weight``)."""
    return [getattr(p, "key", None) for p in path[-2:]] == [
        "dense_h_to_4h", "weight"]


@jax.jit
def _halves_apart(w):
    """``[..., 2*ffn, h]`` interleaved -> ``[..., 2, ffn, h]``, as one
    program (called outside ``jit`` it makes one copy, not two)."""
    *lead, rows, h = w.shape
    return jnp.swapaxes(w.reshape(*lead, rows // 2, 2, h), -3, -2)


def split_gated_mlp_params(params, config):
    """Re-lay every gated ``ParallelMLP``'s ``dense_h_to_4h.weight`` ONCE
    from the interleaved ``[2*ffn, h]`` (rows ``gate_0, up_0, gate_1,
    ...``: the form of init, training and checkpoints) to halves apart,
    ``[2, ffn, h]`` (plane 0 = gate, plane 1 = up), stacked layers or a
    per-layer list alike. The interleaved product is sliced along a lane
    dim of 2, and on the chip XLA turns that into a copy of the whole
    weight in every program run (528 MB a step at ``dots-vlm1-inst``,
    three times the product it feeds: PERF.md section 6, PR 36);
    ``ParallelMLP.apply`` sees the form from the weight's rank. Returns
    ``(params, bytes)``, the bytes of weight now held halves apart. The
    identity (0 bytes) on a model that is not gated; a weight already
    apart is kept and counted; every other leaf is the same object."""
    from jax.tree_util import tree_map_with_path

    from apex_tpu.utils.activations import is_gated

    # (the supervisor hands over whatever config its model has; the
    # model checker's stub has no activation)
    if not is_gated(getattr(config, "activation", "")):
        return params, 0
    ffn = config.ffn_size
    apart = 0

    def relay(path, x):
        nonlocal apart
        if not is_gated_mlp_weight(path):
            return x
        if x.shape[-2] == 2 * ffn:
            x = _halves_apart(x)
        apart += x.size * x.dtype.itemsize
        return x

    return tree_map_with_path(relay, params), apart


def flatten_decode_caches(caches, num_layers: int):
    """Prefill caches -> the FLAT per-layer list form ``[(k, v)]`` of
    ``[b, S, h*d]`` — the fast decode form (see :func:`init_kv_caches`).
    Accepts the stacked ``(k, v)`` ``[L, b, h, S, d]`` pair or the
    per-layer list of 4D ``(k, v)`` pairs."""

    def fl(x):
        if x.ndim == 3:           # latent rows: flat as they are made
            return x
        b, h, s, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)

    if isinstance(caches, list):
        return [(fl(k), fl(v)) for k, v in caches]
    ck, cv = caches
    return [(fl(ck[i]), fl(cv[i])) for i in range(num_layers)]


def preslice_layer_params(params, num_layers: int):
    """Pre-slice stacked ``params['transformer']['layers']`` into a
    per-layer list behind an ``optimization_barrier``: inside a decode
    scan XLA re-slices (and lays out copies of) the stacked weights
    EVERY step (~115 us/step at GPT-2 124M bs8 — PERF.md round 5); the
    barrier pins the slices as buffers so XLA cannot sink them back.
    No-op when the params are already a list or have no stacked
    transformer layers (a model whose layers differ holds a per-layer
    list from the start: two parameter shapes cannot be stacked)."""
    if "transformer" not in params or "layers" not in params["transformer"]:
        return params
    lp = params["transformer"]["layers"]
    if isinstance(lp, (list, tuple)):
        return params
    params = dict(params)
    params["transformer"] = dict(params["transformer"])
    params["transformer"]["layers"] = jax.lax.optimization_barrier(
        [jax.tree.map(lambda x: x[i], lp) for i in range(num_layers)])
    return params


def _latent_widths(config):
    """Minor dims of a latent-attention layer's two caches: the latent
    ``c`` and the shared rotary key on whole lane tiles."""
    from apex_tpu.ops.decode_attention import latent_rope_lanes

    return (config.kv_lora_rank, latent_rope_lanes(config.qk_rope_head_dim))


def init_kv_caches(model, batch_size: int, max_len: int,
                   dtype=None, *, stacked: bool = True, flat: bool = False):
    """Preallocate K/V caches. ``stacked=True`` (default): ``(k, v)``, each
    ``[num_layers, batch, local_kv_heads, max_len, head_dim]`` — the scan
    form. ``stacked=False``: a LIST of per-layer ``(k, v)`` pairs, each
    ``[batch, local_kv_heads, max_len, head_dim]`` — the fast decode form
    (per-layer buffers update in place; scanning over a stacked cache
    pays full-cache slice/restack copies every step, measured 2.4x slower
    at bs8 — PERF.md round 4). ``stacked=False, flat=True``: the per-layer
    pairs are FLAT ``[batch, max_len, local_kv_heads * head_dim]`` — the
    fastest decode form (the 4D carry's minor dim is head_dim = half a
    128-lane tile, so XLA pads the cache 2x and reads it at ~50% HBM
    bandwidth; the flat minor dim stays full-lane — PERF.md round 5).
    ``generate()`` uses the flat list form. A latent-attention model
    (``kv_lora_rank``) has the list form only, each entry the flat ``(c
    [batch, max_len, rank], kR [batch, max_len, lanes])`` pair.

    Heads are K/V heads (``config.kv_heads``), which under GQA/MQA is
    ``num_query_groups``, not the query head count. Inside ``shard_map``
    with a bound tensor axis the head count is the TP-local slice
    (``kv_heads // tp``), matching the per-rank QKV shapes.
    """
    from apex_tpu.transformer.tensor_parallel.mappings import axis_bound

    c = model.config
    dtype = dtype or c.compute_dtype
    if c.latent_attention:
        # one row a token, no head axis: (c, kR) per layer, flat already
        if stacked:
            raise ValueError(
                "latent attention (kv_lora_rank) caches per-layer LIST "
                "entries: init_kv_caches(stacked=False)")
        return [tuple(jnp.zeros((batch_size, max_len, w), dtype)
                      for w in _latent_widths(c))
                for _ in range(c.num_layers)]
    heads = c.kv_heads                     # == query heads unless GQA/MQA
    if axis_bound(c.axis_name):
        tp = axis_size(c.axis_name)
        if heads % tp:
            raise ValueError(
                f"kv heads ({heads}) must be divisible by the "
                f"tensor-parallel size ({tp}); with GQA/MQA keep "
                f"num_query_groups a multiple of tp")
        heads //= tp
    per_layer = (batch_size, heads, max_len, c.head_dim)
    if not stacked:
        if flat:
            per_layer = (batch_size, max_len, heads * c.head_dim)
        return [(jnp.zeros(per_layer, dtype), jnp.zeros(per_layer, dtype))
                for _ in range(c.num_layers)]
    if flat:
        raise ValueError("flat=True is a per-layer (stacked=False) form")
    shape = (c.num_layers,) + per_layer
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_paged_kv_caches(model, n_pages: int, page_size: int, dtype=None,
                         *, quantized: bool = False):
    """Preallocate the PAGED decode cache: a list of per-layer
    ``(k_pages, v_pages)`` pairs, each ``[n_pages, page_size,
    local_kv_heads * head_dim]`` — the serving engine's page pool
    (docs/serving.md#paged-kv). The pool keeps
    the flat form's fused heads-minor dim (full-lane page reads, and the
    dim the sharded engine splits over the tensor axis); slots map onto
    pool rows through a host-owned page table, so HBM is committed to
    actual context length instead of ``max_slots * max_len``. Head count
    is TP-local inside ``shard_map``, exactly as in
    :func:`init_kv_caches`.

    ``quantized=True`` (``kv_dtype="int8"``,
    docs/serving.md#kv-quantization): pools are int8 and each of k/v
    nests as a ``(pages, scales)`` pair, ``scales`` the per-(page,
    kv-head) float32 sidecar ``[n_pages, local_kv_heads]`` the fused
    decode op dequantizes from — halving the decode-step HBM stream.

    A latent-attention model's pair is ``(c pool [n_pages, page_size,
    kv_lora_rank], kR pool [n_pages, page_size, lanes])``, one shared row
    a token, bf16 only (docs/serving.md#latent-kv)."""
    from apex_tpu.transformer.tensor_parallel.mappings import axis_bound

    c = model.config
    dtype = dtype or c.compute_dtype
    if c.latent_attention:
        # (c pool, kR pool): docs/serving.md#latent-kv
        if quantized:
            raise ValueError(
                "kv_dtype='int8' quantizes a page per KV head; latent "
                "attention (kv_lora_rank) rows have no head axis")
        return [tuple(jnp.zeros((n_pages, page_size, w), dtype)
                      for w in _latent_widths(c))
                for _ in range(c.num_layers)]
    heads = c.kv_heads
    if axis_bound(c.axis_name):
        tp = axis_size(c.axis_name)
        if heads % tp:
            raise ValueError(
                f"kv heads ({heads}) must be divisible by the "
                f"tensor-parallel size ({tp}); with GQA/MQA keep "
                f"num_query_groups a multiple of tp")
        heads //= tp
    shape = (n_pages, page_size, heads * c.head_dim)
    if quantized:
        sshape = (n_pages, heads)
        return [((jnp.zeros(shape, jnp.int8),
                  jnp.zeros(sshape, jnp.float32)),
                 (jnp.zeros(shape, jnp.int8),
                  jnp.zeros(sshape, jnp.float32)))
                for _ in range(c.num_layers)]
    return [(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
            for _ in range(c.num_layers)]


def _gather_vocab(logits: jax.Array, axis_name: str) -> jax.Array:
    """Vocab-parallel logits -> full vocab (argmax/categorical need global
    token ids; shard-local winners would be garbage under TP)."""
    from apex_tpu.transformer.tensor_parallel.mappings import axis_bound

    if axis_bound(axis_name):
        logits = lax.all_gather(logits, axis_name, axis=-1, tiled=True)
    return logits


def _cached_forward(model, params, caches, tokens: jax.Array, index,
                    last_only: bool = False, last_index=None,
                    paged_state=None, lora=None, routing=None):
    """Run ``tokens`` [batch, s] occupying cache slots [index, index+s) ->
    (fp32 full-vocab logits [s, batch, V], new caches). ``last_only``:
    compute the LM head for the FINAL position only (returns [1, b, V]) —
    a 1024-token prefill otherwise materializes [s, b, V] fp32 logits
    (1.65 GB at GPT-2 vocab) of which sampling reads one row.
    ``last_index`` (scalar, may be traced): compute the LM head for that
    SINGLE sequence position instead — the bucketed-prefill form, where
    the prompt is right-padded to a bucket length and the last real token
    sits mid-sequence. ``index`` may be a ``[batch]`` vector of per-row
    cache offsets (continuous-batching decode over FLAT caches): each
    row then reads its own learned-position rows / rope angles and
    writes K/V at its own offset. ``routing``: a
    ``transformer.moe.RoutingStats`` for the routed layers' counts."""
    c = model.config
    emb_p = params["embedding"]
    s = tokens.shape[1]
    emb = model.embedding.apply(emb_p["word_embeddings"], tokens)  # [b,s,h]
    if c.embedding_multiplier != 1.0:
        emb = emb.astype(jnp.float32) * c.embedding_multiplier
    if c.position_embedding_type == "learned":
        if getattr(index, "ndim", 0) == 1:
            positions = index[:, None] + jnp.arange(s)[None, :]    # [b, s]
            pos = jnp.take(emb_p["position_embeddings"], positions,
                           axis=0)                                 # [b,s,h]
            emb = emb + pos
        else:
            pos = lax.dynamic_slice_in_dim(emb_p["position_embeddings"],
                                           index, s, axis=0)       # [s, h]
            emb = emb + pos[None]
    # (rope rotates q/k inside attention at offset ``index``; nothing to add)
    hidden = emb.transpose(1, 0, 2)                                 # [s,b,h]
    hidden = hidden.astype(c.compute_dtype)
    hidden, new_caches = model.transformer.apply(
        params["transformer"], hidden, kv_caches=caches, cache_index=index,
        paged_state=paged_state, lora=lora, routing=routing)
    from apex_tpu.models.gpt import lm_head_loss, output_weight
    if last_only:
        hidden = hidden[-1:]
    elif last_index is not None:
        hidden = lax.dynamic_slice_in_dim(hidden, last_index, 1, axis=0)
    logits = lm_head_loss(output_weight(params, c), hidden, None, None, c)
    logits = _gather_vocab(logits, c.axis_name)
    return logits.astype(jnp.float32), new_caches


def decode_step(model, params, caches, tokens: jax.Array, index,
                paged_state=None, lora=None, routing=None):
    """One incremental step: ``tokens`` [batch] at position ``index`` ->
    (fp32 full-vocab logits [batch, V], updated caches). ``caches`` is
    either form :func:`init_kv_caches` produces — the stacked ``(k, v)``
    pair or the per-layer list (the form ``generate()`` decodes with) —
    and the return matches the input form. ``index`` may be a ``[batch]``
    vector of per-row positions on the FLAT list form (continuous
    batching — the serving engine's batched decode over independent
    slots). With ``paged_state`` (a ``[batch, pages_per_slot]`` page
    table) ``caches`` is the :func:`init_paged_kv_caches` pool list and
    ``index`` MUST be the per-row position vector. MoE models route
    drop-free on the cache path (prefill and decode; see
    :func:`generate`)."""
    logits, new_caches = _cached_forward(model, params, caches,
                                         tokens[:, None], index,
                                         paged_state=paged_state, lora=lora,
                                         routing=routing)
    return logits[0], new_caches


def generate(model, params, prompt: jax.Array, max_new_tokens: int, *,
             max_len: Optional[int] = None, temperature: float = 0.0,
             top_k: Optional[int] = None,
             rng: Optional[jax.Array] = None,
             eos_token: Optional[int] = None) -> jax.Array:
    """Generate ``[batch, prompt_len + max_new_tokens]`` token ids.

    ``temperature == 0`` is greedy; otherwise softmax sampling (optionally
    truncated to ``top_k`` logits) with ``rng``. ``eos_token`` freezes
    finished rows (they keep emitting ``eos_token``). Fully jittable; decode
    runs as one ``lax.scan``.

    MoE models route DROP-FREE on the whole generation path — batched
    prefill and single-token decode alike (round 5; factor-based capacity
    drops are a training-time load-balancing trade) — so cached logits
    match the drop-free serving forward
    (``model.apply(..., moe_drop_free=True)``) at ANY
    ``moe_capacity_factor``: no capacity-induced divergence remains. (As
    in any MoE system, a router whose top-k gap for some token is below
    the numerical noise between two differently-shaped computations can
    still flip that token's expert; trained routers are confident,
    random-init ones are not.)
    """
    if max_new_tokens < 1:
        # max_new_tokens=0 would make total == prompt_len, so the
        # out.at[:, prompt_len] first-token write silently clamps onto the
        # last prompt slot — reject instead of corrupting the prompt
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if top_k is not None and top_k < 1:
        # lax.top_k(logits, 0) would yield an empty kth slice (and a
        # shape error only deep inside the sampling trace)
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if temperature > 0.0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng")
    # pre-cast fp32 params to the compute dtype ONCE (decode is inference;
    # bf16 weights are the standard serving precision). The barrier pins
    # the cast params as materialized buffers; without it XLA sinks the
    # (loop-invariant) casts back into the scan body.
    # A gated dense layer's gate/up weight is re-laid halves apart beside
    # the cast, once a call (split_gated_mlp_params).
    c = model.config
    params, _ = split_gated_mlp_params(params, c)
    if c.compute_dtype != jnp.float32:
        params = jax.lax.optimization_barrier(
            cast_decode_params(params, c.compute_dtype))
    b, prompt_len = prompt.shape
    total = prompt_len + max_new_tokens
    if (model.config.position_embedding_type == "learned"
            and total > model.config.max_position_embeddings):
        raise ValueError(
            f"prompt + new tokens ({total}) exceeds "
            f"max_position_embeddings "
            f"({model.config.max_position_embeddings}); the clamped "
            "position lookup would silently repeat the last row")
    S = max_len or total
    if S < total:
        raise ValueError(f"max_len {S} < prompt+new tokens {total}")
    # prefill runs on the per-layer LIST form (unrolled layer loop): the
    # stacked form's scan re-slices and re-stacks the whole [L, ...]
    # cache every layer (~2 ms of a ~20 ms 124M bs8 prefill — PERF.md
    # round 5); the deeper unrolled HLO is a one-time compile cost
    caches = init_kv_caches(model, b, S, stacked=False)
    params = preslice_layer_params(params, c.num_layers)
    rng = jax.random.PRNGKey(0) if rng is None else rng

    out = jnp.zeros((b, total), prompt.dtype)
    out = out.at[:, :prompt_len].set(prompt)

    def pick_next(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)
        logits = logits / temperature
        if top_k is not None:
            kth = lax.top_k(logits, top_k)[0][:, -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        return jax.random.categorical(key, logits).astype(prompt.dtype)

    # batched prefill: one forward writes all prompt K/V; its last-position
    # logits produce the first generated token
    prefill_logits, caches = _cached_forward(model, params, caches, prompt,
                                             0, last_only=True)
    # convert ONCE into the FLAT per-layer form for the decode scan
    # ([b, S, h*d] keeps the cache minor dim full-lane — PERF.md round 5)
    caches = flatten_decode_caches(caches, c.num_layers)
    first = pick_next(prefill_logits[-1], jax.random.fold_in(rng, 0))
    out = out.at[:, prompt_len].set(first)
    done0 = ((first == eos_token) if eos_token is not None
             else jnp.zeros((b,), bool))
    if max_new_tokens == 1:
        return out

    def step(carry, i):
        # i = absolute position of the token being fed (already written)
        caches, out, done = carry
        token = lax.dynamic_index_in_dim(out, i, axis=1, keepdims=False)
        logits, caches = decode_step(model, params, caches, token, i)
        nxt = pick_next(logits, jax.random.fold_in(rng, i))
        if eos_token is not None:
            nxt = jnp.where(done, eos_token, nxt)
            done = jnp.logical_or(done, nxt == eos_token)
        out = lax.dynamic_update_slice(out, nxt[:, None], (0, i + 1))
        return (caches, out, done), None

    (caches, out, _), _ = lax.scan(
        step, (caches, out, done0), jnp.arange(prompt_len, total - 1))
    return out
