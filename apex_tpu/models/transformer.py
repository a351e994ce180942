"""Megatron-style parallel transformer blocks, TPU-native.

Capability counterpart of the reference's Megatron LM building blocks
(``apex/transformer/testing/standalone_transformer_lm.py``: ``ParallelMLP``
~:610-672, ``ParallelAttention`` ~:675-884, ``ParallelTransformerLayer``
~:1033-1148, ``ParallelTransformer`` ~:1151-1380), built on the
tensor/sequence-parallel layers of :mod:`apex_tpu.transformer.tensor_parallel`.

Design (not a port):

- modules are functional: ``init(key) -> params`` (global shapes),
  ``spec() -> PartitionSpec`` pytree, ``apply(params, ...)`` written against
  the local-shard view inside ``shard_map`` (identical code runs unsharded).
- layout is Megatron's ``[seq, batch, hidden]``; under sequence parallelism
  dim 0 holds the local sequence shard between matmul regions.
- core attention is the Pallas flash kernel (``apex_tpu.ops.flash_attention``)
  when the mask is causal/lengths-shaped and attention dropout is off;
  otherwise the :class:`FusedScaleMaskSoftmax` path with dropout, matching
  the reference's kernel-availability dispatch
  (``functional/fused_softmax.py:222-248``).
- layer stacking is ``lax.scan`` over stacked per-layer params — one trace,
  one compile, regardless of depth; optional ``jax.checkpoint`` per layer is
  the activation-recompute story (reference
  ``tensor_parallel/random.py:~240-311``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from apex_tpu.observability.tracing import (SCOPE_ATTENTION, SCOPE_MLA,
                                            SCOPE_MLA_ABSORB,
                                            SCOPE_MLA_PREFILL, SCOPE_MLP)
from apex_tpu.ops.attention import flash_chunk_fwd
from apex_tpu.ops.rope import (YarnScaling, fused_rope_cached,
                               yarn_inv_freq, yarn_mscale)
from apex_tpu.ops import (
    flash_attention,
    flash_attention_packed,
    packed_attention_supported,
    fused_layer_norm_affine,
    fused_rms_norm_affine,
)
from apex_tpu.transformer.enums import AttnMaskType, AttnType, LayerType
from apex_tpu.transformer.functional import FusedScaleMaskSoftmax
from apex_tpu.transformer.parallel_state import CONTEXT_AXIS, TENSOR_AXIS
from apex_tpu.transformer.tensor_parallel.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
)
from apex_tpu.transformer.tensor_parallel.mappings import (
    axis_size,
    mark_sequence_parallel_parameter,
)
from apex_tpu.transformer.tensor_parallel.random import model_parallel_rng_key
from apex_tpu.transformer.tensor_parallel.utils import divide
from apex_tpu.utils.profiling import nvtx_range
from apex_tpu.utils.activations import (
    apply_activation,
    gated_product,
    is_gated,
    validate_activation,
)

__all__ = [
    "TransformerConfig",
    "ParallelMLP",
    "ParallelAttention",
    "LatentAttention",
    "ParallelTransformerLayer",
    "ParallelTransformer",
]


@dataclass(frozen=True)
class TransformerConfig:
    """Hyperparameters (subset of the reference's Megatron global args,
    ``apex/transformer/testing/arguments.py``, that shape the model)."""

    num_layers: int
    hidden_size: int
    num_attention_heads: int
    # GQA/MQA (exceeds reference): number of K/V head groups; None = MHA.
    # Query heads are split evenly over the groups; the flash kernel reads
    # shared K/V blocks per group with no HBM broadcast.
    num_query_groups: Optional[int] = None
    ffn_hidden_size: Optional[int] = None
    vocab_size: int = 32000
    max_position_embeddings: int = 2048
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layernorm_epsilon: float = 1e-5
    # "learned" (reference GPT/BERT fixtures), "rope" (rotary via the fused
    # rope op applied to q/k inside attention, the NeMo/Megatron fused_rope
    # capability in real use), or "none"
    position_embedding_type: str = "learned"
    rotary_percent: float = 1.0        # fraction of head_dim rotated
    rope_theta: float = 10000.0
    # MLP activation: "gelu" (reference ParallelMLP), "relu", or the gated
    # pairs "swiglu"/"geglu" (LLaMA/PaLM-class; one fused bias-free 2*ffn
    # column projection, gate/up unit-interleaved — utils/activations.py)
    activation: str = "gelu"
    # "layernorm" (reference) or "rmsnorm" (LLaMA-class; bias-free, RMS
    # statistics via the fused Pallas RMSNorm kernel)
    normalization: str = "layernorm"
    attn_mask_type: AttnMaskType = AttnMaskType.causal
    # Mistral-class local attention: keep only the last sliding_window keys
    # per query (causal only); far-past flash blocks are skipped, cost
    # O(seq * window). None = full attention.
    sliding_window: Optional[int] = None
    sequence_parallel: bool = False
    # context parallelism (long-context; the reference has none, SURVEY.md §5):
    # None | "ring" (ppermute KV rotation) | "ulysses" (all-to-all head swap)
    context_parallel_method: Optional[str] = None
    context_axis: str = CONTEXT_AXIS
    # MoE (exceeds reference, SURVEY.md §2.2 EP: absent): when set, every
    # layer's MLP becomes a SwitchMLP with this many experts; apply() then
    # returns (hidden, aux_loss)
    num_moe_experts: Optional[int] = None
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 1e-2
    moe_router_jitter: float = 0.0
    moe_expert_axis: Optional[str] = None   # e.g. "data" for EP over DP
    # activation recompute: False = save everything; True/'full' = full
    # per-layer recompute (reference `tensor_parallel.random.checkpoint`
    # semantics); 'selective' = save matmul outputs, recompute elementwise
    # (Megatron's selective activation recompute, expressed as a
    # jax.checkpoint dot-saveable policy instead of hand-split forward)
    recompute: Any = False
    # lax.scan unroll factor for the layer stack: >1 trades compile time
    # for fewer while-loop iterations and cross-layer fusion of the
    # activation-save writes (the dynamic-update-slice traffic)
    scan_unroll: int = 1
    # compute the LM-head loss in this many sequence chunks (remat'd scan)
    # so only one chunk's [s/nc, b, V] logits ever materialize — the
    # long-context memory guard for the vocab head (no-op at 1, under SP,
    # or when the sequence does not divide evenly)
    loss_seq_chunks: int = 1
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32  # activations cast at block entry
    init_method_std: float = 0.02
    axis_name: str = TENSOR_AXIS
    # -- layers of more than one kind, and the sparse-expert serving block
    # -- (docs/moe.md). Every default below leaves a model as it was.
    # head size where it is not hidden / heads (Megatron's kv_channels)
    kv_channels: Optional[int] = None
    # attention kind of each layer: "sliding" (the last `sliding_window`
    # keys, rotary positions where the model has them) or "full" (every
    # key, NO rotary positions); None = every layer alike, windowed iff
    # `sliding_window` is set
    attention_layer_types: Optional[Tuple[str, ...]] = None
    add_bias_linear: bool = True       # False: no bias on any projection
    qk_layernorm: bool = False         # RMSNorm over each head of q and k
    attention_output_gate: bool = False  # ctx * sigmoid(x W_gate) before W_o
    # four norms a block: h += N(attn(N(h))); h += N(mlp(N(h)))
    sandwich_norm: bool = False
    embedding_multiplier: float = 1.0
    untie_embeddings_and_output_weights: bool = False
    # routed experts (transformer/moe.py RoutedExperts): the leading
    # `num_dense_layers` layers keep the dense MLP, the rest route
    num_routed_experts: Optional[int] = None
    routed_top_k: int = 1
    routed_ffn_hidden_size: Optional[int] = None   # one expert's width
    route_scale: float = 1.0
    num_shared_experts: int = 0
    routed_expert_range: Optional[Tuple[int, int]] = None   # held here
    num_dense_layers: int = 0
    # group-limited selection (DeepSeek-V3's noaux_tc): the experts in
    # `routed_num_groups` consecutive groups, a group's score the sum of
    # its two best, the top-k taken inside the best `routed_topk_groups`
    routed_num_groups: int = 1
    routed_topk_groups: int = 1
    # -- latent attention (MLA, docs/serving.md#latent-kv): set
    # -- `kv_lora_rank` and every layer's attention is LatentAttention:
    # low-rank q and kv projections with their RMSNorms, `qk_rope_head_dim`
    # rotary channels shared by all heads, and a cache of one row of
    # `kv_lora_rank + qk_rope_head_dim` values a token (no head axis)
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: Optional[int] = None
    qk_rope_head_dim: Optional[int] = None
    v_head_dim: Optional[int] = None
    # YaRN frequencies for the rotary channels of latent attention, and
    # its share in the softmax scale (ops/rope.py)
    rope_yarn: Optional[YarnScaling] = None

    def __post_init__(self):
        if self.position_embedding_type not in ("learned", "rope", "none"):
            raise ValueError(
                f"position_embedding_type must be 'learned', 'rope', or "
                f"'none', got {self.position_embedding_type!r}")
        if not 0.0 < self.rotary_percent <= 1.0:
            raise ValueError(
                f"rotary_percent must be in (0, 1], got "
                f"{self.rotary_percent}")
        validate_activation(self.activation)
        if self.normalization not in ("layernorm", "rmsnorm"):
            raise ValueError(
                f"normalization must be 'layernorm' or 'rmsnorm', got "
                f"{self.normalization!r}")
        if self.sliding_window is not None:
            if self.sliding_window < 1:
                raise ValueError(
                    f"sliding_window must be >= 1, got "
                    f"{self.sliding_window}")
            if self.attn_mask_type != AttnMaskType.causal:
                raise ValueError("sliding_window requires causal attention")
            # under context parallelism the window is exact across chunk
            # boundaries: ring masks with global positions, ulysses windows
            # the gathered full sequence
        kinds = self.attention_layer_types
        if kinds is not None:
            if len(kinds) != self.num_layers:
                raise ValueError(
                    f"attention_layer_types has {len(kinds)} entries for "
                    f"num_layers = {self.num_layers}")
            bad = sorted(set(kinds) - {"full", "sliding"})
            if bad:
                raise ValueError(
                    f"attention_layer_types entries must be 'full' or "
                    f"'sliding', got {bad}")
            if "sliding" in kinds and self.sliding_window is None:
                raise ValueError(
                    "attention_layer_types names 'sliding' layers but "
                    "sliding_window is not set")
            if self.attn_mask_type != AttnMaskType.causal:
                raise ValueError(
                    "attention_layer_types requires causal attention "
                    "(attn_mask_type)")
        if self.num_routed_experts:
            if self.num_moe_experts:
                raise ValueError(
                    "num_routed_experts (the serving layer) and "
                    "num_moe_experts (the training layer) are exclusive")
            if not self.routed_ffn_hidden_size:
                raise ValueError(
                    "num_routed_experts needs routed_ffn_hidden_size (one "
                    "routed expert's width)")
            if not 0 <= self.num_dense_layers <= self.num_layers:
                raise ValueError(
                    f"num_dense_layers ({self.num_dense_layers}) must lie "
                    f"in 0..num_layers ({self.num_layers})")
        if self.latent_attention:
            sizes = ("q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                     "v_head_dim")
            missing = [k for k in sizes if not getattr(self, k)]
            if missing:
                raise ValueError(
                    f"latent attention (kv_lora_rank) needs {missing}")
            if self.qk_rope_head_dim % 2:
                raise ValueError(
                    f"qk_rope_head_dim ({self.qk_rope_head_dim}) must be "
                    f"even: rotary channels come in pairs")
            if self.position_embedding_type != "rope":
                raise ValueError(
                    "latent attention carries its positions in the shared "
                    "rotary key: position_embedding_type must be 'rope'")
            for key, off in (("attention_layer_types", None),
                             ("sliding_window", None),
                             ("num_query_groups", None),
                             ("qk_layernorm", False),
                             ("attention_output_gate", False),
                             ("context_parallel_method", None),
                             ("sequence_parallel", False)):
                if getattr(self, key) != off:
                    raise ValueError(
                        f"latent attention (kv_lora_rank) does not take "
                        f"{key}")
            if self.attn_mask_type != AttnMaskType.causal:
                raise ValueError("latent attention is causal only")
        elif self.rope_yarn is not None:
            raise ValueError(
                "rope_yarn scales the rotary channels of latent attention "
                "(kv_lora_rank); no other attention kind reads it")

    @property
    def ffn_size(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank is not None

    @property
    def head_dim(self) -> int:
        if self.kv_channels is not None:
            return self.kv_channels
        return divide(self.hidden_size, self.num_attention_heads)

    @property
    def layer_kinds(self) -> Tuple[Tuple[str, str], ...]:
        """``(attention kind, feed-forward kind)`` of each layer:
        ``"default" | "full" | "sliding"`` and ``"dense" | "routed"``."""
        attn = self.attention_layer_types or ("default",) * self.num_layers
        dense = (self.num_dense_layers if self.num_routed_experts
                 else self.num_layers)
        return tuple((a, "dense" if i < dense else "routed")
                     for i, a in enumerate(attn))

    @property
    def mixed_layers(self) -> bool:
        """More than one kind of layer: the parameters are a per-layer
        LIST (two shapes cannot be stacked) and the stack runs unrolled."""
        return len(set(self.layer_kinds)) > 1

    @property
    def kv_heads(self) -> int:
        """K/V heads per replica (== query heads unless GQA/MQA)."""
        if self.num_query_groups is None:
            return self.num_attention_heads
        divide(self.num_attention_heads, self.num_query_groups)  # validates
        return self.num_query_groups

    @property
    def rotary_dim(self) -> int:
        """Even number of head-dim channels rotated by RoPE (≥ 2; a
        rotary_percent low enough to round below 2 is rejected)."""
        rot = int(self.head_dim * self.rotary_percent)
        rot -= rot % 2
        if rot < 2:
            raise ValueError(
                f"rotary_percent ({self.rotary_percent}) rotates fewer than "
                f"2 of {self.head_dim} head-dim channels; use "
                f"position_embedding_type='none' to disable rotation")
        return rot

    def init_method(self) -> Callable:
        std = self.init_method_std
        return jax.nn.initializers.normal(stddev=std)

    def output_init_method(self) -> Callable:
        # Megatron scales residual-output layer init by 1/sqrt(2*L)
        # (standalone_transformer_lm.py `scaled_init_method_normal`).
        std = self.init_method_std / (2.0 * self.num_layers) ** 0.5
        return jax.nn.initializers.normal(stddev=std)


def position_table_params(config: "TransformerConfig", key) -> dict:
    """Learned-position table params, or ``{}`` under rope/none — the one
    shared guard every model's ``init`` uses so param trees stay consistent
    across GPT/BERT/encoder-decoder/pipelined for the same config."""
    if config.position_embedding_type != "learned":
        return {}
    return {"position_embeddings": config.init_method()(
        key, (config.max_position_embeddings, config.hidden_size),
        config.params_dtype)}


def position_table_spec(config: "TransformerConfig") -> dict:
    if config.position_embedding_type != "learned":
        return {}
    return {"position_embeddings": PartitionSpec()}


def rope_freqs(start, length: int, rot_dim: int, theta: float) -> jax.Array:
    """RoPE angles for positions ``[start, start+length)`` in the layout
    :func:`apex_tpu.ops.fused_rope` expects: ``[s, 1, 1, rot_dim]`` with the
    Megatron ``concat(f, f)`` convention (reference
    ``apex/transformer/functional/fused_rope.py`` pairs with
    ``RotaryEmbedding`` in NeMo producing exactly this). ``start`` may be a
    traced value (decode offset, context-parallel shard offset), or a
    ``[batch]`` VECTOR of per-row offsets (the serving engine's
    continuous-batching decode, where every cache slot sits at its own
    position) — then the return is ``[s, batch, 1, rot_dim]``, which
    broadcasts against ``[s, b, h, d]`` q/k exactly like the scalar form."""
    inv = 1.0 / theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                          / rot_dim)
    if getattr(start, "ndim", 0) == 1:
        pos = (jnp.asarray(start, jnp.float32)[None, :]
               + jnp.arange(length, dtype=jnp.float32)[:, None])  # [s, b]
        f = pos[:, :, None] * inv[None, None, :]      # [s, b, rot_dim/2]
        return jnp.concatenate([f, f], axis=-1)[:, :, None, :]
    pos = start + jnp.arange(length, dtype=jnp.float32)
    f = pos[:, None] * inv[None, :]                   # [s, rot_dim/2]
    return jnp.concatenate([f, f], axis=-1)[:, None, None, :]


def _dropout(x, rate, key, deterministic, model_parallel_region, axis_name):
    """Dropout with Megatron RNG semantics: inside model-parallel regions
    each TP rank draws a distinct mask (reference
    ``tensor_parallel/random.py:90-240``); in replicated regions all ranks
    draw the same mask."""
    if deterministic or rate == 0.0 or key is None:
        return x
    if model_parallel_region:
        key = model_parallel_rng_key(key, axis_name)
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), jnp.zeros_like(x))


def embed_tokens(embedding, emb_params, tokens, config, *, tokentype_params=None,
                 tokentype_ids=None, rng=None, deterministic=True):
    """Shared embedding pipeline: word + position (+ tokentype) lookups,
    [b,s,h] -> [s,b,h] transpose, SP scatter, embedding dropout (reference
    ``standalone_transformer_lm.py`` ``Embedding.forward``)."""
    c = config
    from apex_tpu.transformer.tensor_parallel.mappings import axis_bound

    emb = embedding.apply(emb_params["word_embeddings"], tokens)
    if c.embedding_multiplier != 1.0:
        emb = emb.astype(jnp.float32) * c.embedding_multiplier
    s_local = tokens.shape[1]
    if c.position_embedding_type == "learned":
        if c.context_parallel_method and axis_bound(c.context_axis):
            # tokens are this context rank's contiguous sequence chunk:
            # position ids start at rank * s_local. dynamic_slice clamps
            # out-of-range starts, so overlong sequences must be rejected
            # loudly here (the unsharded path fails with a shape error
            # instead).
            cp = axis_size(c.context_axis)
            if cp * s_local > c.max_position_embeddings:
                raise ValueError(
                    f"global sequence length ({cp} context shards x "
                    f"{s_local}) exceeds max_position_embeddings "
                    f"({c.max_position_embeddings})")
            offset = lax.axis_index(c.context_axis) * s_local
            pos = lax.dynamic_slice_in_dim(
                emb_params["position_embeddings"], offset, s_local, axis=0)
        else:
            pos = emb_params["position_embeddings"][:s_local]
        emb = emb + pos[None, :, :]
    if tokentype_ids is not None:
        emb = emb + jnp.take(tokentype_params, tokentype_ids, axis=0)
    hidden = emb.transpose(1, 0, 2).astype(c.compute_dtype)
    if c.sequence_parallel:
        from apex_tpu.transformer.tensor_parallel.mappings import (
            scatter_to_sequence_parallel_region,
        )
        hidden = scatter_to_sequence_parallel_region(hidden, c.axis_name)
    return _dropout(hidden, c.hidden_dropout, rng, deterministic,
                    model_parallel_region=c.sequence_parallel,
                    axis_name=c.axis_name)


def _ln_params(hidden_size, dtype, norm: str = "layernorm"):
    p = {"weight": jnp.ones((hidden_size,), dtype)}
    if norm == "layernorm":
        p["bias"] = jnp.zeros((hidden_size,), dtype)
    return p


def _ln_spec(norm: str = "layernorm"):
    s = {"weight": PartitionSpec()}
    if norm == "layernorm":
        s["bias"] = PartitionSpec()
    return s


def _ln(params, x, eps, sequence_parallel=False, axis_name=TENSOR_AXIS,
        norm: str = "layernorm"):
    w = params["weight"]
    if sequence_parallel:
        # norm runs on sequence shards; psum the param grads (reference
        # layer_norm.py:26-99 ``sequence_parallel_enabled`` marking)
        w = mark_sequence_parallel_parameter(w, axis_name)
    # out_dtype=x.dtype: the consumer (QKV/MLP GEMM, residual add) runs in
    # the compute dtype, so promote-to-fp32 output (bf16 x, fp32 norm
    # params) would write 2x the bytes only for a convert to follow —
    # measured ~3 ms/step of fp32 LN writes + converts on BERT (round 5)
    if norm == "rmsnorm":
        return fused_rms_norm_affine(x, w, (x.shape[-1],), eps,
                                     out_dtype=x.dtype)
    b = params["bias"]
    if sequence_parallel:
        b = mark_sequence_parallel_parameter(b, axis_name)
    return fused_layer_norm_affine(x, w, b, (x.shape[-1],), eps,
                                   out_dtype=x.dtype)


def _head_rms(x, weight, eps):
    """RMSNorm over the last (head) dim of ``[..., heads, dh]`` with one
    learned ``[dh]`` weight for all heads: float32 statistics, the input's
    dtype out (the q/k norm of ``qk_layernorm``)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)
            * weight.astype(jnp.float32)).astype(x.dtype)


def _lora_delta(x, lora):
    """Per-slot low-rank delta for one target projection: ``(x @ A) @ B``
    with PER-BATCH-ELEMENT factors — ``x [s, b, h]``, ``A [b, h, r]``,
    ``B [b, r, out(_local)]`` -> ``[s, b, out]``. The serving engine
    gathers each slot's factors from the adapter bank by adapter index
    (apex_tpu.lora; the null row is all-zeros, so base-traffic slots add
    an exact 0). Math in fp32 — the factors train in fp32 and rank is
    tiny, so the two skinny GEMMs round once at the final cast."""
    xf = x.astype(jnp.float32)
    d = jnp.einsum("sbh,bhr->sbr", xf, lora["A"].astype(jnp.float32))
    return jnp.einsum("sbr,bro->sbo", d, lora["B"].astype(jnp.float32))


@dataclass
class ParallelMLP:
    """h -> ffn (column) -> act -> h (row).

    Reference: ``standalone_transformer_lm.py`` ``ParallelMLP`` (~:610-672):
    ColumnParallelLinear with ``gather_output=False``, fused bias-gelu,
    RowParallelLinear with ``input_is_parallel=True``. Gated activations
    (``config.activation = "swiglu"/"geglu"``, LLaMA/PaLM-class — exceeds
    the gelu-only reference) widen the column projection to ``2*ffn`` with
    gate/up **unit-interleaved** along the output dim (column ``2i`` =
    gate_i, ``2i+1`` = up_i), so one matmul + one input-grad collective
    serves both halves and every TP slice holds matched pairs.

    That interleaved ``[2*ffn, h]`` weight is the form of ``init``,
    ``spec()``, training, checkpoints and whatever a caller hands over.
    The serving side (``InferenceEngine``, ``generate()``) re-lays it
    once at intake to halves apart, ``[2, ffn, h]`` (plane 0 = gate,
    plane 1 = up; ``models.generation.split_gated_mlp_params``), because
    on the chip the interleaved product is sliced along a lane dim of 2
    and XLA pays for that with a copy of the whole weight every program
    run. ``apply`` tells the two by the weight's rank and computes the
    same ``act(x Wg) * (x Wu)`` from either; a TP slice of the ``ffn``
    axis holds matched pairs in both.
    """

    config: TransformerConfig

    def __post_init__(self):
        c = self.config
        self.gated = is_gated(c.activation)
        # gated projections are bias-free (LLaMA convention; the pre-fusion
        # gate_proj had bias=False — the fused layout keeps that invariant
        # for both halves)
        self.dense_h_to_4h = ColumnParallelLinear(
            c.hidden_size, (2 if self.gated else 1) * c.ffn_size,
            gather_output=False,
            bias=not self.gated and c.add_bias_linear,
            init_method=c.init_method(),
            sequence_parallel_enabled=c.sequence_parallel,
            params_dtype=c.params_dtype, axis_name=c.axis_name)
        self.dense_4h_to_h = RowParallelLinear(
            c.ffn_size, c.hidden_size, input_is_parallel=True,
            bias=c.add_bias_linear,
            init_method=c.output_init_method(),
            sequence_parallel_enabled=c.sequence_parallel,
            params_dtype=c.params_dtype, axis_name=c.axis_name)

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"dense_h_to_4h": self.dense_h_to_4h.init(k1),
                "dense_4h_to_h": self.dense_4h_to_h.init(k2)}

    def spec(self):
        return {"dense_h_to_4h": self.dense_h_to_4h.spec(),
                "dense_4h_to_h": self.dense_4h_to_h.spec()}

    def apply(self, params, hidden, *, lora=None):
        c = self.config
        p_in = params["dense_h_to_4h"]
        w = p_in["weight"]
        apart = w.ndim == 3
        if apart:
            # halves apart (the serving side's form): one product over
            # the [2*ffn, h] view of the major dims, split at lane ffn
            ffn = w.shape[1]
            p_in = {"weight": w.reshape(2 * ffn, w.shape[2])}
        x = self.dense_h_to_4h.apply(p_in, hidden)
        if lora is not None:
            d = _lora_delta(hidden, lora).astype(x.dtype)
            if apart:
                # the delta's columns are interleaved, as B's are
                d = jnp.swapaxes(d.reshape(*d.shape[:-1], ffn, 2),
                                 -1, -2).reshape(d.shape)
            x = x + d
        if apart:
            x = gated_product(x[..., :ffn], x[..., ffn:], c.activation)
        else:
            x = apply_activation(x, c.activation)
        return self.dense_4h_to_h.apply(params["dense_4h_to_h"], x)


@dataclass
class ParallelAttention:
    """Self- or cross-attention with TP-sharded heads.

    Reference: ``standalone_transformer_lm.py`` ``ParallelAttention``
    (~:675-884): fused QKV ColumnParallelLinear (``gather_output=False``) for
    self-attention, separate Q and fused KV projections for cross-attention
    (``attention_type == AttnType.cross_attn`` branch), per-rank head slice,
    core attention (fused softmax + dropout + BMMs or flash),
    RowParallelLinear output projection.
    """

    config: TransformerConfig
    attn_type: Any = AttnType.self_attn
    #: "default" (the config's one window), or this layer's kind in a
    #: model whose layers differ: "full" | "sliding"
    layer_kind: str = "default"

    def __post_init__(self):
        c = self.config
        self.window = (None if self.layer_kind == "full"
                       else c.sliding_window)
        self.rope = (c.position_embedding_type == "rope"
                     and self.layer_kind != "full")
        proj = c.num_attention_heads * c.head_dim
        if self.attn_type == AttnType.self_attn:
            # fused QKV, grouped layout [g0: qpg·dh + k·dh + v·dh | g1: ...]
            # so a TP slice holds whole K/V groups (Megatron fuses the same
            # way for plain MHA; the grouped layout generalizes it to GQA)
            qpg = c.num_attention_heads // c.kv_heads
            qkv_size = c.kv_heads * (qpg + 2) * c.head_dim
            self.query_key_value = ColumnParallelLinear(
                c.hidden_size, qkv_size, gather_output=False,
                bias=c.add_bias_linear,
                init_method=c.init_method(),
                sequence_parallel_enabled=c.sequence_parallel,
                params_dtype=c.params_dtype, axis_name=c.axis_name)
        else:
            self.query = ColumnParallelLinear(
                c.hidden_size, c.hidden_size, gather_output=False,
                init_method=c.init_method(),
                sequence_parallel_enabled=c.sequence_parallel,
                params_dtype=c.params_dtype, axis_name=c.axis_name)
            # CONTRACT: encoder_output is the full (gathered) sequence; the
            # KV projection runs without SP, so a sequence-sharded input
            # would silently attend over one shard — callers under SP must
            # gather first (see ParallelTransformerLayer.apply docstring)
            self.key_value = ColumnParallelLinear(
                c.hidden_size, 2 * c.hidden_size, gather_output=False,
                init_method=c.init_method(),
                sequence_parallel_enabled=False,
                params_dtype=c.params_dtype, axis_name=c.axis_name)
        self.dense = RowParallelLinear(
            proj, c.hidden_size, input_is_parallel=True,
            bias=c.add_bias_linear,
            init_method=c.output_init_method(),
            sequence_parallel_enabled=c.sequence_parallel,
            params_dtype=c.params_dtype, axis_name=c.axis_name)
        self.gate = None
        if c.attention_output_gate:
            self.gate = ColumnParallelLinear(
                c.hidden_size, proj, gather_output=False, bias=False,
                init_method=c.init_method(),
                sequence_parallel_enabled=c.sequence_parallel,
                params_dtype=c.params_dtype, axis_name=c.axis_name)
        self.scale_mask_softmax = FusedScaleMaskSoftmax(
            attn_mask_type=(AttnMaskType.padding
                            if self.attn_type == AttnType.cross_attn
                            else c.attn_mask_type),
            scaled_masked_softmax_fusion=True,
            softmax_in_fp32=True)

    def init(self, key):
        k1, k2 = jax.random.split(key)
        if self.attn_type == AttnType.self_attn:
            p = {"query_key_value": self.query_key_value.init(k1),
                 "dense": self.dense.init(k2)}
        else:
            k1a, k1b = jax.random.split(k1)
            p = {"query": self.query.init(k1a),
                 "key_value": self.key_value.init(k1b),
                 "dense": self.dense.init(k2)}
        c = self.config
        if self.gate is not None:
            p["gate"] = self.gate.init(jax.random.fold_in(key, 2))
        if c.qk_layernorm:
            for name in ("q_layernorm", "k_layernorm"):
                p[name] = {"weight": jnp.ones((c.head_dim,), c.params_dtype)}
        return p

    def spec(self):
        if self.attn_type == AttnType.self_attn:
            s = {"query_key_value": self.query_key_value.spec(),
                 "dense": self.dense.spec()}
        else:
            s = {"query": self.query.spec(),
                 "key_value": self.key_value.spec(),
                 "dense": self.dense.spec()}
        if self.gate is not None:
            s["gate"] = self.gate.spec()
        if self.config.qk_layernorm:
            for name in ("q_layernorm", "k_layernorm"):
                s[name] = {"weight": PartitionSpec()}
        return s

    def _core_attention(self, q, k, v, attention_mask, kv_lengths,
                        rng, deterministic, window=None):
        """q/k/v: [b, local_heads, s, dh]. ``window``: sliding-window span
        for THIS call — the caller zeroes it on the cache-decode path, where
        the window is already folded into ``attention_mask`` at the correct
        cache offsets (the generic row/col clause below assumes queries sit
        at the sequence end, which padded caches violate)."""
        c = self.config
        causal = (self.attn_type == AttnType.self_attn
                  and c.attn_mask_type == AttnMaskType.causal)
        if c.context_parallel_method and self.attn_type != AttnType.self_attn:
            raise NotImplementedError(
                "context parallelism shards the self-attention sequence; "
                "cross-attention K/V come from the (unsharded) encoder")
        if (k.shape[1] != q.shape[1]
                and c.context_parallel_method == "ulysses"):
            from apex_tpu.transformer.tensor_parallel.mappings import (
                axis_bound,
            )
            cp_sz = (axis_size(c.context_axis)
                     if axis_bound(c.context_axis) else 1)
            if k.shape[1] % cp_sz:
                # GQA under Ulysses needs kv_heads divisible by cp for the
                # head all-to-all (grouped reads stay aligned after the
                # swap); broadcast K/V heads only up to the SMALLEST such
                # multiple — the repeat factor must also divide the query
                # group so each repeated head serves a whole subgroup. The
                # ring path reads shared K/V natively (small chunks rotate).
                group = q.shape[1] // k.shape[1]
                rep = next((r for r in range(1, group + 1)
                            if group % r == 0
                            and (k.shape[1] * r) % cp_sz == 0),
                           group)   # fallback: ulysses raises its own error
                k = jnp.repeat(k, rep, axis=1)
                v = jnp.repeat(v, rep, axis=1)
        if c.context_parallel_method:
            from apex_tpu.ops.ring_attention import (
                ring_attention,
                ulysses_attention,
            )
            if attention_mask is not None or (
                    not deterministic and c.attention_dropout > 0.0):
                raise NotImplementedError(
                    "context parallelism supports causal/full attention "
                    "without attention dropout or explicit masks")
            fn = {"ring": ring_attention,
                  "ulysses": ulysses_attention}[c.context_parallel_method]
            # kv_lengths are GLOBAL valid lengths for both CP methods
            return fn(q, k, v, causal=causal, axis_name=c.context_axis,
                      kv_lengths=kv_lengths, sliding_window=window)
        use_flash = attention_mask is None and (
            deterministic or c.attention_dropout == 0.0)
        if use_flash:
            return flash_attention(q, k, v, causal=causal,
                                   kv_lengths=kv_lengths,
                                   sliding_window=window)
        if kv_lengths is not None:
            # fold varlen lengths into the boolean mask (True = masked out)
            # so the unfused path matches flash semantics
            invalid = jnp.arange(k.shape[2])[None, None, None, :] >= \
                kv_lengths[:, None, None, None]
            attention_mask = invalid if attention_mask is None else (
                jnp.logical_or(attention_mask, invalid))
        if window is not None and causal:
            # window clause for the unfused path (the causal clause rides
            # the mask-type dispatcher / explicit mask)
            row = jnp.arange(q.shape[2])[None, None, :, None]
            col = jnp.arange(k.shape[2])[None, None, None, :]
            far = col <= row + (k.shape[2] - q.shape[2]) - window
            attention_mask = far if attention_mask is None else (
                jnp.logical_or(attention_mask, far))
        inv_scale = jnp.sqrt(
            jnp.asarray(c.head_dim, jnp.float32)).astype(q.dtype)
        if k.shape[1] != q.shape[1]:
            # grouped einsum: q heads fold into [kv_heads, group] so K/V are
            # contracted once per group with no HBM broadcast copy
            g = q.shape[1] // k.shape[1]
            qg = q.reshape(q.shape[0], k.shape[1], g, *q.shape[2:])
            scores = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k) / inv_scale
            scores = scores.reshape(q.shape[0], q.shape[1], *scores.shape[3:])
            probs = self.scale_mask_softmax(scores, attention_mask)
            probs = _dropout(probs, c.attention_dropout, rng, deterministic,
                             model_parallel_region=True, axis_name=c.axis_name)
            pg = probs.reshape(q.shape[0], k.shape[1], g, *probs.shape[2:])
            ctx = jnp.einsum("bhgqk,bhkd->bhgqd", pg.astype(v.dtype), v)
            return ctx.reshape(q.shape[0], q.shape[1], *ctx.shape[3:])
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / inv_scale
        probs = self.scale_mask_softmax(scores, attention_mask)
        probs = _dropout(probs, c.attention_dropout, rng, deterministic,
                         model_parallel_region=True, axis_name=c.axis_name)
        return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)

    def _flat_cache_attention(self, out_proj, q, k, v, ck, cv, cache_index,
                              attention_mask, kv_lengths, rng,
                              deterministic):
        """Incremental decode over a FLAT ``[b, S, kvh*dh]`` cache pair.

        Same semantics as the 4D cached path (causal/prefix mask over the
        padded cache, sliding window, ``kv_lengths``, GQA grouping,
        dropout) but the cache keeps heads*head_dim fused as the minor
        dimension so reads and the one-row write stay full-lane, and the
        single-token path reads both cache streams through MXU GEMMs so
        XLA's layout assignment has no reason to re-lay the carry (see
        the in-branch comments; the per-head view is a bitcast —
        ``reshape`` splitting the minor dim).
        ``q``/``k``/``v`` arrive as ``[b, local_heads, s, dh]``.

        ``cache_index`` may be a ``[b]`` VECTOR of per-row offsets
        (continuous batching: each cache row is an independent request at
        its own position) — the write becomes a per-row scatter and the
        causal mask is taken per row, so one batched decode step serves
        rows at arbitrary, unequal positions.
        """
        c = self.config
        dh = c.head_dim
        b, hl, s, _ = q.shape
        kvh = k.shape[1]
        kf = k.transpose(0, 2, 1, 3).reshape(b, s, kvh * dh)
        vf = v.transpose(0, 2, 1, 3).reshape(b, s, kvh * dh)
        if getattr(cache_index, "ndim", 0) == 1:
            # per-row offsets: each row r writes its s tokens at
            # [cache_index[r], cache_index[r]+s) in its own cache row
            row_update = jax.vmap(
                lambda cache, update, idx: lax.dynamic_update_slice(
                    cache, update, (idx, 0)))
            ck = row_update(ck, kf.astype(ck.dtype), cache_index)
            cv = row_update(cv, vf.astype(cv.dtype), cache_index)
            ci = cache_index[:, None, None, None]         # [b, 1, 1, 1]
        else:
            ck = lax.dynamic_update_slice(ck, kf.astype(ck.dtype),
                                          (0, cache_index, 0))
            cv = lax.dynamic_update_slice(cv, vf.astype(cv.dtype),
                                          (0, cache_index, 0))
            ci = cache_index
        S = ck.shape[1]
        # identical mask to the 4D cached branch: query i of the slice may
        # see slots j <= cache_index + i, within the window and (varlen)
        # below the row's valid length
        slots = jnp.arange(S)[None, None, None, :]
        allowed_up_to = ci + jnp.arange(s)[None, None, :, None]
        invalid = slots > allowed_up_to
        if self.window is not None:
            invalid = jnp.logical_or(
                invalid, slots <= allowed_up_to - self.window)
        if kv_lengths is not None:
            invalid = jnp.logical_or(
                invalid, slots >= kv_lengths[:, None, None, None])
        mask = (invalid if attention_mask is None
                else jnp.logical_or(attention_mask, invalid))
        inv_scale = jnp.sqrt(
            jnp.asarray(c.head_dim, jnp.float32)).astype(q.dtype)
        g = hl // kvh
        if s == 1:
            # single-token fast path. The per-head einsum formulation lets
            # XLA's layout assignment put the SEQUENCE dim minor on the
            # cache carry (the softmax's preference propagates backward),
            # which turns the one-row cache write into a full-cache copy
            # every step (measured 0.5 ms/step at 124M bs8). Instead BOTH
            # cache streams go through MXU GEMMs:
            #   scores = K_flat @ Qblock  — one GEMM per batch, where
            #     Qblock [kvh*dh, hl] holds each query head's vector in its
            #     K/V head's row block and zeros elsewhere, so the cache is
            #     read as contiguous full-lane [S, kvh*dh] rows (the 12x
            #     redundant MACs are free — decode is bandwidth-bound);
            #   ctx = probs @ V_flat — every (head, V column) pair, each
            #     head's own dh block kept by a static selector.
            # Neither expression gives XLA a reason to re-lay the carry.
            q2 = q[:, :, 0, :]                            # [b, hl, dh]
            q_tiled = jnp.tile(q2.transpose(0, 2, 1), (1, kvh, 1))
            frow = jnp.arange(kvh * dh)[:, None]
            jcol = jnp.arange(hl)[None, :]
            blockmask = (frow // dh == jcol // g).astype(q.dtype)
            qblock = q_tiled * blockmask                  # [b, kvh*dh, hl]
            scores = jnp.einsum("bsf,bfh->bsh", ck.astype(q.dtype),
                                qblock) / inv_scale       # [b, S, hl]
            neg = jnp.asarray(-1e30, jnp.float32)
            invalid1 = jnp.swapaxes(mask[:, 0], 1, 2)     # [b|1, S, 1]
            sf = jnp.where(invalid1, neg, scores.astype(jnp.float32))
            sf = sf - jnp.max(sf, axis=1, keepdims=True)
            e = jnp.exp(sf)
            probs = (e / jnp.sum(e, axis=1, keepdims=True)).astype(q.dtype)
            probs = _dropout(probs, c.attention_dropout, rng, deterministic,
                             model_parallel_region=True,
                             axis_name=c.axis_name)
            # context as a second MXU GEMM over the flat V (an elementwise
            # broadcast-multiply-reduce here makes XLA lay the V carry
            # S-minor, reintroducing the full-cache-copy write): compute
            # every (query head, V column) pair, then keep each head's own
            # dh block — kvh x redundant MACs, still free on the MXU
            ctx_big = jnp.einsum("bsh,bsf->bhf", probs,
                                 cv.astype(q.dtype))      # [b, hl, kvh*dh]
            sel = (jnp.arange(kvh)[None, :]
                   == (jnp.arange(hl) // g)[:, None]).astype(q.dtype)
            ctx = jnp.einsum("bjkd,jk->bjd",
                             ctx_big.reshape(b, hl, kvh, dh), sel)
            ctx = ctx.reshape(b, hl * dh)[None]           # [1, b, hl*dh]
            return out_proj(ctx), (ck, cv)
        K4 = ck.reshape(b, S, kvh, dh).astype(q.dtype)
        V4 = cv.reshape(b, S, kvh, dh).astype(q.dtype)
        qg = q.reshape(b, kvh, g, s, dh)
        scores = jnp.einsum("bhgqd,bkhd->bhgqk", qg, K4) / inv_scale
        scores = scores.reshape(b, hl, s, S)
        probs = self.scale_mask_softmax(scores, mask)
        probs = _dropout(probs, c.attention_dropout, rng, deterministic,
                         model_parallel_region=True, axis_name=c.axis_name)
        pg = probs.astype(V4.dtype).reshape(b, kvh, g, s, S)
        ctx = jnp.einsum("bhgqk,bkhd->bhgqd", pg, V4).reshape(b, hl, s, dh)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, hl * dh)
        return out_proj(ctx), (ck, cv)

    @nvtx_range(SCOPE_ATTENTION)
    def apply(self, params, hidden, *, encoder_output=None,
              attention_mask=None, kv_lengths=None, kv_cache=None,
              cache_index=None, rng=None, deterministic=True,
              dropout_seed=None, paged_state=None, lora=None):
        """hidden: [s(, shard), b, h] -> [s(, shard), b, h]; cross-attention
        reads K/V from ``encoder_output`` [s_enc, b, h].

        ``dropout_seed`` (scalar/``(1,)`` i32) overrides the packed path's
        in-kernel attention-dropout hash seed — the transformer stack
        passes a per-layer offset of ONE base draw so masks are
        structurally distinct across layers (independent 32-bit draws per
        layer collide at ~L^2/2^33 per step and would then share a mask).
        The XLA/bernoulli dropout paths key on ``rng`` and ignore it.

        Incremental decoding: pass ``kv_cache=(k, v)`` (``[b, local_kv_heads,
        S_max, dh]`` each — K/V heads, i.e. ``num_query_groups`` under
        GQA/MQA) and ``cache_index`` (tokens already cached); the
        current K/V are written at that offset, attention runs over the
        cache, and the return becomes ``(out, new_cache)``. On the FLAT
        cache form ``cache_index`` may be a ``[b]`` vector of per-row
        offsets (continuous batching; rope rotates each row at its own
        position).

        ``paged_state`` (a ``[b, pages_per_slot]`` int32 page table)
        switches the cache interpretation to the PAGED pool form: the
        ``kv_cache`` pair is ``[n_pages, page_size, kv_heads*head_dim]``
        pools shared by all slots and ``cache_index`` must be the ``[b]``
        per-row position vector — single-token decode only, served by the
        fused append+attend op (:mod:`apex_tpu.ops.decode_attention`).
        """
        c = self.config
        dh = c.head_dim
        gate = (None if self.gate is None
                else self.gate.apply(params["gate"], hidden))

        def out_proj(ctx):
            """``ctx [s, b, heads * dh]`` -> the block's output: the
            sigmoid gate (float32, one rounding) and then ``W_o``."""
            if gate is not None:
                ctx = (ctx.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(ctx.dtype)
            return self.dense.apply(params["dense"], ctx)

        if self.attn_type == AttnType.self_attn:
            qkv = self.query_key_value.apply(params["query_key_value"],
                                             hidden)
            if lora is not None:
                # per-slot low-rank QKV delta (B pre-sliced to the local
                # out-dim under TP, so the delta matches the qkv slice)
                qkv = qkv + _lora_delta(hidden, lora).astype(qkv.dtype)
            s, b = qkv.shape[0], qkv.shape[1]
            qpg = c.num_attention_heads // c.kv_heads
            block = (qpg + 2) * dh
            if qkv.shape[-1] % block:
                raise ValueError(
                    f"tensor-parallel slice of the fused QKV projection "
                    f"({qkv.shape[-1]}) cuts through a K/V group (group "
                    f"block = {block}); num_query_groups ({c.kv_heads}) "
                    f"must be divisible by the tensor-parallel size")
            local_groups = qkv.shape[-1] // block
            # layout-native fast path: feed the packed projection straight
            # to the attention kernel and get ctx back in [s, b, h*dh] —
            # no [b,h,s,dh] transposes in either direction, and the VJP
            # emits the packed dqkv cotangent the wgrad GEMM wants (at
            # 355M the transposes + cotangent reassembly were ~18 ms of a
            # 202 ms step — PERF.md round 5)
            drop_active = (not deterministic
                           and c.attention_dropout > 0.0)
            if (kv_cache is None and cache_index is None
                    and attention_mask is None
                    and not c.context_parallel_method
                    and not c.qk_layernorm and gate is None
                    and (not drop_active or rng is not None)
                    and packed_attention_supported(s, local_groups, qpg,
                                                   dh)):
                freqs = None
                if self.rope:
                    # positions start at 0: no cache offset (cache_index
                    # gated above) and no bound context axis (CP gated
                    # above)
                    freqs = rope_freqs(0, s, c.rotary_dim, c.rope_theta)
                seed = None
                if drop_active:
                    if dropout_seed is not None:
                        seed = jnp.asarray(dropout_seed,
                                           jnp.int32).reshape(1)
                    else:
                        # Megatron RNG semantics: attention dropout lives
                        # in a model-parallel region — each TP rank draws
                        # its own mask (same convention as _dropout)
                        dkey = model_parallel_rng_key(rng, c.axis_name)
                        seed = jax.random.randint(
                            dkey, (1,), -2**31, 2**31 - 1, jnp.int32)
                ctx = flash_attention_packed(
                    qkv, queries_per_group=qpg, head_dim=dh,
                    causal=c.attn_mask_type == AttnMaskType.causal,
                    kv_lengths=kv_lengths,
                    sliding_window=self.window,
                    rope_freqs=freqs,
                    dropout_rate=(c.attention_dropout if drop_active
                                  else 0.0),
                    dropout_seed=seed)
                return out_proj(ctx)
            qkv = qkv.reshape(s, b, local_groups, qpg + 2, dh)
            q = qkv[:, :, :, :qpg].reshape(s, b, local_groups * qpg, dh)
            k = qkv[:, :, :, qpg]
            v = qkv[:, :, :, qpg + 1]
            local_heads = local_groups * qpg
            if c.qk_layernorm:
                q = _head_rms(q, params["q_layernorm"]["weight"],
                              c.layernorm_epsilon)
                k = _head_rms(k, params["k_layernorm"]["weight"],
                              c.layernorm_epsilon)
            if self.rope:
                from apex_tpu.ops import fused_rope
                from apex_tpu.transformer.tensor_parallel.mappings import (
                    axis_bound,
                )

                start = 0 if cache_index is None else cache_index
                if c.context_parallel_method and axis_bound(c.context_axis):
                    if cache_index is not None:
                        raise NotImplementedError(
                            "incremental decode (kv_cache) with a bound "
                            "context-parallel axis and rope positions: the "
                            "per-rank rope offset for a sharded cache is "
                            "not wired up — decode without the context "
                            "axis")
                    start = lax.axis_index(c.context_axis) * s
                freqs = rope_freqs(start, s, c.rotary_dim, c.rope_theta)
                q = fused_rope(q, freqs)
                k = fused_rope(k, freqs)
        else:
            if encoder_output is None:
                raise ValueError("cross-attention needs encoder_output")
            q = self.query.apply(params["query"], hidden)
            kv = self.key_value.apply(params["key_value"], encoder_output)
            s, b = q.shape[0], q.shape[1]
            local_heads = q.shape[-1] // dh
            q = q.reshape(s, b, local_heads, dh)
            kv = kv.reshape(kv.shape[0], b, local_heads, 2 * dh)
            k, v = jnp.split(kv, 2, axis=-1)
        # [s, b, hl, dh] -> [b, hl, s, dh]
        q, k, v = (t.transpose(1, 2, 0, 3) for t in (q, k, v))
        new_cache = None
        if kv_cache is not None:
            if self.attn_type != AttnType.self_attn:
                raise NotImplementedError(
                    "kv_cache is for self-attention decode; cross-attention "
                    "K/V are static — precompute them once instead")
            ck, cv = kv_cache
            if paged_state is not None:
                # PAGED decode: the cache pair is the global page pool and
                # ``paged_state`` maps this batch's slots onto it. The
                # fused op appends each row's K/V at its own position and
                # attends over its mapped pages in one pass (one HBM read
                # of the KV stream per step); its reference path replays
                # the flat s==1 formulation below bit-for-bit on the
                # gathered logical view, so paged serving stays
                # token-exact against generate(). ``s > 1`` is the
                # speculative verify window: each slot appends/attends a
                # window of ``s`` rows starting at its own cache_index
                # (window query t masks to rows <= index + t). With an
                # int8 pool each of ck/cv is a ``(pages, scales)`` pair
                # and the op carries the per-page scales through.
                if attention_mask is not None or kv_lengths is not None:
                    raise NotImplementedError(
                        "paged decode derives validity from cache_index; "
                        "attention_mask/kv_lengths are not supported")
                from apex_tpu.ops import fused_paged_decode_attention
                k_scales = v_scales = None
                if isinstance(ck, (tuple, list)):
                    ck, k_scales = ck
                    cv, v_scales = cv
                kvh_l = k.shape[1]
                # [b, hl, s, dh] -> windowed [b, s, hl, dh] / [b, s, f]
                qw = q.transpose(0, 2, 1, 3)
                kw = k.transpose(0, 2, 1, 3).reshape(b, s, kvh_l * dh)
                vw = v.transpose(0, 2, 1, 3).reshape(b, s, kvh_l * dh)
                res = fused_paged_decode_attention(
                    qw, kw, vw, ck, cv, paged_state, cache_index,
                    queries_per_group=local_heads // kvh_l,
                    sliding_window=self.window,
                    k_scales=k_scales, v_scales=v_scales)
                if k_scales is not None:
                    ctx, ck, cv, k_scales, v_scales = res
                    new = ((ck, k_scales), (cv, v_scales))
                else:
                    ctx, ck, cv = res
                    new = (ck, cv)
                # ctx [b, s, hl*dh] -> [s, b, hl*dh] for the dense proj
                return out_proj(ctx.transpose(1, 0, 2)), new
            if ck.ndim == 3:
                # FLAT decode cache [b, S, local_kv_heads*dh]: with the 4D
                # [b, h, S, d] carry XLA picks a layout whose minor dim is
                # head_dim (64) — half a 128-lane tile — so the cache is
                # physically padded 2x and every decode-attention read runs
                # at ~50% HBM bandwidth; the flat form keeps the minor dim
                # at h*d (>= 128) and the whole cache stream full-lane
                # (PERF.md round 5: bs8 decode 10.4k -> 13.8k tok/s)
                out, new_cache = self._flat_cache_attention(
                    out_proj, q, k, v, ck, cv, cache_index, attention_mask,
                    kv_lengths, rng, deterministic)
                return out, new_cache
            if getattr(cache_index, "ndim", 0) == 1:
                raise NotImplementedError(
                    "per-row cache_index (continuous-batching decode) "
                    "needs the FLAT cache form — "
                    "init_kv_caches(stacked=False, flat=True)")
            ck = lax.dynamic_update_slice(
                ck, k.astype(ck.dtype), (0, 0, cache_index, 0))
            cv = lax.dynamic_update_slice(
                cv, v.astype(cv.dtype), (0, 0, cache_index, 0))
            new_cache = (ck, cv)
            if (isinstance(cache_index, int) and cache_index == 0
                    and attention_mask is None and kv_lengths is None
                    and (deterministic or c.attention_dropout == 0.0)):
                # PREFILL fast path (statically at slot 0): queries occupy
                # cache slots [0, s), so attention over the populated
                # prefix is plain causal flash — the empty tail slots
                # never enter the kernel, and the [s, S]-mask einsum path
                # below (built for mid-cache offsets) is skipped entirely
                ctx = flash_attention(
                    q, ck[:, :, :s].astype(q.dtype),
                    cv[:, :, :s].astype(q.dtype), causal=True,
                    sliding_window=self.window)
                ctx = ctx.transpose(2, 0, 1, 3).reshape(
                    s, b, local_heads * dh)
                return out_proj(ctx), new_cache
            k, v = ck.astype(q.dtype), cv.astype(q.dtype)
            # per-query causal+prefix mask over the padded cache: query i of
            # the slice may see slots j <= cache_index + i (the dispatcher's
            # offset-causal tril assumes queries sit at the cache END, which
            # padded caches violate — so encode causality explicitly)
            slots = jnp.arange(k.shape[2])[None, None, None, :]
            allowed_up_to = cache_index + jnp.arange(s)[None, None, :, None]
            invalid = slots > allowed_up_to
            if self.window is not None:
                invalid = jnp.logical_or(
                    invalid, slots <= allowed_up_to - self.window)
            attention_mask = (invalid if attention_mask is None
                              else jnp.logical_or(attention_mask, invalid))
        window = (self.window
                  if (self.attn_type == AttnType.self_attn
                      and kv_cache is None) else None)
        ctx = self._core_attention(q, k, v, attention_mask, kv_lengths,
                                   rng, deterministic, window=window)
        ctx = ctx.transpose(2, 0, 1, 3).reshape(s, b, local_heads * dh)
        out = out_proj(ctx)
        return out if new_cache is None else (out, new_cache)


@dataclass
class LatentAttention:
    """Multi-head latent attention (DeepSeek-V2/V3's MLA), causal.

    Per token: ``cq = N(x W_DQ)``, ``[qC_i ; qR_i] = cq W_UQ`` a head;
    ``[c ; kR] = x W_DKV``, ``c = N(c)``, ``kR = RoPE(kR)`` ONE for all
    heads; ``[kC_i ; v_i] = c [W_UK_i ; W_UV_i]``; ``q_i = [qC_i ;
    RoPE(qR_i)]``, ``k_i = [kC_i ; kR]``; softmax over ``q_i . k_j *
    scale``, ``scale = (nope + rope)^-0.5 * mscale^2`` (YaRN's share).
    What a token leaves in the cache is ``c`` and ``kR``: ``kv_lora_rank +
    qk_rope_head_dim`` values, no head axis (docs/serving.md#latent-kv).

    Two forms of the same attention. EXPANDED: ``k_i``, ``v_i`` made from
    the rows, flash attention at q/k head size ``nope + rope`` and v head
    size ``v_head_dim``: a forward with no cache and a whole-prompt
    prefill. ABSORBED: ``qL_i = qC_i W_UK_i`` (the query in the latent's
    coordinates), ``score = qL_i . c_j + RoPE(qR_i) . kR_j``, ``oL_i =
    sum_j p_j c_j``, ``o_i = oL_i W_UV_i``: every step that attends over
    cached rows (a chunk or a suffix of a prompt, a decode step), since
    the rows are then read as they lie and nothing a head wide is made
    from them. Parameters: ``q_down``, ``q_up``, ``kv_down``, ``dense``
    ``[out, in]``; ``k_up`` ``[heads, nope, rank]`` and ``v_up``
    ``[heads, v, rank]`` apart, as the absorbed form reads them.
    """

    config: TransformerConfig

    def __post_init__(self):
        c = self.config
        self.heads, self.rank = c.num_attention_heads, c.kv_lora_rank
        self.nope, self.rot = c.qk_nope_head_dim, c.qk_rope_head_dim
        self.scale = float(self.nope + self.rot) ** -0.5
        if c.rope_yarn is None:
            self.inv_freq, self.rot_scale = (
                1.0 / c.rope_theta ** (jnp.arange(
                    0, self.rot, 2, dtype=jnp.float32) / self.rot), 1.0)
        else:
            self.inv_freq, self.rot_scale = yarn_inv_freq(
                self.rot, c.rope_theta, c.rope_yarn)
            if c.rope_yarn.mscale_all_dim:
                self.scale *= yarn_mscale(c.rope_yarn.factor,
                                          c.rope_yarn.mscale_all_dim) ** 2

    def init(self, key):
        c = self.config
        h, dt = c.hidden_size, c.params_dtype
        keys = iter(jax.random.split(key, 7))

        def w(shape, init=None):
            return {"weight": (init or c.init_method())(next(keys), shape,
                                                        dt)}

        return {
            "q_down": w((c.q_lora_rank, h)),
            "q_layernorm": {"weight": jnp.ones((c.q_lora_rank,), dt)},
            "q_up": w((self.heads * (self.nope + self.rot), c.q_lora_rank)),
            "kv_down": w((self.rank + self.rot, h)),
            "kv_layernorm": {"weight": jnp.ones((self.rank,), dt)},
            "k_up": w((self.heads, self.nope, self.rank)),
            "v_up": w((self.heads, c.v_head_dim, self.rank)),
            "dense": w((h, self.heads * c.v_head_dim),
                       c.output_init_method()),
        }

    def spec(self):
        return {name: {"weight": PartitionSpec()} for name in (
            "q_down", "q_layernorm", "q_up", "kv_down", "kv_layernorm",
            "k_up", "v_up", "dense")}

    def _rotate(self, t, start):
        """Rotary positions ``start .. start + s`` (``start`` a scalar or
        a ``[b]`` vector of per-row offsets) on ``t [s, b, n, rot]``,
        rotate-half, in float32."""
        pos = jnp.arange(t.shape[0], dtype=jnp.float32)[:, None] \
            + jnp.asarray(start, jnp.float32).reshape(1, -1)      # [s, b|1]
        f = pos[..., None] * jnp.asarray(self.inv_freq)[None, None, :]
        f = jnp.concatenate([f, f], axis=-1)[:, :, None, :]
        return fused_rope_cached(t, jnp.cos(f) * self.rot_scale,
                                 jnp.sin(f) * self.rot_scale)

    def _expanded(self, params, qc, qr, c, kr):
        """Causal attention of ``s`` fresh tokens over themselves, K and V
        made from their rows: ``[s, b, heads * v]``."""
        s, b = qc.shape[:2]
        k_up = params["k_up"]["weight"].astype(c.dtype)
        v_up = params["v_up"]["weight"].astype(c.dtype)
        kc = jnp.einsum("sbr,hnr->bhsn", c, k_up)
        v = jnp.einsum("sbr,hdr->bhsd", c, v_up)
        k = jnp.concatenate([kc, jnp.broadcast_to(
            kr.transpose(1, 0, 2)[:, None], kc.shape[:3] + (self.rot,))], -1)
        q = jnp.concatenate([qc, qr], -1).transpose(1, 2, 0, 3)
        o, _ = flash_chunk_fwd(q, k, v, q_start=0, k_start=0, causal=True,
                               softmax_scale=self.scale,
                               name=SCOPE_MLA_PREFILL)
        return o.transpose(2, 0, 1, 3).reshape(s, b, -1)

    def _absorb_q(self, params, qc):
        with nvtx_range(SCOPE_MLA_ABSORB):
            return jnp.einsum("sbhn,hnr->sbhr", qc,
                              params["k_up"]["weight"].astype(qc.dtype))

    def _absorb_o(self, params, o_latent):
        """``[s, b, heads, rank]`` -> ``[s, b, heads * v]``."""
        with nvtx_range(SCOPE_MLA_ABSORB):
            o = jnp.einsum("sbhr,hdr->sbhd", o_latent,
                           params["v_up"]["weight"].astype(o_latent.dtype))
        return o.reshape(*o.shape[:2], -1)

    def _over_cached_rows(self, params, qc, qr, rows_c, rows_kr, start):
        """Absorbed causal attention of ``s`` queries at positions ``start
        ..`` over the flat cached rows ``[b, S, rank]`` / ``[b, S,
        lanes]`` (their own rows written): one shared K/V "head" of
        ``rank + lanes`` / ``rank`` for all query heads."""
        lanes = rows_kr.shape[-1]
        ql = self._absorb_q(params, qc)
        q = jnp.concatenate(
            [ql, jnp.pad(qr, ((0, 0),) * 3 + ((0, lanes - self.rot),))],
            -1).transpose(1, 2, 0, 3)
        k = jnp.concatenate([rows_c, rows_kr], -1)[:, None].astype(q.dtype)
        o, _ = flash_chunk_fwd(q, k, rows_c[:, None].astype(q.dtype),
                               q_start=start, k_start=0, causal=True,
                               softmax_scale=self.scale, block_q=512,
                               block_k=512, name=SCOPE_MLA_PREFILL)
        return self._absorb_o(params, o.transpose(2, 0, 1, 3))

    @nvtx_range(SCOPE_MLA)
    def apply(self, params, hidden, *, attention_mask=None, kv_lengths=None,
              kv_cache=None, cache_index=None, rng=None, deterministic=True,
              dropout_seed=None, paged_state=None, lora=None):
        """``hidden [s, b, h] -> [s, b, h]``, or ``(out, new_cache)`` with
        ``kv_cache = (c rows, kR rows)``: flat ``[b, S, rank]`` / ``[b, S,
        lanes]`` with a scalar ``cache_index`` (0: a whole prompt or a
        prompt's first chunk, expanded; else a chunk over the rows before
        it, absorbed), or the two page pools with ``paged_state`` and the
        ``[b]`` position vector (one token a slot, the absorbed decode
        kernel)."""
        c = self.config
        del rng, dropout_seed
        if attention_mask is not None or kv_lengths is not None:
            raise NotImplementedError(
                "latent attention is causal over whole sequences: "
                "attention_mask / kv_lengths are not supported")
        if lora is not None:
            raise ValueError(
                "LoRA adapters target the fused query_key_value "
                "projection, which latent attention (kv_lora_rank) does "
                "not have")
        if not deterministic and c.attention_dropout > 0.0:
            raise NotImplementedError(
                "latent attention has no attention dropout")
        s, b = hidden.shape[:2]
        eps = c.layernorm_epsilon

        def mm(x, name):
            return jnp.matmul(x, params[name]["weight"].T.astype(x.dtype))

        cq = _head_rms(mm(hidden, "q_down"),
                       params["q_layernorm"]["weight"], eps)
        q = mm(cq, "q_up").reshape(s, b, self.heads, self.nope + self.rot)
        down = mm(hidden, "kv_down")
        row_c = _head_rms(down[..., :self.rank],
                          params["kv_layernorm"]["weight"], eps)
        start = 0 if cache_index is None else cache_index
        qc, qr = q[..., :self.nope], self._rotate(q[..., self.nope:], start)
        row_kr = self._rotate(down[..., None, self.rank:], start)[:, :, 0]

        if kv_cache is None:
            return mm(self._expanded(params, qc, qr, row_c, row_kr), "dense")
        rows_c, rows_kr = kv_cache
        if paged_state is not None:
            if s != 1:
                raise ValueError(
                    "the latent decode kernel takes one token a slot: a "
                    "speculation window (s > 1) over latent attention is "
                    "not supported")
            from apex_tpu.ops.decode_attention import (
                fused_latent_decode_attention)
            ql = self._absorb_q(params, qc)
            o_latent, rows_c, rows_kr = fused_latent_decode_attention(
                ql[0], qr[0], row_c[0], row_kr[0], rows_c, rows_kr,
                paged_state, cache_index, softmax_scale=self.scale)
            return (mm(self._absorb_o(params, o_latent[None]), "dense"),
                    (rows_c, rows_kr))
        if getattr(cache_index, "ndim", 0) == 1:
            raise NotImplementedError(
                "latent attention over a flat cache takes ONE offset for "
                "the batch; per-row offsets are the paged form's")
        lanes = rows_kr.shape[-1]
        rows_c = lax.dynamic_update_slice(
            rows_c, row_c.transpose(1, 0, 2).astype(rows_c.dtype),
            (0, cache_index, 0))
        rows_kr = lax.dynamic_update_slice(
            rows_kr, jnp.pad(row_kr, ((0, 0), (0, 0), (0, lanes - self.rot))
                             ).transpose(1, 0, 2).astype(rows_kr.dtype),
            (0, cache_index, 0))
        if isinstance(cache_index, int) and cache_index == 0:
            ctx = self._expanded(params, qc, qr, row_c, row_kr)
        else:
            # a chunk that opens its prompt attends to nothing cached: it
            # is a whole-prompt prefill, whatever program it rides in (the
            # expanded form multiplies 320 values a pair where the
            # absorbed one multiplies 1,088)
            ctx = lax.cond(
                cache_index == 0,
                lambda: self._expanded(params, qc, qr, row_c, row_kr),
                lambda: self._over_cached_rows(params, qc, qr, rows_c,
                                               rows_kr, cache_index))
        return mm(ctx, "dense"), (rows_c, rows_kr)


@dataclass
class ParallelTransformerLayer:
    """Pre-LN block: ln -> attn -> add -> ln -> mlp -> add.

    Reference: ``standalone_transformer_lm.py`` ``ParallelTransformerLayer``
    (~:1033-1148). Under sequence parallelism the norms and dropouts run on
    sequence shards (``transformer/layers/layer_norm.py:26-99`` marks those
    params ``sequence_parallel_enabled`` for grad sync; here that sync is the
    train step's psum of replicated-param grads).
    """

    config: TransformerConfig
    layer_type: Any = LayerType.encoder
    #: this layer's ``(attention kind, feed-forward kind)``, an entry of
    #: ``TransformerConfig.layer_kinds``
    layer_kind: Tuple[str, str] = ("default", "dense")

    def __post_init__(self):
        c = self.config
        self.attention = (
            LatentAttention(c) if c.latent_attention
            else ParallelAttention(c, layer_kind=self.layer_kind[0]))
        self.routed = self.layer_kind[1] == "routed"
        if self.layer_type == LayerType.decoder:
            # decoder blocks add cross-attention over the encoder output
            # (reference ParallelTransformerLayer inter_attention branch,
            # standalone_transformer_lm.py ~:1090-1115)
            self.inter_attention = ParallelAttention(
                c, attn_type=AttnType.cross_attn)
        if self.routed:
            from apex_tpu.transformer.moe import (RoutedExperts,
                                                  RoutedMoEConfig)
            self.mlp = RoutedExperts(RoutedMoEConfig(
                hidden_size=c.hidden_size,
                ffn_hidden_size=c.routed_ffn_hidden_size,
                num_experts=c.num_routed_experts,
                top_k=c.routed_top_k,
                route_scale=c.route_scale,
                num_shared_experts=c.num_shared_experts,
                expert_range=c.routed_expert_range,
                num_groups=c.routed_num_groups,
                topk_groups=c.routed_topk_groups,
                params_dtype=c.params_dtype,
                compute_dtype=c.compute_dtype,
                init_method_std=c.init_method_std))
        elif c.num_moe_experts:
            from apex_tpu.transformer.moe import MoEConfig, SwitchMLP
            self.mlp = SwitchMLP(MoEConfig(
                hidden_size=c.hidden_size,
                ffn_hidden_size=c.ffn_size,
                num_experts=c.num_moe_experts,
                top_k=c.moe_top_k,
                capacity_factor=c.moe_capacity_factor,
                aux_loss_weight=c.moe_aux_loss_weight,
                router_jitter=c.moe_router_jitter,
                expert_axis=c.moe_expert_axis,
                activation=c.activation,
                params_dtype=c.params_dtype,
                compute_dtype=c.compute_dtype,
                init_method_std=c.init_method_std))
        else:
            self.mlp = ParallelMLP(c)

    def init(self, key):
        c = self.config
        k1, k2, k3 = jax.random.split(key, 3)
        p = {
            "input_layernorm": _ln_params(c.hidden_size, c.params_dtype,
                                          c.normalization),
            "self_attention": self.attention.init(k1),
            "post_attention_layernorm": _ln_params(
                c.hidden_size, c.params_dtype, c.normalization),
            "mlp": self.mlp.init(k2),
        }
        if c.sandwich_norm:
            # post_attention_layernorm then norms the attention OUTPUT
            for name in ("pre_mlp_layernorm", "post_mlp_layernorm"):
                p[name] = _ln_params(c.hidden_size, c.params_dtype,
                                     c.normalization)
        if self.layer_type == LayerType.decoder:
            p["inter_attention"] = self.inter_attention.init(k3)
            p["post_inter_attention_layernorm"] = _ln_params(
                c.hidden_size, c.params_dtype, c.normalization)
        return p

    def spec(self):
        norm = self.config.normalization
        s = {
            "input_layernorm": _ln_spec(norm),
            "self_attention": self.attention.spec(),
            "post_attention_layernorm": _ln_spec(norm),
            "mlp": self.mlp.spec(),
        }
        if self.config.sandwich_norm:
            s["pre_mlp_layernorm"] = _ln_spec(norm)
            s["post_mlp_layernorm"] = _ln_spec(norm)
        if self.layer_type == LayerType.decoder:
            s["inter_attention"] = self.inter_attention.spec()
            s["post_inter_attention_layernorm"] = _ln_spec(norm)
        return s

    def apply(self, params, hidden, *, encoder_output=None,
              enc_dec_attn_mask=None, enc_kv_lengths=None,
              attention_mask=None, kv_lengths=None, kv_cache=None,
              cache_index=None, rng=None, deterministic=True,
              moe_drop_free=None, attention_seed=None, paged_state=None,
              lora=None, routing=None):
        """``encoder_output`` (decoder layers) must be the FULL encoder
        sequence ``[s_enc, b, h]`` — under sequence parallelism gather it
        first (``gather_from_sequence_parallel_region``), as
        :class:`~apex_tpu.models.bert.BertModel` does for its heads.
        ``enc_kv_lengths`` ([batch] valid encoder lengths) keeps padded
        cross-attention on the varlen flash path instead of a boolean
        ``enc_dec_attn_mask``. With ``kv_cache`` (incremental decoding) the
        return becomes ``(out, new_cache)``. ``routing``: a
        ``transformer.moe.RoutingStats`` that a routed layer's call
        reports its counts to."""
        c = self.config
        decoder = self.layer_type == LayerType.decoder
        # decoder layers draw a 4th key; encoder layers keep the historical
        # 3-way split so fixed-seed dropout streams stay reproducible
        n_keys = 4 if decoder else 3
        rngs = ((None,) * n_keys if rng is None
                else tuple(jax.random.split(rng, n_keys)))
        x = _ln(params["input_layernorm"], hidden, c.layernorm_epsilon,
                c.sequence_parallel, c.axis_name, c.normalization)
        attn_out = self.attention.apply(
            params["self_attention"], x.astype(c.compute_dtype),
            attention_mask=attention_mask, kv_lengths=kv_lengths,
            kv_cache=kv_cache, cache_index=cache_index,
            rng=rngs[2], deterministic=deterministic,
            dropout_seed=attention_seed, paged_state=paged_state,
            lora=None if lora is None else lora.get("query_key_value"))
        new_cache = None
        if kv_cache is not None:
            attn_out, new_cache = attn_out

        def norm(name, x):
            return _ln(params[name], x, c.layernorm_epsilon,
                       c.sequence_parallel, c.axis_name, c.normalization)

        if c.sandwich_norm:
            attn_out = norm("post_attention_layernorm", attn_out)
        attn_out = _dropout(attn_out, c.hidden_dropout, rngs[0], deterministic,
                            model_parallel_region=c.sequence_parallel,
                            axis_name=c.axis_name)
        hidden = hidden + attn_out
        if decoder:
            x = _ln(params["post_attention_layernorm"], hidden,
                    c.layernorm_epsilon, c.sequence_parallel, c.axis_name,
                    c.normalization)
            r_attn = None if rngs[3] is None else jax.random.fold_in(rngs[3], 0)
            r_drop = None if rngs[3] is None else jax.random.fold_in(rngs[3], 1)
            inter_out = self.inter_attention.apply(
                params["inter_attention"], x.astype(c.compute_dtype),
                encoder_output=encoder_output,
                attention_mask=enc_dec_attn_mask,
                kv_lengths=enc_kv_lengths,
                rng=r_attn, deterministic=deterministic)
            inter_out = _dropout(
                inter_out, c.hidden_dropout, r_drop, deterministic,
                model_parallel_region=c.sequence_parallel,
                axis_name=c.axis_name)
            hidden = hidden + inter_out
            norm_name = "post_inter_attention_layernorm"
        else:
            norm_name = ("pre_mlp_layernorm" if c.sandwich_norm
                         else "post_attention_layernorm")
        x = norm(norm_name, hidden)
        aux = None
        if self.routed:
            # the serving layer: drop-free by construction, no aux loss
            mlp_out = self.mlp.apply(params["mlp"],
                                     x.astype(c.compute_dtype), routing)
        elif c.num_moe_experts:
            moe_rng = (None if rngs[1] is None
                       else jax.random.fold_in(rngs[1], 1))
            # drop-free routing on the whole generation path (prefill AND
            # single-token decode) and wherever the caller asks
            # (moe_drop_free=True = the serving forward): factor-based
            # capacity drops are a TRAINING load-balancing trade, and a
            # capacity prefill would disagree with the drop-free decode
            # steps it seeds (round 5; the round-4 caveat in generate()).
            # Cost model: E/top_k x the routed FLOPs either way; above 512
            # tokens SwitchMLP switches to its dense per-expert scan
            # (O(T*ffn) memory — the cap=T one-hot machinery is quadratic
            # in T), below it the one-shot capacity dispatch.
            if moe_drop_free is None:
                moe_drop_free = kv_cache is not None
            with nvtx_range(SCOPE_MLP):
                mlp_out, aux = self.mlp.apply(
                    params["mlp"], x.astype(c.compute_dtype),
                    rng=moe_rng, deterministic=deterministic,
                    drop_free=moe_drop_free)
        else:
            with nvtx_range(SCOPE_MLP):
                mlp_out = self.mlp.apply(
                    params["mlp"], x.astype(c.compute_dtype),
                    lora=None if lora is None
                    else lora.get("dense_h_to_4h"))
        if c.sandwich_norm:
            mlp_out = norm("post_mlp_layernorm", mlp_out)
        mlp_out = _dropout(mlp_out, c.hidden_dropout, rngs[1], deterministic,
                           model_parallel_region=c.sequence_parallel,
                           axis_name=c.axis_name)
        out = hidden + mlp_out
        if new_cache is not None:
            # decode is inference: the MoE load-balancing aux loss is a
            # training signal, so it is dropped on the cache path (expert
            # dispatch itself runs normally inside the decode scan)
            return out, new_cache
        return (out, aux) if c.num_moe_experts else out


@dataclass
class ParallelTransformer:
    """Stack of :class:`ParallelTransformerLayer` via ``lax.scan``.

    Reference: ``standalone_transformer_lm.py`` ``ParallelTransformer``
    (~:1151-1380). ``num_layers`` here is the *local* (per-pipeline-stage)
    depth; pipeline schedules stack these per stage.
    """

    config: TransformerConfig
    layer_type: Any = LayerType.encoder

    def __post_init__(self):
        # one layer object per KIND of layer; a model of one kind has one
        # (``self.layer``) and stacks its parameters for ``lax.scan``, a
        # mixed model holds a per-layer LIST of parameter trees
        self.layer_kinds = self.config.layer_kinds
        self.layers = {
            kind: ParallelTransformerLayer(self.config, self.layer_type,
                                           layer_kind=kind)
            for kind in dict.fromkeys(self.layer_kinds)}
        self.layer = self.layers[self.layer_kinds[0]]

    def _layer(self, idx: int) -> ParallelTransformerLayer:
        return self.layers[self.layer_kinds[idx]]

    def init(self, key):
        keys = jax.random.split(key, self.config.num_layers)
        if self.config.mixed_layers:
            stacked = [self._layer(i).init(k) for i, k in enumerate(keys)]
        else:
            stacked = jax.vmap(self.layer.init)(keys)
        return {"layers": stacked,
                "final_layernorm": _ln_params(
                    self.config.hidden_size, self.config.params_dtype,
                    self.config.normalization)}

    def spec(self):
        if self.config.mixed_layers:
            stacked = [self._layer(i).spec()
                       for i in range(self.config.num_layers)]
        else:
            stacked = jax.tree.map(
                lambda s: PartitionSpec(None, *s), self.layer.spec(),
                is_leaf=lambda x: isinstance(x, PartitionSpec))
        return {"layers": stacked,
                "final_layernorm": _ln_spec(self.config.normalization)}

    def apply(self, params, hidden, *, encoder_output=None,
              enc_dec_attn_mask=None, enc_kv_lengths=None,
              attention_mask=None, kv_lengths=None, kv_caches=None,
              cache_index=None, rng=None, deterministic=True,
              final_norm=True, moe_drop_free=None, paged_state=None,
              lora=None, routing=None):
        """Returns ``hidden`` — or ``(hidden, moe_aux_loss)`` (aux summed
        over layers) when the config enables MoE, or ``(hidden, new_caches)``
        when decoding with ``kv_caches`` — either ``(k, v)`` stacked
        ``[L, ...]`` (scan form) or a LIST of per-layer ``(k, v)`` pairs
        (unrolled form; ``init_kv_caches(stacked=False)``). The list form
        is the fast decode path: scanning over a stacked cache pays
        full-cache slice/restack copies every step (measured 2.4x slower
        at bs8 — PERF.md round 4), while per-layer buffers update in
        place. ``routing`` (list form): a ``transformer.moe.RoutingStats``
        handed to every layer, for the routed layers' counts."""
        c = self.config
        moe = bool(c.num_moe_experts)

        # attention-dropout seeds: ONE base draw per step, offset per layer
        # by an odd constant (injective mod 2^32) — masks are structurally
        # distinct across layers, where independent per-layer 32-bit draws
        # collide (and then share a mask) at ~L^2/2^33 per step. The base
        # key folds num_layers so it never collides with the per-layer
        # fold_in(rng, idx) stream below; model_parallel_rng_key keeps the
        # per-TP-rank distinctness of the in-attention derivation.
        attn_seed_base = None
        if (rng is not None and not deterministic
                and c.attention_dropout > 0.0):
            skey = model_parallel_rng_key(
                jax.random.fold_in(rng, c.num_layers), c.axis_name)
            attn_seed_base = jax.random.randint(
                skey, (1,), -2 ** 31, 2 ** 31 - 1, jnp.int32)

        def _attn_seed(idx):
            if attn_seed_base is None:
                return None
            golden = jnp.int32(-1640531527)  # 0x9E3779B9, odd
            return attn_seed_base + jnp.int32(idx) * golden

        if lora is not None and not (
                kv_caches is not None and isinstance(kv_caches, list)):
            # per-slot adapters exist for the serving step programs, which
            # all decode over the per-layer LIST cache form; training and
            # merged-reference paths fold adapters into the weights instead
            # (apex_tpu.lora.merge_adapter)
            raise NotImplementedError(
                "lora (per-slot adapter factors) needs the per-layer LIST "
                "kv_caches form — merge adapters into the weights for "
                "cache-free or scan-form forwards")
        if lora is not None and c.sequence_parallel:
            raise NotImplementedError(
                "lora deltas read the layer input pre-gather; sequence "
                "parallelism is not supported on the adapter path")
        if paged_state is not None and not (
                kv_caches is not None and isinstance(kv_caches, list)):
            raise NotImplementedError(
                "paged decode needs the per-layer LIST cache form (each "
                "entry one layer's page pool pair) — the stacked scan "
                "form re-slices the whole pool every layer")
        # a LIST means per-layer (k, v) pairs (the stacked scan form is a
        # 2-TUPLE of [L, ...] arrays — do not widen this check to tuple)
        if kv_caches is not None and isinstance(kv_caches, list):
            # quantized paged entries nest one level deeper: each of
            # k/v is a (pages, scales) pair — validate on the pages
            k0 = kv_caches[0][0] if (
                isinstance(kv_caches[0], (tuple, list))
                and len(kv_caches[0]) == 2) else None
            if (isinstance(k0, (tuple, list)) and len(k0) == 2
                    and paged_state is not None):
                k0 = k0[0]
            if (len(kv_caches) != c.num_layers
                    # entries must be (k, v) PAIRS: a stacked (k, v) pair
                    # that became a [k, v] list in a serialization
                    # round-trip would otherwise run SILENTLY wrong on
                    # 2-layer models — each [2, ...] ARRAY entry unpacks
                    # into two per-layer slices of valid shape, so the
                    # entry type check (not just the lengths) is what
                    # actually catches it
                    or not isinstance(kv_caches[0], (tuple, list))
                    or len(kv_caches[0]) != 2
                    or getattr(k0, "ndim", 0) not in (3, 4)):
                raise ValueError(
                    f"list-form kv_caches must hold num_layers "
                    f"({c.num_layers}) per-layer (k, v) pairs of "
                    f"[batch, heads, S, head_dim] (or flat "
                    f"[batch, S, heads*head_dim]) arrays; got a "
                    f"{len(kv_caches)}-element list — a stacked cache is "
                    f"a (k, v) TUPLE of [L, ...] arrays")
            # unrolled per-layer cache loop (no remat: decode is inference)
            h = hidden
            new_caches = []
            layers_p = params["layers"]
            for idx, layer_cache in enumerate(kv_caches):
                # a list/tuple of per-layer pytrees skips the in-loop slice
                # of the stacked params: inside a decode scan XLA re-slices
                # (and lays out copies of) the stacked weights EVERY step
                # (~115 us/step at GPT-2 124M bs8 — PERF.md round 5);
                # generate() pre-slices once outside the scan
                layer_params = (layers_p[idx]
                                if isinstance(layers_p, (list, tuple))
                                else jax.tree.map(lambda x: x[idx],
                                                  layers_p))
                layer_rng = (None if rng is None
                             else jax.random.fold_in(rng, idx))
                # adapter-bank leaves are [L, b, ...] (gathered per slot
                # by the caller); slice this layer's factors
                layer_lora = (None if lora is None
                              else jax.tree.map(lambda x: x[idx], lora))
                h, new_cache = self._layer(idx).apply(
                    layer_params, h, encoder_output=encoder_output,
                    enc_dec_attn_mask=enc_dec_attn_mask,
                    enc_kv_lengths=enc_kv_lengths,
                    attention_mask=attention_mask,
                    kv_lengths=kv_lengths, kv_cache=layer_cache,
                    cache_index=cache_index, rng=layer_rng,
                    deterministic=deterministic,
                    moe_drop_free=moe_drop_free,
                    attention_seed=_attn_seed(idx),
                    paged_state=paged_state, lora=layer_lora,
                    routing=routing)
                new_caches.append(new_cache)
            if final_norm:
                h = _ln(params["final_layernorm"], h, c.layernorm_epsilon,
                        c.sequence_parallel, c.axis_name, c.normalization)
            return h, new_caches

        if c.mixed_layers:
            if kv_caches is not None:
                raise NotImplementedError(
                    "a model whose layers differ (attention_layer_types / "
                    "num_dense_layers) decodes over the per-layer LIST "
                    "cache form; the stacked (k, v) scan form needs layers "
                    "of one kind")
            if c.recompute:
                raise NotImplementedError(
                    "recompute runs inside the lax.scan layer stack, which "
                    "needs layers of one kind; training a mixed model is "
                    "not wired up (ROADMAP M1)")
            # the cache-free forward of a mixed model: the same per-layer
            # list, unrolled (what the serving programs run, minus a cache)
            h = hidden
            for idx, layer_params in enumerate(params["layers"]):
                h = self._layer(idx).apply(
                    layer_params, h, encoder_output=encoder_output,
                    enc_dec_attn_mask=enc_dec_attn_mask,
                    enc_kv_lengths=enc_kv_lengths,
                    attention_mask=attention_mask, kv_lengths=kv_lengths,
                    rng=(None if rng is None
                         else jax.random.fold_in(rng, idx)),
                    deterministic=deterministic,
                    moe_drop_free=moe_drop_free,
                    attention_seed=_attn_seed(idx))
            if final_norm:
                h = _ln(params["final_layernorm"], h, c.layernorm_epsilon,
                        c.sequence_parallel, c.axis_name, c.normalization)
            return h

        def one_layer(carry, xs):
            h, aux_sum, idx = carry
            if kv_caches is not None:
                layer_params, layer_cache = xs
            else:
                layer_params, layer_cache = xs, None
            layer_rng = None if rng is None else jax.random.fold_in(rng, idx)

            def run(h):
                out = self.layer.apply(
                    layer_params, h, encoder_output=encoder_output,
                    enc_dec_attn_mask=enc_dec_attn_mask,
                    enc_kv_lengths=enc_kv_lengths,
                    attention_mask=attention_mask,
                    kv_lengths=kv_lengths, kv_cache=layer_cache,
                    cache_index=cache_index, rng=layer_rng,
                    deterministic=deterministic,
                    moe_drop_free=moe_drop_free,
                    attention_seed=_attn_seed(idx))
                if layer_cache is not None:
                    return out        # (h, new_cache)
                return out if moe else (out, jnp.zeros((), jnp.float32))

            if c.recompute == "selective":
                run = jax.checkpoint(
                    run,
                    policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)
            elif c.recompute:
                run = jax.checkpoint(run)
            h, extra = run(h)
            if layer_cache is not None:
                return (h, aux_sum, idx + 1), extra
            return (h, aux_sum + extra, idx + 1), None

        xs = (params["layers"] if kv_caches is None
              else (params["layers"], kv_caches))
        (hidden, aux_sum, _), new_caches = lax.scan(
            one_layer, (hidden, jnp.zeros((), jnp.float32), 0), xs,
            unroll=min(c.scan_unroll, c.num_layers))
        if final_norm:
            hidden = _ln(params["final_layernorm"], hidden,
                         c.layernorm_epsilon, c.sequence_parallel,
                         c.axis_name, c.normalization)
        if kv_caches is not None:
            return hidden, new_caches
        return (hidden, aux_sum) if moe else hidden
