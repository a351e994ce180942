"""Standalone GPT — the flagship causal LM.

Capability counterpart of the reference's test-fixture GPT
(``apex/transformer/testing/standalone_gpt.py:~40-111`` on top of
``standalone_transformer_lm.py``: ``TransformerLanguageModel`` ~:1390-1550,
``post_language_model_processing`` lm-head + vocab-parallel loss): vocab- and
tensor-sharded embedding, learned positions, parallel transformer stack,
weight-tied vocab-parallel LM head, vocab-parallel cross entropy.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from apex_tpu.models.transformer import (
    ParallelTransformer,
    TransformerConfig,
    embed_tokens,
    position_table_params,
    position_table_spec,
)
from apex_tpu.observability.tracing import SCOPE_LM_HEAD_LOSS
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    VocabParallelEmbedding,
    linear_with_grad_accumulation_and_async_allreduce,
)
from apex_tpu.utils.profiling import nvtx_range
__all__ = ["GPTModel", "lm_head_loss", "output_weight"]


def output_weight(params, config):
    """The LM head's ``[V, h]`` matrix: the word embedding (tied, the
    default) or the model's own ``output_layer``
    (``untie_embeddings_and_output_weights``)."""
    if config.untie_embeddings_and_output_weights:
        return params["output_layer"]["weight"]
    return params["embedding"]["word_embeddings"]["weight"]


@nvtx_range(SCOPE_LM_HEAD_LOSS)
def lm_head_loss(embedding_weight, hidden, labels, loss_mask, config):
    """Weight-tied LM head + vocab-parallel loss tail shared by
    :class:`GPTModel` and :class:`~apex_tpu.models.pipelined.PipelinedGPT`.

    Reference: ``standalone_transformer_lm.py`` ``post_language_model_
    processing`` — ColumnParallelLinear forward with the vocab-sharded
    embedding matrix (under SP this all-gathers the sequence shards back into
    the matmul), then ``vocab_parallel_cross_entropy``. Returns vocab-parallel
    logits ``[s, b, V/tp]`` when ``labels`` is None, else the scalar
    (optionally loss-masked) mean loss.
    """
    c = config

    def head(hid):
        # LM-head matmul in compute dtype (bf16 on the MXU runs ~4x fp32
        # and halves the [s, b, V] logits footprint); the CE upcasts
        # internally (vocab_parallel_cross_entropy fp32 math, Megatron
        # kernel semantics)
        return linear_with_grad_accumulation_and_async_allreduce(
            hid.astype(c.compute_dtype),
            embedding_weight,  # callee casts weight to x.dtype (amp-O2 rule)
            None,
            sequence_parallel_enabled=c.sequence_parallel,
            axis_name=c.axis_name)                          # [s, b, V/tp]

    if labels is None:
        return head(hidden)
    labels_sb = labels.transpose(1, 0)                      # [s, b]
    nc = c.loss_seq_chunks
    if nc > 1 and not c.sequence_parallel and hidden.shape[0] % nc == 0:
        # long-context memory guard: the [s, b, V] logits of a 64k sequence
        # are ~13 GB in fp32 — compute head+CE per sequence chunk under
        # remat so only one chunk's logits ever exist (the chunk re-runs
        # its matmul in backward, a cheap trade at vocab width). Skipped
        # under SP, where the head's all-gather interleaves global
        # positions across chunks.
        s = hidden.shape[0]
        hc = hidden.reshape(nc, s // nc, *hidden.shape[1:])
        lc = labels_sb.reshape(nc, s // nc, labels_sb.shape[1])

        @jax.checkpoint
        def chunk_losses(hid, lab):
            return vocab_parallel_cross_entropy(head(hid), lab,
                                                axis_name=c.axis_name)

        losses = jax.lax.map(lambda xs: chunk_losses(*xs), (hc, lc))
        losses = losses.reshape(s, -1)
    else:
        losses = vocab_parallel_cross_entropy(head(hidden), labels_sb,
                                              axis_name=c.axis_name)
    if loss_mask is None:
        return jnp.mean(losses)
    mask_sb = loss_mask.transpose(1, 0).astype(losses.dtype)
    return jnp.sum(losses * mask_sb) / jnp.maximum(jnp.sum(mask_sb), 1.0)


@dataclass
class GPTModel:
    """GPT: embeddings -> ParallelTransformer (causal) -> LM head (tied
    to the embedding unless ``untie_embeddings_and_output_weights``)."""

    config: TransformerConfig

    def __post_init__(self):
        c = self.config
        self.embedding = VocabParallelEmbedding(
            c.vocab_size, c.hidden_size, init_method=c.init_method(),
            params_dtype=c.params_dtype, axis_name=c.axis_name)
        self.transformer = ParallelTransformer(c)

    def init(self, key: jax.Array) -> Dict[str, Any]:
        c = self.config
        k_emb, k_pos, k_tr = jax.random.split(key, 3)
        p = {
            "embedding": {
                "word_embeddings": self.embedding.init(k_emb),
                **position_table_params(c, k_pos),
            },
            "transformer": self.transformer.init(k_tr),
        }
        if c.untie_embeddings_and_output_weights:
            # same shape and sharding as the embedding it is untied from
            p["output_layer"] = self.embedding.init(
                jax.random.fold_in(key, 3))
        return p

    def spec(self) -> Dict[str, Any]:
        s = {
            "embedding": {
                "word_embeddings": self.embedding.spec(),
                **position_table_spec(self.config),
            },
            "transformer": self.transformer.spec(),
        }
        if self.config.untie_embeddings_and_output_weights:
            s["output_layer"] = self.embedding.spec()
        return s

    def _embed(self, params, tokens, rng, deterministic):
        """tokens [b, s] -> hidden [s(, shard), b, h] (Megatron layout)."""
        return embed_tokens(self.embedding, params["embedding"], tokens,
                            self.config, rng=rng, deterministic=deterministic)

    def apply(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,
        labels: Optional[jax.Array] = None,
        *,
        loss_mask: Optional[jax.Array] = None,
        rng: Optional[jax.Array] = None,
        deterministic: bool = True,
        moe_drop_free: Optional[bool] = None,
    ):
        """tokens/labels/loss_mask: ``[batch, seq]``.

        With ``labels`` returns the scalar masked-mean LM loss (the
        reference's loss path through ``vocab_parallel_cross_entropy``);
        otherwise returns vocab-parallel logits ``[s, b, vocab/tp]``.
        ``moe_drop_free=True`` routes MoE layers without capacity drops —
        the serving forward that matches ``generate()``'s cached logits
        exactly at ANY ``moe_capacity_factor`` (the generation path itself
        always routes drop-free); default (None) keeps the factor-based
        training routing.
        """
        rngs = (None, None) if rng is None else tuple(jax.random.split(rng))
        hidden = self._embed(params, tokens, rngs[0], deterministic)
        hidden = self.transformer.apply(
            params["transformer"], hidden, rng=rngs[1],
            deterministic=deterministic, moe_drop_free=moe_drop_free)
        moe_aux = None
        if self.config.num_moe_experts:
            hidden, moe_aux = hidden
        out = lm_head_loss(
            output_weight(params, self.config), hidden,
            labels, loss_mask, self.config)
        if moe_aux is not None and labels is not None:
            out = out + moe_aux        # load-balancing term, pre-scaled
        return out
