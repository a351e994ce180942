"""Tensor-parallel serving engine over the device mesh.

One :class:`~apex_tpu.serving.InferenceEngine` serves from one chip's
HBM; a model too large (or a batch too hungry) for one chip needs the
decode step itself spread over the mesh. :class:`ShardedEngine` is the
same engine — same slot and page pools, same scheduler, same
quarantine and telemetry, same host-side arrays, page table included —
with its five device programs (decode / bucketed prefill / suffix
prefill, which is also the chunk program / quarantine scrub / scale
reset) wrapped in ``shard_map`` over the ``tensor`` mesh axis (via the
:mod:`apex_tpu.utils.sharding`
shims), reusing the :mod:`apex_tpu.transformer` TP layers the multichip
training dryruns already hold parity with:

- **Parameters** shard by the model's own partition spec
  (``model.spec()``): column/row-parallel QKV and MLP blocks, the
  vocab-sharded embedding doubling as the LM head.
- **The KV page pools shard on the heads axis**: each rank owns the
  ``[n_pages, page_size, local_kv_heads * head_dim]`` slice whose head
  block its QKV projection computes (and, for int8 pools, that block's
  scales), so prefill's page scatter and the fused append+attend of
  decode stay rank-local — no KV traffic crosses the mesh, exactly
  like the training-side cache layout under TP. The page table is
  replicated: the mapping is the same on every rank.
- **Logits are gathered to full vocab inside the step** (the same
  ``all_gather`` the generation path uses), so sampling and the
  per-slot integrity flags run replicated and every rank agrees on the
  next token — the host-side engine logic cannot tell it is driving a
  sharded program.

Parity bar (tier-1/slow tests): decode on a tp=2 CPU mesh is
TOKEN-EXACT against the unsharded engine, greedy and sampled, with zero
decode retraces — the same bar every multichip training dryrun meets.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import tree_map_with_path

from apex_tpu.models.generation import is_gated_mlp_weight
from apex_tpu.serving.engine import EngineConfig, InferenceEngine
from apex_tpu.transformer import parallel_state
from apex_tpu.utils.sharding import shard_map

__all__ = ["ShardedEngine"]


class ShardedEngine(InferenceEngine):
    """Tensor-parallel :class:`~apex_tpu.serving.InferenceEngine`; see
    the module docstring. ``mesh`` defaults to the initialized
    :mod:`~apex_tpu.transformer.parallel_state` mesh
    (``initialize_model_parallel(tensor_model_parallel_size=tp)``)."""

    def __init__(self, model, params, config: Optional[EngineConfig] = None,
                 *, mesh=None, metrics=None, faults=None,
                 replica_id: Optional[int] = None, adapters=None):
        self.mesh = mesh if mesh is not None else parallel_state.get_mesh()
        c = model.config
        self._tp = self.mesh.shape[c.axis_name]
        if getattr(c, "latent_attention", False):
            raise ValueError(
                "ShardedEngine shards the K/V pools by their heads; latent "
                "attention (kv_lora_rank) rows have no head axis: "
                "docs/serving.md#latent-kv")
        if c.kv_heads % self._tp:
            raise ValueError(
                f"kv heads ({c.kv_heads}) must be divisible by the "
                f"tensor-parallel size ({self._tp}); with GQA/MQA keep "
                f"num_query_groups a multiple of tp")
        if c.vocab_size % self._tp:
            raise ValueError(
                f"vocab_size ({c.vocab_size}) must be divisible by the "
                f"tensor-parallel size ({self._tp}) — the embedding / LM "
                f"head shard on the vocab dim (pad the vocab, as training "
                f"TP does)")
        if c.sequence_parallel:
            raise ValueError(
                "ShardedEngine decodes single tokens per slot — "
                "sequence_parallel has nothing to shard; build the model "
                "with sequence_parallel=False for serving")
        super().__init__(model, params, config, metrics=metrics,
                         faults=faults, replica_id=replica_id,
                         adapters=adapters)
        if not self._spec:
            # the carried token vector is a decode step's own output
            # from the second step on: place the first one as the
            # program will return it (replicated over the mesh), or the
            # second call would meet another sharding and compile again
            self._carry = jax.device_put(
                self._carry, NamedSharding(self.mesh, P()))

    # -- sharding specs ---------------------------------------------------

    def _param_spec(self):
        """``model.spec()`` reshaped to match the engine's prepared
        params: the one-time ``preslice_layer_params`` turns the stacked
        ``[L, ...]`` transformer layers into a per-layer LIST, so the
        stacked spec's leading (layer) dim is stripped and the per-layer
        spec repeated; and a gated dense layer's in-projection weight is
        held halves apart, ``[2, ffn, h]`` (``split_gated_mlp_params``),
        so its row spec moves to the ``ffn`` axis: every rank holds
        matched gate/up pairs, as it does of the interleaved rows."""
        spec = self.model.spec()
        layers = self._params.get("transformer", {}).get("layers")
        if isinstance(layers, (list, tuple)):
            is_spec = lambda x: isinstance(x, P)           # noqa: E731
            per_layer = jax.tree_util.tree_map(
                lambda s: P(*tuple(s)[1:]),
                spec["transformer"]["layers"], is_leaf=is_spec)
            spec = dict(spec)
            spec["transformer"] = dict(spec["transformer"])
            spec["transformer"]["layers"] = [per_layer] * len(layers)
        return tree_map_with_path(
            lambda path, x, s: P(None, *s)
            if is_gated_mlp_weight(path) and x.ndim == len(s) + 1 else s,
            self._params, spec)

    def _cache_spec(self):
        """The ``[n_pages, page_size, kv_heads * head_dim]`` pools shard
        their fused heads*head_dim minor dim over the tensor axis: each
        rank's contiguous block is exactly the head slice its QKV
        projection produces (page tables stay host-side/replicated; the
        mapping is identical on every rank).
        Quantized pools nest the per-page scale sidecar ``[n_pages,
        kv_heads]`` alongside each int8 pool, sharded on ITS heads dim —
        every rank holds exactly the scales of the head block it owns,
        so quantize/rescale/dequant stay rank-local too."""
        axis = self.model.config.axis_name
        if self._quantized:
            half = (P(None, None, axis), P(None, axis))
            pair = (half, half)
        else:
            pair = (P(None, None, axis), P(None, None, axis))
        return [pair for _ in range(self.model.config.num_layers)]

    def _lora_spec(self):
        """Spec for the LoRA adapter bank argument. Both LoRA targets
        (QKV, dense_h_to_4h) are column-parallel, so each ``B`` bank
        ``[L, n_adapters+1, r, out]`` shards its OUT dim over the tensor
        axis — each rank's slice is exactly the out block its projection
        computes, so ``y += (x @ A) @ B`` stays rank-local with zero
        collective cost (the rank-r inner product replicates). ``A``
        banks replicate (their dims are hidden x r on every target).
        With no :class:`~apex_tpu.lora.AdapterStore` the bank argument
        is ``None`` (an empty pytree) and a bare replicated spec
        suffices."""
        if self.adapters is None:
            return P()
        axis = self.model.config.axis_name
        target = {"A": P(), "B": P(None, None, None, axis)}
        return {t: target for t in self.adapters.bank}

    def _build_step_fns(self, donate: bool):
        """The base engine's step bodies, ``shard_map``-wrapped over the
        mesh: params by ``model.spec()``, KV pools on the heads axis,
        the page table (or the slot's table row), tokens, positions and
        sampling params replicated. The bodies themselves are INHERITED
        — this class changes where the math runs, not what it
        computes."""
        mesh = self.mesh
        pspec = self._param_spec()
        cspec = self._cache_spec()
        rep = P()
        lspec = self._lora_spec()
        # the plain body takes the fed tokens as three replicated
        # arguments (the host's vector, the carried one of the step
        # before, the mask that chooses between them); the speculative
        # verify body takes one, the [n, k] window matrix
        decode_body = (self._spec_decode_body if self._spec
                       else self._paged_decode_body)
        fed = (rep,) if self._spec else (rep, rep, rep)
        decode = shard_map(
            decode_body, mesh=mesh,
            in_specs=(pspec, cspec, rep, *fed, rep, rep, rep, rep,
                      rep, lspec),
            out_specs=(rep, rep, cspec))
        # a prefill program also takes the carried token vector and the
        # slot's index, and returns the vector with its first token in:
        # replicated, as the decode program returns it
        prefill = shard_map(
            self._paged_prefill_body, mesh=mesh,
            in_specs=(pspec, cspec, rep, rep, rep, rep, rep, rep,
                      rep, lspec, rep, rep),
            out_specs=(rep, rep, cspec, rep))
        # suffix prefill (prefix-cache hit, and every chunk of a chunked
        # prefill): the gather/scatter of the slot's pages is rank-local
        # on each rank's head slice, so sharding follows the pool spec;
        # everything scalar — start, lengths, sampling, the skip_first
        # flag — replicates
        suffix = shard_map(
            self._suffix_prefill_body, mesh=mesh,
            in_specs=(pspec, cspec, rep, rep, rep, rep, rep, rep,
                      rep, rep, rep, rep, lspec, rep, rep),
            out_specs=(rep, rep, cspec, rep))
        scrub = shard_map(
            self._paged_scrub_body, mesh=mesh,
            in_specs=(cspec, rep), out_specs=cspec)
        donate_args = (1,) if donate else ()
        donate_pool = (0,) if donate else ()
        reset = None
        if self._quantized:
            reset = jax.jit(
                shard_map(self._reset_scales_body, mesh=mesh,
                          in_specs=(cspec, rep), out_specs=cspec),
                donate_argnums=donate_pool)
        return (jax.jit(decode, donate_argnums=donate_args),
                jax.jit(prefill, donate_argnums=donate_args),
                jax.jit(suffix, donate_argnums=donate_args),
                jax.jit(scrub, donate_argnums=donate_pool),
                reset)
