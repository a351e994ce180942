"""Mixture-of-Experts with expert parallelism (EP).

**Exceeds the reference**: apex has no MoE/expert code anywhere in the tree
(SURVEY.md §2.2 "EP — absent"). This module completes the parallelism matrix
(DP/TP/SP/PP/CP/EP) with the TPU-native shape of switch routing:

- router: top-1 or top-2 gating with optional jitter and the standard
  load-balancing auxiliary loss (Shazeer/Fedus switch-transformer recipe —
  public algorithm, implemented fresh);
- capacity-based dispatch: per-shard token buffers ``[E, C, h]`` built with
  one-hot matmuls (MXU-friendly, no scatters), tokens over capacity dropped
  to the residual path;
- expert parallelism over a mesh axis (default: the ``data`` axis, the
  standard "EP rides DP" layout): one ``lax.all_to_all`` ships each
  expert's buffer to its owning rank, the expert FFNs run as one batched
  einsum over the local experts, and a second ``all_to_all`` ships results
  back. Unsharded (axis unbound) it degrades to a dense dispatch over all
  experts locally.

Layout follows the transformer stack: ``[s, b, h]`` activations, functional
``init/apply``, works inside ``shard_map`` next to
:class:`~apex_tpu.models.transformer.ParallelTransformerLayer`.

Two layers live here, for two jobs:

- :class:`SwitchMLP` is the TRAINING layer: softmax top-1/top-2 with a
  capacity factor, dropped tokens, the load-balancing loss and the
  ``all_to_all`` exchange over an expert axis. Its drop-free mode (every
  local expert over every token) exists so that a model trained with it
  can be decoded without capacity drops; it is not a serving kernel.
- :class:`RoutedExperts` is the SERVING layer of a sparse-expert model
  (docs/moe.md): any ``top_k``, sigmoid scores with a selection bias,
  normalised and scaled weights, a shared expert, no capacity and no
  dropped token. Rows are sorted by expert and multiplied by the weights of
  the experts they chose and no others
  (:mod:`apex_tpu.ops.grouped_matmul`). It is told which experts of the
  layer it holds (``expert_range``) and computes their part of the result;
  on one chip it holds all and runs without an exchange.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from apex_tpu.observability.tracing import (SCOPE_MOE, SCOPE_MOE_ROUTER,
                                            SCOPE_MOE_SHARED)
from apex_tpu.transformer.parallel_state import DATA_AXIS
from apex_tpu.transformer.tensor_parallel.mappings import axis_bound, axis_size
from apex_tpu.transformer.tensor_parallel.utils import divide
from apex_tpu.utils.activations import (
    apply_activation,
    is_gated,
    validate_activation,
)
from apex_tpu.utils.profiling import nvtx_range

__all__ = ["MoEConfig", "SwitchMLP", "RoutedMoEConfig", "RoutedExperts",
           "RoutingStats", "ROUTING_STATS"]


@dataclass(frozen=True)
class MoEConfig:
    hidden_size: int
    ffn_hidden_size: int
    num_experts: int
    # 1 = switch, 2 = GShard-style. The capacity dispatch below builds one
    # [tokens, experts, cap] one-hot per k: fine for the 1 or 2 a model is
    # trained with here; more experts a token at serving time is
    # RoutedExperts' job (sorted rows, grouped products, nothing dropped)
    top_k: int = 1
    capacity_factor: float = 1.25
    aux_loss_weight: float = 1e-2
    router_jitter: float = 0.0          # multiplicative input jitter at train
    expert_axis: Optional[str] = DATA_AXIS
    # expert FFN activation; gated pairs ("swiglu"/"geglu") widen w_in to
    # 2*ffn with gate/up unit-interleaved (same layout as ParallelMLP)
    activation: str = "gelu"
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    init_method_std: float = 0.02

    def __post_init__(self):
        validate_activation(self.activation)

    @property
    def gated(self) -> bool:
        return is_gated(self.activation)


class SwitchMLP:
    """Top-k routed expert FFN bank.

    ``apply(params, x[s, b, h], rng, deterministic) ->
    (y[s, b, h], aux_loss)``; ``aux_loss`` is already scaled by
    ``config.aux_loss_weight`` — callers add it to the training objective
    as-is.
    """

    def __init__(self, config: MoEConfig):
        self.config = config

    # -- params --------------------------------------------------------------

    def init(self, key: jax.Array) -> Dict[str, Any]:
        c = self.config
        kr, k1, k2 = jax.random.split(key, 3)
        std = c.init_method_std
        dt = c.params_dtype
        fin = (2 if c.gated else 1) * c.ffn_hidden_size
        p = {
            "router": jax.random.normal(
                kr, (c.hidden_size, c.num_experts), dt) * std,
            "w_in": jax.random.normal(
                k1, (c.num_experts, c.hidden_size, fin),
                dt) * std,
            "w_out": jax.random.normal(
                k2, (c.num_experts, c.ffn_hidden_size, c.hidden_size),
                dt) * std,
            "b_out": jnp.zeros((c.num_experts, c.hidden_size), dt),
        }
        if not c.gated:
            # gated projections are bias-free (shared convention with
            # ParallelMLP, utils/activations.py)
            p["b_in"] = jnp.zeros((c.num_experts, fin), dt)
        return p

    def spec(self) -> Dict[str, PartitionSpec]:
        """Experts sharded dim-0 over the expert axis; router replicated."""
        e = self.config.expert_axis
        s = {
            "router": PartitionSpec(),
            "w_in": PartitionSpec(e, None, None),
            "w_out": PartitionSpec(e, None, None),
            "b_out": PartitionSpec(e, None),
        }
        if not self.config.gated:
            s["b_in"] = PartitionSpec(e, None)
        return s

    # -- routing -------------------------------------------------------------

    def _route(self, params, x2d, rng, deterministic):
        """x2d: [T, h] -> (weights [T, k], experts [T, k], aux_loss)."""
        c = self.config
        inp = x2d
        if not deterministic and c.router_jitter > 0.0 and rng is not None:
            eps = jax.random.uniform(
                rng, x2d.shape, x2d.dtype,
                1.0 - c.router_jitter, 1.0 + c.router_jitter)
            inp = x2d * eps
        logits = inp.astype(jnp.float32) @ params["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)            # [T, E]
        weights, experts = lax.top_k(probs, c.top_k)       # [T, k]
        if c.top_k > 1:
            weights = weights / jnp.sum(weights, -1, keepdims=True)

        # load-balancing loss: E * sum_e fraction_e * mean_prob_e
        # (switch-transformer aux objective)
        top1 = experts[:, 0]
        frac = jnp.mean(
            jax.nn.one_hot(top1, c.num_experts, dtype=jnp.float32), axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        aux = (c.aux_loss_weight * c.num_experts
               * jnp.sum(frac * mean_prob))
        return weights, experts, aux

    # -- dispatch/combine ----------------------------------------------------

    def _capacity(self, tokens: int) -> int:
        c = self.config
        cap = int(tokens * c.capacity_factor * c.top_k / c.num_experts)
        return max(cap, 1)

    def apply(self, params, x, *, rng=None, deterministic: bool = True,
              drop_free: bool = False) -> Tuple[jax.Array, jax.Array]:
        """``drop_free=True`` sizes the capacity buffers at ``tokens`` (an
        expert can hold every token), guaranteeing no capacity drops — the
        decode path uses this: per-step token counts are tiny, so the
        factor-based capacity would drop tokens batch-size-dependently and
        decode logits would silently diverge from the batched forward."""
        c = self.config
        s, b, h = x.shape
        tokens = s * b
        x2d = x.reshape(tokens, h)
        weights, experts, aux = self._route(params, x2d, rng, deterministic)
        if drop_free and tokens > 512:
            # DENSE drop-free evaluation for batched token counts (round
            # 5): the capacity machinery with cap = tokens builds
            # [T, E, T] dispatch/combine one-hots — QUADRATIC in tokens
            # (a 32k-token 64-expert prefill would need ~275 GB) — and
            # computes every buffer slot anyway. Scanning local experts
            # over all tokens pays the same E/top_k FLOP blowup with
            # O(T * ffn) memory; under EP each rank runs its local
            # experts and one psum replaces both all_to_alls. Small
            # token counts (single-token decode) keep the one-shot
            # capacity dispatch below.
            y = self._dense_drop_free(params, x2d, weights, experts)
            return y.reshape(s, b, h).astype(x.dtype), aux
        cap = tokens if drop_free else self._capacity(tokens)

        # position of each token within its expert's capacity buffer, one
        # pass per k (cumsum over the one-hot assignment matrix)
        dispatch = jnp.zeros((tokens, c.num_experts, cap), x.dtype)
        combine = jnp.zeros((tokens, c.num_experts, cap), jnp.float32)
        prior = jnp.zeros((c.num_experts,), jnp.int32)
        for k in range(c.top_k):
            onehot = jax.nn.one_hot(experts[:, k], c.num_experts,
                                    dtype=jnp.int32)       # [T, E]
            pos = jnp.cumsum(onehot, axis=0) - 1 + prior   # [T, E]
            prior = prior + jnp.sum(onehot, axis=0)
            within = jnp.take_along_axis(
                pos, experts[:, k:k + 1], axis=1)[:, 0]    # [T]
            keep = within < cap                            # overflow dropped
            pos_oh = jax.nn.one_hot(jnp.where(keep, within, cap),
                                    cap + 1, dtype=x.dtype)[:, :cap]
            contrib = onehot.astype(x.dtype)[:, :, None] * pos_oh[:, None, :]
            dispatch = dispatch + contrib
            combine = combine + (contrib.astype(jnp.float32)
                                 * weights[:, k, None, None])

        # gather tokens into expert buffers: [E, C, h] (one-hot matmul — a
        # dense MXU op instead of data-dependent scatters)
        buffers = jnp.einsum("tec,th->ech", dispatch, x2d)

        ep = (axis_size(c.expert_axis)
              if c.expert_axis and axis_bound(c.expert_axis) else 1)
        if ep > 1:
            divide(c.num_experts, ep)    # validate E % ep == 0
            # ship expert buffers to their owners: split the expert dim
            # (chunk i -> rank i), concat received chunks along capacity:
            # [E, C, h] -> [E/ep, ep*C, h]; each rank now holds its local
            # experts' tokens from every rank
            buffers = lax.all_to_all(buffers, c.expert_axis, split_axis=0,
                                     concat_axis=1, tiled=True)

        cd = c.compute_dtype
        # params inside shard_map are already the local expert shard
        # ([E/ep, ...]) under spec(); unsharded they are the full bank
        w_in = params["w_in"]
        w_out, b_out = params["w_out"], params["b_out"]
        hmid = jnp.einsum("ech,ehf->ecf", buffers.astype(cd),
                          w_in.astype(cd))
        if not c.gated:
            hmid = hmid + params["b_in"][:, None, :].astype(cd)
        hmid = apply_activation(hmid, c.activation)
        out = jnp.einsum("ecf,efh->ech", hmid,
                         w_out.astype(cd)) + b_out[:, None, :].astype(cd)

        if ep > 1:
            # inverse shuffle: split capacity back per source rank, concat
            # experts back to global order: [E/ep, ep*C, h] -> [E, C, h]
            out = lax.all_to_all(out, c.expert_axis, split_axis=1,
                                 concat_axis=0, tiled=True)

        # combine back to token order with routing weights
        y = jnp.einsum("tec,ech->th", combine.astype(jnp.float32),
                       out.astype(jnp.float32))
        return y.reshape(s, b, h).astype(x.dtype), aux

    def _dense_drop_free(self, params, x2d, weights, experts):
        """Every local expert processes every token; per-token routing
        weights combine the results (exactly the drop-free capacity math,
        without its [T, E, cap] one-hots). Returns fp32 ``[T, h]``."""
        c = self.config
        tokens, h = x2d.shape
        ep = (axis_size(c.expert_axis)
              if c.expert_axis and axis_bound(c.expert_axis) else 1)
        if ep > 1:
            divide(c.num_experts, ep)
            # the token batch is SHARDED along the expert axis (EP rides
            # DP), so shard-local partials must not be psum'd as-is (each
            # rank's rows are DIFFERENT tokens — the capacity path handles
            # this with its all_to_all pair): gather every rank's tokens
            # and routing decisions, let the local experts process the
            # full set, psum the partial outputs, then slice this rank's
            # rows back out. The compact [T, k] weights/experts move over
            # the interconnect (E/(2k)x less than the dense [T, E] wte,
            # which is pure local compute built post-gather).
            e_local = c.num_experts // ep
            idx = lax.axis_index(c.expert_axis)
            x2d = lax.all_gather(x2d, c.expert_axis, axis=0, tiled=True)
            weights = lax.all_gather(weights, c.expert_axis, axis=0,
                                     tiled=True)
            experts = lax.all_gather(experts, c.expert_axis, axis=0,
                                     tiled=True)
        wte = jnp.zeros((x2d.shape[0], c.num_experts), jnp.float32)
        for k in range(c.top_k):
            wte = wte + (jax.nn.one_hot(experts[:, k], c.num_experts,
                                        dtype=jnp.float32)
                         * weights[:, k:k + 1].astype(jnp.float32))
        if ep > 1:
            wte = lax.dynamic_slice(
                wte, (jnp.int32(0), idx * e_local),
                (x2d.shape[0], e_local))
        cd = c.compute_dtype
        xc = x2d.astype(cd)

        def one_expert(y, ew):
            if c.gated:
                w_in, w_out, b_out, w_col = ew
                hm = xc @ w_in.astype(cd)
            else:
                w_in, b_in, w_out, b_out, w_col = ew
                hm = xc @ w_in.astype(cd) + b_in.astype(cd)
            hm = apply_activation(hm, c.activation)
            oe = hm @ w_out.astype(cd) + b_out.astype(cd)
            return y + w_col[:, None] * oe.astype(jnp.float32), None

        if c.gated:
            xs = (params["w_in"], params["w_out"], params["b_out"], wte.T)
        else:
            xs = (params["w_in"], params["b_in"], params["w_out"],
                  params["b_out"], wte.T)
        y, _ = lax.scan(one_expert,
                        jnp.zeros((x2d.shape[0], h), jnp.float32), xs)
        if ep > 1:
            y = lax.psum(y, c.expert_axis)
            y = lax.dynamic_slice_in_dim(y, idx * tokens, tokens, axis=0)
        return y


# -- the serving layer: drop-free, any top_k, sorted rows ---------------------

#: what one call of :class:`RoutedExperts` reports of its routing, in order:
#: assignments routed to experts held here, held experts that got at least
#: one row, rows of the busiest held expert
ROUTING_STATS = ("rows_routed", "experts_touched", "max_expert_rows")


class RoutingStats:
    """What the :class:`RoutedExperts` calls of one traced program report
    of their routing. The caller makes one, hands it down the forward
    (``decode_step(..., routing=stats)``) and returns ``stats.stacked()``
    from its program: the serving engine's decode programs do, so the
    counters ride the tick's read-back (docs/serving.md).

    ``active``: bool ``[b]``, the batch rows that count (a serving
    engine's idle slots route like any row and are left out).
    """

    def __init__(self, active: jax.Array):
        self.active = active
        self.calls = []

    def record(self, expert_of, n_held: int, top_k: int) -> None:
        """One layer call: ``expert_of [s * b * top_k]`` is each
        assignment's held expert (``n_held`` = held elsewhere), rows in
        ``[s, b]`` order."""
        per_row = jnp.tile(self.active, expert_of.size // top_k
                           // self.active.shape[0])
        sizes = jnp.zeros((n_held + 1,), jnp.int32).at[expert_of].add(
            jnp.repeat(per_row, top_k).astype(jnp.int32))[:n_held]
        self.calls.append(jnp.stack(
            [jnp.sum(sizes), jnp.sum(sizes > 0), jnp.max(sizes)]))

    def stacked(self) -> jax.Array:
        """int32 ``[calls, 3]``, columns :data:`ROUTING_STATS`."""
        return jnp.stack(self.calls).astype(jnp.int32)


@dataclass(frozen=True)
class RoutedMoEConfig:
    hidden_size: int
    ffn_hidden_size: int                # one routed expert's width
    num_experts: int                    # the router's width: the whole layer
    top_k: int
    route_scale: float = 1.0            # times the normalised weights
    num_shared_experts: int = 0         # as one expert of that many widths
    # the experts of the layer held here, ``(lo, hi)``; None = all. The
    # router still scores all ``num_experts``; rows that chose an expert
    # outside the range add nothing here (another holder computes them)
    expert_range: Optional[Tuple[int, int]] = None
    # group-limited selection: `num_groups` groups of consecutive experts,
    # a group's score the sum of its two best biased scores, the top_k
    # taken inside the `topk_groups` best groups (1 group: plain top_k)
    num_groups: int = 1
    topk_groups: int = 1
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    init_method_std: float = 0.02

    def __post_init__(self):
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(f"top_k ({self.top_k}) must lie in 1.."
                             f"num_experts ({self.num_experts})")
        if self.num_groups > 1:
            per_group, rest = divmod(self.num_experts, self.num_groups)
            if (rest or per_group < 2
                    or not 1 <= self.topk_groups <= self.num_groups
                    or self.topk_groups * per_group < self.top_k):
                raise ValueError(
                    f"{self.num_groups} groups over {self.num_experts} "
                    f"experts, {self.topk_groups} kept: groups must be "
                    f"equal, of two experts or more, and the kept ones "
                    f"must hold top_k ({self.top_k})")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(
                f"expert_range {self.expert_range} must be (lo, hi) with "
                f"0 <= lo < hi <= num_experts ({self.num_experts})")

    @property
    def held(self) -> Tuple[int, int]:
        return self.expert_range or (0, self.num_experts)


class RoutedExperts:
    """Drop-free routed expert layer with a shared expert.

    ``apply(params, x[s, b, h]) -> y[s, b, h]``:
    ``y = FFN_shared(x) + sum_{e in sel} w_e FFN_e(x)`` over the experts
    held here, each ``FFN`` the gated form ``(silu(x Wg) * (x Wu)) Wd``.
    ``s = sigmoid(x W_r)`` in float32; ``sel = top_k(s + bias)`` (the bias
    selects only; with ``num_groups`` > 1 over the ``topk_groups`` groups
    whose two best biased scores add up highest);
    ``w = s[sel] / (sum(s[sel]) + 1e-20) * route_scale``.
    The two halves
    are also callable apart (:meth:`routed`, :meth:`shared`): what every
    holder of a share computes alike is counted once by the caller that
    adds shares up.
    """

    def __init__(self, config: RoutedMoEConfig):
        self.config = config

    def init(self, key: jax.Array) -> Dict[str, Any]:
        c = self.config
        kr, k1, k2, k3, k4 = jax.random.split(key, 5)
        std, dt = c.init_method_std, c.params_dtype
        lo, hi = c.held
        h, f = c.hidden_size, c.ffn_hidden_size
        p = {
            # float32 always: rounding the router would let two runs of
            # one model choose different experts (cast_decode_params keeps
            # leaves under a "router" key as they are)
            "router": {
                "weight": jax.random.normal(
                    kr, (h, c.num_experts), jnp.float32) * std,
                "bias": jnp.zeros((c.num_experts,), jnp.float32)},
            "w_in": jax.random.normal(k1, (hi - lo, h, 2 * f), dt) * std,
            "w_out": jax.random.normal(k2, (hi - lo, f, h), dt) * std,
        }
        if c.num_shared_experts:
            fs = f * c.num_shared_experts
            p["shared"] = {
                "w_in": jax.random.normal(k3, (h, 2 * fs), dt) * std,
                "w_out": jax.random.normal(k4, (fs, h), dt) * std}
        return p

    def spec(self) -> Dict[str, Any]:
        s = {"router": {"weight": PartitionSpec(), "bias": PartitionSpec()},
             "w_in": PartitionSpec(), "w_out": PartitionSpec()}
        if self.config.num_shared_experts:
            s["shared"] = {"w_in": PartitionSpec(), "w_out": PartitionSpec()}
        return s

    def route(self, params, x2d):
        """``x2d [T, h]`` -> ``(weights [T, k] float32, experts [T, k])``
        over all ``num_experts``; the product, the scores and the
        selection are float32 at the highest matmul precision."""
        c = self.config
        r = params["router"]
        logits = jnp.dot(x2d.astype(jnp.float32),
                         r["weight"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        biased = scores + r["bias"].astype(jnp.float32)
        if c.num_groups > 1:
            groups = biased.reshape(-1, c.num_groups,
                                    c.num_experts // c.num_groups)
            best_two = jnp.sum(lax.top_k(groups, 2)[0], axis=-1)
            _, kept = lax.top_k(best_two, c.topk_groups)       # [T, kept]
            in_kept = jnp.any(
                kept[:, :, None] == jnp.arange(c.num_groups)[None, None, :],
                axis=1)                                        # [T, groups]
            biased = jnp.where(in_kept[:, :, None], groups,
                               -jnp.inf).reshape(biased.shape)
        _, experts = lax.top_k(biased, c.top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
        return weights * c.route_scale, experts

    def routed(self, params, x2d, stats: Optional[RoutingStats] = None):
        """The held experts' part of the result, float32 ``[T, h]``;
        the call's routing counts go to ``stats`` where one is given."""
        # (imported here: apex_tpu.ops pulls in modules that import this
        # package)
        from apex_tpu.ops.grouped_matmul import (grouped_gated_ffn,
                                                 routed_layout,
                                                 tile_rows_for)

        c = self.config
        tokens, h = x2d.shape
        lo, hi = c.held
        n_held = hi - lo
        with nvtx_range(SCOPE_MOE_ROUTER):
            weights, experts = self.route(params, x2d)
            here = (experts >= lo) & (experts < hi)
            expert_of = jnp.where(here, experts - lo, n_held).reshape(-1)
            tile_rows = tile_rows_for(tokens * c.top_k, n_held)
            dest, tile_expert, tiles_used = routed_layout(
                expert_of.astype(jnp.int32), n_held, tile_rows)
            rows = tile_expert.shape[0] * tile_rows
            token_of = jnp.arange(tokens * c.top_k, dtype=jnp.int32) \
                // c.top_k
            # padded row -> the token it holds (``tokens``, out of range,
            # for a padding row: gathered as zeros)
            source = jnp.full((rows,), tokens, jnp.int32).at[dest].set(
                token_of, mode="drop")
            if stats is not None:
                stats.record(expert_of, n_held, c.top_k)
        xp = x2d.astype(c.compute_dtype).at[source].get(
            mode="fill", fill_value=0)
        out = grouped_gated_ffn(
            xp, params["w_in"].astype(c.compute_dtype),
            params["w_out"].astype(c.compute_dtype), tile_expert,
            tiles_used, tile_rows=tile_rows)
        # a row that chose an expert held elsewhere has ``dest`` out of
        # range and gathers an exact zero
        picked = out.at[dest].get(mode="fill", fill_value=0).reshape(
            tokens, c.top_k, h).astype(jnp.float32)
        return jnp.sum(picked * weights[..., None], axis=1)

    def shared(self, params, x2d):
        """The shared expert over every row, float32 ``[T, h]``."""
        c = self.config
        p = params["shared"]
        fs = p["w_out"].shape[0]
        with nvtx_range(SCOPE_MOE_SHARED):
            xc = x2d.astype(c.compute_dtype)
            gu = jnp.dot(xc, p["w_in"].astype(c.compute_dtype),
                         preferred_element_type=jnp.float32)
            mid = (jax.nn.silu(gu[:, :fs]) * gu[:, fs:]).astype(
                c.compute_dtype)
            return jnp.dot(mid, p["w_out"].astype(c.compute_dtype),
                           preferred_element_type=jnp.float32)

    def apply(self, params, x, stats: Optional[RoutingStats] = None):
        s, b, h = x.shape
        x2d = x.reshape(s * b, h)
        with nvtx_range(SCOPE_MOE):
            y = self.routed(params, x2d, stats)
            if self.config.num_shared_experts:
                y = y + self.shared(params, x2d)
        return y.reshape(s, b, h).astype(x.dtype)
