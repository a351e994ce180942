"""Continuous-batching inference engine over the cached decode path.

``generate()`` (apex_tpu/models/generation.py) is a single-shot batch
primitive: every caller pays one lockstep prefill+decode, and short
requests wait for the longest. :class:`InferenceEngine` turns those
primitives into a request-level serving loop — Orca-style continuous
(in-flight) batching: requests are admitted and retired **per decode
step**, not per batch, over one fixed-shape jitted decode program.

Architecture (docs/serving.md has the full walkthrough):

- **Page pool**: every layer holds one ``[n_pages, page_size, kv_heads *
  head_dim]`` K and V pool (``init_paged_kv_caches``; int8 with a
  per-(page, kv-head) scale sidecar under ``kv_dtype="int8"``; for a
  latent-attention model the pair is the ``c`` and ``kR`` row pools,
  docs/serving.md#latent-kv) shared
  by all slots. :class:`~apex_tpu.serving.slots.SlotPool` hands out
  slots, :class:`~apex_tpu.serving.slots.PagePool` the pages behind
  them: a request maps the pages of its prompt at admission (its worst
  case is reserved, so growth cannot fail), grows by a page when decode
  crosses a boundary, and returns them on EOS/length budget/cancel/
  timeout. A prompt's full pages are interned by content, so a later
  prompt with the same prefix maps them refcounted.
- **Page table**: a host ``[max_slots, pages_per_slot]`` int32 array,
  uploaded with every step; an unmapped entry holds the sentinel
  ``n_pages`` (reads clamp and mask, scatters drop). Arrivals,
  retirements and page growth change host arrays only.
- **One decode program**: a single ``jax.jit`` step over ALL slots with
  per-slot position vectors. Each layer runs one fused append+attend
  (:mod:`apex_tpu.ops.decode_attention`): the step's K/V row is written
  into the slot's current page and the slot's mapped pages are read
  once, each row masked to its own length, rope rotated at its own
  offset; per-request sampling runs in-jit from per-slot
  temperature/top-k/seed arrays. The step NEVER retraces — asserted by
  a :class:`~apex_tpu.analysis.retrace.RetraceWatchdog`, since the
  decode roofline (PAPERS: arXiv 2502.17728) is only reachable when
  every step is the same compiled program. With ``speculation=k`` the
  same step feeds a ``[n, k]`` verify window.
- **One step in flight**: the plain decode path dispatches step k
  before it reads step k-1. Positions advance by one a step, page
  growth depends on position only and the sampler is keyed by ``(seed,
  position)``, so the host can build step k without step k-1's tokens;
  the one true dependence, the fed token, stays on the device (the
  program selects it from the vector the step before returned). The
  tick's host work then runs behind the device step; EOS, a poisoned
  row, a cancel are learned one step late, at the price of at most one
  dropped row a slot (docs/serving.md#one-step-in-flight).
  Speculation reads each step in the tick that dispatched it.
- **Bucketed prefill that fills pages**: prompts prefill one at a time,
  right-padded to power-of-two buckets, on the SAME 4D-list/flash path
  ``generate()`` uses; the K/V rows are then flattened and scattered
  into the slot's pages, whole pages at a time. Compile count is bounded
  by the bucket set and outputs are token-exact against a per-request
  reference (``tests/serving_reference.py``).
- **Suffix prefill, doubling as the chunk program**: a prefix-cache hit
  prefills only what its shared pages do not cover: the slot's pages
  are gathered into a small 4D cache, the suffix runs at its absolute
  offset (a traced scalar) and its rows are scattered back one by one.
  Under ``prefill_token_budget`` a long prompt is a sequence of such
  calls carried across ticks, so chunking adds no program and no shape.
- **Scheduling**: FCFS bounded queue with a decode-starvation cap
  (:mod:`apex_tpu.serving.scheduler`); queue-full rejection, deadlines,
  and cancellation follow ``resilience``'s structured ``log_event``
  conventions, and every terminal request emits one ``kind="request"``
  JSONL record plus latency/occupancy histograms into an attached
  :class:`~apex_tpu.observability.MetricsRegistry` (rendered by
  ``python -m apex_tpu.monitor``).
- **Decode-output integrity**: the jitted decode step also returns a
  per-slot ``isfinite(logits)`` flag (one cheap in-jit reduction —
  resilience's off-critical-path watchdog idea applied per slot). A row
  with non-finite logits or an out-of-vocab token is **quarantined**:
  its request retires with ``finish_reason="error"``, the pages it
  frees are scrubbed and the slot released — co-tenant rows keep serving,
  unperturbed (a slot reads no page but its own, so one poisoned row
  cannot contaminate the others).
  Tick-level failures (decode/prefill exceptions, hung ticks) and
  admission control under overload are the
  :class:`~apex_tpu.serving.supervisor.EngineSupervisor`'s job —
  docs/serving.md#robustness has the full fault model.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.analysis.retrace import RetraceWatchdog
from apex_tpu.models.generation import (
    _cached_forward,
    cast_decode_params,
    decode_step,
    flatten_decode_caches,
    init_kv_caches,
    init_paged_kv_caches,
    preslice_layer_params,
    split_gated_mlp_params,
)
from apex_tpu.observability import MetricsRegistry
from apex_tpu.observability.trace import (
    SPAN_PREEMPT,
    SPAN_QUARANTINE,
    SPAN_SPEC_VERIFY,
    emit_request_spans,
    emit_span,
)
from apex_tpu.ops._support import use_pallas
from apex_tpu.ops.decode_attention import (
    paged_page_range,
    paged_quant_fill,
    paged_quant_scatter,
)
from apex_tpu.ops.sampling import sample_topk, sample_topk_takes
from apex_tpu.serving.request import (
    FINISH_CANCELLED,
    FINISH_EOS,
    FINISH_ERROR,
    FINISH_LENGTH,
    FINISH_REJECTED,
    FINISH_TIMEOUT,
    PRIORITY_RANK,
    Request,
    RequestResult,
)
from apex_tpu.lora import UnknownAdapterError
from apex_tpu.serving import clock
from apex_tpu.serving.prefix import (
    adapter_salt,
    prefix_hash_chain,
    prefix_salt,
)
from apex_tpu.observability.tracing import (
    SCOPE_SAMPLE,
    TICK_COMMIT,
    TICK_DISPATCH,
    TICK_ENGINE,
    TICK_PREFILL,
    TICK_READBACK,
    TICK_SCHEDULE,
    TICK_UPLOAD,
    recording,
    span,
)
from apex_tpu.serving.scheduler import (
    DeadlineExpiredError,
    FCFSScheduler,
    QueueFullError,
    SchedulerConfig,
    bucket_for,
    prefill_buckets,
)
from apex_tpu.serving.slots import PagePool, SlotPool
from apex_tpu.transformer.moe import RoutingStats
from apex_tpu.serving.speculation import propose_draft
from apex_tpu.utils.logging import get_logger, log_event
from apex_tpu.utils.profiling import nvtx_range

__all__ = ["EngineConfig", "InferenceEngine"]

_LOG = get_logger(__name__)

#: declared up front so final counter snapshots carry every key even for
#: outcomes that never fired — the monitor report reconciles these
#: against the per-request records key-for-key
_COUNTERS = ("requests_submitted", "requests_eos", "requests_length",
             "requests_cancelled", "requests_timeout", "requests_rejected",
             "requests_error", "prefills", "decode_steps",
             # one decode step in flight (docs/serving.md#one-step-in-
             # flight): steps dispatched while the step before had not
             # been read (beside decode_steps: the share of steps the
             # host path ran behind), and rows whose result was thrown
             # away because the slot's request had left by the time it
             # was read (the price of learning EOS, a cancel or a
             # poisoned row one step late)
             "decode_steps_overlapped", "decode_rows_dropped",
             # decode rows fed a first token that a prefill or final
             # chunk of the same tick put on the device, unread by the
             # host (== prefills on the plain path, but for requests
             # whose first token is their last and prefills read early
             # for an admission that shares their prefix)
             "first_tokens_carried",
             "tokens_generated", "slots_quarantined",
             "requests_shed_pages",
             # multi-LoRA (docs/serving.md#multi-lora): submits whose
             # adapter_id the AdapterStore doesn't know, fast-failed at
             # submit() — reconciled against request_shed events with
             # reason="unknown_adapter"
             "requests_shed_adapter",
             # prefix cache (docs/serving.md#prefix-cache): hits + misses
             # == paged prefills when prefix_cache is on, so hit_rate is
             # derivable; pages_shared counts prefill pages NOT recomputed
             "prefix_hits", "prefix_misses", "prefix_pages_shared",
             "prefix_evictions",
             # speculative decoding (docs/serving.md#speculative-decoding):
             # proposed counts drafted positions beyond the forced first
             # feed; accepted counts the ones the target agreed with, so
             # accepted/proposed is the fleet-wide acceptance rate
             "draft_tokens_proposed", "draft_tokens_accepted",
             # chunked prefill (docs/serving.md#chunked-prefill): chunk
             # programs run under prefill_token_budget — reconciled
             # against the per-request prefill_chunks record field and
             # the prefill_tokens_per_tick histogram's observation sum
             "prefill_chunks",
             # priority preemption (docs/serving.md#priority-preemption-
             # and-quotas): running slots parked for a higher class (or a
             # brownout rung) — reconciled against request_preempted
             # events key-for-key; parks are not terminal, so this never
             # enters the finish-reason sum
             "requests_preempted")


@dataclass
class EngineConfig:
    """Engine sizing and robustness knobs.

    ``retrace_budget`` guards the one-compile decode invariant: after the
    warmup compile, that many decode retraces are tolerated before
    :class:`~apex_tpu.analysis.retrace.RetraceBudgetExceeded` aborts the
    engine (0 = any retrace is a bug; None = log only). ``donate_caches``
    donates the KV-cache buffers into the jitted steps so decode updates
    in place on TPU; ``None`` auto-disables it on the CPU backend (which
    cannot donate and would warn every compile).

    KV pages (docs/serving.md#paged-kv): slots are backed by a shared
    page pool — ``n_pages`` pages of ``page_size`` tokens per layer —
    so HBM is committed to actual context length and ``max_slots`` can
    exceed what dense rows would fit; decode runs the fused
    append+attend kernel. ``n_pages=None`` sizes the pool to fully back
    every slot at ``max_len`` (admission then waits on slots only);
    size it below that to overcommit, and the engine sheds
    ``pages_exhausted`` when a request's worst case can never fit.

    Prefix cache (docs/serving.md#prefix-cache):
    ``prefix_cache=True`` interns each prompt's page-aligned prefix into
    the pool's content-addressed index, so a later prompt sharing that
    prefix maps the interned pages refcounted and prefills ONLY its
    suffix — token-exact, and admission reserves just the suffix +
    worst-case-new pages, so the hit rate directly raises effective
    capacity. ``prefix_lru_capacity`` bounds the index (entries; evicted
    LRU-first under page pressure). ``prefix_cache=False`` restores the
    PR 9 one-owner pool bit-for-bit.

    Decode-roofline knobs:
    ``kv_dtype="int8"`` (docs/serving.md#kv-quantization) stores the
    page pools int8 with per-(page, kv-head) scale sidecars — half the
    decode HBM stream, dequantized inline in the fused kernel;
    ``"bf16"`` (default) is the exact path and the bisection baseline.
    ``speculation=k`` (docs/serving.md#speculative-decoding, ``k >= 2``)
    turns each decode tick into a k-row self-speculative verify window:
    n-gram drafts ride the batched step and every accepted draft is one
    more token per KV-stream read. 0 disables (the PR 9 single-token
    step). Both knobs keep greedy streams token-exact against the
    defaults; speculation keeps SAMPLED streams exact too (the
    acceptance rule reproduces the sequential per-position sampling).

    Chunked prefill (docs/serving.md#chunked-prefill):
    ``prefill_token_budget=n`` bounds the prefill TOKENS one tick may
    run — a long prompt prefills as a sequence of bucketed chunk
    programs carried across ticks, interleaved with the batched decode
    step, so co-tenant TPOT never stalls for more than one chunk's
    compute. Internal chunk boundaries are page-aligned (so int8 scales
    and prefix interning stay bitwise what the monolithic fill
    produces), hence ``prefill_token_budget >= page_size``, and outputs
    are token-exact, greedy and sampled. ``None`` (default) keeps the
    one-shot prefill path unchanged.
    """

    max_slots: int = 8
    max_len: int = 512
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    retrace_budget: Optional[int] = 0
    donate_caches: Optional[bool] = None
    page_size: int = 64
    n_pages: Optional[int] = None
    prefix_cache: bool = True
    prefix_lru_capacity: int = 32
    kv_dtype: str = "bf16"
    speculation: int = 0
    prefill_token_budget: Optional[int] = None

    def __post_init__(self):
        if self.max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {self.max_slots}")
        if self.max_len < 2:
            raise ValueError(
                f"max_len must be >= 2 (one prompt + one generated token), "
                f"got {self.max_len}")
        if self.page_size < 1:
            raise ValueError(
                f"page_size must be >= 1, got {self.page_size}")
        if self.n_pages is not None and self.n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {self.n_pages}")
        if self.prefix_lru_capacity < 0:
            raise ValueError(
                f"prefix_lru_capacity must be >= 0, got "
                f"{self.prefix_lru_capacity}")
        if self.kv_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'bf16' or 'int8', got "
                f"{self.kv_dtype!r}")
        if self.speculation < 0 or self.speculation == 1:
            raise ValueError(
                f"speculation is 0 (off) or a verify window >= 2, got "
                f"{self.speculation}")
        if self.prefill_token_budget is not None:
            if self.prefill_token_budget < 1:
                raise ValueError(
                    f"prefill_token_budget must be >= 1 (or None to "
                    f"disable chunking), got {self.prefill_token_budget}")
            if self.prefill_token_budget < self.page_size:
                raise ValueError(
                    f"prefill_token_budget ({self.prefill_token_budget}) "
                    f"must be >= page_size ({self.page_size}) — internal "
                    f"chunk boundaries are page-aligned, so a smaller "
                    f"budget could never make progress on a multi-page "
                    f"prompt")

    @property
    def pages_per_slot(self) -> int:
        """Page-table width: pages covering one slot at ``max_len``."""
        return -(-self.max_len // self.page_size)


class _Active:
    """Host-side state of a request holding a slot."""

    __slots__ = ("request", "slot", "tokens", "last_token", "position",
                 "submit_ts", "prefill_start", "prefill_end",
                 "first_token_ts", "last_token_ts", "cancelled",
                 "reserved_pages", "adapter_ix",
                 "spec_proposed", "spec_accepted",
                 "prefill_pos", "prefill_chunks", "chunk_marks",
                 "page_row", "chain", "shared_used", "skip_first",
                 "finite_ok")

    def __init__(self, request: Request, slot: int, submit_ts: float):
        self.request = request
        self.slot = slot
        self.tokens: List[int] = []
        self.last_token = 0
        self.position = 0       # cache rows written for this slot
        self.reserved_pages = 0  # worst-case pages minus shared-prefix hit
        self.adapter_ix = 0     # bank row (null row when no adapter)
        self.submit_ts = submit_ts
        self.prefill_start = 0.0
        self.prefill_end = 0.0
        self.first_token_ts = 0.0   # when token #1 reached the host (TTFT)
        self.last_token_ts = 0.0    # latest token arrival (TPOT numerator)
        self.cancelled = False
        self.spec_proposed = 0   # draft positions offered over the lifetime
        self.spec_accepted = 0   # draft positions the target agreed with
        # chunked-prefill progress, carried across ticks as plain host
        # data (page ids + an absolute token offset — never jit-trace
        # state, the seam a dedicated prefill replica would ship)
        self.prefill_pos = 0     # prompt tokens whose K/V are written
        self.prefill_chunks = 0  # chunk programs run so far
        self.chunk_marks: List[float] = []  # interior chunk-end stamps
        self.page_row = None     # the slot's REAL page row while chunking
        self.chain = ()          # prefix hash chain (interned at the end)
        self.shared_used = 0     # prefix-hit pages mapped at admission
        self.skip_first = False  # fully page-aligned hit (COW seam)
        self.finite_ok = True    # AND of every chunk's isfinite flag


class _Flight(NamedTuple):
    """One dispatched decode step whose result the host has not read:
    the rows as dispatched (``(slot, rec)``: a row counts at commit only
    if the slot still holds that record), the two device results (their
    copies to the host started at dispatch) and the fault injector's
    index of the decode call (None without one)."""

    rows: List
    nxt: jax.Array
    finite: jax.Array
    call: Optional[int]


class _Unread(NamedTuple):
    """A prefill or chunk dispatched this tick whose results the host
    has not read: the record, the program's first token (None for a
    chunk that is not the prompt's last: its sample is thrown away) and
    its finite flag."""

    rec: _Active
    first: Optional[jax.Array]
    finite: jax.Array


def _carry_first(carry, slot, first):
    """``carry`` with a prefill's first token at the slot's row: the
    vector the next decode step takes a carried slot's fed token from
    (None under speculation, whose steps carry nothing)."""
    return None if carry is None else carry.at[slot].set(first)


def _kth_largest(rows, k):
    """The ``k[i]``-th largest value of each row of float32 ``rows``
    [n, V] (ties counted, as ``sort(row)[V - k]``), found without
    ordering the row. Each value maps to a uint32 key of the same order
    (all bits of a negative flipped, the sign bit of the rest set); the
    largest key ``t`` that at least ``k`` keys of the row reach is built
    from the top down, two bits to a counting pass over the row (the
    three keys that extend ``t`` are counted in one read), and mapped
    back. Exact for any k in [1, V]; a k outside it gives NaN, under
    which the caller's ``<`` masks nothing."""
    bits = jax.lax.bitcast_convert_type(rows, jnp.uint32)
    top = np.uint32(1 << 31)
    keys = jnp.where(bits >= top, ~bits, bits | top)
    digits = np.arange(1, 4, dtype=np.uint32)

    def settle(i, t):
        shift = np.uint32(30) - 2 * i.astype(jnp.uint32)
        trial = t[:, None] | (digits << shift)              # [n, 3], rising
        reach = jnp.sum(keys[:, None, :] >= trial[:, :, None], axis=-1,
                        dtype=jnp.int32)
        digit = jnp.sum(reach >= k[:, None], axis=-1).astype(jnp.uint32)
        return t | (digit << shift)

    t = jax.lax.fori_loop(0, 16, settle, jnp.zeros(rows.shape[:1], jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.where(t >= top, t ^ top, ~t), jnp.float32)


def _gumbel_rows(seeds, steps, vocab, dtype):
    """The Gumbel noise ``jax.random.categorical`` adds to each row of
    ``_sample_tokens``' draw, under the same keys: ``[n, vocab]``."""
    def row(seed, step):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return jax.random.gumbel(key, (vocab,), dtype)

    return jax.vmap(row)(seeds, steps)


@nvtx_range(SCOPE_SAMPLE)
def _sample_tokens(logits, temps, topks, seeds, steps):
    """Per-row sampling over ``logits`` [n, V]: greedy where
    ``temps == 0``, else softmax at the row's temperature truncated to
    its top-k (``topks == V`` disables truncation), keyed by
    ``fold_in(PRNGKey(seed), step)`` so a request's stream depends only
    on its own (seed, positions) — never on batch co-tenants."""
    if use_pallas() and sample_topk_takes(logits.shape[-1]):
        # one Pallas pass a block of rows (ops/sampling.py): the same
        # threshold, mask and draw on the same noise, so the same tokens
        # bit for bit. Taken at every row count: on the v5e it measured
        # faster than the form below at one row (the prefill's first
        # token) as at 64-96 (a decode step), for V from 16,160 to
        # 200,192 (PERF.md, section 6)
        noise = _gumbel_rows(seeds, steps, logits.shape[-1], logits.dtype)
        return sample_topk(logits, temps, topks, noise)[0]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.where(temps > 0.0, temps, 1.0).astype(logits.dtype)
    scaled = logits / safe_t[:, None]
    # top_k varies per row, so the static-k lax.top_k form generate()
    # uses cannot batch here: the row's kth largest is found by a search
    # over order-preserving integer keys (16 counting passes, no sort);
    # mask logits < kth — identical support to generate()'s truncation
    kth = _kth_largest(scaled, topks)
    masked = jnp.where(scaled < kth[:, None], -jnp.inf, scaled)

    def draw(seed, step, row):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return jax.random.categorical(key, row)

    sampled = jax.vmap(draw)(seeds, steps, masked).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def _select_adapters(lora, adapter_ix):
    """Gather per-slot LoRA factors from the stacked adapter bank: leaves
    ``[L, max_adapters + 1, ...]`` at bank rows ``adapter_ix`` (``[b]``)
    -> ``[L, b, ...]``, the layout the transformer's per-layer loop
    slices. ``None`` passes through — an engine without an AdapterStore
    compiles the identical no-delta program."""
    if lora is None:
        return None
    return jax.tree.map(lambda x: x[:, adapter_ix], lora)


class InferenceEngine:
    """Continuous-batching serving engine; see the module docstring.

    Drive it either with :meth:`serve` (submit a request list, tick to
    completion, collect results) or manually: :meth:`submit` +
    :meth:`tick` in a loop, harvesting :attr:`completed`.
    """

    def __init__(self, model, params, config: Optional[EngineConfig] = None,
                 *, metrics: Optional[MetricsRegistry] = None,
                 faults=None, replica_id: Optional[int] = None,
                 adapters=None):
        self.model = model
        self.config = config or EngineConfig()
        #: optional AdapterStore (apex_tpu.lora) — multi-tenant serving:
        #: per-request adapter_id selects a bank row, the step programs
        #: gather per-slot factors in-jit (docs/serving.md#multi-lora).
        #: The bank is re-read every call, so host-side load/unload
        #: between ticks applies on the next step without a retrace.
        self.adapters = adapters
        #: fleet replica label stamped on every RequestResult / JSONL
        #: record this engine emits (None = single-engine deployment)
        self.replica_id = replica_id
        #: optional ServingFaultInjector (apex_tpu.testing_faults) — hook
        #: points are host-side on purpose: injected faults must never
        #: retrace the compiled decode step
        self._faults = faults
        self._closed = False
        c = model.config
        #: latent attention (docs/serving.md#latent-kv): each layer's
        #: pool pair is (c, kR) rows with no head axis
        self._latent = bool(getattr(c, "latent_attention", False))
        if self._latent:
            for what, on in (
                    ("kv_dtype='int8'", self.config.kv_dtype == "int8"),
                    ("speculation", bool(self.config.speculation)),
                    ("LoRA adapters", adapters is not None)):
                if on:
                    raise ValueError(
                        f"{what} is not supported with latent attention "
                        f"(kv_lora_rank): docs/serving.md#latent-kv")
        if (c.position_embedding_type == "learned"
                and self.config.max_len > c.max_position_embeddings):
            raise ValueError(
                f"max_len ({self.config.max_len}) exceeds the model's "
                f"max_position_embeddings ({c.max_position_embeddings})")
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.metrics.declare_counters(*_COUNTERS)
        if self.adapters is not None:
            # per-adapter submit counters, declared up front like the
            # fleet's replica{i}_dispatches so final snapshots carry every
            # key; the monitor reconciles them against adapter_request
            # events key-for-key
            self.metrics.declare_counters(
                *(f"adapter{ix}_requests"
                  for ix in range(self.adapters.max_adapters)))
        #: routed expert layers (transformer/moe.py RoutedExperts): the
        #: decode program then appends each layer call's routing counts
        #: to the token vector it returns, one read-back for both
        #: (docs/serving.md#routed-experts-and-mixed-layer-kinds)
        self._routed = bool(getattr(c, "num_routed_experts", None))
        if self._routed:
            self.metrics.declare_counters("moe_rows_routed")
        #: assignments a live row makes in one routed layer call where
        #: this engine holds a SHARE of the layer's experts (0 = it holds
        #: them all): those that do not land here are counted as
        #: ``moe_rows_elsewhere``, what an exchange between the holders
        #: would carry (docs/moe.md)
        self._share_top_k = 0
        if self._routed and getattr(c, "routed_expert_range", None) \
                not in (None, (0, c.num_routed_experts)):
            self._share_top_k = c.routed_top_k
            self.metrics.declare_counters("moe_rows_elsewhere")
        #: share of the layers that are window layers, for the
        #: kv_pages_out_of_window gauge (0 = one kind of layer)
        kinds = getattr(c, "attention_layer_types", None) or ()
        self._window_share = (kinds.count("sliding") / len(kinds)
                              if kinds else 0.0)
        self.scheduler = FCFSScheduler(self.config.scheduler)
        self.slots = SlotPool(self.config.max_slots)
        self.buckets = prefill_buckets(self.config.max_len)
        self.completed: Dict[int, RequestResult] = {}
        #: request ids in admission (prefill) order — the FCFS audit trail
        self.admission_log: List[int] = []
        self._active: Dict[int, _Active] = {}      # slot -> state
        #: slots mid-chunked-prefill, in admission order (insertion-
        #: ordered dict) — excluded from _active so the batched decode
        #: step never sees them; the slot's real page row lives on the
        #: rec until the final chunk lands (see _begin_chunked_prefill)
        self._prefilling: Dict[int, _Active] = {}
        #: preempted (parked) requests as (request, generated_tokens,
        #: submit_ts) — host-side token cursors with slot and pages
        #: released; the supervisor drains them via take_parked() into
        #: restart-style continuations that resume TOKEN-EXACT (sampling
        #: keys on absolute position, docs/serving.md#priority-
        #: preemption-and-quotas)
        self._parked: List = []
        #: set True by a caller that drains take_parked() every tick
        #: (the EngineSupervisor). Without a consumer the engine never
        #: preempts on its own — a parked request would have nowhere to
        #: resume. park_class() is exempt: an explicit call owns the
        #: drain responsibility.
        self.resume_consumer = False
        self._chunk_tokens_tick = 0   # prefill tokens run this tick
        self._vocab = c.vocab_size

        # serving precision: generate()'s own one-time pre-cast, re-lay
        # of a gated dense layer's gate/up weight to halves apart, and
        # per-layer param pre-slice, materialized ONCE at engine build
        if c.compute_dtype != jnp.float32:
            params = cast_decode_params(params, c.compute_dtype)
        params, apart = split_gated_mlp_params(params, c)
        self.metrics.set_gauge("decode_weights_relaid_bytes", apart)
        self._params = preslice_layer_params(params, c.num_layers)
        pps = self.config.pages_per_slot
        n_pages = self.config.n_pages or self.config.max_slots * pps
        self.pages = PagePool(
            n_pages, self.config.page_size, pps,
            lru_capacity=(self.config.prefix_lru_capacity
                          if self.config.prefix_cache else 0))
        #: salt for the prompt-prefix hash chains — keyed by the
        #: model fingerprint (K/V are sampling-invariant), with each
        #: request's adapter_id folded in at hash time: adapter
        #: deltas write adapter-specific K/V, so tenants must never
        #: alias pages across adapters (see prefix.adapter_salt)
        self._prefix_salt = prefix_salt(c)
        self._evictions_seen = 0
        self._quantized = self.config.kv_dtype == "int8"
        self._caches = init_paged_kv_caches(
            model, n_pages, self.config.page_size,
            quantized=self._quantized)
        # HBM bytes one decode step streams per mapped page (K + V
        # across all layers, plus the f32 scale sidecars when
        # quantized) — the kv_bytes_per_step gauge's unit, computed
        # from the GLOBAL head count so the number means the same
        # thing sharded and unsharded
        if self._latent:
            self._page_read_bytes = sum(
                pool.dtype.itemsize * pool.shape[1] * pool.shape[2]
                for pair in self._caches for pool in pair)
        else:
            f_dim = c.kv_heads * c.head_dim
            item = 1 if self._quantized else jnp.dtype(
                c.compute_dtype).itemsize
            self._page_read_bytes = 2 * c.num_layers * (
                self.config.page_size * f_dim * item
                + (c.kv_heads * 4 if self._quantized else 0))
        # host page table; n_pages is the unmapped sentinel (reads
        # clamp+mask, scatters drop — see ops/decode_attention.py)
        self._page_table_h = np.full(
            (self.config.max_slots, pps), n_pages, np.int32)
        #: worst-case pages promised to admitted requests — admission
        #: only lets a request in when its full total_len reservation
        #: fits, so decode-time extends can NEVER exhaust the pool
        #: (no mid-flight eviction policy needed; see _admit)
        self._reserved_pages = 0

        n = self.config.max_slots
        self._tokens_h = np.zeros(n, np.int32)
        self._positions_h = np.zeros(n, np.int32)
        self._temps_h = np.zeros(n, np.float32)
        self._topks_h = np.full(n, self._vocab, np.int32)
        self._seeds_h = np.zeros(n, np.int32)
        #: per-slot adapter bank row; idle/base slots point at the
        #: all-zeros null row, so their delta is an exact zero
        self._null_adapter = (0 if self.adapters is None
                              else self.adapters.null_index)
        self._adapter_ix_h = np.full(n, self._null_adapter, np.int32)
        #: speculation host state: per-slot verify window (row 0 is the
        #: token being fed — the sequential step's _tokens_h — rows 1..
        #: the n-gram draft, padded by repeating the last real feed) and
        #: its valid length
        self._spec = self.config.speculation
        if self._spec:
            self._window_h = np.zeros((n, self._spec), np.int32)
            self._wlen_h = np.ones(n, np.int32)
        #: the plain decode path keeps ONE step in flight
        #: (docs/serving.md#one-step-in-flight): the step dispatched
        #: last tick, read and committed this tick AFTER the next step
        #: went out, and the token vector that step returned, which the
        #: next step takes its continuing slots' fed tokens from on the
        #: device (its shape is the program's output: the routing counts
        #: of a routed model ride behind the tokens, three a routed
        #: layer). Speculation reads every step in the tick that
        #: dispatched it: its draft is built from the host's tokens.
        self._flight: Optional[_Flight] = None
        self._carry = None
        if not self._spec:
            routed_calls = (c.num_layers - c.num_dense_layers
                            if self._routed else 0)
            self._carry = jnp.zeros(n + 3 * routed_calls, jnp.int32)
        #: the prefills and chunks dispatched this tick and not read yet:
        #: a prefill puts its first token into ``_carry`` on the device,
        #: the tick's decode step is fed it from there, and the host
        #: reads and commits it after that step went out
        #: (``_read_prefills``; with speculation at once)
        self._unread: List[_Unread] = []
        #: what one decode step uploads (the ``tick.upload`` span's
        #: attributes): the host arrays of ``_decode_args``
        up = [self._window_h if self._spec else self._tokens_h,
              self._positions_h, self._temps_h, self._topks_h,
              self._seeds_h, self._adapter_ix_h, self._page_table_h]
        if not self._spec:
            up.append(np.zeros(n, np.bool_))     # the take-the-host's mask
        self._decode_upload = (len(up), sum(a.nbytes for a in up))

        donate = self.config.donate_caches
        if donate is None:
            donate = jax.default_backend() != "cpu"

        decode_fn, prefill_fn, suffix_fn, scrub_fn, reset_fn = \
            self._build_step_fns(donate)
        self._decode_fn = RetraceWatchdog(
            decode_fn,
            budget=self.config.retrace_budget, expected_compiles=1,
            name="serving_decode", metrics=self.metrics)
        # one jit whose compile count is bounded by the bucket set (each
        # distinct padded prompt shape is one entry); budget=None — bucket
        # compiles are expected, the TEST asserts compiles <= buckets
        self._prefill_fn = RetraceWatchdog(
            prefill_fn, budget=None, expected_compiles=len(self.buckets),
            name="serving_prefill", metrics=self.metrics)
        # suffix prefill (prefix-cache hits) buckets exactly like full
        # prefill, so its compile count has the same bound; under
        # chunked prefill it doubles as the CHUNK program (the
        # chunk offset is a traced scalar, so chunking adds no shapes)
        self._suffix_fn = RetraceWatchdog(
            suffix_fn, budget=None, expected_compiles=len(self.buckets),
            name="serving_suffix_prefill", metrics=self.metrics)
        self._scrub_fn = scrub_fn
        self._reset_scales_fn = reset_fn

    # -- step programs (overridable: ShardedEngine wraps these bodies in
    # -- shard_map over the device mesh) ----------------------------------

    def _routing(self, positions) -> Optional[RoutingStats]:
        """The collector a decode body hands down its forward: None for a
        model without routed layers. An idle slot is fed position 0
        (``_clear_slot``) and a live one is past its prompt, so
        ``positions > 0`` are the rows that count."""
        return RoutingStats(active=positions > 0) if self._routed else None

    @staticmethod
    def _with_routing(nxt, stats: Optional[RoutingStats]):
        """The tokens a decode body returns; with routed layers the int32
        ``[calls, 3]`` counts (``transformer.moe.ROUTING_STATS``) ride
        behind them, flattened, so the tick reads both back at once
        (``_split_routing`` parts them on the host)."""
        if stats is None:
            return nxt
        return jnp.concatenate([nxt.reshape(-1),
                                stats.stacked().reshape(-1)])

    def _split_routing(self, nxt: np.ndarray):
        shape = (self._window_h if self._spec else self._tokens_h).shape
        size = int(np.prod(shape))
        return nxt[:size].reshape(shape), nxt[size:].reshape(-1, 3)

    def _paged_decode_body(self, params, caches, page_table, tokens,
                           carry, from_host, positions, temps, topks,
                           seeds, adapter_ix, lora):
        # one decode step over the page pool: one fused append+attend
        # per layer (apex_tpu.ops.decode_attention); with the pool
        # donated the appends are in-place row writes, so per step the
        # KV traffic is one read of the mapped stream plus one row.
        # A slot with a row in the step before is fed that row's sampled
        # token straight from ``carry``, the vector that step returned
        # (tokens first), so the host need not have read it yet; the
        # host's ``tokens`` feed the slots it wrote since (a prefill's
        # first token, a cleared slot)
        tokens = jnp.where(from_host, tokens, carry[:tokens.shape[0]])
        stats = self._routing(positions)
        logits, caches = decode_step(self.model, params, caches, tokens,
                                     positions, paged_state=page_table,
                                     lora=_select_adapters(lora, adapter_ix),
                                     routing=stats)
        nxt = _sample_tokens(logits, temps, topks, seeds, positions + 1)
        finite = jnp.all(jnp.isfinite(logits), axis=-1)
        return self._with_routing(nxt, stats), finite, caches

    def _spec_decode_body(self, params, caches, page_table, windows,
                          positions, temps, topks, seeds, adapter_ix,
                          lora):
        # speculative decode: each slot feeds a k-token verify window
        # (row 0 = the sequential step's token, rows 1.. the draft) in
        # ONE forward — one read of the mapped KV stream buys up to k
        # target samples. Sampling is per-position with the SAME
        # fold_in(seed, position) keys the sequential step would use,
        # and every _sample_tokens op is row-independent, so row j of
        # the [n, k] output is bitwise what a sequential step at
        # position + j would emit given the same fed tokens — the host
        # acceptance loop then consumes exactly the prefix the
        # sequential engine would have produced.
        n, k = windows.shape
        stats = self._routing(positions)
        logits, caches = _cached_forward(
            self.model, params, caches, windows, positions,
            paged_state=page_table,
            lora=_select_adapters(lora, adapter_ix),
            routing=stats)                                # [k, n, V]
        lf = logits.transpose(1, 0, 2).reshape(n * k, -1)
        steps = (positions[:, None] + 1 + jnp.arange(k)[None, :]).reshape(-1)
        nxt = _sample_tokens(lf, jnp.repeat(temps, k), jnp.repeat(topks, k),
                             jnp.repeat(seeds, k), steps)
        finite = jnp.all(jnp.isfinite(logits), axis=-1).T  # [n, k]
        return self._with_routing(nxt.reshape(n, k), stats), finite, caches

    def _paged_scrub_body(self, caches, page_row):
        # zero exactly the quarantined slot's mapped pages across every
        # layer (``page_row`` is its fixed-width table row; sentinel
        # entries drop), so its NaNs can never reach a future occupant
        # even through a masked-weight * NaN-value product; foreign
        # slots' pages are never touched. Quantized pools
        # zero the scale sidecar too, so a recycled page starts from a
        # clean rescale baseline (slots.PagePool.check asserts this).
        if self._quantized:
            return [((k.at[page_row].set(0, mode="drop"),
                      ks.at[page_row].set(0.0, mode="drop")),
                     (v.at[page_row].set(0, mode="drop"),
                      vs.at[page_row].set(0.0, mode="drop")))
                    for (k, ks), (v, vs) in caches]
        return [(k.at[page_row].set(0.0, mode="drop"),
                 v.at[page_row].set(0.0, mode="drop"))
                for k, v in caches]

    def _reset_scales_body(self, caches, page_row):
        # zero ONLY the scale sidecar for freshly allocated pages (the
        # int8 payload is overwritten before it can be read, but a
        # stale scale from the previous tenant would poison the
        # scatter-max rescale floor). No-op program for bf16 pools.
        return [((k, ks.at[page_row].set(0.0, mode="drop")),
                 (v, vs.at[page_row].set(0.0, mode="drop")))
                for (k, ks), (v, vs) in caches]

    def _paged_prefill_body(self, params, caches, page_row, prompt,
                            prompt_len, temp, topk, seed, adapter_ix,
                            lora, carry, slot):
        # the EXACT prefill generate() runs (4D per-layer list -> the
        # cache_index==0 causal-flash fast path) at the bucket-padded
        # length, so outputs stay token-exact; the flattened rows then
        # scatter into this slot's freshly mapped pages, a page at a
        # time. Chunks past the mapped count (bucket padding)
        # carry the sentinel and drop; garbage rows inside the last
        # mapped page are causally masked by the row's position forever.
        # The first token also goes into ``carry`` at the slot's row, so
        # the tick's decode step is fed it on the device
        # (_carry_first; carry is not donated: the step in flight's
        # result, which the host reads after this tick's step went out)
        model = self.model
        small = init_kv_caches(model, 1, prompt.shape[1], stacked=False)
        logits, small = _cached_forward(model, params, small, prompt, 0,
                                        last_index=prompt_len - 1,
                                        lora=_select_adapters(lora,
                                                              adapter_ix))
        flat = flatten_decode_caches(small, model.config.num_layers)
        ps = self.config.page_size
        bucket = prompt.shape[1]
        n_chunks = -(-bucket // ps)
        pad = n_chunks * ps - bucket
        dest = page_row[:n_chunks]
        new = []
        for cache, (fk, fv) in zip(caches, flat):
            fk1 = jnp.pad(fk[0], ((0, pad), (0, 0)))
            fv1 = jnp.pad(fv[0], ((0, pad), (0, 0)))
            if self._quantized:
                # whole-page overwrite: the chunk IS the page content,
                # so each page's scale comes straight from its own amax
                # (pad rows are zeros and cannot inflate it)
                (bk, bks), (bv, bvs) = cache
                new.append(
                    (paged_quant_fill(bk, bks,
                                      fk1.reshape(n_chunks, ps, -1), dest),
                     paged_quant_fill(bv, bvs,
                                      fv1.reshape(n_chunks, ps, -1), dest)))
                continue
            bk, bv = cache
            new.append(
                (bk.at[dest].set(fk1.reshape(n_chunks, ps, -1)
                                 .astype(bk.dtype), mode="drop"),
                 bv.at[dest].set(fv1.reshape(n_chunks, ps, -1)
                                 .astype(bv.dtype), mode="drop")))
        first = _sample_tokens(logits[0], temp[None], topk[None],
                               seed[None], prompt_len[None])[0]
        # finite flag gates publishing these pages to the prefix-intern
        # index: a poisoned prefill must never become a shared prefix
        return (first, jnp.all(jnp.isfinite(logits)), new,
                _carry_first(carry, slot, first))

    def _suffix_prefill_body(self, params, caches, page_row, suffix,
                             start, suffix_len, prompt_len, temp, topk,
                             seed, skip_first, adapter_ix, lora, carry,
                             slot):
        """Prefill ONLY the suffix of a prefix-cache hit.

        The slot's page table already maps the shared prefix pages for
        tokens ``[0, start)``; this body gathers those rows into a
        small 4D cache, runs the suffix forward at ``cache_index=start``
        (offset-causal mask + rope at the absolute offset), and
        scatters the suffix K/V into the slot's PRIVATE pages row by
        row. Shared pages are never written: when ``skip_first`` is set
        (a fully page-aligned hit, whose one-token "suffix" is a
        recompute of the prompt's LAST token purely to produce first-
        token logits), the recomputed row's scatter is masked so the
        boundary page keeps its original bitwise K/V — the copy-on-write
        seam with the copy elided, since the row is already resident.
        The sampled token goes into ``carry`` at ``slot`` as the full
        prefill's does.
        """
        model = self.model
        ps = self.config.page_size
        pps = self.config.pages_per_slot
        n_pages = self.pages.n_pages
        bucket = suffix.shape[1]
        s0 = pps * ps
        # static length s0 + bucket keeps the suffix update in-bounds for
        # any traced start (no dynamic_update_slice clamping)
        small = init_kv_caches(model, 1, s0 + bucket, stacked=False)
        valid_page = page_row < n_pages
        clamped = jnp.clip(page_row, 0, n_pages - 1)
        filled = []
        for cache, (sk, sv) in zip(caches, small):
            def place(pool, sm, scales=None):
                g = pool[clamped]                       # [pps, ps, h*d]
                if scales is not None:
                    # dequantize the shared-prefix rows with their pages'
                    # sidecar scales before they enter the fp forward
                    sc = jnp.repeat(scales[clamped], sm.shape[3], axis=-1)
                    g = g.astype(jnp.float32) * sc[:, None, :]
                # sentinel rows must read as EXACT zeros (a clamped
                # gather could otherwise import a co-tenant's transient
                # NaN into causally masked positions: 0-weight * NaN
                # is still NaN)
                g = jnp.where(valid_page[:, None, None], g, 0.0)
                if sm.ndim == 3:      # latent rows: flat, no head axis
                    return sm.at[:, :s0].set(
                        g.reshape(1, s0, -1).astype(sm.dtype))
                h, d = sm.shape[1], sm.shape[3]
                g = g.reshape(s0, h, d).transpose(1, 0, 2)[None]
                return sm.at[:, :, :s0, :].set(g.astype(sm.dtype))

            if self._quantized:
                (bk, bks), (bv, bvs) = cache
                filled.append((place(bk, sk, bks), place(bv, sv, bvs)))
            else:
                bk, bv = cache
                filled.append((place(bk, sk), place(bv, sv)))
        logits, filled = _cached_forward(model, params, filled, suffix,
                                         start, last_index=suffix_len - 1,
                                         lora=_select_adapters(lora,
                                                               adapter_ix))
        # scatter the suffix K/V into the slot's pages, one row per
        # suffix position (rows can straddle page boundaries, so the
        # whole-page chunk scatter of the miss path does not apply)
        idx = jnp.arange(bucket)
        pos = start + idx
        dest_page = page_row[jnp.clip(pos // ps, 0, pps - 1)]
        dest_off = pos % ps
        valid = (idx < suffix_len) & ~(skip_first & (idx == 0))
        dest_page = jnp.where(valid, dest_page, n_pages)  # drop pads
        new = []
        for cache, (fk, fv) in zip(caches, filled):
            def rows(f):
                if f.ndim == 3:       # latent rows: flat already
                    return jax.lax.dynamic_slice_in_dim(
                        f, start, bucket, axis=1)[0]
                r = jax.lax.dynamic_slice_in_dim(f, start, bucket, axis=2)
                return r[0].transpose(1, 0, 2).reshape(
                    bucket, f.shape[1] * f.shape[3])

            if self._quantized:
                # suffix rows straddle pages, so they go through the
                # rescale-on-append scatter (sentinel dests drop; the
                # shared boundary page's scale only grows monotonically,
                # which every co-tenant's dequant view tolerates)
                (bk, bks), (bv, bvs) = cache
                new.append(
                    (paged_quant_scatter(bk, bks, rows(fk), dest_page,
                                         dest_off),
                     paged_quant_scatter(bv, bvs, rows(fv), dest_page,
                                         dest_off)))
            else:
                bk, bv = cache
                new.append(
                    (bk.at[dest_page, dest_off].set(
                        rows(fk).astype(bk.dtype), mode="drop"),
                     bv.at[dest_page, dest_off].set(
                         rows(fv).astype(bv.dtype), mode="drop")))
        first = _sample_tokens(logits[0], temp[None], topk[None],
                               seed[None], prompt_len[None])[0]
        return (first, jnp.all(jnp.isfinite(logits)), new,
                _carry_first(carry, slot, first))

    def _build_step_fns(self, donate: bool):
        """Compile the device programs:
        ``(decode, prefill, suffix_prefill, scrub, reset_scales)``.
        ``suffix_prefill`` is also the chunk program of chunked prefill
        (the chunk offset is a traced scalar) and ``reset_scales`` is
        None unless the pool is quantized. The base engine jits the
        bodies directly (single-chip);
        :class:`~apex_tpu.serving.fleet.ShardedEngine` overrides this to
        wrap each body in ``shard_map`` over the tensor axis first.
        Every body that runs the model takes the caches as argument 1,
        so donation and the watchdogs are shared. With ``speculation``
        on, the decode program is the windowed verify body: the [n]
        token vector becomes the [n, k] window matrix, and the carried
        token vector and its mask are not among its arguments. The two
        prefill programs take the carried vector and the slot's index
        last and return the vector with their first token put in (None
        in and out under speculation)."""
        donate_args = (1,) if donate else ()
        decode_body = (self._spec_decode_body if self._spec
                       else self._paged_decode_body)
        return (jax.jit(decode_body, donate_argnums=donate_args),
                jax.jit(self._paged_prefill_body,
                        donate_argnums=donate_args),
                jax.jit(self._suffix_prefill_body,
                        donate_argnums=donate_args),
                jax.jit(self._paged_scrub_body,
                        donate_argnums=(0,) if donate else ()),
                jax.jit(self._reset_scales_body,
                        donate_argnums=(0,) if donate else ())
                if self._quantized else None)

    @property
    def _bank(self):
        """Current adapter bank (None without an AdapterStore) — read
        fresh per step call so hot load/unload lands next tick."""
        return None if self.adapters is None else self.adapters.bank

    def _adapter_index(self, adapter_id, *, strict: bool) -> int:
        """Resolve an ``adapter_id`` to its bank row. ``strict`` raises
        :class:`UnknownAdapterError` (submit validation); non-strict
        falls back to the null row — the prefill/decode path for a
        request whose adapter was unloaded after admission, which
        degrades to base-model output instead of crashing the batch."""
        if self.adapters is None:
            if adapter_id is not None and strict:
                raise UnknownAdapterError(
                    f"adapter {adapter_id!r}: engine has no AdapterStore")
            return self._null_adapter
        try:
            return self.adapters.index_of(adapter_id)
        except UnknownAdapterError:
            if strict:
                raise
            return self._null_adapter

    # -- introspection ----------------------------------------------------

    @property
    def decode_retraces(self) -> int:
        """Decode-step recompiles beyond the warmup — must stay 0."""
        return self._decode_fn.retraces

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill shapes compiled — bounded by ``len(buckets)``."""
        return self._prefill_fn.compiles

    @property
    def chunk_compiles(self) -> int:
        """Distinct chunk-program shapes compiled under chunked prefill
        — bounded by ``len(buckets)`` (the chunk program IS the suffix
        program, so this counts its shapes)."""
        return self._suffix_fn.compiles

    @property
    def decode_compiles(self) -> int:
        """Decode-step compilations (warmup included) — the supervisor
        exempts compile ticks from its hung-tick wall-clock budget."""
        return self._decode_fn.compiles

    @property
    def active_count(self) -> int:
        return self.slots.active_count

    @property
    def queued_count(self) -> int:
        return self.scheduler.depth

    @property
    def queued_tokens(self) -> int:
        """Prompt tokens waiting in the queue — the token-aware load
        signal the supervisor's shed/cost estimates fold in (a backlog
        of long prompts is more work than its depth suggests)."""
        return self.scheduler.queued_tokens

    @property
    def parked_count(self) -> int:
        """Preempted requests awaiting resume — non-terminal work the
        supervisor's idle checks must count."""
        return len(self._parked)

    def take_parked(self) -> List:
        """Drain the parked (preempted) requests as ``(request,
        generated_tokens, submit_ts)`` tuples — the supervisor turns each
        into a restart-style continuation (original prompt + generated
        prefix, remaining budget, same request/trace ids and deadline
        clock) and resubmits it when capacity allows."""
        parked, self._parked = self._parked, []
        return parked

    def queued_tokens_by_class(self) -> Dict[str, int]:
        """Queued prompt tokens per priority class (scheduler
        passthrough) — the supervisor's per-class shed pricing input."""
        return self.scheduler.queued_tokens_by_class()

    def queued_depth_by_class(self) -> Dict[str, int]:
        """Queue depth per priority class (scheduler passthrough)."""
        return self.scheduler.depth_by_class()

    def set_admission_floor(self, priority: Optional[str]) -> None:
        """Scheduler passthrough: pause dispatch of classes below
        ``priority`` (the brownout ladder's admission rungs)."""
        self.scheduler.set_admission_floor(priority)

    def inflight(self) -> List:
        """Snapshot of active (admitted, non-terminal) requests as
        ``(request, generated_tokens, submit_ts)`` tuples in slot order —
        what the supervisor re-prefills after an engine restart. The
        tokens are the COMMITTED ones: a row still in flight is not
        among them (whoever continues the request samples that token
        again, bit for bit, from its ``(seed, position)``).
        Mid-chunked-prefill requests are included with NO tokens: a
        restart re-prefills them from the prompt through the same admit
        path (their chunk progress died with the engine's pages).
        Parked (preempted) requests are included WITH their tokens: a
        restart resumes them exactly like the supervisor's ordinary
        take_parked() drain would have."""
        recs = [(rec.request, list(rec.tokens), rec.submit_ts)
                for _, rec in sorted(self._active.items())]
        recs += [(rec.request, [], rec.submit_ts)
                 for rec in self._prefilling.values()]
        recs += [(request, list(tokens), submit_ts)
                 for request, tokens, submit_ts in self._parked]
        return recs

    # -- request lifecycle ------------------------------------------------

    def submit(self, request: Request, *, resubmission: bool = False) -> int:
        """Enqueue; returns the request id. Raises
        :class:`~apex_tpu.serving.scheduler.QueueFullError` when the
        bounded queue is full, and
        :class:`~apex_tpu.serving.scheduler.DeadlineExpiredError` when
        the request's deadline already elapsed (stale ``arrival_ts``) —
        both rejections are also recorded: counter, ``request_rejected``
        event (with a ``reason``), and a terminal ``kind="request"``
        record with ``finish_reason="rejected"``.

        ``resubmission=True`` is the supervisor's restart-continuation
        path: the request was already counted at its ORIGINAL submit, so
        ``requests_submitted`` is not incremented again (one arrival ==
        one count == one terminal record)."""
        if self._closed:
            raise RuntimeError("engine is closed")
        if request.request_id in self.completed:
            raise ValueError(
                f"request id {request.request_id} already completed")
        if request.total_len > self.config.max_len:
            raise ValueError(
                f"prompt ({request.prompt_len}) + max_new_tokens "
                f"({request.max_new_tokens}) exceeds the engine's max_len "
                f"({self.config.max_len})")
        now = clock.now()
        if not resubmission:
            self.metrics.inc("requests_submitted")
        aid = request.sampling.adapter_id
        try:
            # restart continuations (resubmission) were validated at their
            # ORIGINAL submit; if the adapter vanished since, they degrade
            # to the null row (base output) instead of failing the restart
            ix = self._adapter_index(aid, strict=not resubmission)
        except UnknownAdapterError:
            # fast-fail BEFORE the queue: an unknown/unloaded adapter_id
            # can never produce the tenant's output, so it sheds with its
            # own counter + request_shed reason (the supervisor-shed
            # convention) and a terminal rejected record
            self.metrics.inc("requests_shed_adapter")
            log_event(_LOG, "request_shed",
                      request_id=request.request_id,
                      reason="unknown_adapter", adapter_id=aid)
            self.metrics.event("request_shed",
                               request_id=request.request_id,
                               reason="unknown_adapter", adapter_id=aid)
            self._finish(request, [], FINISH_REJECTED, submit_ts=now,
                         now=now, detail="unknown_adapter")
            raise
        if aid is not None and not resubmission:
            # per-adapter arrival ledger (monitor reconciles the counter
            # against these events key-for-key)
            self.metrics.inc(f"adapter{ix}_requests")
            self.metrics.event("adapter_request",
                               request_id=request.request_id,
                               adapter_id=aid, adapter_ix=ix)
        try:
            self.scheduler.submit(request, now)
        except QueueFullError:
            self._finish(request, [], FINISH_REJECTED, submit_ts=now,
                         now=now, detail="queue_full")
            raise
        except DeadlineExpiredError:
            self._finish(request, [], FINISH_REJECTED, submit_ts=now,
                         now=now, detail="deadline_expired")
            raise
        return request.request_id

    def cancel(self, request_id: int) -> bool:
        """Cancel a queued or in-flight request; returns True when found.
        A queued request terminates immediately; an in-flight one is
        evicted at the start of the next tick, keeping its partial
        tokens in the result."""
        queued = self.scheduler.cancel(request_id)
        if queued is not None:
            request, submit_ts = queued
            self._finish(request, [], FINISH_CANCELLED, submit_ts=submit_ts,
                         now=clock.now())
            return True
        for i, (request, tokens, submit_ts) in enumerate(self._parked):
            if request.request_id == request_id:
                # a parked request holds no slot or pages — it terminates
                # immediately, keeping the tokens generated before the park
                del self._parked[i]
                self._finish(request, tokens, FINISH_CANCELLED,
                             submit_ts=submit_ts, now=clock.now())
                return True
        for rec in (*self._active.values(), *self._prefilling.values()):
            if rec.request.request_id == request_id:
                rec.cancelled = True
                return True
        return False

    def tick(self) -> List[RequestResult]:
        """One scheduler iteration: expire deadlines, evict cancellations,
        dispatch admissions' prefills FCFS (decode-starvation capped),
        then dispatch one batched decode step over the active slots, read
        and commit the tick's prefills, and read and commit the step
        dispatched a tick ago (with speculation: prefills and step are
        read at once). Returns the requests that reached a terminal state
        during this tick."""
        if self._closed:
            raise RuntimeError("engine is closed")
        finished: List[RequestResult] = []
        with span(TICK_ENGINE):
            with span(TICK_SCHEDULE, queued=self.scheduler.depth,
                      active=len(self._active)):
                now = clock.now()
                self._expire(now, finished)
                self._evict_cancelled(finished)
                self._maybe_preempt(now)
                self._chunk_tokens_tick = 0
            if self.config.prefill_token_budget is None:
                self._admit(finished)
            else:
                self._chunked_admit(finished)
            if self._chunk_tokens_tick:
                # one observation per tick with prefill activity — the
                # histogram's sum is the total chunked prefill tokens, its
                # max must never exceed prefill_token_budget
                self.metrics.observe("prefill_tokens_per_tick",
                                     self._chunk_tokens_tick)
            self._decode_tick(finished)
            with span(TICK_COMMIT):
                self.metrics.observe("slot_occupancy", self.slots.occupancy)
                self.metrics.set_gauge("kv_pages_in_use",
                                       self.pages.in_use_count)
                self.metrics.set_gauge("kv_pages_free",
                                       self.pages.free_count)
                if self._latent:
                    self.metrics.set_gauge(
                        "kv_latent_bytes_in_use",
                        self.pages.in_use_count * self._page_read_bytes)
                self.metrics.observe("kv_page_occupancy",
                                     self.pages.occupancy)
                if self._window_share:
                    self.metrics.set_gauge(
                        "kv_pages_out_of_window",
                        self._pages_out_of_window())
                delta = self.pages.evictions - self._evictions_seen
                if delta:
                    self.metrics.inc("prefix_evictions", delta)
                    self._evictions_seen = self.pages.evictions
        return finished

    def serve(self, requests: Sequence[Request], *,
              on_tick: Optional[Callable[["InferenceEngine", int], None]]
              = None, max_ticks: Optional[int] = None
              ) -> List[RequestResult]:
        """Serve ``requests`` to completion: submits lazily as the bounded
        queue drains (backpressure without rejections), ticks until idle,
        and returns results in input order. ``on_tick(engine, i)`` runs
        after each tick — the hook fault-injection and tests use to
        cancel/submit mid-flight."""
        pending = list(requests)
        ids = [r.request_id for r in pending]
        ticks = 0
        while pending or self.scheduler.depth or self._active \
                or self._prefilling or self._flight is not None:
            while pending and \
                    self.scheduler.depth < self.config.scheduler.max_queue:
                self.submit(pending.pop(0))
            before = (len(pending), self.scheduler.depth,
                      len(self._active), len(self._prefilling))
            self.tick()
            ticks += 1
            if on_tick is not None:
                on_tick(self, ticks)
            if max_ticks is not None and ticks >= max_ticks:
                break
            if (before == (len(pending), self.scheduler.depth,
                           len(self._active), len(self._prefilling))
                    and not self._active and not self._prefilling
                    and self.scheduler.depth):
                raise RuntimeError(
                    "serve() made no progress: queued requests exist but "
                    "none are admissible (admission_hook deferring "
                    "forever?)")
        return [self.completed[i] for i in ids if i in self.completed]

    def close(self) -> None:
        """Release every slot and flush the metrics registry (final
        counter snapshot — what the monitor report reconciles against
        the request records). Idempotent: a second ``close()`` is a
        no-op, so exception paths can close unconditionally."""
        if self._closed:
            return
        self._closed = True
        if self._flight is not None:
            # a step nobody will read: its rows are thrown away
            self.metrics.inc("decode_rows_dropped", len(self._flight.rows))
            self._flight = None
        self._unread = []
        self._active.clear()
        self._prefilling.clear()
        self._parked.clear()
        self.slots.reset()
        # the page free list resets WITH the slot pool — a rebuild
        # that reused this registry must start from a full pool
        self.pages.reset()
        self._reserved_pages = 0
        self._page_table_h[:] = self.pages.n_pages
        self.metrics.flush()

    def __enter__(self) -> "InferenceEngine":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False

    # -- tick phases ------------------------------------------------------

    def _expire(self, now: float, finished: List[RequestResult]) -> None:
        for request, submit_ts in self.scheduler.expire(now):
            finished.append(self._finish(
                request, [], FINISH_TIMEOUT, submit_ts=submit_ts, now=now))
        if self._parked:
            # a park never stops the deadline clock — parked requests
            # expire exactly like queued ones, keeping their partial
            # tokens in the result
            kept = []
            for request, tokens, submit_ts in self._parked:
                d = request.deadline_s
                if d is not None and now - submit_ts > d:
                    finished.append(self._finish(
                        request, tokens, FINISH_TIMEOUT,
                        submit_ts=submit_ts, now=now))
                else:
                    kept.append((request, tokens, submit_ts))
            self._parked = kept
        for slot in sorted(self._active):
            rec = self._active[slot]
            d = rec.request.deadline_s
            if d is not None and now - rec.submit_ts > d:
                finished.append(self._retire(rec, FINISH_TIMEOUT, now))
        for slot in list(self._prefilling):
            rec = self._prefilling[slot]
            d = rec.request.deadline_s
            if d is not None and now - rec.submit_ts > d:
                finished.append(self._abandon_prefill(
                    rec, FINISH_TIMEOUT, now))

    def _evict_cancelled(self, finished: List[RequestResult]) -> None:
        for slot in sorted(self._active):
            rec = self._active[slot]
            if rec.cancelled:
                finished.append(self._retire(
                    rec, FINISH_CANCELLED, clock.now()))
        for slot in list(self._prefilling):
            rec = self._prefilling[slot]
            if rec.cancelled:
                finished.append(self._abandon_prefill(
                    rec, FINISH_CANCELLED, clock.now()))

    def _plan_prefix(self, request: Request):
        """Match ``request``'s page-aligned prompt prefix against the
        intern index: ``(chain, shared_pages, skip_first)``. The chain is
        always computed (the miss path interns it); ``shared_pages`` is
        the longest currently-interned leading run (empty on a miss or
        with ``prefix_cache=False``). ``skip_first`` marks the fully
        page-aligned hit, whose suffix prefill is a single recompute of
        the prompt's last token with its K/V scatter masked (the COW
        seam — the boundary row already lives, bitwise, in the last
        shared page). A match is trimmed when its suffix bucket would
        overrun ``max_len`` (only possible for non-power-of-two page
        sizes) so the static bucket set keeps holding."""
        ps = self.config.page_size
        # fold the request's adapter identity into the salt: adapter
        # deltas make K/V adapter-specific, so same-prompt tenants under
        # different adapters must never share a chain (base traffic,
        # adapter_id=None, keeps the plain model salt and still shares)
        salt = adapter_salt(self._prefix_salt, request.sampling.adapter_id)
        chain = prefix_hash_chain(request.prompt, ps, salt)
        if not self.config.prefix_cache or not chain:
            return chain, [], False
        pages, matched = self.pages.match_prefix(chain)
        max_len = self.config.max_len
        while matched:
            start = (request.prompt_len - 1
                     if matched * ps == request.prompt_len
                     else matched * ps)
            if start + bucket_for(request.prompt_len - start,
                                  max_len) <= max_len:
                break
            matched -= 1
        if matched == 0:
            return chain, [], False
        return chain, pages[:matched], \
            matched * ps == request.prompt_len

    def _make_page_predicate(self):
        """Pages-aware admission predicate: a request enters only when
        its WORST-CASE page need (total_len,
        minus the shared-prefix pages a cache hit maps refcounted) fits
        alongside every other admitted request's outstanding reservation
        — so decode-time on-demand extends can never exhaust the pool.
        ``reclaimable`` pages (held only by the intern index) count as
        capacity since allocation evicts entries under pressure, but
        this request's own shared pages are subtracted from that pot
        first: mapping PINS them, so they stop being evictable. A head
        that can never fit (need > n_pages) is shed as
        ``pages_exhausted``; one that merely must wait defers (FCFS
        head-blocking). The ``planned`` tallies accumulate across the
        pops of ONE call — chunked admission builds a fresh predicate
        per single-head pop because it maps pages between pops."""
        planned = 0          # private pages promised this tick
        planned_shared = 0   # reclaimable pages pinned this tick

        def predicate(request):
            nonlocal planned, planned_shared
            need = self.pages.pages_for(request.total_len)
            if need > self.pages.n_pages:
                return "shed"
            _, shared_pages, _ = self._plan_prefix(request)
            shared = len(shared_pages)
            pool = self.pages
            avail = (pool.free_count
                     + max(0, pool.reclaimable_count
                           - planned_shared - shared)
                     - (self._reserved_pages - pool.owned_count)
                     - planned)
            if need - shared <= avail:
                planned += need - shared
                planned_shared += shared
                return "admit"
            return "defer"

        return predicate

    def _maybe_preempt(self, now: float) -> None:
        """Park ONE lowest-class running slot when a strictly-higher-class
        queued head is blocked on slots or pages (the tentpole's
        preemption rule). Runs before admission so the freed slot/pages
        can admit the head in the same tick; one park per tick converges
        without thrashing (the parked continuation re-queues in its own
        class lane, where strict priority keeps it behind the traffic
        that displaced it). The head's TRUE class decides — a batch head
        aged up to standard rank may dispatch ahead of standard, but it
        never preempts anyone."""
        if not self.resume_consumer or not self._active:
            return
        head = self.scheduler.head(now=now)
        if head is None:
            return
        head_rank = PRIORITY_RANK[head[0].sampling.priority]
        blocked = self.slots.free_count == 0
        if not blocked:
            blocked = self._make_page_predicate()(head[0]) == "defer"
        if not blocked:
            return
        victim, victim_key = None, None
        for slot in sorted(self._active):
            rec = self._active[slot]
            rank = PRIORITY_RANK[rec.request.sampling.priority]
            if rank <= head_rank:
                continue
            # lowest class first; among peers the one with the least
            # generated work (cheapest re-prefill), ids breaking ties
            key = (rank, -len(rec.tokens), rec.request.request_id)
            if victim_key is None or key > victim_key:
                victim, victim_key = rec, key
        if victim is not None:
            self._park(victim, now, cause="priority")

    def _park(self, rec: _Active, now: float, *, cause: str) -> None:
        """Preempt one ACTIVE slot: release the slot and its pages
        (shared prefix pages outlive it, refcounted — exactly the
        `_retire` release sequence) but emit NO terminal record and NO
        phase spans — a park is not an outcome. The host-side cursor
        (request, generated tokens, submit_ts) moves to the parked list
        for the supervisor's continuation path; a zero-width ``preempt``
        mark span annotates the timeline under the request's original
        trace_id."""
        slot = rec.slot
        del self._active[slot]
        self.slots.release(slot)
        self._release_pages(rec)
        self._clear_slot(slot)
        self._parked.append((rec.request, list(rec.tokens), rec.submit_ts))
        self.metrics.inc("requests_preempted")
        log_event(_LOG, "request_preempted",
                  request_id=rec.request.request_id, cause=cause,
                  priority=rec.request.sampling.priority,
                  tokens_parked=len(rec.tokens))
        self.metrics.event("request_preempted",
                           request_id=rec.request.request_id, cause=cause,
                           priority=rec.request.sampling.priority,
                           tokens_parked=len(rec.tokens))
        emit_span(self.metrics, SPAN_PREEMPT,
                  trace_id=rec.request.trace_id,
                  request_id=rec.request.request_id,
                  start_s=now, end_s=now, wall=clock.wall(),
                  replica_id=self.replica_id, detail=cause,
                  tokens_parked=len(rec.tokens),
                  priority=rec.request.sampling.priority)

    def park_class(self, priority: str, *, cause: str = "brownout") -> int:
        """Park EVERY active slot of ``priority`` (the brownout ladder's
        "preempt batch slots" rung); returns the number parked. The
        caller owns the take_parked() drain. Mid-chunked-prefill slots
        are not parked — their progress lives in half-filled pages, not
        a host cursor; the admission floor already stops new ones."""
        now = clock.now()
        victims = [self._active[s] for s in sorted(self._active)
                   if self._active[s].request.sampling.priority == priority]
        for rec in victims:
            self._park(rec, now, cause=cause)
        return len(victims)

    def _admit(self, finished: List[RequestResult]) -> None:
        with span(TICK_SCHEDULE):
            shed: List = []
            now = clock.now()
            batch = self.scheduler.pop_admissible(
                self.slots.free_count, decoding=bool(self._active),
                predicate=self._make_page_predicate(), shed=shed, now=now)
            for request, submit_ts in shed:
                finished.append(self._shed_pages(request, submit_ts, now))
        for request, submit_ts in batch:
            slot = self.slots.allocate()
            assert slot is not None  # pop_admissible respects free_count
            self._prefill_into(request, slot, submit_ts, finished)

    def _chunked_admit(self, finished: List[RequestResult]) -> None:
        """Token-budgeted mixed tick (docs/serving.md#chunked-prefill):
        continue in-flight chunked prefills in admission order, then
        admit new heads while budget remains, each running its first
        chunk(s) in the same tick. ``max_prefills_per_tick`` still caps
        NEW admissions per tick while requests are decoding; the token
        budget bounds the total prefill compute of the whole tick, so a
        long prompt can never stall co-tenant decode for more than one
        chunk's worth."""
        budget = self.config.prefill_token_budget
        spent = 0
        for slot in list(self._prefilling):
            if spent >= budget:
                break
            ran = self._run_chunk(self._prefilling[slot], budget - spent,
                                  finished)
            if ran == 0:
                break           # remaining budget below one page
            spent += ran
        admitted = 0
        limit = self.slots.free_count
        if self._active:
            limit = min(limit,
                        self.config.scheduler.max_prefills_per_tick)
        while spent < budget and admitted < limit and self.scheduler.depth:
            with span(TICK_SCHEDULE):
                shed: List = []
                now = clock.now()
                batch = self.scheduler.pop_admissible(
                    1, decoding=False,
                    predicate=self._make_page_predicate(),
                    shed=shed, now=now)
                for request, submit_ts in shed:
                    finished.append(
                        self._shed_pages(request, submit_ts, now))
            if not batch:
                break           # head deferred (pages) or queue drained
            request, submit_ts = batch[0]
            self._read_prefixes_ahead(request, finished)
            with span(TICK_SCHEDULE):
                slot = self.slots.allocate()
                assert slot is not None
                rec = self._begin_chunked_prefill(request, slot, submit_ts)
                if rec is None:
                    break       # intern-eviction race: requeued at front
            admitted += 1
            ran = self._run_chunk(rec, budget - spent, finished)
            if ran == 0:
                break           # admitted; first chunk waits for budget
            spent += ran

    def _shed_pages(self, request: Request, submit_ts: float,
                    now: float) -> RequestResult:
        """Reject a request whose worst-case page reservation exceeds the
        whole pool — its own shed counter + ``request_shed`` reason, the
        supervisor-shed convention, instead of a prefill-time failure."""
        need = self.pages.pages_for(request.total_len)
        self.metrics.inc("requests_shed_pages")
        log_event(_LOG, "request_shed", request_id=request.request_id,
                  reason="pages_exhausted", pages_needed=need,
                  n_pages=self.pages.n_pages)
        self.metrics.event("request_shed", request_id=request.request_id,
                           reason="pages_exhausted", pages_needed=need,
                           n_pages=self.pages.n_pages)
        return self._finish(request, [], FINISH_REJECTED,
                            submit_ts=submit_ts, now=now,
                            detail="pages_exhausted")

    def _prefill_into(self, request: Request, slot: int, submit_ts: float,
                      finished: List[RequestResult]) -> None:
        """Dispatch ``request``'s prefill into ``slot`` and put the slot
        in the batch at ``position = prompt_len``, one token owed: its
        first token reaches the tick's decode step on the device and the
        host, in ``_read_prefills``. Under speculation it is read
        here."""
        self._read_prefixes_ahead(request, finished)
        with span(TICK_PREFILL, trace_id=request.trace_id,
                  prompt_tokens=request.prompt_len) as group:
            with span(TICK_SCHEDULE):
                rec = _Active(request, slot, submit_ts)
                rec.prefill_start = clock.now()
                sp = request.sampling
                # resolve the adapter row NOW (non-strict: an id unloaded
                # while queued degrades to the null row — base output —
                # rather than crashing admission; submit() already validated
                # it existed)
                rec.adapter_ix = self._adapter_index(sp.adapter_id,
                                                     strict=False)
            with span(TICK_UPLOAD, arrays=2):
                aix = jnp.asarray([rec.adapter_ix], jnp.int32)
                bank = self._bank
                topk = jnp.int32(sp.top_k if sp.top_k is not None
                                 else self._vocab)
            with span(TICK_SCHEDULE) as sched:
                # re-match the prefix NOW (the predicate's match may have
                # been reshaped by a later head's intern eviction), commit
                # the worst-case reservation minus the shared pages, then
                # physically map only the prompt's pages (decode extends
                # on demand)
                chain, shared_pages, skip_first = \
                    self._plan_prefix(request)
                shared_used = len(shared_pages)
                need = (self.pages.pages_for(request.total_len)
                        - shared_used)
                mapped = self.pages.map_slot(slot, request.prompt_len,
                                             shared=shared_pages or None)
                if mapped is None:
                    self.slots.release(slot)
                    if self.config.prefix_cache:
                        # an intern eviction between the admission
                        # predicate and this map changed what's
                        # reclaimable — FCFS honest, the request retries
                        # from the FRONT of the queue on a later tick
                        # (co-tenant retirements will unpin pages)
                        self.scheduler.requeue_front(request, submit_ts)
                        return
                    raise RuntimeError(
                        f"page pool exhausted at prefill despite "
                        f"admission reservation (slot {slot}, "
                        f"free={self.pages.free_count}) — reservation "
                        f"accounting is broken")
                rec.reserved_pages = need
                self._reserved_pages += need
                row = self._page_table_h[slot]
                row[:] = self.pages.n_pages
                row[:len(mapped)] = mapped
                # freshly mapped PRIVATE pages may be recycled (e.g. from
                # a pressure-evicted intern run) with stale scales; zero
                # them so the rescale-on-append floor starts clean. Shared
                # pages keep their scales — that's their dequant key.
                self._reset_fresh_scales(mapped[shared_used:])
                sched.set_metadata(pages_mapped=len(mapped))
            try:
                if self._faults is not None:
                    self._faults.before_prefill()
                if shared_used:
                    # prefix-cache hit: prefill ONLY the suffix (bucketed
                    # like a full prefill). start is the first token NOT
                    # covered by shared pages — or, fully covered, the
                    # prompt's last token recomputed for its logits only
                    with span(TICK_UPLOAD) as up:
                        ps = self.config.page_size
                        start = (request.prompt_len - 1 if skip_first
                                 else shared_used * ps)
                        suffix_len = request.prompt_len - start
                        bucket = bucket_for(suffix_len, self.config.max_len)
                        suffix = np.zeros((1, bucket), np.int32)
                        suffix[0, :suffix_len] = request.prompt[start:]
                        args = (
                            self._params, self._caches,
                            jnp.asarray(self._page_table_h[slot]),
                            jnp.asarray(suffix), jnp.int32(start),
                            jnp.int32(suffix_len),
                            jnp.int32(request.prompt_len),
                            jnp.float32(sp.temperature), topk,
                            jnp.int32(sp.seed), jnp.bool_(skip_first), aix,
                            bank, *self._carry_args(slot))
                        up.set_metadata(
                            arrays=8, bytes=suffix.nbytes
                            + self._page_table_h[slot].nbytes)
                    with span(TICK_DISPATCH, program="suffix_prefill",
                              rows=bucket):
                        first, finite, self._caches, carry = \
                            self._suffix_fn(*args)
                else:
                    with span(TICK_UPLOAD) as up:
                        bucket = bucket_for(request.prompt_len,
                                            self.config.max_len)
                        padded = np.zeros((1, bucket), np.int32)
                        padded[0, :request.prompt_len] = request.prompt
                        args = (
                            self._params, self._caches,
                            jnp.asarray(self._page_table_h[slot]),
                            jnp.asarray(padded), jnp.int32(request.prompt_len),
                            jnp.float32(sp.temperature), topk,
                            jnp.int32(sp.seed), aix, bank,
                            *self._carry_args(slot))
                        up.set_metadata(
                            arrays=5, bytes=padded.nbytes
                            + self._page_table_h[slot].nbytes)
                    with span(TICK_DISPATCH, program="paged_prefill",
                              rows=bucket):
                        first, finite, self._caches, carry = \
                            self._prefill_fn(*args)
                del args
                group.set_metadata(bucket=bucket, tokens=suffix_len
                                   if shared_used else request.prompt_len)
            except Exception:
                # keep the pool invariants even as the failure propagates:
                # the slot never held committed state (nothing scattered, or
                # the scatter's result was discarded with the raised call)
                self.slots.release(slot)
                self._release_pages(rec)
                raise
            with span(TICK_SCHEDULE):
                # what the decode step needs is known without the token:
                # the slot's position, page row and sampling row
                self._carry = carry
                rec.chain, rec.shared_used = chain, shared_used
                rec.position = request.prompt_len
                self._active[slot] = rec
                self.admission_log.append(request.request_id)
                self._sync_slot(rec)
                self._queue_read(rec, first, finite)
        if self._spec:
            self._read_prefills(finished)

    def _begin_chunked_prefill(self, request: Request, slot: int,
                               submit_ts: float) -> Optional[_Active]:
        """Admission half of a chunked prefill: allocate the slot,
        commit the page reservation and map the prompt's pages (shared
        prefix refcounted, exactly like the monolithic path) — but run
        NO compute yet. The slot's real page row lives on the rec while
        chunks land; the GLOBAL table row stays all-sentinel, so the
        batched decode step treats the slot exactly like an idle one
        (gathers mask, appends drop) and mid-prefill slots are excluded
        from decode with no program or shape change. Returns None when
        an intern-eviction race requeued the request (FCFS front)."""
        rec = _Active(request, slot, submit_ts)
        rec.prefill_start = clock.now()
        rec.adapter_ix = self._adapter_index(request.sampling.adapter_id,
                                             strict=False)
        chain, shared_pages, skip_first = self._plan_prefix(request)
        shared_used = len(shared_pages)
        need = self.pages.pages_for(request.total_len) - shared_used
        mapped = self.pages.map_slot(slot, request.prompt_len,
                                     shared=shared_pages or None)
        if mapped is None:
            self.slots.release(slot)
            if self.config.prefix_cache:
                self.scheduler.requeue_front(request, submit_ts)
                return None
            raise RuntimeError(
                f"page pool exhausted at prefill despite admission "
                f"reservation (slot {slot}, "
                f"free={self.pages.free_count}) — reservation "
                f"accounting is broken")
        rec.reserved_pages = need
        self._reserved_pages += need
        rec.page_row = self._page_row(mapped)
        rec.chain = chain
        rec.shared_used = shared_used
        rec.skip_first = skip_first
        # shared prefix rows are already resident: chunking starts
        # at the first uncovered token (page-aligned), or — fully
        # covered — at the last-token recompute (the COW seam)
        rec.prefill_pos = (request.prompt_len - 1 if skip_first
                           else shared_used * self.config.page_size)
        self._reset_fresh_scales(mapped[shared_used:])
        self._prefilling[slot] = rec
        self.admission_log.append(request.request_id)
        return rec

    def _run_chunk(self, rec: _Active, budget_left: int,
                   finished: List[RequestResult]) -> int:
        """Run ONE maximal prefill chunk for ``rec`` within
        ``budget_left`` tokens; returns the tokens consumed (0 = no
        progress possible this tick). Chunks reuse the suffix program
        (the slot's pages ARE the carried state). The final chunk's
        sample — keyed
        at step ``prompt_len`` from the prompt's last-token logits —
        is the request's first token, bitwise what the monolithic
        prefill emits; intermediate chunks' samples are discarded."""
        request = rec.request
        with span(TICK_PREFILL, trace_id=request.trace_id,
                  prompt_tokens=request.prompt_len,
                  chunk=rec.prefill_chunks) as group:
            with span(TICK_SCHEDULE):
                remaining = request.prompt_len - rec.prefill_pos
                chunk_len = min(remaining, budget_left)
                if chunk_len < remaining:
                    # internal chunk boundaries stay page-aligned: every
                    # fresh page is then written whole in ONE scatter onto a
                    # zeroed scale, so int8 page contents (and the interned
                    # prefix pages) are bitwise what the monolithic fill
                    # produces
                    ps = self.config.page_size

                    def aligned(n):
                        return ((rec.prefill_pos + n) // ps) * ps \
                            - rec.prefill_pos

                    # a chunk is a whole pass over the weights: neither it
                    # nor what it leaves behind should be a sliver. A
                    # chunk that is not the prompt's last is a quarter of
                    # the budget or more and leaves as much; what is left
                    # of a tick's budget goes UNUSED where that cannot be
                    # (the head of a tick has the whole budget and always
                    # runs): docs/serving.md#chunked-prefill says what
                    # that trades. The chunk programs that can be compiled
                    # are then those of whole prompts and of the top two
                    # octaves of the budget
                    budget = self.config.prefill_token_budget
                    floor = budget // 4
                    whole = aligned(chunk_len)
                    chunk_len = whole
                    if remaining - chunk_len < floor:
                        chunk_len = aligned(remaining - floor)
                    if chunk_len < floor:
                        if budget_left < budget:
                            return 0
                        chunk_len = max(chunk_len, whole)
                if chunk_len <= 0:
                    return 0
            with span(TICK_UPLOAD, arrays=2, bytes=8):
                sp = request.sampling
                start = rec.prefill_pos
                bucket = bucket_for(chunk_len, self.config.max_len)
                chunk = np.zeros((1, bucket), np.int32)
                chunk[0, :chunk_len] = request.prompt[start:start + chunk_len]
                aix = jnp.asarray([rec.adapter_ix], jnp.int32)
                topk = jnp.int32(sp.top_k if sp.top_k is not None
                                 else self._vocab)
            group.set_metadata(bucket=bucket, tokens=chunk_len)
            try:
                if self._faults is not None:
                    self._faults.before_prefill()
                with span(TICK_UPLOAD, arrays=8, bytes=chunk.nbytes
                          + rec.page_row.nbytes):
                    args = (
                        self._params, self._caches,
                        jnp.asarray(rec.page_row), jnp.asarray(chunk),
                        jnp.int32(start), jnp.int32(chunk_len),
                        jnp.int32(request.prompt_len),
                        jnp.float32(sp.temperature), topk,
                        jnp.int32(sp.seed),
                        jnp.bool_(rec.skip_first
                                  and rec.prefill_chunks == 0),
                        aix, self._bank, *self._carry_args(rec.slot))
                with span(TICK_DISPATCH, program="suffix_prefill",
                          rows=bucket):
                    first, finite, self._caches, carry = \
                        self._suffix_fn(*args)
                del args
            except Exception:
                # same failure contract as the monolithic prefill: the slot
                # never held committed state — release everything as the
                # exception propagates; the supervisor's restart re-prefills
                # the request from its prompt through the same admit path
                del self._prefilling[rec.slot]
                self.slots.release(rec.slot)
                self._release_pages(rec)
                self._clear_slot(rec.slot)
                raise
            with span(TICK_COMMIT):
                self._carry = carry
                rec.prefill_pos += chunk_len
                rec.prefill_chunks += 1
                self.metrics.inc("prefill_chunks")
                self._chunk_tokens_tick += chunk_len
                last = rec.prefill_pos >= request.prompt_len
                if last:
                    self._complete_chunked_prefill(rec)
                self._queue_read(rec, first if last else None, finite)
        if self._spec:
            self._read_prefills(finished)
        return chunk_len

    def _complete_chunked_prefill(self, rec: _Active) -> None:
        """Final chunk dispatched: publish the page row to the global
        table (the batched decode step sees — and appends to — the slot
        from this tick's step on) and promote the rec to the active set,
        one token owed, as a monolithic prefill's; ``_read_prefills``
        commits the token."""
        slot = rec.slot
        del self._prefilling[slot]
        self._page_table_h[slot] = rec.page_row
        rec.position = rec.request.prompt_len
        self._active[slot] = rec
        self._sync_slot(rec)

    def _carry_args(self, slot: int) -> tuple:
        """The last two arguments of a prefill program: the carried token
        vector and the slot's index (None, None under speculation)."""
        if self._carry is None:
            return None, None
        return self._carry, jnp.int32(slot)

    def _queue_read(self, rec: _Active, first: Optional[jax.Array],
                    finite: jax.Array) -> None:
        """Leave a prefill's results to ``_read_prefills``; their copies
        to the host start now, so the read finds them there."""
        for value in (first, finite):
            if value is not None:
                value.copy_to_host_async()
        self._unread.append(_Unread(rec, first, finite))

    def _read_prefixes_ahead(self, request: Request,
                             finished: List[RequestResult]) -> None:
        """Read this tick's prefills before ``request`` plans its prefix
        where one of them may intern pages it would share: one whose
        prompt starts with the same page (the chain is cumulative, so no
        other can match any of it). The lock-step order interned each
        prefill before the next admission planned, and this keeps the
        hits it gave."""
        if not self._unread or not self.config.prefix_cache:
            return
        ps = self.config.page_size
        head = prefix_hash_chain(
            request.prompt[:ps], ps,
            adapter_salt(self._prefix_salt, request.sampling.adapter_id))
        if head and any(u.first is not None and u.rec.chain[:1] == head
                        for u in self._unread):
            self._read_prefills(finished)

    def _read_prefills(self, finished: List[RequestResult]) -> None:
        """Read the prefills and chunks dispatched since the last call
        and commit what they give; on the plain path after the tick's
        decode step went out, so each read waits for its prefill with
        that step queued behind it. A chunk that is not the prompt's
        last adds its finite flag to the request's. A first token is
        committed as it came: the prefix is interned if every program of
        the prompt was finite and the slot quarantined if not, the token
        appended and stamped (TTFT), and the request retired on EOS or
        length. The row the tick's step holds for a slot that leaves
        here is dropped when that step is read."""
        unread, self._unread = self._unread, []
        for rec, first, finite in unread:
            request = rec.request
            with span(TICK_PREFILL, trace_id=request.trace_id,
                      prompt_tokens=request.prompt_len):
                with span(TICK_READBACK, reads=2 if first is not None else 1,
                          bytes=5 if first is not None else 1):
                    rec.finite_ok = rec.finite_ok and bool(np.asarray(finite))
                    if first is not None:
                        first = int(np.asarray(first))
                with span(TICK_COMMIT) as commit:
                    if first is None:
                        rec.chunk_marks.append(clock.now())
                        continue
                    retired = len(finished)
                    took = self._commit_first(rec, first, finished)
                    commit.set_metadata(tokens=took,
                                        retired=len(finished) - retired)

    def _commit_first(self, rec: _Active, first: int,
                      finished: List[RequestResult]) -> int:
        """Commit a prefill's first token; returns the tokens taken (0
        when the prefill was not finite and the slot is quarantined)."""
        slot = rec.slot
        if self._active.get(slot) is not rec:
            return 0            # retired since by a failed page extend
        if self.config.prefix_cache:
            # hit/miss accounting lands with the first token so hits +
            # misses stays == prefills even when a mid-prefill
            # request times out or is cancelled
            if rec.shared_used:
                self.metrics.inc("prefix_hits")
                self.metrics.inc("prefix_pages_shared", rec.shared_used)
            else:
                self.metrics.inc("prefix_misses")
            # publish the prompt's full pages (shared run + freshly
            # prefilled privates) so later prompts hit; gated on
            # finite logits — a poisoned prefill must never be
            # shared. On an exact repeat this is a no-op; a longer
            # prompt upgrades the subsumed shorter entry.
            if rec.chain and rec.finite_ok:
                row = self._page_table_h[slot]
                self.pages.intern_prefix(
                    rec.chain, [int(p) for p in row[:len(rec.chain)]])
        self.metrics.inc("prefills")
        now = clock.now()
        rec.prefill_end = now
        if not rec.finite_ok:
            finished.append(self._quarantine(rec, "nonfinite_logits", now))
            return 0
        rec.tokens.append(first)
        rec.last_token = first
        # token #1 reaches the host here — TTFT is submit -> here
        rec.first_token_ts = rec.last_token_ts = now
        self.metrics.inc("tokens_generated")
        self._sync_slot(rec)
        done = self._finish_reason(rec, first)
        if done is not None:
            finished.append(self._retire(rec, done, now))
        return 1

    def _abandon_prefill(self, rec: _Active, reason: str,
                         now: float) -> RequestResult:
        """Retire a request whose chunked prefill never completed
        (deadline/cancel): release the slot and its pages. Partially
        written rows need no scrub unless a chunk went non-finite —
        finite garbage is causally invisible to any future occupant,
        exactly like bucket-padding rows."""
        del self._prefilling[rec.slot]
        self.slots.release(rec.slot)
        self._release_pages(rec, scrub=not rec.finite_ok)
        self._clear_slot(rec.slot)
        return self._finish(
            rec.request, [], reason, submit_ts=rec.submit_ts, now=now,
            prefill_start=rec.prefill_start, prefill_end=now,
            prefill_segments=tuple(rec.chunk_marks),
            prefill_chunks=rec.prefill_chunks or None)

    def _page_row(self, pages) -> np.ndarray:
        """``pages`` as one fixed-width page-table row, sentinel-padded:
        the shape every per-slot program takes, so none adds a compile
        shape."""
        row = np.full(self.config.pages_per_slot, self.pages.n_pages,
                      np.int32)
        row[:len(pages)] = pages
        return row

    def _release_pages(self, rec: _Active, *, scrub: bool = False) -> None:
        """Give back ``rec``'s slot's pages, its reservation and its
        table row, together. Only the pages whose LAST reference this
        drop removed are freed — shared prefix pages outlive the slot —
        and with ``scrub`` exactly those are zeroed."""
        freed = self.pages.release_slot(rec.slot)
        self._reserved_pages -= rec.reserved_pages
        self._page_table_h[rec.slot, :] = self.pages.n_pages
        if scrub and freed:
            self._caches = self._scrub_fn(
                self._caches, jnp.asarray(self._page_row(freed)))
            # PagePool.check() can now assert these free pages hold
            # zero scales until their next allocation
            self.pages.note_scrubbed(freed)

    def _reset_fresh_scales(self, pages) -> None:
        """Zero the scale sidecar for freshly allocated ``pages``
        (quantized pools only) — one fixed-width sentinel-padded row
        through a dedicated program, so it never adds a compile shape."""
        if not self._quantized or len(pages) == 0:
            return
        self._caches = self._reset_scales_fn(
            self._caches, jnp.asarray(self._page_row(pages)))

    def _build_windows(self) -> None:
        """Fill the per-slot verify windows for the next speculative
        step: row 0 is the token the sequential engine would feed
        (``last_token``), rows ``1..wl-1`` the n-gram draft over the
        slot's own history, rows past ``wl`` repeat the last real feed
        (causally invisible padding that cannot inflate an int8 page
        scale). ``wl`` is clipped so a nearly-finished request cannot
        overrun its ``max_new_tokens`` page reservation."""
        k = self._spec
        for slot in sorted(self._active):
            rec = self._active[slot]
            wl = max(1, min(
                k, rec.request.max_new_tokens - len(rec.tokens)))
            draft = propose_draft(
                list(rec.request.prompt) + rec.tokens, wl - 1)
            window = [rec.last_token] + draft
            window += [window[-1]] * (k - wl)
            self._window_h[slot] = window
            self._wlen_h[slot] = wl

    def _pages_out_of_window(self) -> float:
        """Pages mapped whose every position lies before ``position -
        window`` of their slot, times the share of layers that are window
        layers: what an allocator with a second, windowed kind of page row
        would free (one page row per slot backs every layer today)."""
        window = self.model.config.sliding_window
        ps = self.config.page_size
        dead = sum(max(0, (rec.position - window) // ps)
                   for rec in self._active.values())
        return dead * self._window_share

    def _dispatch_pages(self, slots, positions) -> dict:
        """``pages`` of the decode dispatch span: the page copies the
        decode kernel of ONE full-attention layer makes this step, the
        width of every dispatched slot's page range summed (a window
        layer copies no more) — how much the kernel's page walk is asked
        to do, beside ``rows``. Nothing unless a trace is being taken: no
        tick pays for the sum otherwise."""
        if not recording():
            return {}
        first, stop = paged_page_range(
            positions[slots], self._spec or 1, self.config.page_size)
        return {"pages": int((np.minimum(stop, self.config.pages_per_slot)
                              - first).sum())}

    def _decode_args(self, table=None, positions=None,
                     from_host=None) -> tuple:
        """The decode program's arguments (the page table rides right
        after the pool). By default from the host arrays as they stand,
        every fed token the host's; a step dispatched behind another
        hands in its own view: ``table`` and ``positions`` with the
        slots that sit the step out blanked and the others one row on,
        ``from_host`` false where the fed token is the carried one. With
        speculation the fed tokens are the ``[n, k]`` window matrix and
        nothing is carried."""
        if table is None:
            table, positions = self._page_table_h, self._positions_h
        if self._spec:
            fed = (jnp.asarray(self._window_h),)
        else:
            if from_host is None:
                from_host = np.ones(self.config.max_slots, np.bool_)
            fed = (jnp.asarray(self._tokens_h), self._carry,
                   jnp.asarray(from_host))
        return (self._params, self._caches, jnp.asarray(table), *fed,
                jnp.asarray(positions), jnp.asarray(self._temps_h),
                jnp.asarray(self._topks_h), jnp.asarray(self._seeds_h),
                jnp.asarray(self._adapter_ix_h), self._bank)

    def decode_program_text(self) -> str:
        """Optimized HLO text of the decode program, compiled for the
        current backend without running it (nothing is donated) — what
        ``chip_smoke.py`` reads to prove the fused kernel was compiled by
        Mosaic (a ``tpu_custom_call``) and neither interpreted nor
        replaced by the ``jnp`` reference."""
        return (self._decode_fn._fn.lower(*self._decode_args())
                .compile().as_text())

    def _decode_tick(self, finished: List[RequestResult]) -> None:
        """The decode half of a tick. Plain decode keeps one step in
        flight: dispatch step k, built from what the host knows without
        step k-1's tokens or the tick's first tokens, THEN read and
        commit the tick's prefills and step k-1, so the host's work on a
        tick runs while the device computes. A tick with a step in
        flight and nothing to dispatch still commits it. With
        speculation the step is read in the tick that dispatched it
        (the draft needs the host's newest tokens)."""
        try:
            step = self._dispatch_step(finished, self._flight)
        except Exception:
            # the tick's prefills went out before the step failed: their
            # first tokens are committed, so that a restart resumes from
            # them as it did when a prefill was read at once
            self._read_prefills(finished)
            raise
        self._read_prefills(finished)
        if self._spec:
            if step is not None:
                self._commit_step(step, finished, lag=0)
            return
        ahead, self._flight = self._flight, step
        if ahead is not None:
            self._commit_step(ahead, finished, lag=int(step is not None))

    def _dispatch_step(self, finished: List[RequestResult],
                       ahead: Optional[_Flight]) -> Optional[_Flight]:
        """Schedule, upload and dispatch one decode step behind
        ``ahead`` (the step still unread, or None). A slot with a row in
        ``ahead`` is one position further than the host has committed,
        and its fed token is that row's, taken on the device. A slot
        prefilled this tick whose first token the host has not read is
        fed that token from the device too (the prefill put it into
        ``_carry``). A slot whose token in flight is its last
        (``max_new_tokens`` reached, or the next position would be
        ``max_len``) sits the step out: its finish is known without the
        token. Returns None when no slot takes part."""
        n = self.config.max_slots
        with span(TICK_SCHEDULE, active=len(self._active)) as sched:
            lead = np.zeros(n, np.int32)    # rows in flight, slot by slot
            if ahead is not None:
                for slot, rec in ahead.rows:
                    if self._active.get(slot) is rec:
                        lead[slot] = 1
            # a first token not read yet is owed like a row in flight,
            # at the position the prefill already reached
            owed = lead.copy()
            for u in self._unread:
                if u.first is not None:
                    owed[u.rec.slot] = 1
            if self._spec and self._active:
                self._build_windows()
            now = clock.now()
            rows = []
            for slot in sorted(self._active):
                rec = self._active[slot]
                at = rec.position + int(lead[slot])
                if len(rec.tokens) + int(owed[slot]) \
                        >= rec.request.max_new_tokens \
                        or at >= self.config.max_len:
                    continue            # its token in flight is its last
                # a speculative step appends K/V for the whole verify
                # window (positions at..at+wl-1); wl is clipped to the
                # request's max_new_tokens, so the target stays within
                # the admission reservation
                grow = int(self._wlen_h[slot]) if self._spec else 1
                if self._extend_pages(rec, at + grow, now, finished):
                    rows.append((slot, rec))
            if not rows:
                return None
            call = None
            if self._faults is not None:
                call = self._faults.before_decode()
            # roofline gauge: bytes of KV stream one decode step
            # reads (mapped pages of every dispatched slot, dtype- and
            # sidecar-aware) — THE denominator speculation and int8
            # shrink
            mapped = sum(len(self.pages.slot_pages(s)) for s, _ in rows)
            self.metrics.set_gauge("kv_bytes_per_step",
                                   mapped * self._page_read_bytes)
            sched.set_metadata(pages_mapped=mapped)
        with span(TICK_UPLOAD, arrays=self._decode_upload[0],
                  bytes=self._decode_upload[1]):
            slots = np.fromiter((s for s, _ in rows), np.intp, len(rows))
            part = np.zeros(n, np.bool_)
            part[slots] = True
            table = np.where(part[:, None], self._page_table_h,
                             np.int32(self.pages.n_pages))
            positions = np.where(part, self._positions_h + lead,
                                 np.int32(0))
            carried = int((part & (owed > lead)).sum())
            args = self._decode_args(table, positions, owed == 0)
        with span(TICK_DISPATCH, program="decode", rows=len(rows),
                  in_flight=int(ahead is not None), carried=carried,
                  **self._dispatch_pages(slots, positions)):
            nxt, finite, self._caches = self._decode_fn(*args)
            # the copies to the host start now, so the read a tick
            # later finds the results there
            nxt.copy_to_host_async()
            finite.copy_to_host_async()
        del args
        if not self._spec:
            self._carry = nxt
        self.metrics.inc("decode_steps")
        if ahead is not None:
            self.metrics.inc("decode_steps_overlapped")
        if carried:
            self.metrics.inc("first_tokens_carried", carried)
        self.metrics.observe("decode_batch_size", len(rows))
        return _Flight(rows, nxt, finite, call)

    def _commit_step(self, step: _Flight, finished: List[RequestResult],
                     *, lag: int) -> None:
        """Read ``step``'s results and commit its rows as dispatched. A
        row whose slot no longer holds the record it was dispatched for
        (expired, cancelled, parked, quarantined or retired since, EOS
        learned a step late) is dropped and counted. ``lag``: decode
        steps dispatched since this one."""
        with span(TICK_READBACK, reads=2, lag=lag) as back:
            nxt = np.asarray(step.nxt)
            finite = np.asarray(step.finite)
            back.set_metadata(bytes=nxt.nbytes + finite.nbytes)
        with span(TICK_COMMIT) as commit:
            retired = len(finished)
            if self._routed:
                nxt, routing = self._split_routing(nxt)
                # every live row of the step (each row of its window,
                # under speculation) made top_k assignments a layer call
                made = (len(step.rows) * max(self._spec, 1)
                        * self._share_top_k)
                for rows, touched, busiest in routing:
                    # one routed layer call of this step: how many of the
                    # experts' weights it had to stream, and the straggler
                    self.metrics.inc("moe_rows_routed", int(rows))
                    if made:
                        self.metrics.inc("moe_rows_elsewhere",
                                         made - int(rows))
                    self.metrics.observe("moe_experts_touched",
                                         int(touched))
                    self.metrics.observe("moe_max_expert_rows",
                                         int(busiest))
            if self._faults is not None:
                nxt, finite = self._faults.corrupt_decode(
                    nxt, finite, step.call)
            now = clock.now()
            if self._spec:
                self._accept_windows(nxt, finite, now, finished)
                commit.set_metadata(retired=len(finished) - retired)
                return
            emitted = dropped = 0
            for slot, rec in step.rows:
                if self._active.get(slot) is not rec:
                    dropped += 1
                    continue
                token = int(nxt[slot])
                # integrity check, off the critical path: non-finite
                # logits or an out-of-vocab token mean THIS row is
                # poisoned — quarantine it alone, co-tenant rows keep
                # their clean step
                if not bool(finite[slot]) or not 0 <= token < self._vocab:
                    cause = ("nonfinite_logits" if not bool(finite[slot])
                             else "out_of_vocab_token")
                    finished.append(self._quarantine(rec, cause, now))
                    continue
                rec.position += 1        # last_token's K/V are now cached
                rec.tokens.append(token)
                rec.last_token = token
                rec.last_token_ts = now
                emitted += 1
                self.metrics.inc("tokens_generated")
                self._sync_slot(rec)
                done = self._finish_reason(rec, token)
                if done is not None:
                    finished.append(self._retire(rec, done, now))
            if dropped:
                self.metrics.inc("decode_rows_dropped", dropped)
            commit.set_metadata(tokens=emitted, dropped=dropped,
                                retired=len(finished) - retired)

    def _accept_windows(self, nxt, finite, now: float,
                        finished: List[RequestResult]) -> None:
        """Consume each slot's verified window: walk positions left to
        right, keep the target's sample at row ``j`` only while the
        token FED at row ``j`` was itself the target's previous output
        — the first disagreement invalidates everything to its right
        (those rows attended to a token the sequential engine would
        never have fed; their K/V rows are garbage the next window
        overwrites). Row 0 is always the sequential feed, so every
        step emits >= 1 token; a window is never slower than plain
        decode, only cheaper per token when drafts land."""
        for slot in sorted(self._active):
            rec = self._active[slot]
            wl = int(self._wlen_h[slot])
            consumed = 0
            quarantined = done = None
            for j in range(wl):
                token = int(nxt[slot, j])
                if not bool(finite[slot, j]) or \
                        not 0 <= token < self._vocab:
                    quarantined = ("nonfinite_logits"
                                   if not bool(finite[slot, j])
                                   else "out_of_vocab_token")
                    break
                rec.position += 1     # row j's fed K/V are now cached
                rec.tokens.append(token)
                rec.last_token = token
                rec.last_token_ts = now
                consumed += 1
                self.metrics.inc("tokens_generated")
                done = self._finish_reason(rec, token)
                if done is not None:
                    break
                if j + 1 >= wl or int(self._window_h[slot, j + 1]) != token:
                    break             # draft diverged from the target
            # rows 1..wl-1 were drafted; the drafts the walk consumed
            # BEYOND the mandatory row-0 token are the accepted ones
            proposed = wl - 1
            accepted = max(0, consumed - 1)
            if proposed:
                self.metrics.inc("draft_tokens_proposed", proposed)
                self.metrics.inc("draft_tokens_accepted", accepted)
                self.metrics.observe("spec_accept_rate",
                                     accepted / proposed)
                rec.spec_proposed += proposed
                rec.spec_accepted += accepted
            if quarantined is not None:
                # poisoned at any window row: quarantine the slot even
                # if clean tokens landed first — its KV is suspect
                finished.append(self._quarantine(rec, quarantined, now))
                continue
            self._sync_slot(rec)
            if done is not None:
                finished.append(self._retire(rec, done, now))

    def _extend_pages(self, rec: _Active, rows: int, now: float,
                      finished: List[RequestResult]) -> bool:
        """On-demand page growth before the decode step: the slot must
        have the pages backing its first ``rows`` rows mapped (the fused
        kernel appends there). Admission reserved each request's worst
        case, so the extend cannot fail — the defensive branch retires
        the slot as an error rather than corrupting a foreign page,
        counts the shed so the monitor surfaces it, and returns False."""
        slot = rec.slot
        fresh = self.pages.extend_slot(slot, rows)
        if fresh is None:
            self.metrics.inc("requests_shed_pages")
            log_event(_LOG, "request_shed",
                      request_id=rec.request.request_id,
                      reason="pages_exhausted", mid_flight=True)
            self.metrics.event("request_shed",
                               request_id=rec.request.request_id,
                               reason="pages_exhausted",
                               mid_flight=True)
            finished.append(self._retire(rec, FINISH_ERROR, now))
            return False
        if fresh:
            row = self._page_table_h[slot]
            pages = self.pages.slot_pages(slot)
            row[len(pages) - len(fresh):len(pages)] = fresh
            self._reset_fresh_scales(fresh)
        return True

    # -- retirement & bookkeeping ----------------------------------------

    def _quarantine(self, rec: _Active, cause: str,
                    now: float) -> RequestResult:
        """Retire ONE poisoned slot and keep the batch serving: scrub its
        KV (NaNs must not outlive the occupant — a masked attention
        weight times a NaN value is still NaN), release the slot, and
        finish the request with ``finish_reason="error"`` — co-tenants
        are untouched and the decode program never retraces.

        Only the pages this release actually FREES are scrubbed
        (``_retire(scrub=True)``): shared prefix
        pages still referenced by co-tenant slots or the intern index
        hold exclusively pre-intern prefill data (interned pages are
        never written again — decode appends land past the prompt's full
        pages, and interning is gated on finite prefill logits), so they
        are clean by construction and co-tenants keep token-exact
        streams; they are zeroed when their LAST reference drops."""
        slot = rec.slot
        self.metrics.inc("slots_quarantined")
        log_event(_LOG, "slot_quarantined", slot=slot,
                  request_id=rec.request.request_id, cause=cause)
        self.metrics.event("slot_quarantined", slot=slot,
                           request_id=rec.request.request_id, cause=cause)
        # mark span (zero-width): annotates the timeline with the scrub —
        # excluded from the phase-span conservation sum
        emit_span(self.metrics, SPAN_QUARANTINE,
                  trace_id=rec.request.trace_id,
                  request_id=rec.request.request_id,
                  start_s=now, end_s=now, wall=clock.wall(),
                  replica_id=self.replica_id, detail=cause)
        return self._retire(rec, FINISH_ERROR, now, scrub=True)

    def _finish_reason(self, rec: _Active, token: int) -> Optional[str]:
        if rec.request.eos_token is not None and \
                token == rec.request.eos_token:
            return FINISH_EOS
        if len(rec.tokens) >= rec.request.max_new_tokens:
            return FINISH_LENGTH
        return None

    def _sync_slot(self, rec: _Active) -> None:
        sp = rec.request.sampling
        i = rec.slot
        self._tokens_h[i] = rec.last_token
        self._positions_h[i] = rec.position
        self._temps_h[i] = sp.temperature
        self._topks_h[i] = sp.top_k if sp.top_k is not None else self._vocab
        self._seeds_h[i] = sp.seed
        self._adapter_ix_h[i] = rec.adapter_ix

    def _clear_slot(self, slot: int) -> None:
        self._tokens_h[slot] = 0
        self._positions_h[slot] = 0
        self._temps_h[slot] = 0.0
        self._topks_h[slot] = self._vocab
        self._seeds_h[slot] = 0
        self._adapter_ix_h[slot] = self._null_adapter
        if self._spec:
            self._window_h[slot] = 0
            self._wlen_h[slot] = 1

    def _retire(self, rec: _Active, reason: str, now: float, *,
                scrub: bool = False) -> RequestResult:
        del self._active[rec.slot]
        self.slots.release(rec.slot)
        self._release_pages(rec, scrub=scrub)
        self._clear_slot(rec.slot)
        if rec.spec_proposed:
            # mark span over the decode stretch the verify windows rode:
            # lifetime speculation totals, for the --trace timeline
            emit_span(self.metrics, SPAN_SPEC_VERIFY,
                      trace_id=rec.request.trace_id,
                      request_id=rec.request.request_id,
                      start_s=rec.prefill_end, end_s=now,
                      wall=clock.wall(), replica_id=self.replica_id,
                      proposed=rec.spec_proposed,
                      accepted=rec.spec_accepted)
        return self._finish(
            rec.request, rec.tokens, reason, submit_ts=rec.submit_ts,
            now=now, prefill_start=rec.prefill_start,
            prefill_end=rec.prefill_end,
            first_token_ts=rec.first_token_ts,
            last_token_ts=rec.last_token_ts,
            prefill_segments=tuple(rec.chunk_marks),
            prefill_chunks=rec.prefill_chunks or None)

    def _finish(self, request: Request, tokens: List[int], reason: str, *,
                submit_ts: float, now: float, prefill_start: float = 0.0,
                prefill_end: float = 0.0, first_token_ts: float = 0.0,
                last_token_ts: float = 0.0,
                prefill_segments: Sequence[float] = (),
                prefill_chunks: Optional[int] = None,
                detail: Optional[str] = None) -> RequestResult:
        if prefill_start:
            queue_s = prefill_start - submit_ts
            prefill_s = prefill_end - prefill_start
            decode_s = now - prefill_end
        else:                       # never left the queue
            queue_s, prefill_s, decode_s = now - submit_ts, 0.0, 0.0
        # SLO primitives, from the engine's own token timestamps: TTFT is
        # submit -> first token on the host; TPOT is the mean inter-token
        # interval (needs >= 2 tokens to define an interval)
        ttft_s = (first_token_ts - submit_ts
                  if tokens and first_token_ts else None)
        tpot_s = ((last_token_ts - first_token_ts) / (len(tokens) - 1)
                  if len(tokens) >= 2 and first_token_ts else None)
        result = RequestResult(
            request_id=request.request_id, prompt_len=request.prompt_len,
            tokens=list(tokens), finish_reason=reason, queue_s=queue_s,
            prefill_s=prefill_s, decode_s=decode_s,
            total_s=now - submit_ts, ttft_s=ttft_s, tpot_s=tpot_s,
            replica_id=self.replica_id,
            adapter_id=request.sampling.adapter_id,
            trace_id=request.trace_id,
            prefill_chunks=prefill_chunks,
            priority=request.sampling.priority)
        self.completed[request.request_id] = result
        self.metrics.inc(f"requests_{reason}")
        # the span timeline, stamped at the SAME terminal choke point and
        # from the SAME timestamps as the queue/prefill/decode
        # decomposition above — so span-sum == total_s by construction,
        # and restarts stay exactly-once (a dead incarnation emits
        # neither a record nor spans)
        emit_request_spans(
            self.metrics, trace_id=request.trace_id,
            request_id=request.request_id, submit_ts=submit_ts, now=now,
            wall=clock.wall(), prefill_start=prefill_start,
            prefill_end=prefill_end, replica_id=self.replica_id,
            prefill_segments=prefill_segments, detail=detail)
        for name, value in (("request_queue_s", result.queue_s),
                            ("request_prefill_s", result.prefill_s),
                            ("request_decode_s", result.decode_s),
                            ("request_total_s", result.total_s)):
            self.metrics.observe(name, value)
        tps = result.tokens_per_s
        if tps is not None:
            self.metrics.observe("request_tokens_per_s", tps)
        if result.ttft_s is not None:
            self.metrics.observe("request_ttft_s", result.ttft_s)
        if result.tpot_s is not None:
            self.metrics.observe("request_tpot_s", result.tpot_s)
        self.metrics.emit_record(result.record(wall=clock.wall()))
        if reason in (FINISH_REJECTED, FINISH_TIMEOUT, FINISH_CANCELLED,
                      FINISH_ERROR):
            extra = {"reason": detail} if detail else {}
            log_event(_LOG, f"request_{reason}",
                      request_id=request.request_id,
                      prompt_len=request.prompt_len,
                      new_tokens=result.new_tokens,
                      total_s=result.total_s, **extra)
            self.metrics.event(f"request_{reason}",
                               request_id=request.request_id,
                               new_tokens=result.new_tokens, **extra)
        return result
