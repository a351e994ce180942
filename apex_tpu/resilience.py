"""Resilient training driver: watchdog, rollback, retrying checkpoints.

The reference's only built-in robustness is the amp loss-scaler's skip-step
loop and the AutoResume hook (``apex/amp/scaler.py``,
``pipeline_parallel/utils.py:142-144``); everything else — surviving
preemptions, flaky storage, numeric blow-ups — is left to user scripts.
Production pre-training stacks (TorchTitan, arxiv 2410.06511) put that
layer in the framework: async distributed checkpointing, auto-resume and
failure recovery wrapped around the train step. This module is that layer
for apex_tpu, composing the pieces that already exist —
:func:`apex_tpu.training.make_train_step`-style stepping,
:class:`apex_tpu.checkpoint.CheckpointManager` and
:class:`apex_tpu.amp.scaler.LossScaler` — into a run that survives faults:

- :class:`Watchdog` — NaN/divergence detection: consecutive-skip abort
  (the reference amp aborts after repeated overflow skips), plus
  loss-spike and grad-norm anomaly detection against rolling medians.
  Metrics are computed **on device** inside the jitted step; the driver
  polls them in batches every ``poll_interval_steps`` so the host never
  blocks the step loop on a per-step device sync.
- **rollback-to-last-good** — on a verdict, restore the newest checkpoint
  from *before* the first bad step (suspect newer ones are deleted),
  decay the loss scale, advance the data "retry epoch" so the poisoned
  window is re-seeded, and retry under a bounded ``max_rollbacks`` budget.
- **retrying, atomic, async checkpoint I/O** —
  :class:`apex_tpu.checkpoint.RetryingCheckpointManager` over the
  sharded format (default): the step loop blocks only for the
  device→host snapshot, serialization + fsync + checksum run on a
  background writer inside the retry loop; restore verifies per-shard
  checksums and falls back to older steps on corruption, and is
  *elastic* — it reassembles shards onto a different mesh layout
  (``ResilienceConfig.checkpoint_format`` selects ``"orbax"`` for the
  original whole-array format).
- **preemption hook** — SIGTERM flips a flag; the loop flushes an
  emergency (forced) save and returns cleanly with
  ``status="preempted"``, resumable by the next invocation.
- **retrace watchdog** — :class:`apex_tpu.analysis.retrace.
  RetraceWatchdog` wraps ``step_fn`` and counts jit recompilations; a
  recompilation storm (ragged batches, pytree churn after a restore)
  raises after ``retrace_budget`` instead of silently running 10× slow.
- **observability** — attach an :class:`apex_tpu.observability.
  MetricsRegistry` (``ResilienceConfig.metrics``) and the driver mirrors
  every telemetry counter into it, emits incident events next to
  ``log_event``, and records step-time/tokens-per-s/MFU/memory metrics;
  ``python -m apex_tpu.monitor`` folds a JSONL sink's log into a run
  report that reconciles with :attr:`TrainingResult.telemetry`.

Every recovery path is exercised deterministically in tier-1 CPU tests via
:class:`apex_tpu.testing_faults.FaultInjector`.
"""

from __future__ import annotations

import inspect
import math
import signal
import statistics
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from apex_tpu.amp.scaler import LossScaler, LossScalerState, all_finite
from apex_tpu.analysis.retrace import RetraceWatchdog
from apex_tpu.checkpoint import (
    CheckpointManager,
    RetryingCheckpointManager,
    ShardedCheckpointManager,
)
from apex_tpu.observability.step_metrics import StepMetrics
from apex_tpu.observability.tracing import (
    SCOPE_DP_GRAD_ALL_REDUCE,
    SCOPE_LOSS_SCALE,
    SCOPE_OPTIMIZER,
)
from apex_tpu.training import sync_data_parallel_grads
from apex_tpu.transformer.parallel_state import DATA_AXIS
from apex_tpu.utils.logging import get_logger, log_event
from apex_tpu.utils.profiling import nvtx_range
from apex_tpu.utils.tree import global_norm

__all__ = [
    "ResilienceConfig",
    "Watchdog",
    "WatchdogVerdict",
    "TrainingDiverged",
    "TrainingResult",
    "make_train_state",
    "make_resilient_train_step",
    "run_training",
]


class TrainingDiverged(RuntimeError):
    """Raised when the rollback retry budget is exhausted (the analog of
    the reference amp's abort after repeated overflow skips) or no healthy
    checkpoint exists to roll back to. Carries ``telemetry``."""

    def __init__(self, message: str, telemetry: Optional[dict] = None):
        super().__init__(message)
        self.telemetry = dict(telemetry or {})


@dataclass
class ResilienceConfig:
    """Knobs for :func:`run_training`. Defaults are conservative; tests
    shrink the windows to trip every path in a few steps."""

    # -- watchdog ---------------------------------------------------------
    #: consecutive skipped/non-finite steps before declaring divergence
    #: (the reference amp's repeated-overflow abort).
    max_consecutive_skips: int = 8
    #: loss deviation above the rolling median, in units of
    #: ``max(|median|, spike_floor)``, that counts as an anomaly.
    loss_spike_factor: float = 10.0
    #: same for the gradient norm (norms drift more; keep this loose).
    grad_spike_factor: float = 100.0
    spike_floor: float = 1e-3
    #: consecutive anomalous (but finite) steps before declaring divergence.
    anomaly_patience: int = 2
    history_window: int = 64
    #: spike detection stays silent until this much healthy history exists.
    min_history: int = 8
    #: device→host metric sync cadence; larger = cheaper, slower detection.
    poll_interval_steps: int = 8
    # -- rollback ---------------------------------------------------------
    max_rollbacks: int = 3
    #: divide the restored loss scale by this on every rollback (floored
    #: at 1.0) — re-diverging at the same scale is the common failure.
    rollback_scale_decay: float = 2.0
    #: pass an incremented retry-epoch to ``batch_fn(step, epoch)`` so the
    #: data pipeline can re-seed past the poisoned window.
    reseed_data_on_rollback: bool = True
    # -- checkpointing ----------------------------------------------------
    save_interval_steps: int = 50
    max_to_keep: int = 5
    save_final: bool = True
    resume: bool = True
    save_retries: int = 3
    save_backoff_base: float = 0.5
    save_backoff_max: float = 8.0
    delete_corrupt: bool = True
    #: on-disk format when the driver builds the manager from
    #: ``checkpoint_dir``: ``"sharded"`` (elastic mesh-reshape restore,
    #: per-shard checksums, async-capable) or ``"orbax"`` (the original
    #: whole-array format).
    checkpoint_format: str = "sharded"
    #: with the sharded format, run serialization + fsync + checksum on a
    #: background writer — the step loop blocks only for the device→host
    #: snapshot. ``False`` forces fully synchronous saves.
    checkpoint_async: bool = True
    #: emergency (preemption) saves first quiesce the async writer:
    #: ``True`` drains pending writes to commit, ``False`` abandons
    #: queued ones (the running write still commits atomically).
    preemption_drain: bool = True
    # -- retrace watchdog -------------------------------------------------
    #: recompilations of ``step_fn`` allowed beyond the warmup trace
    #: before :class:`~apex_tpu.analysis.retrace.RetraceBudgetExceeded`
    #: aborts the run (a recompilation storm means a 10× slowdown that
    #: would otherwise pass silently).  ``None`` disables the watchdog.
    retrace_budget: Optional[int] = 8
    # -- observability ----------------------------------------------------
    #: a :class:`apex_tpu.observability.MetricsRegistry`; when attached,
    #: the driver mirrors every ``TrainingResult.telemetry`` counter into
    #: it, emits incident events alongside ``log_event``, and feeds a
    #: :class:`~apex_tpu.observability.StepMetrics` layer (step time,
    #: tokens/s, MFU, memory gauges). ``python -m apex_tpu.monitor`` then
    #: reports the run from a JSONL sink's log.
    metrics: Optional[Any] = None
    #: global tokens per step — enables the ``tokens_per_s`` metric.
    tokens_per_step: Optional[int] = None
    #: model FLOPs per step (see :mod:`apex_tpu.utils.flops`) — enables
    #: ``model_tflops`` and, with a known/overridden peak, ``mfu``.
    model_flops_per_step: Optional[float] = None
    #: per-chip peak FLOP/s override; default reads the chip table (None
    #: on CPU — MFU then stays unset; an unlisted TPU kind raises).
    peak_flops: Optional[float] = None
    #: device ``memory_stats()`` gauge cadence in steps (0 disables).
    memory_stats_interval_steps: int = 50
    #: a :class:`apex_tpu.observability.ProfilerCapture`; the driver
    #: advances its schedule each step and triggers a capture on watchdog
    #: verdicts.
    profiler: Optional[Any] = None
    # -- preemption -------------------------------------------------------
    handle_sigterm: bool = True
    record_history: bool = True


@dataclass
class WatchdogVerdict:
    reason: str          # "consecutive_skips" | "loss_spike" | "grad_spike"
    step: int            # step at which the verdict fired
    first_bad_step: int  # first step of the bad window (rollback bound)
    detail: str = ""


class Watchdog:
    """Host-side divergence detector over polled per-step metrics.

    ``observe(step, loss, grad_norm, skipped)`` returns a
    :class:`WatchdogVerdict` when training is deemed diverged, else None.
    Skipped or non-finite steps never enter the rolling history, so the
    spike baselines only reflect healthy steps; a healthy step resets the
    consecutive-skip and anomaly counters (the scaler's own hysteresis
    handles isolated overflows — the watchdog only fires on runs of them).
    """

    def __init__(self, config: Optional[ResilienceConfig] = None):
        self.config = config or ResilienceConfig()
        self._loss_hist: deque = deque(maxlen=self.config.history_window)
        self._gnorm_hist: deque = deque(maxlen=self.config.history_window)
        self.reset()

    def reset(self) -> None:
        self._loss_hist.clear()
        self._gnorm_hist.clear()
        self._skips = 0
        self._anomalies = 0
        self._first_bad: Optional[int] = None

    def _bad(self, step: int) -> int:
        if self._first_bad is None:
            self._first_bad = step
        return self._first_bad

    def observe(self, step: int, loss: float,
                grad_norm: Optional[float] = None,
                skipped: bool = False) -> Optional[WatchdogVerdict]:
        cfg = self.config
        nonfinite = not math.isfinite(loss) or (
            grad_norm is not None and not math.isfinite(grad_norm))
        if skipped or nonfinite:
            self._skips += 1
            first = self._bad(step)
            if self._skips >= cfg.max_consecutive_skips:
                return WatchdogVerdict(
                    "consecutive_skips", step, first,
                    detail=f"{self._skips} consecutive skipped/non-finite "
                           f"steps")
            return None

        spike = None
        if len(self._loss_hist) >= cfg.min_history:
            med = statistics.median(self._loss_hist)
            if loss - med > cfg.loss_spike_factor * max(abs(med),
                                                        cfg.spike_floor):
                spike = ("loss_spike",
                         f"loss {loss:.4g} vs median {med:.4g}")
        if (spike is None and grad_norm is not None
                and len(self._gnorm_hist) >= cfg.min_history):
            med = statistics.median(self._gnorm_hist)
            if grad_norm > cfg.grad_spike_factor * max(med, cfg.spike_floor):
                spike = ("grad_spike",
                         f"grad_norm {grad_norm:.4g} vs median {med:.4g}")

        if spike is not None:
            self._anomalies += 1
            first = self._bad(step)
            if self._anomalies >= cfg.anomaly_patience:
                return WatchdogVerdict(spike[0], step, first,
                                       detail=spike[1])
            return None

        self._skips = 0
        self._anomalies = 0
        self._first_bad = None
        self._loss_hist.append(loss)
        if grad_norm is not None:
            self._gnorm_hist.append(grad_norm)
        return None


@dataclass
class TrainingResult:
    state: Any
    status: str               # "completed" | "preempted"
    steps_completed: int
    rollbacks: int
    telemetry: Dict[str, int]
    history: List[dict] = field(default_factory=list)


def make_train_state(params: Any, opt_state: Any,
                     scaler_state: Optional[LossScalerState] = None,
                     step: int = 0) -> dict:
    """The train-state pytree :func:`run_training` drives: one dict holding
    everything a resume needs (the whole thing round-trips through one
    checkpoint call pair — scaler state and fp32 masters are ordinary
    leaves, per ``apex_tpu.checkpoint``'s design)."""
    state = {
        "params": params,
        "opt_state": opt_state,
        "step": jnp.asarray(step, jnp.int32),
    }
    if scaler_state is not None:
        state["scaler"] = scaler_state
    return state


def make_resilient_train_step(
    loss_fn: Callable,
    optimizer,
    scaler: Optional[LossScaler] = None,
    *,
    mesh=None,
    param_spec=None,
    batch_spec=None,
    opt_state_spec=None,
    params_template=None,
    data_axes: Sequence[str] = (DATA_AXIS,),
    donate: bool = True,
) -> Callable:
    """Build ``step(state, batch, rng) -> (state, metrics)`` — the
    amp-aware sibling of :func:`apex_tpu.training.make_train_step` with the
    driver's contract: ``state`` is a :func:`make_train_state` dict and
    ``metrics`` carries on-device ``loss`` / ``grad_norm`` / ``skipped``
    (and ``loss_scale`` when a scaler is wired) for the watchdog to poll.

    With ``scaler`` the loss is scaled before autodiff, grads are unscaled
    with non-finites zeroed, the optimizer skips on overflow via its
    ``found_inf`` select, and the scaler state updates — the reference
    recommended-flow loop (``README.md:63-103``) as one jitted program.
    Without a scaler, ``skipped`` still reports a fused finiteness check of
    the raw grads so the watchdog sees NaN blow-ups either way.

    Mesh semantics (``mesh``/``param_spec``/``batch_spec``/``data_axes``)
    match ``make_train_step``: per-rank autodiff under shard_map, grad
    pmean over the data axes, single-device fast path on a size-1 mesh.
    """
    if mesh is not None and opt_state_spec is None:
        if params_template is None:
            raise ValueError(
                "need opt_state_spec or params_template to derive it")
        opt_state_spec = optimizer.state_spec(params_template, param_spec)

    if getattr(optimizer, "handles_grad_sync", False):
        opt_axis = getattr(optimizer, "axis_name", None)
        grad_sync_axes = tuple(a for a in data_axes if a != opt_axis)
    else:
        grad_sync_axes = tuple(data_axes)

    def per_rank(state, batch, rng):
        params, opt_state = state["params"], state["opt_state"]
        sstate = state.get("scaler")
        if rng is not None:
            # per-data-shard dropout streams, exactly as make_train_step
            for a in data_axes:
                try:
                    idx = lax.axis_index(a)
                except NameError:
                    idx = 0
                rng = jax.random.fold_in(rng, idx)

        def fwd(p):
            loss = loss_fn(p, batch, rng)
            scaled = loss if sstate is None else scaler.scale(loss, sstate)
            return scaled, loss

        grads, loss = jax.grad(fwd, has_aux=True)(params)
        if mesh is not None:
            with nvtx_range(SCOPE_DP_GRAD_ALL_REDUCE):
                grads = sync_data_parallel_grads(grads, grad_sync_axes,
                                                 param_spec)
                loss = sync_data_parallel_grads(loss, data_axes)
        with nvtx_range(SCOPE_LOSS_SCALE):
            if sstate is not None:
                grads, found_inf = scaler.unscale(grads, sstate)
            else:
                found_inf = jnp.logical_not(all_finite(grads))
        gnorm = global_norm(grads)
        with nvtx_range(SCOPE_OPTIMIZER):
            new_params, new_opt = optimizer.step(grads, params, opt_state,
                                                 found_inf=found_inf)
        new_state = {"params": new_params, "opt_state": new_opt,
                     "step": state["step"] + 1}
        metrics = {"loss": loss, "grad_norm": gnorm, "skipped": found_inf}
        if sstate is not None:
            with nvtx_range(SCOPE_LOSS_SCALE):
                new_sstate = scaler.update(sstate, found_inf)
            new_state["scaler"] = new_sstate
            metrics["loss_scale"] = new_sstate.loss_scale
        return new_state, metrics

    donate_argnums = (0,) if donate else ()
    if mesh is None or mesh.size == 1:
        return jax.jit(per_rank, donate_argnums=donate_argnums)

    state_spec = {"params": param_spec, "opt_state": opt_state_spec,
                  "step": PartitionSpec()}
    metrics_spec = {"loss": PartitionSpec(), "grad_norm": PartitionSpec(),
                    "skipped": PartitionSpec()}
    if scaler is not None:
        state_spec["scaler"] = jax.tree.map(lambda _: PartitionSpec(),
                                            scaler.init())
        metrics_spec["loss_scale"] = PartitionSpec()
    from apex_tpu.utils.sharding import shard_map

    sharded = shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(state_spec, batch_spec, PartitionSpec()),
        out_specs=(state_spec, metrics_spec),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=donate_argnums)


class _SigtermGuard:
    """Scoped SIGTERM hook: sets ``triggered`` instead of killing the
    process, restores the previous handler on exit. Installation is a
    no-op off the main thread (signal API restriction) or when handling
    is disabled — ``triggered`` then only reflects injected preemptions."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.triggered = False
        self._prev = None
        self._installed = False

    def __enter__(self):
        if (self.enabled
                and threading.current_thread() is threading.main_thread()):
            self._prev = signal.signal(signal.SIGTERM, self._on_signal)
            self._installed = True
        return self

    def _on_signal(self, signum, frame):
        self.triggered = True

    def __exit__(self, *exc):
        if self._installed:
            signal.signal(signal.SIGTERM, self._prev)
        return False


def _batch_caller(batch_fn: Callable) -> Callable[[int, int], Any]:
    """Normalize ``batch_fn`` to ``(step, retry_epoch) -> batch``.
    A single-parameter callable ignores the retry epoch (its data cannot
    be re-seeded past a poisoned window — fine when faults are transient).
    """
    try:
        sig = inspect.signature(batch_fn)
        takes_epoch = len([
            p for p in sig.parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)
        ]) >= 2 or any(p.kind == p.VAR_POSITIONAL
                       for p in sig.parameters.values())
    except (TypeError, ValueError):
        takes_epoch = False
    if takes_epoch:
        return batch_fn
    return lambda step, epoch: batch_fn(step)


def run_training(
    step_fn: Callable,
    state: dict,
    batch_fn: Callable,
    num_steps: int,
    *,
    rng: Optional[jax.Array] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_manager=None,
    config: Optional[ResilienceConfig] = None,
    fault_injector=None,
) -> TrainingResult:
    """Drive ``step_fn`` for ``num_steps`` with watchdog, rollback,
    retrying checkpoints and preemption handling.

    Args:
      step_fn: ``(state, batch, rng) -> (state, metrics)`` — what
        :func:`make_resilient_train_step` builds. ``metrics`` may carry
        ``loss`` (scalar), ``grad_norm`` and ``skipped``; missing keys
        simply disable the corresponding watchdog checks.
      state: a :func:`make_train_state` dict (must hold a scalar ``step``
        leaf — it is the resume/rollback anchor).
      batch_fn: ``(step) -> batch`` or ``(step, retry_epoch) -> batch``.
        Must be a pure function of its arguments: rollback re-reads past
        steps, and the epoch increments per rollback to re-seed the
        poisoned window.
      rng: optional base PRNG key; the per-step key is
        ``fold_in(rng, step)`` so a rolled-back or resumed run replays
        identical streams.
      checkpoint_dir / checkpoint_manager: where to save. Pass a directory
        (a :class:`RetryingCheckpointManager` is built from the config
        knobs, wired to the fault injector's save hook) or a ready-made
        manager. With neither, the run still watches for divergence but
        cannot roll back — a verdict raises :class:`TrainingDiverged`.
      fault_injector: a :class:`apex_tpu.testing_faults.FaultInjector`.

    Returns a :class:`TrainingResult`; raises :class:`TrainingDiverged`
    when recovery is impossible within the budget.
    """
    cfg = config or ResilienceConfig()
    log = get_logger(__name__)
    if not (isinstance(state, dict) and "step" in state):
        raise ValueError("state must be a make_train_state-style dict with "
                         "a scalar 'step' leaf")

    def _wrap(base) -> RetryingCheckpointManager:
        return RetryingCheckpointManager(
            base, max_retries=cfg.save_retries,
            backoff_base=cfg.save_backoff_base,
            backoff_max=cfg.save_backoff_max,
            delete_corrupt=cfg.delete_corrupt,
            async_writes=cfg.checkpoint_async,
            drain_on_force=cfg.preemption_drain,
            metrics=cfg.metrics,
            before_save=getattr(fault_injector, "before_checkpoint_save",
                                None))

    mgr = None
    own_mgr = False
    if checkpoint_manager is not None:
        mgr = checkpoint_manager
        if isinstance(mgr, (CheckpointManager, ShardedCheckpointManager)):
            mgr = _wrap(mgr)
        elif (isinstance(mgr, RetryingCheckpointManager)
                and cfg.metrics is not None and mgr.metrics is None):
            # a pre-wrapped manager still reports into the attached
            # registry, else the monitor's ckpt_* counters cannot
            # reconcile with the merged telemetry
            mgr.metrics = cfg.metrics
    elif checkpoint_dir is not None:
        # manager-level interval gating stays at 1: the driver decides
        # when to save, and rollback/emergency saves must never be
        # swallowed
        if cfg.checkpoint_format == "sharded":
            base = ShardedCheckpointManager(
                checkpoint_dir, max_to_keep=cfg.max_to_keep,
                save_interval_steps=1)
        elif cfg.checkpoint_format == "orbax":
            base = CheckpointManager(checkpoint_dir,
                                     max_to_keep=cfg.max_to_keep,
                                     save_interval_steps=1)
        else:
            raise ValueError(
                f"unknown checkpoint_format {cfg.checkpoint_format!r} "
                f"(expected 'sharded' or 'orbax')")
        mgr = _wrap(base)
        own_mgr = True

    # a recompilation storm (ragged batch shapes, pytree-structure churn
    # after a restore) must surface as a watchdog event, not as a silent
    # 10× slowdown — wrap the step in the retrace watchdog
    if cfg.retrace_budget is not None and not isinstance(step_fn,
                                                         RetraceWatchdog):
        step_fn = RetraceWatchdog(step_fn, budget=cfg.retrace_budget,
                                  name="train_step", logger=log,
                                  metrics=cfg.metrics)
    elif (isinstance(step_fn, RetraceWatchdog) and cfg.metrics is not None
            and step_fn.metrics is None):
        # a pre-wrapped watchdog still reports into the attached registry,
        # else the monitor's retrace counter cannot reconcile
        step_fn.metrics = cfg.metrics

    watchdog = Watchdog(cfg)
    get_batch = _batch_caller(batch_fn)
    telemetry = {"steps": 0, "skips": 0, "rollbacks": 0, "preemptions": 0,
                 "emergency_saves": 0, "resumes": 0, "verdicts": 0,
                 "retraces": 0}
    reg = cfg.metrics
    prof = cfg.profiler
    step_metrics = None
    if reg is not None:
        # every telemetry key exists in the registry from step 0, so the
        # final counters snapshot reconciles key-for-key even for
        # incident types that never fired
        reg.declare_counters(*telemetry)
        ckpt_telemetry = getattr(mgr, "telemetry", None) or {}
        reg.declare_counters(*("ckpt_" + k for k in ckpt_telemetry))
        for k, v in ckpt_telemetry.items():
            if v:
                # a pre-used manager arrives with history: seed the
                # registry so the final snapshot still equals the merged
                # telemetry key-for-key
                reg.inc("ckpt_" + k, v)
        step_metrics = StepMetrics(
            reg, tokens_per_step=cfg.tokens_per_step,
            model_flops_per_step=cfg.model_flops_per_step,
            peak_flops=cfg.peak_flops,
            memory_interval_steps=cfg.memory_stats_interval_steps)

    def _tick(key: str, n: int = 1) -> None:
        """One incident, two ledgers: the TrainingResult telemetry dict
        and (when attached) the registry counter of the same name."""
        telemetry[key] += n
        if reg is not None:
            reg.inc(key, n)

    history: List[dict] = []
    pending: List[Tuple[int, Any]] = []

    host_step = int(jax.device_get(state["step"]))
    rollbacks = 0
    data_epoch = 0

    if mgr is not None and cfg.resume:
        restored = mgr.restore_latest(state)
        if restored is not None:
            ckpt_step, state = restored
            host_step = int(jax.device_get(state["step"]))
            _tick("resumes")
            log_event(log, "training_resumed", step=host_step,
                      checkpoint=ckpt_step, level="info")
            if reg is not None:
                reg.event("training_resumed", step=host_step,
                          checkpoint=ckpt_step)

    def _flush() -> Optional[WatchdogVerdict]:
        """Sync pending device metrics to host and feed the watchdog —
        the ONLY place the driver blocks on the device, so the step loop
        runs ``poll_interval_steps`` ahead of the anomaly checks."""
        nonlocal pending
        if not pending:
            return None
        values = jax.device_get([m for _, m in pending])
        verdict = None
        for (step_i, _), vals in zip(pending, values):
            loss = float(vals["loss"]) if "loss" in vals else float("nan")
            gnorm = vals.get("grad_norm")
            gnorm = None if gnorm is None else float(gnorm)
            skipped = bool(vals.get("skipped", False))
            _tick("skips", int(skipped))
            if cfg.record_history:
                history.append({"step": step_i, "loss": loss,
                                "grad_norm": gnorm, "skipped": skipped})
            if step_metrics is not None:
                scale = vals.get("loss_scale")
                step_metrics.record_polled(
                    step_i, loss=loss, grad_norm=gnorm, skipped=skipped,
                    loss_scale=None if scale is None else float(scale))
                if skipped:
                    reg.event("skip", step=step_i)
            if verdict is None:
                verdict = watchdog.observe(step_i, loss, gnorm, skipped)
        pending = []
        return verdict

    def _rollback(verdict: WatchdogVerdict) -> None:
        nonlocal state, host_step, data_epoch, rollbacks
        _tick("verdicts")
        log_event(log, "watchdog_verdict", reason=verdict.reason,
                  step=verdict.step, first_bad_step=verdict.first_bad_step,
                  detail=verdict.detail, level="error")
        if reg is not None:
            reg.event("watchdog_verdict", reason=verdict.reason,
                      step=verdict.step,
                      first_bad_step=verdict.first_bad_step,
                      detail=verdict.detail)
        if prof is not None:
            prof.on_incident(verdict.reason, verdict.step)
        if mgr is None:
            raise TrainingDiverged(
                f"watchdog verdict '{verdict.reason}' at step "
                f"{verdict.step} and no checkpoint manager to roll back "
                f"with: {verdict.detail}", telemetry)
        rollbacks += 1
        _tick("rollbacks")
        if rollbacks > cfg.max_rollbacks:
            raise TrainingDiverged(
                f"rollback budget exhausted ({cfg.max_rollbacks}) after "
                f"verdict '{verdict.reason}' at step {verdict.step}",
                telemetry)
        restored = mgr.restore_before(verdict.first_bad_step, state)
        if restored is None:
            raise TrainingDiverged(
                f"no healthy checkpoint older than step "
                f"{verdict.first_bad_step} to roll back to", telemetry)
        ckpt_step, state = restored
        # checkpoints newer than the restore point were written inside the
        # undetected window — delete them so neither a later rollback nor
        # a crash-resume can land on suspect state
        for s in mgr.manager.all_steps():
            if s > ckpt_step:
                try:
                    mgr.manager.delete(s)
                except Exception:  # noqa: BLE001
                    pass
        if "scaler" in state:
            sc = state["scaler"]
            state = dict(state)
            state["scaler"] = sc.replace(
                loss_scale=jnp.maximum(
                    sc.loss_scale / cfg.rollback_scale_decay,
                    1.0).astype(jnp.float32),
                growth_tracker=jnp.zeros_like(sc.growth_tracker),
                unskipped=jnp.zeros_like(sc.unskipped),
            )
        host_step = int(jax.device_get(state["step"]))
        if cfg.reseed_data_on_rollback:
            data_epoch += 1
        watchdog.reset()
        log_event(log, "rollback", to_step=ckpt_step, attempt=rollbacks,
                  budget=cfg.max_rollbacks, data_epoch=data_epoch,
                  level="warning")
        if reg is not None:
            reg.event("rollback", to_step=ckpt_step, attempt=rollbacks,
                      budget=cfg.max_rollbacks, data_epoch=data_epoch)

    status = "completed"
    try:
        with _SigtermGuard(cfg.handle_sigterm) as guard:
            while True:
                while host_step < num_steps:
                    faults = (fault_injector.begin_step()
                              if fault_injector is not None else None)
                    if guard.triggered or (faults is not None
                                           and faults.preempt):
                        source = ("sigterm" if guard.triggered
                                  else "injected")
                        _flush()
                        _tick("preemptions")
                        status = "preempted"
                        if mgr is not None:
                            saved = mgr.save(host_step, state, force=True)
                            _tick("emergency_saves", int(saved))
                            log_event(log, "preemption_save",
                                      step=host_step, saved=saved,
                                      source=source, level="warning")
                            if reg is not None:
                                reg.event("preemption_save",
                                          step=host_step, saved=saved,
                                          source=source)
                        break
                    batch = get_batch(host_step, data_epoch)
                    if faults is not None and faults.nan_grads:
                        from apex_tpu.testing_faults import poison_batch
                        batch = poison_batch(batch)
                    step_rng = (None if rng is None
                                else jax.random.fold_in(rng, host_step))
                    if step_metrics is not None:
                        step_metrics.begin_step()
                    state, metrics = step_fn(state, batch, step_rng)
                    host_step += 1
                    _tick("steps")
                    if step_metrics is not None:
                        step_metrics.end_step(host_step)
                    if prof is not None:
                        prof.on_step(host_step)
                    pending.append((host_step, metrics))

                    at_save = (mgr is not None
                               and host_step % cfg.save_interval_steps == 0)
                    if len(pending) >= cfg.poll_interval_steps or at_save:
                        # vet before saving: a checkpoint is only written
                        # once every step it contains passed the watchdog
                        verdict = _flush()
                        if verdict is not None:
                            _rollback(verdict)
                            continue
                    if at_save:
                        mgr.save(host_step, state)

                if status == "preempted":
                    break
                # the tail of the run may not land on a poll boundary —
                # flush, and if the LAST window diverged, roll back and
                # take another pass over the remaining steps
                verdict = _flush()
                if verdict is not None:
                    _rollback(verdict)
                    continue
                if mgr is not None and cfg.save_final:
                    # settle in-flight async writes before deciding
                    # whether the final step still needs a (sync) save
                    mgr.wait_until_finished()
                    if mgr.manager.latest_step() != host_step:
                        mgr.save(host_step, state, force=True)
                break
    finally:
        if isinstance(step_fn, RetraceWatchdog):
            telemetry["retraces"] = step_fn.retraces
        if prof is not None and prof.active:
            prof.stop(host_step)
        if mgr is not None:
            try:
                mgr.wait_until_finished()
            finally:
                if own_mgr:
                    mgr.close()
            # merge the (now-quiesced) checkpoint ledger into the run
            # telemetry under a ckpt_ prefix — the same names the
            # registry counters carry, so the monitor reconciles both
            for k, v in (getattr(mgr, "telemetry", None) or {}).items():
                telemetry["ckpt_" + k] = v
        if reg is not None:
            # the final snapshot is the monitor CLI's reconciliation
            # anchor — flush even on the TrainingDiverged exit paths
            reg.flush()

    return TrainingResult(state, status, host_step, rollbacks, telemetry,
                          history)
