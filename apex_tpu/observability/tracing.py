"""Span tracing + on-demand profiler capture.

The program's two ways of putting a name on work, and the names:

- :func:`span` — the one HOST-span primitive: a
  ``jax.profiler.TraceAnnotation``. While any profiler session is on
  (a ``ProfilerCapture`` window, ``jax.profiler.start_trace``, a
  benchmark's traced run) the span is written into the profiler's
  trace, on the clock of the device ops, with its keyword attributes as
  the event's stats; with no session on it costs a flag test. Given a
  ``registry`` it also observes its host wall time into the
  ``span/<name>_s`` histogram. A host span names host work only: it puts
  nothing into a compiled program.
- names INSIDE jitted code are :func:`~apex_tpu.utils.profiling.
  nvtx_range` (``jax.named_scope``): the scope becomes a path element of
  every enclosed instruction's ``op_name``, which the trace file carries
  in each program's HLO proto.
- :class:`ProfilerCapture` — windowed ``jax.profiler`` trace capture the
  resilience driver can drive: start every N steps and stop
  ``capture_steps`` later, and/or start on a watchdog incident — so when
  a run goes sideways there is a trace of the bad window without having
  profiled the whole run. Around supervisor ticks it is how an operator
  gets the ``tick.*`` spans below into a trace (docs/observability.md).

The names are constants here, imported by the program and quoted by the
benchmark's metric files (``cellbench/metrics/*.json``).
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Callable, Optional

import jax

from apex_tpu.utils.logging import get_logger, log_event
from apex_tpu.utils.profiling import profiler_start, profiler_stop

__all__ = ["span", "recording", "ProfilerCapture", "TICK_LEAF_SPANS"]

# -- host spans of the serving tick (serving/engine.py, supervisor.py) ------
# grouping spans
TICK_SUPERVISOR = "supervisor.tick"  # all of EngineSupervisor.tick
TICK_ENGINE = "engine.tick"          # all of InferenceEngine.tick
TICK_PREFILL = "tick.prefill"        # one prefill or chunk: trace_id,
#                                      prompt_tokens, bucket (chunk)
# leaf spans: disjoint, each around one step of the tick; the counts that
# ride on them as attributes follow the colon
TICK_SCHEDULE = "tick.schedule"      # expire, evict, preempt, pop the
#                                      queue, map and extend pages, draft
#                                      windows: queued, active, pages_mapped
TICK_UPLOAD = "tick.upload"          # host arrays -> device: arrays, bytes
TICK_DISPATCH = "tick.dispatch"      # the jitted call, until it returns:
#                                      program, rows; a decode step also
#                                      pages, in_flight (0 / 1: the step
#                                      before not read yet)
TICK_READBACK = "tick.readback"      # blocking np.asarray: reads, bytes;
#                                      of a decode step also lag (steps
#                                      dispatched since the one read)
TICK_COMMIT = "tick.commit"          # tokens into records, retirements,
#                                      gauges, the supervisor's harvest:
#                                      tokens, retired; of a decode step
#                                      also dropped
TICK_LEAF_SPANS = (TICK_SCHEDULE, TICK_UPLOAD, TICK_DISPATCH, TICK_READBACK,
                   TICK_COMMIT)

# -- scope names inside the step programs (jax.named_scope) -----------------
SCOPE_PAGED_DECODE = "paged_decode_attention"   # ops/decode_attention.py
SCOPE_FLASH_FWD = "flash_attention_fwd"         # ops/attention.py
SCOPE_FLASH_BWD = "flash_attention_bwd"
SCOPE_LAYER_NORM = "layer_norm"                 # ops/layer_norm.py
SCOPE_SAMPLE = "sample"                         # serving/engine.py
SCOPE_ATTENTION = "attention"                   # models/transformer.py
SCOPE_MLP = "mlp"
SCOPE_LM_HEAD_LOSS = "lm_head_loss"
SCOPE_OPTIMIZER = "optimizer"                   # resilience.py: the update
SCOPE_LOSS_SCALE = "loss_scale"                 # unscale + finite check
SCOPE_TP_ALL_REDUCE = "tp_all_reduce"           # tensor_parallel/mappings.py
SCOPE_DP_GRAD_ALL_REDUCE = "dp_grad_all_reduce"  # resilience.py
SCOPE_MOE = "moe"                               # transformer/moe.py: the
#                                                 whole routed layer
SCOPE_MOE_ROUTER = "moe_router"                 # scores, top-k, sort, layout
SCOPE_MOE_EXPERTS = "moe_experts"               # ops/grouped_matmul.py: the
#                                                 routed products (Pallas
#                                                 calls moe_experts_up/_down)
SCOPE_MOE_SHARED = "moe_shared"                 # the shared expert
SCOPE_MLA = "mla"                               # models/transformer.py: a
#                                                 whole latent-attention block
SCOPE_MLA_ABSORB = "mla_absorb"                 # qL = qC W_UK, o = oL W_UV
SCOPE_MLA_DECODE = "mla_decode_attention"       # ops/decode_attention.py:
#                                                 scope and Pallas call alike
SCOPE_MLA_PREFILL = "mla_prefill_attention"     # the flash call of a prefill
#                                                 or a chunk (Pallas name)


@contextlib.contextmanager
def _observed(annotation, name: str, registry):
    t0 = time.perf_counter()
    try:
        with annotation:
            yield annotation
    finally:
        # host-side wall duration: dispatch time, not device time
        registry.observe(f"span/{name}_s", time.perf_counter() - t0)


def span(name: str, registry=None, **attrs):
    """``with span("tick.upload", arrays=7, bytes=n) as s:`` — a host
    span in the profiler's trace (see the module docstring). ``s.
    set_metadata(k=v)`` adds attributes known only at the end of the
    span. With ``registry`` the enclosed host wall time is also observed
    into the ``span/<name>_s`` histogram."""
    annotation = jax.profiler.TraceAnnotation(name, **attrs)
    if registry is None:
        return annotation
    return _observed(annotation, name, registry)


def recording() -> bool:
    """Whether a profiler trace is being taken now. A span costs next to
    nothing when none is; an attribute that takes work to compute (a sum
    over the batch) is worth computing only when this says yes."""
    return jax.profiler.TraceAnnotation.is_enabled()


class ProfilerCapture:
    """Start/stop ``jax.profiler`` traces on a schedule or on demand.

    The driver calls :meth:`on_step` after every completed step and
    :meth:`on_incident` when the watchdog fires; each capture lands in
    its own subdirectory ``<log_dir>/step<N>_<reason>`` (TensorBoard-
    readable).

    Args:
      log_dir: root directory for capture subdirectories.
      every_n_steps: start a capture when ``step % N == 0`` (None: only
        on demand/incident).
      capture_steps: steps per capture window before auto-stop.
      capture_on_incident: start a capture from :meth:`on_incident`.
      max_captures: total capture budget for the run (trace files are
        big; an unhealthy run must not fill the disk).
      registry: optional — capture start/stop emit registry events and a
        ``profiler_captures`` counter.
      start_fn / stop_fn: injectable trace hooks (default
        ``jax.profiler`` via :mod:`apex_tpu.utils.profiling`); tests
        substitute stubs.
    """

    def __init__(self, log_dir: str, *, every_n_steps: Optional[int] = None,
                 capture_steps: int = 2, capture_on_incident: bool = True,
                 max_captures: int = 4, registry=None,
                 start_fn: Callable[[str], None] = profiler_start,
                 stop_fn: Callable[[], None] = profiler_stop,
                 logger=None):
        self.log_dir = os.fspath(log_dir)
        self.every_n_steps = every_n_steps
        self.capture_steps = int(capture_steps)
        self.capture_on_incident = capture_on_incident
        self.max_captures = int(max_captures)
        self.registry = registry
        self._start_fn = start_fn
        self._stop_fn = stop_fn
        self._log = logger or get_logger(__name__)
        self.captures = 0
        self.active = False
        self._stop_at: Optional[int] = None

    def on_step(self, step: int) -> None:
        """Advance the schedule at completed step ``step`` (1-based)."""
        if self.active:
            if self._stop_at is not None and step >= self._stop_at:
                self.stop(step)
        elif (self.every_n_steps
                and step % self.every_n_steps == 0):
            self.start(step, reason="interval")

    def on_incident(self, reason: str, step: int) -> None:
        """Watchdog hook: capture the aftermath of an incident."""
        if self.capture_on_incident and not self.active:
            self.start(step, reason=reason)

    def start(self, step: int, reason: str = "manual") -> bool:
        """Begin a capture window; returns False when already active or
        the capture budget is spent."""
        if self.active or self.captures >= self.max_captures:
            return False
        target = os.path.join(self.log_dir, f"step{step}_{reason}")
        self._start_fn(target)
        self.active = True
        self.captures += 1
        self._stop_at = step + self.capture_steps
        log_event(self._log, "profiler_capture_start", step=step,
                  reason=reason, dir=target, level="info")
        if self.registry is not None:
            self.registry.inc("profiler_captures")
            self.registry.event("profiler_capture_start", step=step,
                                reason=reason, dir=target)
        return True

    def stop(self, step: Optional[int] = None) -> None:
        if not self.active:
            return
        self._stop_fn()
        self.active = False
        self._stop_at = None
        log_event(self._log, "profiler_capture_stop",
                  step=("?" if step is None else step), level="info")
        if self.registry is not None:
            self.registry.event("profiler_capture_stop",
                                step=(-1 if step is None else int(step)))
