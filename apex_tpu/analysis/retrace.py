"""Retrace watchdog: the runtime half of the hazard tooling.

The static rules (APX004) catch signatures *designed* to retrace; this
module catches the storms that only manifest at run time — a data
pipeline that emits a ragged final batch, a checkpoint restore that
changes a pytree's structure, a shape-dependent branch.  A recompilation
storm is the nastiest kind of perf bug: nothing is wrong, the step just
takes 10× longer, and on a preemptible TPU slice the job dies of slowness
before anyone looks at a profile (the PR 1 tier-1 gate truncation was
this, in miniature).

:class:`RetraceWatchdog` wraps a step function.  Per call it measures
whether a compilation happened — via the jit wrapper's ``_cache_size()``
when available, falling back to tracking distinct abstract signatures
``(shape, dtype, pytree structure)`` of the arguments — and

- emits structured ``log_event`` telemetry (``event=retrace``) with the
  call count and signature, ordered by ``seq``/``ts`` stamps;
- mirrors each counted retrace into an attached
  :class:`apex_tpu.observability.MetricsRegistry` (``metrics=`` — a
  ``retraces`` counter plus ``retrace`` events), so the monitor CLI
  reports recompilation storms without scraping log lines;
- raises :class:`RetraceBudgetExceeded` once retraces (compilations
  beyond ``expected_compiles``) exceed ``budget``.

``resilience.run_training`` wraps its ``step_fn`` automatically (config
``retrace_budget``), so a storm surfaces as a watchdog event instead of a
silent slowdown.

While compilations are still expected (``compiles <
expected_compiles``) a call may trace, and it is made from a frame with
room on the interpreter's stack (:func:`_call_with_stack_room`): tracing
a step program is a second of deep, hot Python recursion, and where that
recursion happens to straddle the end of a 16 KiB block of CPython's
frame stack every crossing costs an ``mmap``, a page fault and a
``munmap``. Once the expected programs are compiled the call is direct.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

from apex_tpu.utils.logging import get_logger, log_event

__all__ = ["RetraceBudgetExceeded", "RetraceWatchdog"]


class RetraceBudgetExceeded(RuntimeError):
    """Raised when a wrapped callable recompiles more than its budget."""

    def __init__(self, message: str, *, name: str, retraces: int,
                 budget: int):
        super().__init__(message)
        self.name = name
        self.retraces = retraces
        self.budget = budget


def _call_with_stack_room(fn, args, kwargs):
    """``fn(*args, **kwargs)`` from a frame that leaves the frames below
    it 256 KiB of contiguous interpreter stack.

    CPython (3.11+) keeps Python frames in 16 KiB blocks, maps a new
    block when a call does not fit into the current one and unmaps it as
    soon as the call returns. A loop whose callees fall just beyond the
    end of a block therefore pays two system calls and a page fault per
    iteration (7.6 us against 42 ns for an empty call, measured on
    Python 3.12), and jax's tracing is such a loop at every depth: where
    the block ends is decided by the sizes of all the frames above the
    jitted call, so an unrelated edit or another launcher moves it.
    Lowering the serving engine's six prefill programs for a v5e took
    162,000-216,000 page faults without this frame and 27,000-31,000
    with it (PERF.md, PR 26). A frame larger than a block gets a block
    of its own, twice its size, and everything it calls lives in the
    rest of that block.
    """
    return fn(*args, **kwargs)


# 32,768 slots x 8 bytes: the frame gets a 512 KiB block, half of it free
_call_with_stack_room.__code__ = _call_with_stack_room.__code__.replace(
    co_stacksize=32768)


def _abstract_signature(args: Tuple[Any, ...], kwargs: dict) -> Tuple:
    """Hashable jit-cache key proxy: pytree structure + per-leaf
    (shape, dtype) for array leaves, the value itself for hashable
    non-array leaves (weak-typed scalars collapse to their type, which
    matches jit's weak-type bucketing closely enough for storm
    detection)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append(("arr", tuple(shape), str(dtype)))
        else:
            try:
                hash(leaf)
                sig.append(("val", type(leaf).__name__, leaf))
            except TypeError:
                sig.append(("obj", type(leaf).__name__))
    return (str(treedef), tuple(sig))


class RetraceWatchdog:
    """Wrap a (typically jitted) callable and count its recompilations.

    Args:
      fn: the callable. A ``jax.jit`` wrapper is detected via its
        ``_cache_size()`` method (jax 0.4.x+) and counted exactly; any
        other callable falls back to abstract-signature tracking.
      budget: retraces allowed beyond ``expected_compiles`` before
        :class:`RetraceBudgetExceeded` is raised.  ``None`` = never raise,
        log only.
      expected_compiles: compilations that are legitimate (default 1 —
        the warmup trace).  Donated-buffer aware restarts that *should*
        recompile can raise this.
      name: label for telemetry (defaults to the callable's ``__name__``).
      on_retrace: optional ``(watchdog, signature) -> None`` hook, called
        after telemetry on every counted retrace.
      metrics: optional :class:`apex_tpu.observability.MetricsRegistry` —
        each counted retrace then also increments its ``retraces``
        counter and emits an ``event="retrace"`` record, so the monitor
        CLI reports retraces without scraping log lines.
    """

    def __init__(self, fn: Callable, *, budget: Optional[int] = None,
                 expected_compiles: int = 1, name: Optional[str] = None,
                 logger=None, on_retrace: Optional[Callable] = None,
                 metrics=None):
        self._fn = fn
        self.budget = budget
        self.expected_compiles = expected_compiles
        self.name = name or getattr(fn, "__name__", type(fn).__name__)
        self._log = logger or get_logger(__name__)
        self._on_retrace = on_retrace
        self.metrics = metrics
        self.calls = 0
        self.compiles = 0
        self._signatures: set = set()
        self._cache_probe = getattr(fn, "_cache_size", None)
        # a pre-warmed jit cache is not this watchdog's doing: baseline it
        self._last_cache_size = (self._cache_probe()
                                 if callable(self._cache_probe) else None)

    @property
    def retraces(self) -> int:
        """Compilations beyond the expected warmup count."""
        return max(0, self.compiles - self.expected_compiles)

    def __call__(self, *args, **kwargs):
        if self.compiles < self.expected_compiles:
            out = _call_with_stack_room(self._fn, args, kwargs)
        else:
            out = self._fn(*args, **kwargs)
        self.calls += 1
        self._observe(args, kwargs)
        return out

    # -- counting ---------------------------------------------------------

    def _observe(self, args, kwargs) -> None:
        new_compiles = 0
        sig = None
        if self._last_cache_size is not None and callable(self._cache_probe):
            size = self._cache_probe()
            if size > self._last_cache_size:
                new_compiles = size - self._last_cache_size
            self._last_cache_size = size
        else:
            sig = _abstract_signature(args, kwargs)
            if sig not in self._signatures:
                self._signatures.add(sig)
                new_compiles = 1
        if not new_compiles:
            return
        retraces_before = self.retraces
        self.compiles += new_compiles
        if self.compiles <= self.expected_compiles:
            return
        if sig is None:
            sig = _abstract_signature(args, kwargs)
        log_event(self._log, "retrace", fn=self.name, call=self.calls,
                  compiles=self.compiles, retraces=self.retraces,
                  budget=("none" if self.budget is None else self.budget),
                  signature=hex(abs(hash(sig)))[:10])
        if self.metrics is not None:
            # counter delta, not a bare +1: one batched _cache_size jump
            # can cover several compiles
            self.metrics.inc("retraces", self.retraces - retraces_before)
            self.metrics.event("retrace", fn=self.name, call=self.calls,
                               compiles=self.compiles,
                               retraces=self.retraces)
        if self._on_retrace is not None:
            self._on_retrace(self, sig)
        if self.budget is not None and self.retraces > self.budget:
            line = log_event(
                self._log, "retrace_budget_exceeded", fn=self.name,
                retraces=self.retraces, budget=self.budget,
                calls=self.calls, level="error")
            raise RetraceBudgetExceeded(
                f"'{self.name}' recompiled {self.retraces} times past the "
                f"expected {self.expected_compiles} (budget "
                f"{self.budget}) — recompilation storm; check for "
                f"varying shapes/dtypes or pytree-structure churn in its "
                f"arguments [{line}]",
                name=self.name, retraces=self.retraces, budget=self.budget)
