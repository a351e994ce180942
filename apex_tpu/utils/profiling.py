"""Tracing / profiling helpers.

Counterpart of the reference's NVTX plumbing (SURVEY.md §5: DDP's ``prof``
flag wraps hooks/comm in ``torch.cuda.nvtx`` ranges,
``apex/parallel/distributed.py:361-364``; the imagenet example calls
``cudaProfilerStart`` at a chosen iteration). TPU-native equivalents:

- :func:`nvtx_range` — ``jax.named_scope`` context manager: the one
  primitive for names INSIDE jitted code. The name becomes a path element
  of every enclosed instruction's ``op_name`` in the compiled program,
  which is how a profiler trace attributes device time to it (an NVTX
  range in Nsight). Host-side spans are
  :func:`apex_tpu.observability.tracing.span`;
- :func:`profiler_start` / :func:`profiler_stop` — ``jax.profiler`` trace
  capture to a TensorBoard-readable directory;
- :func:`device_memory_stats` — per-device live-bytes summary (role of
  ``report_memory``, ``pipeline_parallel/utils.py:253-263``, which also
  re-exports this).
"""

from __future__ import annotations

from typing import Any, Dict

import jax

__all__ = ["nvtx_range", "profiler_start", "profiler_stop",
           "device_memory_stats"]


def nvtx_range(name: str):
    """``with nvtx_range("fwd"):`` — names the enclosed computation in
    the compiled program and so in the profiler timeline
    (``jax.named_scope``)."""
    return jax.named_scope(name)


def profiler_start(log_dir: str) -> None:
    """Begin a profiler trace (role of ``cudaProfilerStart`` at iteration N,
    reference ``examples/imagenet/main_amp.py:335-339``)."""
    jax.profiler.start_trace(log_dir)


def profiler_stop() -> None:
    jax.profiler.stop_trace()


def device_memory_stats(device=None) -> Dict[str, Any]:
    """Live/peak byte counts for one device (empty dict when the backend
    doesn't expose stats, e.g. CPU)."""
    dev = device or jax.devices()[0]
    stats = getattr(dev, "memory_stats", lambda: None)()
    return dict(stats) if stats else {}
