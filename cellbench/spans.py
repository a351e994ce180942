"""The harness's own spans around the calls into the program.

Every span is timed on the host clock (kept in memory, reduced when the
run ends). In a ``--trace 1`` run the profiler is switched on for the last
``trace_seconds`` of the window, and the same spans are then also written
into its trace as ``cb.*`` annotations, on the clock of the device ops.
The Python tracer stays off: it slows the very host path being measured.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

import jax


class Recorder:
    def __init__(self, trace_dir, window_seconds: float,
                 trace_seconds: float):
        self.trace_dir = trace_dir
        self.trace_from = max(0.0, window_seconds - trace_seconds)
        self.tracing = False
        self.host = {}             # name -> [(start_s, dur_s)]
        self.first_traced = {}     # name -> index of its first traced span

    def poll(self, t: float) -> None:
        """Switch the profiler on once the window reaches its traced part."""
        if self.trace_dir and not self.tracing and t >= self.trace_from:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.tracing = True
            self.first_traced = {k: len(v) for k, v in self.host.items()}

    @contextmanager
    def span(self, name: str):
        t = time.perf_counter()
        if self.tracing:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.host.setdefault(name, []).append((t, time.perf_counter() - t))

    def traced_from(self, name: str) -> int:
        return self.first_traced.get(name, 0)

    def stop(self) -> None:
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps no
    such count: the CPU of the tests)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()[:chips]]
    return int(max(peaks))


def free_device() -> None:
    """Delete every array the process still holds on the device: the
    program's state, once the window is closed and the peak is read, so
    that the reference has the chip to itself (and a calibration process
    can build the next seed's program)."""
    for a in jax.live_arrays():
        a.delete()
