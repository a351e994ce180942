"""Shared benchmark harness: time a jitted train step, print ONE JSON line
(same contract as the repo-root ``bench.py``). All configs from BASELINE.md
live here as scripts; absolute numbers are self-measured (the reference
publishes none — BASELINE.md)."""

from __future__ import annotations

import functools
import json
import time
from typing import Callable, Optional

# FLOP accounting lives in the library (apex_tpu.utils.flops) — the same
# peak table and estimators drive the observability layer's MFU metric,
# so benchmark MFU and in-run MFU can never drift apart. Re-exported here
# because every benchmark script imports them from the harness.
from apex_tpu.utils.flops import (  # noqa: F401
    peak_flops_per_chip,
    resnet50_train_flops,
    transformer_train_flops,
)


@functools.lru_cache(maxsize=None)
def start() -> dict:
    """First call of every benchmark ``main`` (and of ``chip_smoke.py``):
    place the compile cache, REQUIRE a TPU, and return the device stamp
    every printed row carries. A timing from another backend is not a
    slower benchmark, it is a different program (every fused op takes
    its ``jnp`` path off-TPU) — so no chip means no run, never a
    fallback."""
    import jax

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices()[0].platform == {dev.platform!r} "
            f"(device_kind {dev.device_kind!r}); benchmarks and the chip "
            f"smoke measure the accelerator and do not fall back")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def emit(row: dict) -> dict:
    """Print one result row as a JSON line, stamped with the device."""
    row = {**row, "device": start()}
    print(json.dumps(row), flush=True)
    return row


def run(metric: str, unit: str, step_fn: Callable, *state,
        work_per_step: float, steps: int = 10, windows: int = 3,
        baseline_fn=None,
        model_flops_per_step: Optional[float] = None,
        consume_state: bool = False):
    """``step_fn(*state) -> (*new_state, loss)``; prints the JSON line.

    ``baseline_fn``: optional same-signature unoptimized step; when given,
    ``vs_baseline`` reports measured speedup, else 1.0.
    ``model_flops_per_step``: when given, the line carries ``mfu`` (model-
    FLOPs utilization vs the chip's bf16 peak).
    ``consume_state``: skip the defensive state copy — required when state
    is a large fraction of HBM (the copy doubles residency and OOMs);
    incompatible with ``baseline_fn``.
    """
    import jax
    import numpy as _np

    if consume_state and baseline_fn is not None:
        raise ValueError("consume_state does not compose with baseline_fn "
                         "(the baseline needs the same initial state)")

    def _fetch(x):
        # device->host fetch of the last output: the timed window ends
        # when the value is on the host, not when the call was enqueued
        return _np.asarray(x)

    def _time(fn, state):
        # fresh copies per timing run: a donating step consumes its input
        # buffers, and the baseline run must reuse the same initial state
        if not consume_state:
            state = [jax.tree.map(
                lambda a: a.copy() if hasattr(a, "copy") else a,
                s) for s in state]
        else:
            state = list(state)
        out = fn(*state)
        _fetch(out[-1])
        state = list(out[:-1])
        # best-of-N windows (ROADMAP S0 replaces this with median and
        # quartiles over repeated windows)
        best = float("inf")
        for _w in range(windows):
            t0 = time.perf_counter()
            for _ in range(steps):
                out = fn(*state)
                state = list(out[:-1])
            _fetch(out[-1])
            best = min(best, (time.perf_counter() - t0) / steps)
        return best

    dt = _time(step_fn, state)
    value = work_per_step / dt
    vs = 1.0
    if baseline_fn is not None:
        vs = _time(baseline_fn, state) * value / work_per_step
    line = {"metric": metric, "value": round(value, 1),
            "unit": unit, "vs_baseline": round(vs, 3)}
    if model_flops_per_step is not None:
        line["mfu"] = round(
            model_flops_per_step / dt / peak_flops_per_chip(), 4)
        line["model_tflops"] = round(model_flops_per_step / dt / 1e12, 1)
    return emit(line)
