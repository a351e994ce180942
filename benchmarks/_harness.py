"""The device gate of every program of this repo that runs on the chip.

``start()`` is the first call of ``chip_smoke.py``: it places the compile
cache, REQUIRES a TPU and returns the device stamp the verdict carries.
The benchmark itself is ``cellbench/`` (``BENCHMARK.json``), which has a
gate of its own; ``scenarios/`` beside this file holds the load-test
scenarios of ``python -m apex_tpu.loadtest``."""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def start() -> dict:
    """Place the compile cache, REQUIRE a TPU, and return the device
    stamp ``{platform, kind, count}``. A run on another backend is not a
    slower run, it is a different program (every fused op takes its
    ``jnp`` path off-TPU) — so no chip means no run, never a
    fallback."""
    import jax

    from apex_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices()[0].platform == {dev.platform!r} "
            f"(device_kind {dev.device_kind!r}); the chip smoke measures "
            f"the accelerator and does not fall back")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
