"""Run one cell once.

    python3 -m cellbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (with
``--trace 1`` the per-layer metrics, ``busy_s``/``window_s`` and a
``breakdown``), and last in it ``checks``: every number ``correct``
compared, beside its limit. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from cellbench import check, manifest  # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = ".cellbench_cache"        # in the checkout; fixed: the path is
TRACE_DIR = ".cellbench_trace"        # part of the cache's key
MISS = 1e12                           # a tail in which the misses lie


class Compiles:
    """Backend compilations, counted by jax's own monitoring events: the
    window must see none."""

    def __init__(self):
        import jax

        self.count, self.names = 0, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def reset(self):
        self.count, self.names = 0, []

    def report(self) -> int:
        """The count since ``reset``; what compiled is named on stderr."""
        if self.count:
            print(f"cellbench: compiled inside the window: {self.names}",
                  file=sys.stderr)
        return self.count


def place_cache(root: str) -> None:
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(os.path.abspath(root), CACHE_DIR))
    # sub-second programs are cached too: they recompiled on every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_stamp(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(
            f"cellbench: needs {chips} TPU chip(s); jax sees {len(devs)} x "
            f"{devs[0].platform!r} ({devs[0].device_kind!r}). The benchmark "
            f"measures the accelerator and does not fall back.")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def prepare(workload: str, root: str = ".", require_tpu: bool = True):
    """What every entry point does first: the cell, the compile cache, the
    look for the chip, the compile counter and the cell's loop."""
    cell = manifest.cell(workload, root)
    place_cache(root)
    device = device_stamp(cell.chips, require_tpu)
    if cell.traffic["kind"] == "train":
        from cellbench import loop_train as loop
    else:
        from cellbench import loop_serve as loop
    return cell, device, Compiles(), loop


def execute(workload: str, seed: int, seconds: float, trace: bool,
            root: str = ".", require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object."""
    from cellbench import work

    cell, device, compiles, loop = prepare(workload, root, require_tpu)
    peak = work.peaks(device["kind"]) if require_tpu else None
    trace_dir = None
    if trace:
        trace_dir = os.path.join(os.path.abspath(root), TRACE_DIR)
        shutil.rmtree(trace_dir, ignore_errors=True)

    out = loop.run(cell, seed, seconds, trace_dir, T_PROCESS, compiles)

    values = dict(out["end_to_end"], setup_s=out["setup_s"])
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": None, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    if trace:
        from cellbench import readers, trace_reduce as tr

        t = tr.load(tr.newest_xplane(trace_dir))
        ctx = readers.Context(cell, out, t, peak)
        for m in cell.per_layer:
            v = readers.read(ctx, m)
            if v is None:
                print(f"cellbench: metric {m.name} found nothing to read",
                      file=sys.stderr)
                continue
            result["metrics"][m.name] = {"value": v, "unit": m.unit}
        device["busy_s"] = readers.device_busy_s(ctx)
        device["window_s"] = ctx.t1 - ctx.t0
        dev0 = ctx.devices()[0]
        idle = tr.gaps(dev0.ops, ctx.t0, ctx.t1)
        result["breakdown"] = {
            "device_ops": tr.top_ops(dev0.ops, ctx.t0, ctx.t1),
            "idle_gaps": tr.attribute_gaps(idle, t.spans, t.host)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in cell.end_to_end:
            v = values[m.name]
            # a tail made of misses is infinite; JSON has no infinity
            result["metrics"][m.name] = {
                "value": v if math.isfinite(v) else MISS, "unit": m.unit}
    correct, checks = check.verdict(out["readings"], cell.limits["limits"])
    result["correct"] = bool(correct)
    result["checks"] = checks
    check.print_checks(checks, correct)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="cellbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    result = execute(a.workload, a.seed, a.seconds, bool(a.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
