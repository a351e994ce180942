"""Reductions that per-layer metrics are read with.

A metric's file (``metrics/<name>.json``) names one reduction and its
arguments. A reduction takes the run's context and returns a number, or
``None`` when it finds nothing to read: the harness then leaves the
metric out of the line. A share never reads 0 for "nothing found".
"""

from __future__ import annotations

import statistics

from cellbench import stats, trace_reduce as tr, work


class Context:
    """What a traced run knows: the cell, the loop's facts, the host
    spans, and the trace with its window."""

    def __init__(self, cell, result, trace, peak):
        self.cell, self.facts, self.trace = cell, result["facts"], trace
        self.recorder = result["facts"]["recorder"]
        self.peak = peak
        self.t0, self.t1 = tr.window_of(trace.spans)
        self.sz = result["facts"]["sz"]

    def fill(self, text: str) -> str:
        """``{seq}``-style placeholders of a pattern, from the cell's shapes."""
        return text.format(**self.facts["shapes"])

    def devices(self):
        return [self.trace.devices[k] for k in sorted(self.trace.devices)]


def _traced(ctx, name):
    """Host-side records of the spans ``name`` that the trace also holds."""
    return ctx.recorder.host.get(name, [])[ctx.recorder.traced_from(name):]


def host_span_ms(ctx, span, stat):
    rec = ctx.recorder.host.get(span)
    if not rec:
        return None
    durs = [d * 1e3 for _, d in rec]
    return statistics.median(durs) if stat == "p50" else sum(durs) / len(durs)


def fact_percentile(ctx, fact, p, scale=1.0):
    values = ctx.facts.get(fact)
    return stats.percentile(values, p) * scale if values else None


def fact_mean_pct(ctx, fact, over):
    values = ctx.facts.get(fact)
    return 100.0 * sum(values) / len(values) / ctx.facts[over] \
        if values else None


def module_dev_ms(ctx, module):
    per_dev = []
    for dev in ctx.devices():
        runs = tr.module_runs(dev.modules, module, ctx.t0, ctx.t1)
        if runs:
            per_dev.append(1e3 * sum(d for _, _, d in runs) / len(runs))
    return sum(per_dev) / len(per_dev) if per_dev else None


def prefill_dev_ms_per_ktok(ctx, module):
    runs = tr.module_runs(ctx.devices()[0].modules, module, ctx.t0, ctx.t1)
    tokens = sum(ctx.facts["prefill_tokens_traced"])
    if not runs or not tokens:
        return None
    return 1e3 * sum(d for _, _, d in runs) / (tokens / 1e3)


def mfu_pct(ctx, work_fact):
    flops = ctx.facts.get(work_fact)
    if not flops:
        return None
    return 100.0 * flops / ((ctx.t1 - ctx.t0) * ctx.cell.chips
                            * ctx.peak["bf16_flops_per_s"])


def roofline_pct(ctx, work_fact, patterns, module=None):
    """Least time for the algorithm's work over the device time of every
    op that implements it (mean over chips; the work is one chip's)."""
    w = ctx.facts.get(work_fact)
    if not w:
        return None
    patterns = [ctx.fill(p) for p in patterns]
    seconds = []
    for dev in ctx.devices():
        ops = [o for o in dev.ops if o[1] >= ctx.t0 and o[1] + o[2] <= ctx.t1]
        if module:
            ops = tr.in_modules(ops, dev.modules, module)
        seconds.append(sum(d for _, _, d in tr.matching(ops, patterns)))
    if not all(seconds):
        return None
    least = work.roofline_seconds(w[0], w[1], ctx.peak)
    return 100.0 * least / (sum(seconds) / len(seconds))


def collective_exposed_ms_per_step(ctx, span):
    n = len(_traced(ctx, span))
    if not n or ctx.cell.chips == 1:
        return None
    per_dev = [tr.collective_seconds(d.ops, ctx.t0, ctx.t1)
               for d in ctx.devices()]
    return 1e3 * sum(per_dev) / len(per_dev) / n


def device_idle_pct(ctx):
    busy = device_busy_s(ctx)
    return 100.0 * (1.0 - busy / (ctx.t1 - ctx.t0))


def device_busy_s(ctx):
    per_dev = [tr.busy_seconds(d.ops, ctx.t0, ctx.t1) for d in ctx.devices()]
    return sum(per_dev) / len(per_dev)


REDUCTIONS = {f.__name__: f for f in (
    host_span_ms, fact_percentile, fact_mean_pct, module_dev_ms,
    prefill_dev_ms_per_ktok, mfu_pct, roofline_pct,
    collective_exposed_ms_per_step, device_idle_pct)}


def read(ctx, metric):
    spec = metric.reader
    fn = REDUCTIONS.get(spec["reader"])
    if fn is None:
        raise KeyError(f"metric {metric.name}: no reduction "
                       f"{spec['reader']!r} in cellbench/readers.py")
    return fn(ctx, **spec.get("args", {}))
