"""Reductions that split the serving tick by what rode on it.

A traced ``cb.tick`` is an **admission tick** where a ``tick.prefill``
span that carries ``tokens`` lies inside it (a prefill or a chunk rode
the tick, and its first token was read at once), a **plain tick**
otherwise. In a closed loop the median tick is a plain one; what the mean
adds to it is the admission ticks' excess, and these reductions say what
that excess is made of. ``readers.table`` finds this file by its name:

- ``admit_excess_ms_per(span, per)``: the sum over traced admission ticks
  of (the tick's length - the median of the traced plain ticks), per
  traced ``per`` span (``cb.tick``), in ms. With the median plain tick it
  rebuilds the mean tick, as far as the plain ticks' mean is their median.
- ``admit_idle_ms_per(span, per)``: device 0's idle time inside traced
  admission ticks, per traced ``per``, in ms (the interval arithmetic of
  ``readers_program.span_idle_ms_per``).
- ``admit_dev_ms_per(module, span, per)``: device time of the runs of
  programs whose name matches ``module`` (the prefill and chunk
  programs) in the traced window, mean over chips, per traced ``per``,
  in ms.
- ``padding_pct(span)``: 100 x (1 - sum of ``tokens`` / sum of
  ``bucket``) over the traced ``span``s: the share of the prefill
  programs' rows that were bucket padding.

Where the attributes come from: ``trace_reduce.load`` keeps a host event's
name, start and duration; ``jax.profiler.ProfileData`` also hands out its
``stats``, which are the span's keyword attributes (``tick.prefill``:
``trace_id``, ``prompt_tokens``, ``bucket``, ``tokens``, ``chunk``).
``host_stats`` reads them from the same file, once per context.

All four need ``tokens`` (apex_tpu/observability/tracing.py). A program
whose ``tick.prefill`` spans lack it, or a traced part that admitted
nothing, makes each return ``None``: the harness then leaves the metric
out of the line, and a share never reads 0 for "nothing found".
"""

from __future__ import annotations

import bisect
import statistics

from cellbench import readers, readers_program as rp, trace_reduce as tr


def host_stats(path: str, names) -> list:
    """``(name, start_s, dur_s, stats)`` of the host events named in
    ``names`` on the lines that hold the harness's ``cb.*`` spans, in
    start order; ``stats`` is a dict of the event's attributes."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            events = list(line.events)
            if any(e.name.startswith(tr.SPAN_PREFIX) for e in events):
                out += [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         dict(e.stats)) for e in events if e.name in names]
    return sorted(out, key=lambda e: e[1])


def _attributed(ctx, span) -> list:
    """The traced ``span`` events that carry ``tokens``, read once per
    context: ``(start_s, dur_s, stats)``."""
    cache = getattr(ctx, "_host_stats", None)
    if cache is None:
        cache = ctx._host_stats = {}
    if span not in cache:
        trace_dir = getattr(ctx.recorder, "trace_dir", None)
        cache[span] = (host_stats(tr.newest_xplane(trace_dir), {span})
                       if trace_dir else [])
    return [(s, d, st) for _, s, d, st in cache[span]
            if "tokens" in st and s >= ctx.t0 and s + d <= ctx.t1]


def _ticks(ctx, span, per) -> tuple:
    """The traced ``per`` intervals, and for each whether it admitted."""
    ticks = [(s, s + d) for n, s, d in ctx.trace.spans
             if n == per and s >= ctx.t0 and s + d <= ctx.t1]
    starts = [s for s, _, _ in _attributed(ctx, span)]
    admits = []
    for a, b in ticks:
        i = bisect.bisect_left(starts, a)
        admits.append(i < len(starts) and starts[i] < b)
    return ticks, admits


# -- the reductions -------------------------------------------------------------

def admit_excess_ms_per(ctx, span, per):
    ticks, admits = _ticks(ctx, span, per)
    plain = [b - a for (a, b), admit in zip(ticks, admits) if not admit]
    if not any(admits) or not plain:
        return None
    base = statistics.median(plain)
    excess = sum(b - a - base for (a, b), admit in zip(ticks, admits)
                 if admit)
    return 1e3 * excess / len(ticks)


def admit_idle_ms_per(ctx, span, per):
    ticks, admits = _ticks(ctx, span, per)
    inside = [t for t, admit in zip(ticks, admits) if admit]
    if not inside:
        return None
    return 1e3 * rp._overlap(rp._idle(ctx), inside) / len(ticks)


def admit_dev_ms_per(ctx, module, span, per):
    ticks, admits = _ticks(ctx, span, per)
    if not any(admits):
        return None
    per_dev = [sum(d for _, _, d in tr.module_runs(dev.modules, module,
                                                   ctx.t0, ctx.t1))
               for dev in ctx.devices()]
    if not any(per_dev):
        return None
    return 1e3 * sum(per_dev) / len(per_dev) / len(ticks)


def padding_pct(ctx, span):
    spans = [st for _, _, st in _attributed(ctx, span)]
    rows = sum(int(st["bucket"]) for st in spans)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(int(st["tokens"]) for st in spans) / rows)


readers.register(admit_excess_ms_per, admit_idle_ms_per, admit_dev_ms_per,
                 padding_pct)
