"""Reductions over what the PROGRAM names: its host spans inside the tick
and the scope names inside its step programs.

Importing this file adds them to ``readers.REDUCTIONS``, where a metric
file names them like any other reduction. Nothing in the harness imports
it yet: that takes one line in a file the benchmark already has
(``from cellbench import readers_program`` in ``cellbench/__init__.py``),
which only a ``benchmark`` PR may write. Until then the metrics that use
them are not in ``BENCHMARK.json``: their entries wait, in the manifest's
own form, in ``per_layer_proposed.json`` beside this file (their
``metrics/<name>.json`` files are in place), and

    python3 -m cellbench.readers_program --workload <name> --seed <n> --seconds <s> --trace 1

is ``cellbench.run`` with those entries appended to the cell's per-layer
metrics. The reductions:

- ``span_idle_ms_per(span, per)``: device 0's idle time inside the traced
  window that falls within the program's host spans named ``span``
  (``tick.schedule`` ... ``tick.commit``, apex_tpu/observability/
  tracing.py), per traced ``per`` span (``cb.tick``), in ms. The program's
  spans are ``jax.profiler.TraceAnnotation`` events on the harness's own
  thread, which ``trace_reduce.load`` already keeps in ``Trace.host``.
- ``unspanned_idle_ms_per(spans, per)``: the idle time inside ``per``
  spans that lies under none of ``spans``. With disjoint ``spans`` the
  parts and this remainder add up to the device's idle time inside
  ``per``.
- ``scope_dev_ms_per(scope, module, per)``: device time of the ops, in
  programs whose name matches ``module``, whose ``op_name`` holds the
  path element ``scope`` (a ``jax.named_scope`` in the program; ``scope``
  may be a list), per traced ``per`` span, in ms; the mean over chips.
  Overlapping events (a ``while`` and its body) count once.

Where the op_name comes from: ``jax.profiler.ProfileData`` hands out an
event's name, start and duration, and no more. The ``.xplane.pb`` file
holds more: every event metadata of a ``/device:TPU:<n>`` plane carries
the stat ``tf_op``, which is the instruction's ``op_name`` from the
program's HLO proto (the profiler has made that join already: on the
recorded chip traces under ``tests/data`` the two agree for every
instruction that has an op_name). ``op_names`` below reads that stat with
a small walker over the protobuf wire format: no new dependency.

A program that has no such span or scope (the parent of the PR that added
them, a run traced without the profiler) makes a reduction return
``None``: the harness then leaves the metric out of the line.
"""

from __future__ import annotations

import json
import os
import re
import sys

from cellbench import manifest, readers, trace_reduce as tr

PROPOSED = "per_layer_proposed.json"

_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")


# -- the .xplane.pb file, as far as ProfileData does not show it --------------

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a view of the bytes for a length-delimited field (a string, a
    nested message); fixed-width fields are passed over."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif kind == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def op_names(path: str) -> dict:
    """``{chip: {event name: [op_name, ...]}}`` from the event metadata of
    every ``/device:TPU:<n>`` plane (XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .str_value = 5, .bytes_value = 6).
    One name can belong to instructions of several programs."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = None, [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = _text(value)
            elif field == 4:
                events.append(value)
            elif field == 5:
                entry = dict(_fields(value))
                meta = dict(_fields(entry[2]))
                stat_names[entry[1]] = _text(meta.get(2, b""))
        m = tr.DEVICE_PLANE.match(name or "")
        if not m:
            continue
        wanted = {k for k, v in stat_names.items() if v == "tf_op"}
        names = out.setdefault(int(m.group(1)), {})
        for entry in events:
            event, op = None, None
            for field, value in _fields(dict(_fields(entry))[2]):
                if field == 2:
                    event = _text(value)
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in wanted:
                        op = _text(stat.get(5, stat.get(6, b"")))
            if event and op:
                names.setdefault(event, []).append(op.rstrip(":"))
    return out


def in_scope(op_name: str, scopes) -> bool:
    """Whether a path element of ``op_name`` is one of ``scopes``, under
    any transformation's wrapper: ``transpose(jvp(mlp))`` is ``mlp``."""
    for element in op_name.split("/"):
        while True:
            if element in scopes:
                return True
            m = _WRAPPED.match(element)
            if not m:
                break
            element = m.group(1)
    return False


def _op_names_of(ctx) -> dict:
    """The run's op names, read once per context."""
    cached = getattr(ctx, "_op_names", None)
    if cached is None:
        trace_dir = getattr(ctx.recorder, "trace_dir", None)
        cached = op_names(tr.newest_xplane(trace_dir)) if trace_dir else {}
        ctx._op_names = cached
    return cached


# -- intervals ------------------------------------------------------------------

def _overlap(pieces, intervals) -> float:
    """Seconds of the sorted, disjoint ``pieces`` that lie inside the
    sorted, disjoint ``intervals``."""
    total, j = 0.0, 0
    for a, b in pieces:
        while j < len(intervals) and intervals[j][1] <= a:
            j += 1
        k = j
        while k < len(intervals) and intervals[k][0] < b:
            total += min(b, intervals[k][1]) - max(a, intervals[k][0])
            k += 1
    return total


def _idle(ctx) -> list:
    return tr.gaps(ctx.devices()[0].ops, ctx.t0, ctx.t1)


def _named(events, names, t0, t1) -> list:
    return tr.union(((s, s + d) for n, s, d in events if n in names), t0, t1)


def _count(ctx, per) -> int:
    return sum(1 for n, s, d in ctx.trace.spans
               if n == per and s >= ctx.t0 and s + d <= ctx.t1)


# -- the reductions -------------------------------------------------------------

def span_idle_ms_per(ctx, span, per):
    n = _count(ctx, per)
    under = _named(ctx.trace.host, {span}, ctx.t0, ctx.t1)
    if not n or not under:
        return None
    return 1e3 * _overlap(_idle(ctx), under) / n


def unspanned_idle_ms_per(ctx, spans, per):
    n = _count(ctx, per)
    under = _named(ctx.trace.host, set(spans), ctx.t0, ctx.t1)
    if not n or not under:
        return None
    inside = _named(ctx.trace.spans, {per}, ctx.t0, ctx.t1)
    idle = _idle(ctx)
    return 1e3 * (_overlap(idle, inside) - _overlap(idle, under)) / n


def scope_dev_ms_per(ctx, scope, module, per):
    n = _count(ctx, per)
    scopes = {scope} if isinstance(scope, str) else set(scope)
    names = _op_names_of(ctx)
    per_dev = []
    for chip in sorted(ctx.trace.devices):
        dev = ctx.trace.devices[chip]
        mine = {event for event, ops in names.get(chip, {}).items()
                if any(in_scope(op, scopes) for op in ops)}
        ops = [o for o in tr.in_modules(dev.ops, dev.modules, module)
               if o[0] in mine]
        if ops:
            per_dev.append(tr.busy_seconds(ops, ctx.t0, ctx.t1))
    if not n or not per_dev:
        return None
    return 1e3 * sum(per_dev) / len(per_dev) / n


readers.REDUCTIONS.update({f.__name__: f for f in (
    span_idle_ms_per, unspanned_idle_ms_per, scope_dev_ms_per)})


# -- until the manifest lists the metrics ---------------------------------------

def cell(workload: str, root: str = ".", _cell=manifest.cell):
    """``manifest.cell`` plus the entries of ``per_layer_proposed.json``
    that list this cell. (``_cell`` is the loader as it was on import: ``main`` puts this
    function in its place.)"""
    c = _cell(workload, root)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, PROPOSED)) as f:
        proposed = [manifest._metric(e, True) for e in json.load(f)]
    for m in proposed:
        if workload in m.workloads:
            m.reader = manifest._load(here, f"metrics/{m.name}.json")
            c.per_layer.append(m)
    return c


def main(argv=None) -> int:
    from cellbench import run

    manifest.cell = cell        # what run.prepare calls
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
