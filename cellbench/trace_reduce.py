"""From a profiler trace (``.xplane.pb``) to numbers.

The trace holds device planes (``/device:TPU:<n>``) whose ``XLA Ops`` line
is each core's timeline of HLO instructions (the event's name is the
instruction's full text) and whose ``XLA Modules`` line is the timeline of
jitted programs, and a host plane whose threads carry the runtime's spans
and the harness's own ``cb.*`` annotations, all on one clock. Everything
below works on plain tuples ``(name, start_s, dur_s)`` so that it can be
checked on a small recorded trace.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
# XLA names an instruction after its opcode or after the jax primitive that
# made it: on the v5e the tensor-parallel all-reduces appear as ``%psum``
COLLECTIVE = re.compile(
    r"^%(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|psum|pmean|all_gather|psum_scatter|ppermute)")
SPAN_PREFIX = "cb."


@dataclass
class Device:
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)


@dataclass
class Trace:
    devices: dict                  # chip number -> Device
    spans: list                    # the harness's cb.* annotations
    host: list                     # other events of the thread that made them


def newest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, host = {}, [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = _events(line)
                elif line.name == "XLA Modules":
                    dev.modules = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = _events(line)
                mine = [e for e in evs if e[0].startswith(SPAN_PREFIX)]
                if mine:
                    spans += mine
                    host += [e for e in evs
                             if not e[0].startswith(SPAN_PREFIX)]
    spans.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return Trace(devices=devices, spans=spans, host=host)


def _events(line) -> list:
    return sorted(((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                   for e in line.events), key=lambda e: e[1])


# -- intervals ---------------------------------------------------------------

def union(intervals, t0: float, t1: float) -> list:
    """Merged ``(start, end)`` pieces of ``intervals`` clipped to [t0, t1]."""
    out = []
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(events, t0: float, t1: float) -> float:
    return sum(e - s for s, e in union(
        ((s, s + d) for _, s, d in events), t0, t1))


def gaps(events, t0: float, t1: float) -> list:
    """The idle pieces of [t0, t1]: where no event of ``events`` runs."""
    out, at = [], t0
    for s, e in union(((s, s + d) for _, s, d in events), t0, t1):
        if s > at:
            out.append((at, s))
        at = e
    if t1 > at:
        out.append((at, t1))
    return out


def window_of(spans) -> tuple:
    """The traced window: first start to last end of the harness's spans."""
    if not spans:
        raise ValueError("the trace holds no cb.* span")
    return min(s for _, s, _ in spans), max(s + d for _, s, d in spans)


# -- host attribution ---------------------------------------------------------

def innermost_segments(events) -> list:
    """Flatten nested host events into ``(start, end, name)`` pieces, each
    named by the innermost event running at that time."""
    out, stack = [], []            # stack of (end, name)
    at = None

    def emit(upto):
        nonlocal at
        if stack and upto > at:
            out.append((at, upto, stack[-1][1]))
        at = upto

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        if stack:
            emit(s)
        at = s
        stack.append((s + d, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def attribute_gaps(idle, spans, host, top: int = 10) -> list:
    """Idle seconds by what the host was doing: ``<span>>`<innermost
    runtime event>``, ``<span>`` alone where the runtime is silent, and
    ``(outside spans)``. Longest first, at most ``top``."""
    # a span encloses the runtime events made inside it, so the innermost
    # piece is the runtime event where there is one and the span elsewhere
    pieces = innermost_segments(list(spans) + list(host))
    starts = [p[0] for p in pieces]
    span_pieces = innermost_segments(spans)
    span_starts = [p[0] for p in span_pieces]

    def enclosing(t):
        i = bisect.bisect_right(span_starts, t) - 1
        if i >= 0 and span_pieces[i][1] > t:
            return span_pieces[i][2]
        return None

    total = {}
    for g0, g1 in idle:
        at = g0
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while at < g1 and i < len(pieces):
            s, e, name = pieces[i]
            if s >= g1:
                break
            if e > at:
                if s > at:
                    total["(outside spans)"] = (
                        total.get("(outside spans)", 0.0) + s - at)
                    at = s
                upto = min(e, g1)
                outer = enclosing(at)
                label = (name if outer in (None, name)
                         else f"{outer}>{name}")
                total[label] = total.get(label, 0.0) + upto - at
                at = upto
            i += 1
        if at < g1:
            total["(outside spans)"] = (
                total.get("(outside spans)", 0.0) + g1 - at)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[clean(k), v] for k, v in ranked]


# -- device ops ---------------------------------------------------------------

_INSTR = re.compile(r"^%([A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*?)(?:\.\d+)* = "
                    r"\(?([a-z0-9]+\[[0-9,]*\])")


def short_name(op_text: str) -> str:
    """``%fusion.12 = bf16[96,1024]{...} fusion(...)`` -> ``fusion
    bf16[96,1024]``: the instruction's kind with its first output shape,
    so that the same op in every layer adds up under one name."""
    m = _INSTR.match(op_text)
    if not m:
        return op_text.split(" = ")[0].lstrip("%")[:48]
    return f"{m.group(1)} {m.group(2)}"


def clean(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.>\-\[\],()]+", "_", name)[:96]


def top_ops(ops, t0: float, t1: float, top: int = 10) -> list:
    total = {}
    for name, s, d in ops:
        if s >= t0 and s + d <= t1:
            k = short_name(name)
            total[k] = total.get(k, 0.0) + d
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[clean(k), v] for k, v in ranked]


def in_modules(ops, modules, module_pattern: str) -> list:
    """The ops that run inside a program whose name matches."""
    rx = re.compile(module_pattern)
    spans = sorted((s, s + d) for n, s, d in modules if rx.search(n))
    starts = [s for s, _ in spans]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] < spans[i][1]:
            out.append(op)
    return out


def matching(ops, patterns) -> list:
    rxs = [re.compile(p) for p in patterns]
    return [op for op in ops if any(rx.search(op[0]) for rx in rxs)]


def module_runs(modules, module_pattern: str, t0: float, t1: float) -> list:
    rx = re.compile(module_pattern)
    return [(n, s, d) for n, s, d in modules
            if rx.search(n) and s >= t0 and s + d <= t1]


def collective_seconds(ops, t0: float, t1: float) -> float:
    """Seconds the core's own timeline spends in collective instructions
    (for an asynchronous pair, the wait in its ``-done``): time in which
    no compute instruction runs on that core."""
    return sum(d for n, s, d in ops
               if s >= t0 and s + d <= t1 and COLLECTIVE.match(n))
