"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name the manifest
gives it: ``configs/<config>.json`` (the manifest's ``file``),
``traffic/<traffic>.json``, ``metrics/<metric>.json`` and
``limits/<workload>.json``. Adding a cell is adding files and one
manifest entry; no file that exists is edited.
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """The manifest, or a file it names, breaks the benchmark's contract."""


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: tuple = ()          # empty: every cell
    bound: float | None = None     # end-to-end only
    layer: str | None = None       # per-layer only
    moves: str | None = None
    reader: dict = field(default_factory=dict)

    def reported_by(self, workload: str) -> bool:
        return not self.workloads or workload in self.workloads


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _name(value, what):
    if not isinstance(value, str) or not NAME.match(value):
        raise ManifestError(f"{what} {value!r} is not a name: letters, "
                            f"digits, '_', '.', '-', at most 64")
    return value


def _load(root, rel):
    path = os.path.join(root, rel)
    if not os.path.isfile(path):
        raise ManifestError(f"missing file {rel}")
    with open(path) as f:
        return json.load(f)


def _metric(entry, per_layer):
    m = Metric(name=_name(entry.get("name"), "metric"),
               unit=entry.get("unit", ""), better=entry.get("better", ""),
               source=entry.get("source", ""),
               workloads=tuple(entry.get("workloads", ())))
    if not UNIT.match(m.unit):
        raise ManifestError(f"metric {m.name}: unit {m.unit!r} is not "
                            f"1-16 letters, digits, '_/%.-'")
    if m.better not in ("lower", "higher"):
        raise ManifestError(f"metric {m.name}: better is lower or higher")
    if m.source not in SOURCES:
        raise ManifestError(f"metric {m.name}: unknown source {m.source!r}")
    if per_layer:
        m.layer, m.moves = entry.get("layer"), entry.get("moves")
    else:
        m.bound = entry.get("bound")
        if m.source not in ("host_clock", "device_trace"):
            raise ManifestError(f"end-to-end metric {m.name} takes "
                                f"host_clock or device_trace only")
    return m


def load(root: str = ".") -> dict:
    """The manifest, checked: ``{"manifest", "end_to_end", "per_layer"}``."""
    man = _load(root, "BENCHMARK.json")
    e2e = [_metric(e, False) for e in man["end_to_end"]]
    layer = [_metric(e, True) for e in man["per_layer"]]
    names = [m.name for m in e2e + layer]
    if len(set(names)) != len(names):
        raise ManifestError("two metrics share a name")
    cells = {_name(w["name"], "workload") for w in man["workloads"]}
    by_name = {m.name: m for m in e2e}
    for m in e2e + layer:
        for w in m.workloads:
            if w not in cells:
                raise ManifestError(f"metric {m.name} lists unknown cell {w}")
    for m in layer:
        target = by_name.get(m.moves)
        if target is None:
            raise ManifestError(f"metric {m.name} moves {m.moves!r}, which "
                                f"is no end-to-end metric")
        for w in (m.workloads or cells):
            if not target.reported_by(w):
                raise ManifestError(
                    f"metric {m.name} is read in cell {w}, which does not "
                    f"report {m.moves}")
    for c in man["configs"]:
        _name(c["name"], "config")
    return {"manifest": man, "end_to_end": e2e, "per_layer": layer}


def cell(workload: str, root: str = ".") -> Cell:
    """One cell with its configuration, traffic, limits and metric files."""
    loaded = load(root)
    man = loaded["manifest"]
    entry = next((w for w in man["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise ManifestError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = next((c for c in man["configs"] if c["name"] == entry["config"]),
               None)
    if cfg is None:
        raise ManifestError(f"workload {workload}: no config "
                            f"{entry['config']!r}")
    base = man["paths"][0]
    traffic = _name(entry["traffic"], "traffic")
    per_layer = [m for m in loaded["per_layer"] if m.reported_by(workload)]
    for m in per_layer:
        m.reader = _load(root, f"{base}/metrics/{m.name}.json")
    return Cell(
        name=workload, chips=int(entry["chips"]), config_name=cfg["name"],
        traffic_name=traffic, config=_load(root, cfg["file"]),
        traffic=_load(root, f"{base}/traffic/{traffic}.json"),
        limits=_load(root, f"{base}/limits/{workload}.json"),
        end_to_end=[m for m in loaded["end_to_end"]
                    if m.reported_by(workload)],
        per_layer=per_layer)
