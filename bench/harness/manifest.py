"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by name:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py``. Adding a cell or a metric therefore means
adding files and ``BENCHMARK.json`` entries, never editing the harness.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the benchmark's rules."""


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.fullmatch(name):
        raise ManifestError(f"{what} {name!r} is not a valid name")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.fullmatch(unit):
        raise ManifestError(f"{what} has invalid unit {unit!r}")
    return unit


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: tuple | None
    layer: str | None = None
    moves: str | None = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple
    per_layer: tuple
    readers: dict = field(default_factory=dict)


def _metric(entry: dict, per_layer: bool) -> Metric:
    name = check_name(entry.get("name"), "metric")
    if entry.get("better") not in ("lower", "higher"):
        raise ManifestError(f"metric {name}: better must be lower|higher")
    wl = entry.get("workloads")
    if wl is not None:
        wl = tuple(check_name(w, f"metric {name} workload") for w in wl)
    return Metric(name=name, unit=check_unit(entry.get("unit"), name),
                  better=entry["better"], source=entry.get("source"),
                  workloads=wl,
                  layer=entry.get("layer") if per_layer else None,
                  moves=entry.get("moves") if per_layer else None)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The per-layer metric reader ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{check_name(name, 'metric')}.py"
    if not path.is_file():
        raise ManifestError(f"no reader {path} for per-layer metric {name}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for attr in ("LAYER", "MOVES", "read"):
        if not hasattr(mod, attr):
            raise ManifestError(f"{path} defines no {attr}")
    return mod


class Manifest:
    """A parsed ``BENCHMARK.json``: cells, metrics, and the files each
    names."""

    def __init__(self, doc: dict, bench_dir: Path = BENCH_DIR):
        self.doc = doc
        self.bench_dir = bench_dir
        self.end_to_end = tuple(_metric(m, False)
                                for m in doc.get("end_to_end", ()))
        self.per_layer = tuple(_metric(m, True)
                               for m in doc.get("per_layer", ()))
        self.configs = {check_name(c.get("name"), "config"): c
                        for c in doc.get("configs", ())}
        self.workloads = {}
        for w in doc.get("workloads", ()):
            name = check_name(w.get("name"), "workload")
            check_name(w.get("config"), f"workload {name} config")
            check_name(w.get("traffic"), f"workload {name} traffic")
            if w["config"] not in self.configs:
                raise ManifestError(f"workload {name}: unknown config "
                                    f"{w['config']!r}")
            self.workloads[name] = w
        for c in self.configs.values():
            for key in c.get("reduced", ()):
                check_name(key, f"config {c['name']} reduced key")

    @classmethod
    def load(cls, root: Path = ROOT) -> "Manifest":
        return cls(load_json(Path(root) / "BENCHMARK.json"),
                   Path(root) / "bench")

    def cell(self, name: str) -> Cell:
        if name not in self.workloads:
            raise ManifestError(f"unknown workload {name!r}; declared: "
                                f"{sorted(self.workloads)}")
        w = self.workloads[name]
        config = load_json(self.bench_dir / "configs" / f"{w['config']}.json")
        traffic = load_json(self.bench_dir / "traffic"
                            / f"{w['traffic']}.json")
        per_layer = tuple(m for m in self.per_layer if m.applies_to(name))
        readers = {}
        for m in per_layer:
            mod = load_reader(m.name, self.bench_dir)
            if (mod.LAYER, mod.MOVES) != (m.layer, m.moves):
                raise ManifestError(
                    f"bench/metrics/{m.name}.py names layer {mod.LAYER!r} "
                    f"moving {mod.MOVES!r}; BENCHMARK.json says "
                    f"{m.layer!r} moving {m.moves!r}")
            readers[m.name] = mod
        return Cell(name=name, config_name=w["config"],
                    traffic_name=w["traffic"], chips=int(w["chips"]),
                    config=config, traffic=traffic,
                    end_to_end=tuple(m for m in self.end_to_end
                                     if m.applies_to(name)),
                    per_layer=per_layer, readers=readers)
