"""Find the benchmark's pieces by name.

Everything that belongs to one configuration, one traffic mix, one cell's
output limits, one per-layer metric or one kernel's cost sits in a file of
its own, found from the name in ``BENCHMARK.json``:

    configs/<config>.json      model, cohort, optimizer and implementation keys
    traffic/<traffic>.json     setting, recruitment, selection, epochs, batch
    limits/<cell>.json         the output comparison's limit for each number
    metrics/<metric>.py        ``read(run) -> float | None`` for a per-layer metric
    kernels/<kernel>.py        FLOP and byte counts of one kernel or model step
    references/<name>.py       the plain reference a configuration names
    peaks.json                 chip peaks keyed by ``device_kind``

A later change adds a cell, a mix or a metric by adding files and entries;
no code here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from types import ModuleType

def _read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _module(path: str, name: str) -> ModuleType:
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file {path} for {name!r}")
    spec = importlib.util.spec_from_file_location(f"bench_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple  # the BENCHMARK.json entries this cell reports
    per_layer: tuple


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files under ``<root>/bench``."""

    def __init__(self, root: str, bench_dir: str | None = None):
        self.root = os.path.abspath(root)
        self.dir = os.path.abspath(bench_dir or os.path.join(self.root, "bench"))
        self.manifest = _read_json(os.path.join(self.root, "BENCHMARK.json"))
        self._modules: dict[str, ModuleType] = {}

    def _file(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
        w = cells[name]
        configs = {c["name"]: c for c in self.manifest["configs"]}
        entry = configs[w["config"]]
        config_path = os.path.join(self.root, entry["file"])

        def applies(metric: dict) -> bool:
            return "workloads" not in metric or name in metric["workloads"]

        return Cell(
            name=name,
            config_name=w["config"],
            traffic_name=w["traffic"],
            chips=int(w["chips"]),
            config=_read_json(config_path),
            traffic=_read_json(self._file("traffic", f"{w['traffic']}.json")),
            limits=_read_json(self._file("limits", f"{name}.json")),
            end_to_end=tuple(m for m in self.manifest["end_to_end"] if applies(m)),
            per_layer=tuple(m for m in self.manifest["per_layer"] if applies(m)),
        )

    def peaks(self, device_kind: str) -> dict:
        table = _read_json(self._file("peaks.json"))["devices"]
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json ({sorted(table)})")
        return table[device_kind]

    def _load(self, kind: str, name: str) -> ModuleType:
        key = f"{kind}/{name}"
        if key not in self._modules:
            self._modules[key] = _module(self._file(kind, f"{name}.py"), name)
        return self._modules[key]

    def kernel_names(self) -> list[str]:
        return sorted(f[:-3] for f in os.listdir(self._file("kernels")) if f.endswith(".py"))

    def metric(self, name: str) -> ModuleType:
        return self._load("metrics", name)

    def kernel(self, name: str) -> ModuleType:
        return self._load("kernels", name)

    def reference(self, name: str) -> ModuleType:
        return self._load("references", name)
