"""Shared set-up for the benchmark's CPU tests.

The benchmark's modules live in ``bench/`` at the repo root (not a
package); tests import them from there.  ``tiny_root`` builds a throwaway
checkout-like directory with a copy of ``bench/`` and a ``BENCHMARK.json``
holding small cells, so a run fits a test: the paper's model widths and
traffic on a cohort cut to 1-5% of the paper's stays, held to the
committed limits of the chip cell each tiny cell stands in for.  The
``.src`` stand-in keeps all 189 hospitals so that recruitment and the
per-round draw work on the paper's federation size, and its clients fill
whole minibatches.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

# tiny cell -> (config, traffic, chip cell whose limits it holds, data, local epochs)
TINY_CELLS = {
    "tiny-xla.ac": ("gru-eicu-xla", "federated-ac", "gru-xla.ac", {"scale": 0.01, "num_hospitals": 12}, 2),
    "tiny-xla.src": ("gru-eicu-xla", "federated-src", "gru-xla.src", {"scale": 0.05}, 4),
}


def read_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def write_json(path: str, obj: dict) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(obj, fh, indent=1)


def tiny_root(tmp_path) -> str:
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"), ignore=shutil.ignore_patterns("__pycache__"))
    manifest = read_json(os.path.join(REPO, "BENCHMARK.json"))
    workloads, configs = [], []
    for cell, (config, traffic, chip_cell, data, epochs) in TINY_CELLS.items():
        cfg = read_json(os.path.join(BENCH, "configs", f"{config}.json"))
        cfg["data"].update(data)
        name = f"cfg-{cell}"
        cfg["name"] = name
        write_json(os.path.join(root, "bench", "configs", f"{name}.json"), cfg)
        tr = read_json(os.path.join(BENCH, "traffic", f"{traffic}.json"))
        tr["local_epochs"] = epochs
        write_json(os.path.join(root, "bench", "traffic", f"traffic-{cell}.json"), tr)
        shutil.copy(
            os.path.join(BENCH, "limits", f"{chip_cell}.json"),
            os.path.join(root, "bench", "limits", f"{cell}.json"),
        )
        configs.append(
            {"name": name, "source": "test", "file": f"bench/configs/{name}.json", "reduced": [], "why": "test"}
        )
        workloads.append({"name": cell, "config": name, "traffic": f"traffic-{cell}", "chips": 1, "why": "test"})
    manifest["configs"] = configs
    manifest["workloads"] = workloads
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        metric.pop("workloads", None)
    write_json(os.path.join(root, "BENCHMARK.json"), manifest)
    return root
