"""The benchmark finds every piece by name, and a new piece without code edits."""

from __future__ import annotations

import json
import os
import shutil

import pytest

from bench_testutil import BENCH, REPO, read_json, write_json

from files import Bench

MANIFEST = read_json(os.path.join(REPO, "BENCHMARK.json"))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = [m["name"] for m in MANIFEST["per_layer"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves(cell):
    c = Bench(REPO).cell(cell)
    assert c.config["name"] == c.config_name
    assert c.traffic["setting"]
    for metric in c.per_layer:
        assert metric["moves"] in {m["name"] for m in c.end_to_end}, (cell, metric["name"])
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert set(c.limits) >= {"update_gap", "change_gap", "participants_mismatch"}


@pytest.mark.parametrize("metric", METRICS)
def test_every_metric_reader_loads(metric):
    assert callable(Bench(REPO).metric(metric).read)


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_every_config_names_its_reference_and_step(config):
    bench = Bench(REPO)
    cfg = read_json(os.path.join(REPO, "bench", "configs", f"{config}.json"))
    assert callable(bench.reference(cfg["reference"]).train)
    assert bench.kernel(cfg["step_flops"]).flops_per_example(cfg) > 0


def test_peaks_table_refuses_unknown_device():
    bench = Bench(REPO)
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        bench.peaks("cpu")


def test_new_files_are_found_without_code_edits(tmp_path):
    """A cell, configuration, traffic mix and metric added as files only."""
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "bench"), ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads(json.dumps(MANIFEST))
    cfg = read_json(os.path.join(BENCH, "configs", "gru-eicu-xla.json"))
    cfg["name"] = "gru-eicu-new"
    write_json(os.path.join(root, "bench", "configs", "gru-eicu-new.json"), cfg)
    write_json(
        os.path.join(root, "bench", "traffic", "federated-sc.json"),
        {"setting": "federated-sc", "recruitment": "all", "selection": "uniform:0.1",
         "aggregator": "fedavg", "local_epochs": 4, "batch_size": 128},
    )
    write_json(os.path.join(root, "bench", "limits", "gru-new.sc.json"), {"loss_gap": {"limit": 1.0}})
    with open(os.path.join(root, "bench", "metrics", "engine.rounds.py"), "w", encoding="utf-8") as fh:
        fh.write("def read(run):\n    return float(len(run.window_rounds))\n")
    manifest["configs"].append(
        {"name": "gru-eicu-new", "source": "x", "file": "bench/configs/gru-eicu-new.json", "reduced": [], "why": "x"}
    )
    manifest["workloads"].append(
        {"name": "gru-new.sc", "config": "gru-eicu-new", "traffic": "federated-sc", "chips": 1, "why": "x"}
    )
    manifest["per_layer"].append(
        {"name": "engine.rounds", "unit": "rounds", "better": "higher", "source": "program_counter",
         "layer": "cohort engine", "moves": "round_s", "workloads": ["gru-new.sc"]}
    )
    write_json(os.path.join(root, "BENCHMARK.json"), manifest)

    bench = Bench(root)
    cell = bench.cell("gru-new.sc")
    assert cell.config["name"] == "gru-eicu-new"
    assert cell.traffic["selection"] == "uniform:0.1"
    assert "engine.rounds" in [m["name"] for m in cell.per_layer]
    assert "engine.rounds" not in [m["name"] for m in bench.cell("gru-xla.ac").per_layer]

    class Run:
        window_rounds = [4, 5, 6]

    assert bench.metric("engine.rounds").read(Run()) == 3.0


def test_job_spec_pins_the_cohort_and_takes_the_seed():
    from harness import job_spec

    cell = Bench(REPO).cell("gru-xla.src")
    a, b = job_spec(cell, 2**31 + 5, False), job_spec(cell, 7, False)
    assert a["seed"] == 2**31 + 5 and b["seed"] == 7
    assert a["data"] == b["data"] == {"scale": 1.0, "seed": 0, "split_mode": "global", "num_hospitals": None}
    assert a["observability"] is None and job_spec(cell, 7, True)["observability"] == {"trace": True}
    from repro.launch.federation_service import validate_job_spec

    validate_job_spec(a)
