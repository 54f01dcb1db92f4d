#!/usr/bin/env python3
"""Smoke run of the paper-scale federation on TPU chips.

    python3 chip_smoke.py              # one chip: every phase below but "mesh"
    python3 chip_smoke.py --chips 4    # only the "mesh" phase, on four chips

Everything runs in this one process and goes through the entry points a
user calls: a job spec from ``repro.experiments.paper.job_spec_for``
submitted with ``repro.launch.federation_service.submit_job`` (spec ->
``Federation`` -> ``CohortTrainer``), or the ``Federation`` facade itself.
The scale is the paper's: 189 hospitals and 89,127 stays (62,375 train)
from the seeded synthetic eICU generator, the 2-layer GRU with 32 hidden
units, batch 128, AdamW 5e-3.  Only ``rounds`` and ``local_epochs`` are
cut.  Weights come from the job seed.

Phases:

- ``device``: the default backend is a TPU; nothing falls back to the CPU.
- ``data``: the cohort has the paper's hospital and stay counts.
- ``federated-ac``: all 189 clients every round, 2 rounds, resident
  staging, donation on.  The loss is finite and round 2 compiles nothing
  (``jit.round_compiles`` in ``metrics.jsonl``).
- ``federated-src``: nu-greedy recruitment, 2 rounds.
- ``pallas``: ``federated-ac`` with ``model.use_pallas``, the same 2
  rounds.  The round module handed to XLA holds the Mosaic kernel
  (``tpu_custom_call``, so it was compiled, not interpreted); round 1's
  loss matches ``federated-ac`` within ``PALLAS_LOSS_RTOL``, round 2's
  within ``PALLAS_DRIFT_RTOL`` and the final parameters within
  ``PALLAS_PARAM_ATOL``.
- ``reference``: one round over 8 recruited clients with the vectorized
  engine on the chip, against the sequential engine on the host CPU at
  float32 matmul precision.
- ``mesh`` (``--chips 4`` only): one ``federated-ac`` round with
  ``mesh="auto"`` over four chips (189 clients padded to 192) against the
  same round with ``mesh=None`` on one device.

Each phase prints its seconds, the numbers it checked and the tolerance
each was held to; the script exits non-zero at the first phase that fails.
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Run directories go to ``chiprun_out/chip_smoke/`` (git-ignored).
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import glob
import json
import math
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")
SEED = 0
COHORT_SCALE = 1.0
# Table 2 of the paper; the synthetic generator reproduces these counts.
PAPER_COUNTS = {"hospitals": 189, "stays": 89_127, "train_stays": 62_375}
REFERENCE_CLIENTS = 8

# Pallas vs XLA scan over the same 2 rounds.  Both compute the same
# recurrence from the same batches and keys; only where the recurrent
# matmul runs and the order of its sums differ.  Round 1 starts both from
# the same params, so its loss isolates the kernel: 6.5e-8 relative in
# every TPU v5e run so far, and 1e-4 leaves room for summation order only.
PALLAS_LOSS_RTOL = 1e-4
# Round 2 starts from params that already differ.  AdamW's early steps are
# about the learning rate (5e-3) per coordinate whatever the gradient's
# size, so where a gradient is near zero its sign, and so the step, can
# differ between two summation orders.  One TPU v5e reading: round-2 loss
# 6.7e-5 relative, final params 1.32e-3 max abs.  The limits are 15x and
# 7.6x those; a wrong kernel moves params by the size of a round's update
# (0.18 in the ``reference`` phase).
PALLAS_DRIFT_RTOL = 1e-3
PALLAS_PARAM_ATOL = 1e-2
# Chip vs host-CPU reference, max |param diff| after one round.  Both at
# float32 matmul precision: only summation order and the chip's
# transcendental functions differ, and AdamW's normalised steps can carry
# a last-bit gradient difference into a coordinate at up to the learning
# rate per step only where the gradient is near zero.
REFERENCE_ATOL_F32 = 1e-3
# The chip at its default matmul precision (bfloat16 inputs, f32
# accumulation) against the float32 CPU reference: gradients differ at the
# 2^-9 relative level, and AdamW turns a gradient whose sign flips into a
# full step of size learning rate (5e-3) for that coordinate.  10x the
# learning rate separates that from a wrong round, which moves parameters
# by the size of the round's update (printed beside it).
REFERENCE_ATOL_DEFAULT = 5e-2
# mesh="auto" over 4 chips vs one device, same precision: the per-shard
# programs and the psum change summation order only.
MESH_ATOL = 1e-3


class PhaseFailed(Exception):
    pass


def log(message: str) -> None:
    print(message, flush=True)


def check(ok: bool, message: str) -> None:
    if not ok:
        raise PhaseFailed(message)


def experiment(**overrides):
    from repro.experiments.paper import ExperimentConfig

    fields = {"cohort_scale": COHORT_SCALE, "rounds": 2, "local_epochs": 1}
    fields.update(overrides)
    return ExperimentConfig(**fields)


def job_spec(setting: str, **overrides) -> dict:
    from repro.experiments.paper import job_spec_for

    return job_spec_for(setting, experiment(**overrides), seed=SEED)


def read_jsonl(path: str) -> list[dict]:
    with open(path, encoding="utf-8") as fh:
        return [json.loads(line) for line in fh if line.strip()]


def submit(name: str, spec: dict, subscribers=()) -> dict:
    """Submit one job into its own run directory; return what it wrote."""
    from repro.launch.federation_service import submit_job

    run_dir = os.path.join(OUT, name)
    shutil.rmtree(run_dir, ignore_errors=True)
    t0 = time.perf_counter()
    result = submit_job(spec, run_dir, subscribers=subscribers)
    seconds = time.perf_counter() - t0
    # The engine's step closures hold a reference cycle: collect it so the
    # resident cohort leaves the device before the next phase uploads one.
    gc.collect()
    records = read_jsonl(os.path.join(run_dir, "records.jsonl"))
    metrics = read_jsonl(os.path.join(run_dir, "metrics.jsonl"))
    for rec, met in zip(records, metrics):
        log(
            f"  round {rec['round_index']}: clients={len(rec['participant_ids'])} "
            f"loss={rec['mean_local_loss']!r} round_s={rec['round_time_s']!r} "
            f"local_steps={rec['local_steps']} "
            f"round_compiles={met['gauges'].get('jit.round_compiles')} "
            f"compile_s_total={met['counters'].get('jit.compile_time_s', 0.0)!r}"
        )
    log(f"  submit_job seconds={seconds!r} summary={json.dumps(result['summary'])}")
    for rec in records:
        check(math.isfinite(rec["mean_local_loss"]), f"{name}: non-finite loss {rec}")
    return {"run_dir": run_dir, "result": result, "records": records, "metrics": metrics}


def final_params(run_dir: str) -> dict:
    import numpy as np

    with np.load(os.path.join(run_dir, "final", "arrays.npz")) as z:
        return {k: z[k] for k in z.files}


def max_abs_diff(a, b) -> float:
    import jax
    import numpy as np

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    check(len(la) == len(lb), "parameter trees differ in structure")
    return max(
        float(np.max(np.abs(np.asarray(x, np.float64) - np.asarray(y, np.float64))))
        for x, y in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(chips: int) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"  platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    check(dev.platform == "tpu", f"default backend is {dev.platform!r}, not a TPU")
    check(len(devices) >= chips, f"need {chips} chips, JAX sees {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def phase_data() -> None:
    from repro.data.pipeline import build_client_datasets
    from repro.data.synth_eicu import CohortConfig, generate_cohort

    spec = job_spec("federated-ac")
    cfg = CohortConfig()
    if spec["data"]["scale"] != 1.0:
        cfg = cfg.scaled(spec["data"]["scale"])
    cohort = generate_cohort(cfg, seed=spec["data"]["seed"])
    clients = build_client_datasets(cohort)
    got = {
        "hospitals": len(clients),
        "stays": int(cohort.y.size),
        "train_stays": sum(c.n_train for c in clients),
    }
    log(f"  cohort {got} largest_client_train={max(c.n_train for c in clients)}")
    check(got == PAPER_COUNTS, f"cohort {got} is not the paper's {PAPER_COUNTS}")


def phase_federated_ac(state: dict) -> None:
    run = submit("federated-ac", job_spec("federated-ac"))
    sizes = [len(r["participant_ids"]) for r in run["records"]]
    check(
        sizes == [PAPER_COUNTS["hospitals"]] * 2,
        f"federated-ac trained {sizes} clients, expected all "
        f"{PAPER_COUNTS['hospitals']} in both rounds",
    )
    recompiles = run["metrics"][1]["gauges"].get("jit.round_compiles")
    check(recompiles == 0, f"round 2 compiled {recompiles} programs, expected 0")
    state["federated-ac"] = run


def phase_federated_src() -> None:
    run = submit("federated-src", job_spec("federated-src"))
    summary = run["result"]["summary"]
    check(
        0 < summary["federation_size"] <= PAPER_COUNTS["hospitals"],
        f"recruited federation of {summary['federation_size']}",
    )


def phase_pallas(state: dict) -> None:
    import jax

    ir_dir = os.path.join(OUT, "pallas-ir")
    shutil.rmtree(ir_dir, ignore_errors=True)
    before = jax.config.values["jax_dump_ir_to"]
    jax.config.update("jax_dump_ir_to", ir_dir)
    try:
        run = submit("pallas", job_spec("federated-ac", use_pallas=True))
    finally:
        jax.config.update("jax_dump_ir_to", before)
    modules = sorted(glob.glob(os.path.join(ir_dir, "*cohort_round*_compile.mlir")))
    with_kernel = []
    for path in modules:
        with open(path, encoding="utf-8") as fh:
            if "tpu_custom_call" in fh.read():
                with_kernel.append(os.path.basename(path))
    shutil.rmtree(ir_dir, ignore_errors=True)
    log(f"  round modules={len(modules)} with tpu_custom_call={with_kernel}")
    check(bool(with_kernel), "no round module holds tpu_custom_call: the kernel did not compile")
    xla = state["federated-ac"]
    for rec, ref_rec in zip(run["records"], xla["records"], strict=True):
        loss, ref = rec["mean_local_loss"], ref_rec["mean_local_loss"]
        rel = abs(loss - ref) / abs(ref)
        rtol = PALLAS_LOSS_RTOL if rec["round_index"] == 0 else PALLAS_DRIFT_RTOL
        log(
            f"  round {rec['round_index']} loss pallas={loss!r} xla_scan={ref!r} "
            f"rel_diff={rel!r} rtol={rtol}"
        )
        check(rel <= rtol, f"Pallas loss off by {rel:.3e} > {rtol}")
    diff = max_abs_diff(final_params(run["run_dir"]), final_params(xla["run_dir"]))
    log(f"  final params pallas vs xla_scan: max|diff|={diff!r} atol={PALLAS_PARAM_ATOL}")
    check(diff <= PALLAS_PARAM_ATOL, f"Pallas params off by {diff:.3e} > {PALLAS_PARAM_ATOL}")


def phase_reference() -> None:
    import jax

    from repro.federated.api import Federation
    from repro.launch.federation_service import (
        build_workload,
        federation_config_from_spec,
        validate_job_spec,
    )

    spec = validate_job_spec(job_spec("federated-src", rounds=1))
    workload = build_workload(spec)
    cfg = federation_config_from_spec(spec)
    candidates = Federation(cfg, workload.clients, workload.loss_fn, workload.optimizer)
    ids, _ = candidates.build_federation()
    chosen = set(ids[:REFERENCE_CLIENTS].tolist())
    clients = [c for c in workload.clients if c.client_id in chosen]
    check(len(clients) == REFERENCE_CLIENTS, f"only {len(clients)} recruited clients")
    round_cfg = dataclasses.replace(cfg, recruitment="all", selection="uniform", rounds=1)

    def one_round(config, params):
        fed = Federation(config, clients, workload.loss_fn, workload.optimizer)
        return fed.run(params).params

    t0 = time.perf_counter()
    chip_default = one_round(round_cfg, workload.init_params)
    with jax.default_matmul_precision("float32"):
        chip_f32 = one_round(round_cfg, workload.init_params)
    chip_s = time.perf_counter() - t0
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.default_device(cpu), jax.default_matmul_precision("float32"):
        ref = one_round(
            dataclasses.replace(round_cfg, engine="sequential"),
            jax.device_put(workload.init_params, cpu),
        )
    cpu_s = time.perf_counter() - t0
    update = max_abs_diff(ref, jax.device_get(workload.init_params))
    d_f32 = max_abs_diff(jax.device_get(chip_f32), ref)
    d_default = max_abs_diff(jax.device_get(chip_default), ref)
    log(
        f"  clients={sorted(chosen)} chip_s={chip_s!r} cpu_sequential_s={cpu_s!r} "
        f"max|round update|={update!r}"
    )
    log(f"  chip f32 vs cpu f32: max|diff|={d_f32!r} atol={REFERENCE_ATOL_F32}")
    log(f"  chip default vs cpu f32: max|diff|={d_default!r} atol={REFERENCE_ATOL_DEFAULT}")
    check(d_f32 <= REFERENCE_ATOL_F32, f"f32 reference diff {d_f32:.3e} > {REFERENCE_ATOL_F32}")
    check(
        d_default <= REFERENCE_ATOL_DEFAULT,
        f"default-precision reference diff {d_default:.3e} > {REFERENCE_ATOL_DEFAULT}",
    )


def phase_mesh(chips: int) -> None:
    import jax

    per_device: dict[int, int] = {}

    def live_bytes(_record) -> None:
        # Runs while the federation is alive: its resident cohort dominates.
        counts: collections.Counter = collections.Counter()
        for arr in jax.live_arrays():
            shard = math.prod(arr.sharding.shard_shape(arr.shape)) * arr.dtype.itemsize
            for dev in arr.sharding.device_set:
                counts[dev.id] += shard
        per_device.update(counts)

    mesh_run = submit(
        "mesh-auto", job_spec("federated-ac", rounds=1, mesh="auto"), (live_bytes,)
    )
    resident = mesh_run["metrics"][0]["gauges"].get("staging.bytes_resident")
    log(f"  resident cohort bytes={resident}")
    log(f"  live bytes per device={dict(sorted(per_device.items()))}")
    ids = [d.id for d in jax.devices()[:chips]]
    total = sum(per_device.get(i, 0) for i in ids)
    check(
        all(per_device.get(i, 0) >= total / (2 * chips) for i in ids),
        f"the cohort is not spread over {chips} chips: {per_device}",
    )
    one_run = submit("mesh-none", job_spec("federated-ac", rounds=1))
    diff = max_abs_diff(final_params(mesh_run["run_dir"]), final_params(one_run["run_dir"]))
    loss_mesh = mesh_run["records"][0]["mean_local_loss"]
    loss_one = one_run["records"][0]["mean_local_loss"]
    log(
        f"  mesh vs one device: max|param diff|={diff!r} atol={MESH_ATOL} "
        f"loss mesh={loss_mesh!r} one={loss_one!r}"
    )
    check(diff <= MESH_ATOL, f"mesh params off by {diff:.3e} > {MESH_ATOL}")


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the mesh='auto' client axis across four chips",
    )
    args = parser.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    state: dict = {}
    phases = [("device", lambda: state.update(device=phase_device(args.chips)))]
    if args.chips == 4:
        phases.append(("mesh", lambda: phase_mesh(args.chips)))
    else:
        phases += [
            ("data", phase_data),
            ("federated-ac", lambda: phase_federated_ac(state)),
            ("federated-src", phase_federated_src),
            ("pallas", lambda: phase_pallas(state)),
            ("reference", phase_reference),
        ]
    os.makedirs(OUT, exist_ok=True)
    t_all = time.perf_counter()
    for name, run in phases:
        log(f"[{name}]")
        t0 = time.perf_counter()
        try:
            run()
        except Exception as exc:  # report the phase, then stop
            traceback.print_exc()
            log(f"[{name}] FAILED after {time.perf_counter() - t0!r}s: {exc}")
            return 1
        log(f"[{name}] ok seconds={time.perf_counter() - t0!r}")
    log(f"all phases ok seconds={time.perf_counter() - t_all!r}")
    print(json.dumps({"ok": True, "device": state["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
