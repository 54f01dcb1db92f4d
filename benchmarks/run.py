"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  table4_*   — paper Table 4 (central + 4 federated settings) at benchmark
               scale; us_per_call = wall time per local training step,
               derived = test MSLE.
  table5_*   — paper Table 5 (QG / DG recruitment ablations).
  fig2_*     — paper Fig. 2 (gamma_th sweep); derived = clients recruited.
  kernel_*   — Pallas kernels vs jnp oracle (interpret mode on CPU);
               derived = max |err| vs the oracle.
  roofline_* — per (arch x shape) dry-run roofline terms from
               benchmarks/results/dryrun; us_per_call = dominant-term
               seconds * 1e6, derived = dominant term name.

Full-scale paper numbers (the ones recorded in EXPERIMENTS.md) come from
``python -m repro.experiments.run_full``; this harness keeps the default
run CPU-budget friendly (~ a few minutes).
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.launch.compile_cache import enable_compile_cache

ROWS: list[tuple[str, float, str]] = []


def emit(name: str, us_per_call: float, derived) -> None:
    ROWS.append((name, us_per_call, str(derived)))
    print(f"{name},{us_per_call:.3f},{derived}", flush=True)


# --------------------------------------------------------------------------
# paper tables (benchmark scale)
# --------------------------------------------------------------------------

def bench_paper_tables(scale: float, seeds: list[int]) -> None:
    from repro.experiments.paper import ExperimentConfig, build_cohort, run_setting

    exp = ExperimentConfig(cohort_scale=scale, rounds=5, local_epochs=2, central_epochs=5)
    cohort = build_cohort(exp, seed=0)
    table4 = ["central", "federated-ac", "federated-sc", "federated-arc", "federated-src"]
    table5 = ["federated-src-qg", "federated-src-dg"]
    for setting in table4 + table5:
        msles, taus, steps = [], [], []
        for seed in seeds:
            out = run_setting(setting, exp, cohort, seed=seed)
            msles.append(out["metrics"]["msle"])
            taus.append(out["tau_s"])
            steps.append(out["local_steps"])
        us_per_step = 1e6 * (sum(taus) / len(taus)) / max(sum(steps) / len(steps), 1)
        prefix = "table5" if setting in table5 else "table4"
        emit(f"{prefix}_{setting}", us_per_step, f"msle={sum(msles)/len(msles):.4f}")


def bench_fig2(scale: float) -> None:
    import dataclasses

    from repro.experiments.paper import ExperimentConfig, build_cohort, run_setting

    exp = ExperimentConfig(cohort_scale=scale, rounds=3, local_epochs=1)
    cohort = build_cohort(exp, seed=0)
    for gth in (0.05, 0.1, 0.3, 0.6, 1.0):
        e = dataclasses.replace(exp, gamma_th=gth)
        out = run_setting("federated-src", e, cohort, seed=0)
        us = 1e6 * out["tau_s"] / max(out["local_steps"], 1)
        emit(f"fig2_gamma{gth}", us, f"recruited={out['recruited']}")


# --------------------------------------------------------------------------
# cohort engine: sequential vs vectorized federated rounds
# --------------------------------------------------------------------------

def bench_cohort(
    client_counts: tuple[int, ...] = (8, 32, 128),
    samples_per_client: int = 16,
    batch_size: int = 4,
    local_epochs: int = 1,
    reps: int = 3,
    out_path: str = "BENCH_cohort.json",
) -> None:
    """Per-round wall clock of the two federated engines on a synthetic
    federation, at growing cohort sizes.  Writes ``BENCH_cohort.json`` with
    the sequential/vectorized seconds and the speedup per cohort size.

    Defaults target the dispatch-bound regime the engine exists to remove
    (many small hospitals, a handful of tiny local steps each, as in the
    eICU tail): the sequential loop pays a Python dispatch + device sync
    per client-step, the vectorized engine one jitted call per round.  With
    bigger per-client compute a few-core CPU saturates on raw FLOPs and
    both engines converge to the same floor; on parallel hardware the
    vectorized gain grows with cohort size instead."""
    import jax
    import numpy as np

    from repro.data.pipeline import ArrayDataset, ClientDataset
    from repro.federated.client import LocalTrainer
    from repro.federated.cohort import CohortTrainer
    from repro.federated.fedavg import aggregate
    from repro.models.gru import GRUConfig, init_gru, make_loss_fn
    from repro.optim.adamw import AdamW

    cfg = GRUConfig()  # the paper's LoS model: 38 features, N=32, L=2
    loss_fn = make_loss_fn(cfg)
    opt = AdamW(learning_rate=5e-3, weight_decay=5e-3)
    params = init_gru(jax.random.key(0), cfg)
    data_rng = np.random.default_rng(0)

    def synth_clients(count: int) -> list[ClientDataset]:
        clients = []
        for i in range(count):
            # mild size skew so the padded schedule is exercised
            n = samples_per_client + (i % 4) * (batch_size // 4)
            x = data_rng.normal(size=(n, 24, cfg.input_dim)).astype(np.float32)
            y = data_rng.uniform(0.5, 20.0, size=n).astype(np.float32)
            ds = ArrayDataset(x, y)
            clients.append(ClientDataset(client_id=i, train=ds, val=ds))
        return clients

    # One trainer per engine for the whole sweep — exactly like a multi-round
    # FederatedServer run, compilation is paid once, not per round.
    seq_trainer = LocalTrainer(loss_fn, opt, batch_size=batch_size, local_epochs=local_epochs)
    vec_trainer = CohortTrainer(loss_fn, opt, batch_size=batch_size, local_epochs=local_epochs)

    def run_sequential(clients) -> None:
        rng, key = np.random.default_rng(1), jax.random.key(1)
        outs, weights = [], []
        for c in clients:
            key, sub = jax.random.split(key)
            p, _, n = seq_trainer.train_client(params, c, rng, sub)
            outs.append(p)
            weights.append(n)
        jax.block_until_ready(aggregate(outs, weights))

    def run_vectorized(clients) -> None:
        rng, key = np.random.default_rng(1), jax.random.key(1)
        keys = list(jax.random.split(key, len(clients)))
        p, _, _ = vec_trainer.train_cohort(params, clients, rng, keys)
        jax.block_until_ready(p)

    report = {}
    for count in client_counts:
        clients = synth_clients(count)
        row = {}
        for name, fn in (("sequential", run_sequential), ("vectorized", run_vectorized)):
            fn(clients)  # warmup: compile + caches
            t0 = time.perf_counter()
            for _ in range(reps):
                fn(clients)
            row[name] = (time.perf_counter() - t0) / reps
        row["speedup"] = row["sequential"] / row["vectorized"]
        report[str(count)] = row
        emit(f"cohort_seq_{count}c", 1e6 * row["sequential"], "per-round wall")
        emit(f"cohort_vec_{count}c", 1e6 * row["vectorized"], f"speedup={row['speedup']:.2f}x")

    payload = {
        "bench": "cohort_engine_round",
        "model": "gru_eicu",
        "batch_size": batch_size,
        "samples_per_client": samples_per_client,
        "local_epochs": local_epochs,
        "reps": reps,
        "results": report,
    }
    Path(out_path).write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {out_path}", flush=True)


# --------------------------------------------------------------------------
# paper-scale federation: all five settings at 189 clients, both engines
# --------------------------------------------------------------------------

def bench_paper189(
    rounds: int = 3,
    total_stays: int = 4096,
    mesh_auto: bool = False,
    out_path: str = "BENCH_paper189.json",
) -> None:
    """The paper's full 189-client experiment grid (section 6) end to end.

    Every model setting (central / federated ac, sc, arc, src) runs at the
    full 189-hospital federation under both engines; per-setting rows report
    steady-state microseconds per round and the vectorized-over-sequential
    speedup, plus a donated-vs-plain buffer memory probe.  Per-hospital data
    is CI-scaled (the client axis is the paper-scale dimension); pass
    ``--mesh-auto`` under ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    to run the client axis through the shard_map path.
    """
    from repro.experiments.paper import run_paper_scale

    report = run_paper_scale(
        rounds=rounds,
        total_stays=total_stays,
        mesh="auto" if mesh_auto else None,
    )
    for setting, row in report["settings"].items():
        for engine, entry in row.items():
            if engine == "speedup":
                continue
            derived = f"msle={entry['metrics']['msle']:.4f}"
            if engine == "vectorized" and "speedup" in row:
                derived += f";speedup={row['speedup']:.2f}x"
            if entry.get("time_unit", "round") != "round":
                derived += f";per_{entry['time_unit']}"
            emit(f"paper189_{setting}_{engine}", 1e6 * entry["round_time_s"], derived)
    mem = report["memory"]
    emit(
        "paper189_memory_donated",
        float(mem["donated"]["peak_live_bytes"]),
        f"peak_bufs={mem['donated']['peak_live_buffers']}",
    )
    emit(
        "paper189_memory_plain",
        float(mem["plain"]["peak_live_bytes"]),
        f"donated_lower={mem['donated_peak_lower']}",
    )
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {out_path}", flush=True)


# --------------------------------------------------------------------------
# staging pipeline: rebuild-per-round vs device-resident + prefetch
# --------------------------------------------------------------------------

def bench_pipeline(
    rounds: int = 4,
    total_stays: int = 189 * 64,
    cohort_chunk: int = 48,
    mesh_auto: bool = False,
    out_path: str = "BENCH_pipeline.json",
) -> None:
    """Per-round staging cost at 189 clients: PR 2's rebuild-per-round path
    (full schedule re-materialized in numpy and re-uploaded every round)
    against the device-resident path (data uploaded once, rounds stage only
    int32 index plans, batches gathered on device, plans double-buffered on
    a background thread).  Reports per-variant steady-state round seconds,
    per-round host->device bytes, and the rebuild/resident speedup and byte
    ratio; with more than one visible device (or ``--mesh-auto``) the same
    grid additionally runs through the shard_map client-axis path.  A
    facade-overhead probe rides along: the policy-API ``Federation`` round
    program vs the bare PR-3 ``chain_split_keys`` + ``train_cohort`` loop
    (budget: <= 2% per-round overhead).  Writes ``BENCH_pipeline.json``.
    """
    import jax

    from repro.experiments.paper import run_facade_overhead, run_staging_comparison

    report = {
        "bench": "staging_pipeline",
        "single_device": run_staging_comparison(
            rounds=rounds, total_stays=total_stays, cohort_chunk=cohort_chunk
        ),
        "facade_overhead": run_facade_overhead(),
    }
    if mesh_auto and jax.device_count() > 1:
        # The mesh leg honours cohort_chunk: resident rounds train each
        # client on a lane of the shard that holds its rows, so chunks need
        # no cross-shard gather.
        report["shard_map"] = run_staging_comparison(
            rounds=rounds, total_stays=total_stays, cohort_chunk=cohort_chunk,
            mesh="auto", variants=("rebuild", "rebuild-chunked", "resident"),
        )
    elif mesh_auto:
        emit("pipeline_shard_map_skipped", 0.0, "only one device visible")
    for leg, rep in report.items():
        if not isinstance(rep, dict) or "variants" not in rep:
            continue
        for variant, entry in rep["variants"].items():
            emit(
                f"pipeline_{leg}_{variant}",
                1e6 * entry["round_time_s"],
                f"staged={entry['bytes_staged_per_round']}B"
                f";prefetched={entry['plans_prefetched']}",
            )
        emit(
            f"pipeline_{leg}_speedup",
            1e6 * rep["variants"]["resident"]["round_time_s"],
            f"speedup={rep['speedup']:.2f}x;bytes_ratio={rep['bytes_ratio']:.1f}x"
            f";max_param_diff={rep['max_param_diff']:.2e}",
        )
    facade = report["facade_overhead"]
    emit(
        "pipeline_facade_overhead",
        1e6 * facade["facade_round_s"],
        f"overhead={100 * facade['overhead_frac']:+.2f}%"
        f";within_budget={facade['within_budget']}",
    )
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {out_path}", flush=True)


# --------------------------------------------------------------------------
# control plane: submitted-job overhead vs direct Federation.run
# --------------------------------------------------------------------------

def bench_service(
    rounds: int = 6,
    scale: float = 0.02,
    out_path: str = "BENCH_pipeline.json",
) -> None:
    """The federation-service envelope vs a direct ``Federation.run``.

    Times the same workload end to end through both paths: bare
    ``build_workload`` + facade run, and a job submitted through
    ``repro.launch.federation_service`` (spec validation + hashing,
    job.json, the per-round JSONL record stream, snapshots, final-params
    save).  Budget: <= 2% total overhead.  Merges a ``service_overhead``
    section into ``BENCH_pipeline.json`` next to the facade-overhead probe
    (the two taxes stack on the same hot loop, so they belong in one
    report).
    """
    from repro.experiments.paper import run_service_overhead

    section = run_service_overhead(rounds=rounds, scale=scale)
    path = Path(out_path)
    report = json.loads(path.read_text()) if path.exists() else {
        "bench": "staging_pipeline"
    }
    report["service_overhead"] = section
    emit(
        "pipeline_service_overhead",
        1e6 * section["service_total_s"],
        f"overhead={100 * section['overhead_frac']:+.2f}%"
        f";within_budget={section['within_budget']}",
    )
    path.write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {out_path}", flush=True)


# --------------------------------------------------------------------------
# observability: tracer-off / tracer-on overhead, sync + async engines
# --------------------------------------------------------------------------

def bench_obs(
    rounds: int = 10,
    flushes: int = 10,
    repeats: int = 3,
    out_path: str = "BENCH_obs.json",
    trace_path: str = "BENCH_obs_trace.json",
) -> None:
    """The observability tax at the paper's 189 clients, both engines.

    Three sync variants (bare hot loop, ``Federation`` with the null
    tracer, ``Federation`` with a live tracer) plus an async off/on pair
    (fedbuff, constant latency, so each flush is the same unit of work).
    Budgets: instrumented-off <= 1% over bare, tracer-on <= 5% over off.
    Writes ``BENCH_obs.json`` and exports the async on-run's ring as a
    Perfetto-loadable ``BENCH_obs_trace.json`` sample.
    """
    from repro.experiments.paper import run_obs_overhead

    report = run_obs_overhead(
        rounds=rounds, flushes=flushes, repeats=repeats, trace_path=trace_path
    )
    sync, async_ = report["sync"], report["async"]
    emit(
        "obs_sync_off",
        1e6 * sync["off_round_s"],
        f"overhead={100 * sync['overhead_off_frac']:+.2f}%;budget=1%",
    )
    emit(
        "obs_sync_on",
        1e6 * sync["on_round_s"],
        f"overhead={100 * sync['overhead_on_frac']:+.2f}%;budget=5%",
    )
    emit(
        "obs_async_on",
        1e6 * async_["on_flush_s"],
        f"overhead={100 * async_['overhead_on_frac']:+.2f}%;budget=5%"
        f";events={report['trace']['async_events']}",
    )
    emit("obs_within_budget", 0.0, report["within_budget"])
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {out_path}", flush=True)
    print(f"# wrote {trace_path}", flush=True)


# --------------------------------------------------------------------------
# async runtime: simulated time-to-target under straggler distributions
# --------------------------------------------------------------------------

def bench_async(
    flushes: int = 8,
    cohort_scale: float = 0.05,
    dropout: float = 0.05,
    out_path: str = "BENCH_async.json",
) -> None:
    """Recruited vs all-clients async federations on the virtual clock.

    Runs the ``repro.federated.runtime`` event-driven federation (fedbuff
    buffered aggregation, per-client straggler latencies, dropout) for both
    the ``"all"`` and nu-greedy federations under each latency model, and
    reports the paper's claim on the axis the sync engines cannot measure:
    simulated time-to-target-loss.  Rows quote virtual (simulated) seconds
    scaled to us; ``derived`` carries the recruited-over-all speedup and
    the mean update staleness.  Writes ``BENCH_async.json``.
    """
    from repro.experiments.paper import ASYNC_FEDERATIONS, run_async_comparison

    report = run_async_comparison(
        flushes=flushes, cohort_scale=cohort_scale, dropout=dropout
    )
    for latency, row in report["latency"].items():
        tag = latency.replace(":", "")
        for name, _ in ASYNC_FEDERATIONS:
            entry = row[name]
            reached = entry["time_to_target"]
            stale = entry["mean_staleness"]
            emit(
                f"async_{tag}_{name}",
                1e6 * reached if reached is not None else 0.0,
                ("virtual_s" if reached is not None else "target_unreached")
                + f";fed={entry['federation_size']}"
                + (f";stale={stale:.2f}" if stale is not None else "")
                + f";dropped={entry['dropped']}",
            )
        speedup = row["recruited_speedup"]
        t_rec = row["recruited"]["time_to_target"]
        emit(
            f"async_{tag}_speedup",
            1e6 * t_rec if t_rec is not None else 0.0,
            (
                f"recruited_speedup={speedup:.2f}x"
                if speedup is not None
                else "recruited_speedup=n/a"
            )
            + f";target_loss={row['target_loss']:.4f}",
        )
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {out_path}", flush=True)


# --------------------------------------------------------------------------
# population scale: recruitment + rounds from 10^3 to 10^5 clients
# --------------------------------------------------------------------------

def bench_population(
    populations: tuple[int, ...] = (1_000, 10_000, 100_000),
    rounds: int = 3,
    round_clients: int = 64,
    pool_rows: int = 256,
    out_path: str = "BENCH_population.json",
) -> None:
    """Population-scale curve: streaming nu-greedy recruitment (ingest pass
    vs finalize decision, with the exact ``recruit`` as parity oracle) and
    steady-state round time out of an LRU-pooled device cohort, at each
    population size.  The report asserts the contract on the way out:
    participant sets match the oracle at 10^3 (exact-buffer mode), the
    recruitment decision and the round time grow sub-linearly in population,
    and ``is_recruited`` membership stays O(1) amortized.  Writes
    ``BENCH_population.json``.
    """
    from repro.experiments.population import run_population_scale

    report = run_population_scale(
        populations=populations,
        rounds=rounds,
        round_clients=round_clients,
        pool_rows=pool_rows,
        verbose=False,
    )
    for entry in report["entries"]:
        pop = entry["population"]
        emit(
            f"population_{pop}_recruit",
            1e6 * entry["recruitment_decision_s"],
            f"mode={entry['streaming_mode']}"
            f";ingest_us_per_client={entry['recruitment_ingest_us_per_client']:.1f}"
            f";recruited={entry['num_recruited_streaming']}"
            + (
                f";match={entry['participant_match']}"
                f";jaccard={entry['overlap_jaccard']:.3f}"
                if "participant_match" in entry
                else ""
            ),
        )
        emit(
            f"population_{pop}_round",
            1e6 * entry["round_time_s"],
            f"pool_rows={entry['pool_rows']}"
            f";uploads={entry['pool_uploads_total']}"
            f";evictions={entry['pool_evictions_total']}",
        )
    if "population_ratio" in report:
        emit(
            "population_scaling",
            0.0,
            f"pop_ratio={report['population_ratio']:.0f}x"
            f";decision_ratio={report['recruitment_decision_ratio']:.2f}x"
            f";round_ratio={report['round_time_ratio']:.2f}x"
            f";sublinear={report['recruitment_sublinear'] and report['round_sublinear']}",
        )
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {out_path}", flush=True)


# --------------------------------------------------------------------------
# privacy: DP-SGD and secure-aggregation per-round overhead
# --------------------------------------------------------------------------

def bench_privacy(
    rounds: int = 3,
    total_stays: int = 189 * 8,
    noise_multiplier: float = 1.0,
    out_path: str = "BENCH_privacy.json",
) -> None:
    """Privacy-tier cost at the paper's 189 clients, baseline in-file.

    For each staging mode the grid runs the unprotected federation and the
    in-jit DP-SGD federation under both engines (per-example clipping +
    noise ride the jitted round, so the interesting number is the
    steady-state per-round overhead), plus one masked-sum secure
    aggregation run — secagg's stacked mode forces the sequential engine,
    so its overhead is reported against the sequential baseline of the
    same staging.  DP rows carry the accountant's final epsilon.  Writes
    ``BENCH_privacy.json`` with every baseline next to its protected run.
    """
    import jax
    import numpy as np

    from repro.data.pipeline import build_client_datasets
    from repro.data.synth_eicu import generate_cohort
    from repro.experiments.paper import paper_scale_cohort_config
    from repro.federated.api import Federation, FederationConfig
    from repro.models.gru import GRUConfig, init_gru, make_loss_fn
    from repro.optim.adamw import AdamW
    from repro.privacy.dp import DPConfig

    cohort = generate_cohort(paper_scale_cohort_config(total_stays), seed=0)
    clients = build_client_datasets(cohort)
    model_cfg = GRUConfig()
    loss_fn = make_loss_fn(model_cfg)
    optimizer = AdamW(learning_rate=5e-3, weight_decay=5e-3)
    params0 = init_gru(jax.random.key(0), model_cfg)
    dp = DPConfig(clip_norm=1.0, noise_multiplier=noise_multiplier)

    def one(engine: str, staging: str, privacy=None, aggregator="fedavg"):
        cfg = FederationConfig(
            rounds=rounds, local_epochs=1, batch_size=128,
            aggregator=aggregator, seed=0, engine=engine, staging=staging,
            privacy=privacy,
        )
        fed = Federation(cfg, clients, loss_fn, optimizer)
        result = fed.run(params0)
        times = [r.wall_time_s for r in result.history]
        steady = float(np.mean(times[1:])) if len(times) > 1 else float(times[0])
        return {
            "round_time_s": steady,
            "effective_engine": fed.effective_engine,
            "epsilon": result.summary()["epsilon"],
        }

    report: dict = {
        "bench": "privacy",
        "clients": len(clients),
        "rounds": rounds,
        "noise_multiplier": noise_multiplier,
        "grid": {},
    }
    for staging in ("resident", "rebuild"):
        cell: dict = {}
        for engine in ("vectorized", "sequential"):
            base = one(engine, staging)
            protected = one(engine, staging, privacy=dp)
            overhead = protected["round_time_s"] / base["round_time_s"] - 1.0
            cell[engine] = {
                "unprotected": base,
                "dp": {**protected, "overhead_frac": overhead},
            }
            emit(
                f"privacy_{staging}_{engine}_dp",
                1e6 * protected["round_time_s"],
                f"overhead={100 * overhead:+.1f}%"
                f";eps={protected['epsilon']:.2f}",
            )
        seq_base = cell["sequential"]["unprotected"]["round_time_s"]
        secagg = one("sequential", staging, aggregator="secagg-fedavg")
        cell["secagg"] = {
            **secagg,
            "overhead_frac": secagg["round_time_s"] / seq_base - 1.0,
        }
        emit(
            f"privacy_{staging}_secagg",
            1e6 * secagg["round_time_s"],
            f"overhead={100 * cell['secagg']['overhead_frac']:+.1f}%"
            f";engine={secagg['effective_engine']}",
        )
        report["grid"][staging] = cell
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")
    print(f"# wrote {out_path}", flush=True)


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def bench_kernels(
    *,
    reps: int = 5,
    gru_batch: int = 128,
    lm_seq: int = 256,
    lm_heads: int = 4,
    out_path: str = "BENCH_kernels.json",
) -> None:
    """Training-grade kernel tier: fwd / bwd / local-step timings.

    Compares three backward pairings at the paper's GRU-eICU shape and a
    mamba2-130m-derived LM shape (head_dim/d_state from the zoo config,
    heads and sequence scaled for CPU interpret mode):

      oracle_vjp    — old pairing: backward recomputes the forward through
                      the jnp oracle, then transposes it
      residual_jnp  — new default off-TPU: single reverse scan over stashed
                      residuals, no forward recompute
      pallas_bwd    — the hand-written backward kernel, in interpret mode
                      on every backend (as are the forward kernel timings)

    Also embeds the jaxpr recompute-elimination report (scan sites + FLOP
    accounting of the backward-only graph).  Writes ``BENCH_kernels.json``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.gru_eicu import CONFIG as GRU_EICU
    from repro.configs.mamba2_130m import CONFIG as MAMBA_LM
    from repro.kernels.analysis import recompute_elimination_report
    from repro.kernels.gru_scan.kernel import gru_scan, gru_scan_bwd
    from repro.kernels.gru_scan.ops import gru_scan_op, gru_scan_oracle
    from repro.kernels.gru_scan.ref import gru_scan_bwd_ref, gru_scan_ref
    from repro.kernels.ssd.kernel import ssd_chunk_scan as ssd_chunk_scan_kernel
    from repro.kernels.ssd.kernel import ssd_chunk_scan_bwd
    from repro.kernels.ssd.ops import ssd_chunk_scan, ssd_chunk_scan_oracle
    from repro.kernels.ssd.ref import (
        ssd_chunk_scan_bwd_ref,
        ssd_chunk_scan_ref,
        ssd_chunk_states_ref,
    )

    rng = np.random.default_rng(0)

    def timeit(fn, *args) -> float:
        jax.block_until_ready(fn(*args))  # warmup / compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        return 1e6 * (time.perf_counter() - t0) / reps

    def grad_fn(op, argnums):
        return jax.jit(jax.grad(lambda *a: jnp.sum(op(*a) ** 2), argnums=argnums))

    report: dict = {"bench": "kernels", "backend": jax.default_backend(), "reps": reps}

    # ---- GRU at the paper's eICU shape (hidden from repro.configs) -------
    t_len, n_hid = 24, GRU_EICU.hidden_dim
    xg = jnp.asarray(rng.normal(size=(gru_batch, t_len, 3 * n_hid)), jnp.float32)
    whh = jnp.asarray(rng.normal(size=(n_hid, 3 * n_hid)) * 0.3, jnp.float32)
    bhh = jnp.zeros(3 * n_hid)
    dy = jnp.asarray(rng.normal(size=(gru_batch, t_len, n_hid)), jnp.float32)
    h_seq = gru_scan_ref(xg, whh, bhh)

    # "pallas_interpret" entries pin interpret mode, so they mean the same
    # on every backend (the kernels default to Mosaic on a TPU).
    jit_pallas_fwd = jax.jit(lambda *a: gru_scan(*a, interpret=True))
    err_fwd = float(jnp.max(jnp.abs(jit_pallas_fwd(xg, whh, bhh) - h_seq)))
    _, oracle_vjp = jax.vjp(gru_scan_ref, xg, whh, bhh)
    g_oracle = oracle_vjp(dy)
    maxerr = lambda got: max(
        float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, g_oracle)
    )
    jit_oracle_bwd = jax.jit(lambda ct: jax.vjp(gru_scan_ref, xg, whh, bhh)[1](ct))
    jit_resid_bwd = jax.jit(gru_scan_bwd_ref)
    jit_pallas_bwd = jax.jit(lambda *a: gru_scan_bwd(*a, interpret=True))
    pallas_bwd = lambda: jit_pallas_bwd(xg, whh, bhh, h_seq, dy)

    gru = {
        "shape": {"batch": gru_batch, "seq": t_len, "hidden": n_hid},
        "fwd_us": {
            "pallas_interpret": timeit(jit_pallas_fwd, xg, whh, bhh),
            "jnp_ref": timeit(jax.jit(gru_scan_ref), xg, whh, bhh),
        },
        "bwd_us": {
            "oracle_vjp": timeit(jit_oracle_bwd, dy),
            "residual_jnp": timeit(jit_resid_bwd, xg, whh, bhh, h_seq, dy),
            "pallas_interpret": timeit(pallas_bwd),
        },
        "local_step_us": {
            "oracle_vjp": timeit(grad_fn(gru_scan_oracle, (0, 1, 2)), xg, whh, bhh),
            "residual": timeit(grad_fn(gru_scan_op, (0, 1, 2)), xg, whh, bhh),
            "jnp_autodiff": timeit(grad_fn(gru_scan_ref, (0, 1, 2)), xg, whh, bhh),
        },
        "maxerr": {
            "fwd": err_fwd,
            "bwd_residual_vs_oracle": maxerr(jit_resid_bwd(xg, whh, bhh, h_seq, dy)),
            "bwd_pallas_vs_oracle": maxerr(pallas_bwd()),
        },
        "recompute": recompute_elimination_report(
            gru_scan_op, gru_scan_oracle, xg, whh, bhh
        ),
    }
    report["gru-eicu"] = gru
    emit("kernel_gru_fwd_interp", gru["fwd_us"]["pallas_interpret"], f"maxerr={err_fwd:.2e}")
    for path, us in gru["bwd_us"].items():
        emit(f"kernel_gru_bwd_{path}", us, "")
    for path, us in gru["local_step_us"].items():
        emit(f"kernel_gru_step_{path}", us, "")

    # ---- SSD at a mamba2-130m-derived LM shape ---------------------------
    s_cfg = MAMBA_LM.ssm
    b, s, h, p, n = 2, lm_seq, lm_heads, s_cfg.head_dim, s_cfg.d_state
    chunk = min(64, s)
    nc = s // chunk
    xc = jnp.asarray(rng.normal(size=(b, nc, chunk, h, p)), jnp.float32)
    dtc = jax.nn.softplus(jnp.asarray(rng.normal(size=(b, nc, chunk, h)), jnp.float32))
    a_dec = -jnp.exp(jnp.asarray(rng.normal(size=(h,)) * 0.5, jnp.float32))
    cum = jnp.cumsum(dtc * a_dec[None, None, None, :], axis=2)
    bm = jnp.asarray(rng.normal(size=(b, nc, chunk, n)) * 0.5, jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, nc, chunk, n)) * 0.5, jnp.float32)
    dyc = jnp.asarray(rng.normal(size=(b, nc, chunk, h, p)), jnp.float32)
    ssd_args = (xc, dtc, cum, bm, cm)

    y_ref = ssd_chunk_scan_ref(*ssd_args)
    states = ssd_chunk_states_ref(*ssd_args)
    jit_fwd_kernel = jax.jit(lambda *a: ssd_chunk_scan_kernel(*a, interpret=True))
    fwd_kernel = lambda: jit_fwd_kernel(*ssd_args)
    err_fwd = float(jnp.max(jnp.abs(fwd_kernel() - y_ref)))
    _, oracle_vjp = jax.vjp(ssd_chunk_scan_ref, *ssd_args)
    g_oracle = oracle_vjp(dyc)
    maxerr = lambda got: max(
        float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, g_oracle)
    )
    jit_oracle_bwd = jax.jit(lambda ct: jax.vjp(ssd_chunk_scan_ref, *ssd_args)[1](ct))
    jit_resid_bwd = jax.jit(ssd_chunk_scan_bwd_ref)
    jit_pallas_bwd = jax.jit(lambda *a: ssd_chunk_scan_bwd(*a, interpret=True))
    pallas_bwd = lambda: jit_pallas_bwd(*ssd_args, states, dyc)

    ssd = {
        "shape": {
            "arch": MAMBA_LM.name, "batch": b, "seq": s, "heads": h,
            "head_dim": p, "d_state": n, "chunk": chunk,
        },
        "fwd_us": {
            "pallas_interpret": timeit(fwd_kernel),
            "jnp_ref": timeit(jax.jit(ssd_chunk_scan_ref), *ssd_args),
        },
        "bwd_us": {
            "oracle_vjp": timeit(jit_oracle_bwd, dyc),
            "residual_jnp": timeit(jit_resid_bwd, *ssd_args, states, dyc),
            "pallas_interpret": timeit(pallas_bwd),
        },
        "local_step_us": {
            "oracle_vjp": timeit(grad_fn(ssd_chunk_scan_oracle, (0, 1, 3, 4)), *ssd_args),
            "residual": timeit(grad_fn(ssd_chunk_scan, (0, 1, 3, 4)), *ssd_args),
            "jnp_autodiff": timeit(grad_fn(ssd_chunk_scan_ref, (0, 1, 3, 4)), *ssd_args),
        },
        "maxerr": {
            "fwd": err_fwd,
            "bwd_residual_vs_oracle": maxerr(jit_resid_bwd(*ssd_args, states, dyc)),
            "bwd_pallas_vs_oracle": maxerr(pallas_bwd()),
        },
        "recompute": recompute_elimination_report(
            ssd_chunk_scan, ssd_chunk_scan_oracle, *ssd_args
        ),
    }
    report["mamba2-lm"] = ssd
    emit("kernel_ssd_fwd_interp", ssd["fwd_us"]["pallas_interpret"], f"maxerr={err_fwd:.2e}")
    for path, us in ssd["bwd_us"].items():
        emit(f"kernel_ssd_bwd_{path}", us, "")
    for path, us in ssd["local_step_us"].items():
        emit(f"kernel_ssd_step_{path}", us, "")

    report["recompute_eliminated"] = bool(
        gru["recompute"]["recompute_eliminated"]
        and ssd["recompute"]["recompute_eliminated"]
    )
    assert report["recompute_eliminated"], (
        "residual backward still contains a forward-recompute scan: "
        f"gru={gru['recompute']}, ssd={ssd['recompute']}"
    )
    emit("kernel_recompute_eliminated", 0.0, report["recompute_eliminated"])
    Path(out_path).write_text(json.dumps(report, indent=2) + "\n")


# --------------------------------------------------------------------------
# roofline (reads the dry-run sweep)
# --------------------------------------------------------------------------

def bench_roofline() -> None:
    results = Path(__file__).resolve().parent / "results" / "dryrun"
    if not results.exists():
        emit("roofline_missing", 0.0, "run repro.launch.dryrun first")
        return
    for f in sorted(results.glob("*__single__baseline.json")):
        rec = json.loads(f.read_text())
        if "roofline" not in rec:
            continue
        r = rec["roofline"]
        dom_s = {"compute": r["compute_s"], "memory": r["memory_s"], "collective": r["collective_s"]}[r["dominant"]]
        useful = r["useful_flops_ratio"]
        emit(
            f"roofline_{rec['arch']}_{rec['shape']}",
            dom_s * 1e6,
            f"dominant={r['dominant']};useful={round(useful, 3) if useful else None}",
        )


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--seeds", type=int, nargs="+", default=[0, 1])
    ap.add_argument("--skip-paper", action="store_true")
    ap.add_argument(
        "--mode",
        choices=[
            "all", "cohort", "kernels", "paper", "paper189", "pipeline",
            "async", "service", "population", "privacy", "obs",
        ],
        default="all",
        help="'cohort' times sequential vs vectorized federated rounds only; "
        "'paper189' runs the full five-setting grid at 189 clients; "
        "'pipeline' compares rebuild-per-round vs device-resident staging; "
        "'async' simulates recruited vs all-clients time-to-target-loss "
        "under straggler latency models; 'service' probes the job-service "
        "envelope vs a direct Federation.run (merged into BENCH_pipeline.json); "
        "'population' sweeps streaming recruitment + LRU-pooled rounds from "
        "10^3 to 10^5 synthetic clients (BENCH_population.json); 'privacy' "
        "measures DP-SGD and secure-aggregation per-round overhead at 189 "
        "clients against the unprotected baseline (BENCH_privacy.json); "
        "'obs' probes tracer-off/tracer-on overhead in both engines at 189 "
        "clients and exports a sample Perfetto trace (BENCH_obs.json)",
    )
    ap.add_argument("--cohort-clients", type=int, nargs="+", default=[8, 32, 128])
    ap.add_argument("--paper189-rounds", type=int, default=3)
    ap.add_argument("--paper189-stays", type=int, default=189 * 23)
    ap.add_argument("--pipeline-rounds", type=int, default=4)
    ap.add_argument("--pipeline-stays", type=int, default=189 * 64)
    ap.add_argument(
        "--pipeline-chunk", type=int, default=48,
        help="pipeline: clients per vmapped call (4 chunks at 189 clients, "
        "so the double-buffered plan prefetch has chunks to overlap)",
    )
    ap.add_argument(
        "--async-flushes", type=int, default=8,
        help="async: buffered-aggregation flush budget per federation",
    )
    ap.add_argument(
        "--async-scale", type=float, default=0.05,
        help="async: cohort scale (heterogeneous synthetic eICU population)",
    )
    ap.add_argument(
        "--async-dropout", type=float, default=0.05,
        help="async: per-dispatch client dropout probability",
    )
    ap.add_argument(
        "--population-sizes", type=int, nargs="+",
        default=[1_000, 10_000, 100_000],
        help="population: synthetic client counts to sweep (CI uses a "
        "reduced scale)",
    )
    ap.add_argument(
        "--population-rounds", type=int, default=3,
        help="population: training rounds per size (round 0 pays compile)",
    )
    ap.add_argument(
        "--privacy-rounds", type=int, default=3,
        help="privacy: rounds per grid cell (round 0 pays compile)",
    )
    ap.add_argument(
        "--privacy-stays", type=int, default=189 * 8,
        help="privacy: total stays across the 189 clients (CI-scaled)",
    )
    ap.add_argument(
        "--privacy-noise", type=float, default=1.0,
        help="privacy: DP noise multiplier (sigma / clip_norm)",
    )
    ap.add_argument(
        "--obs-repeats", type=int, default=3,
        help="obs: alternating bare/off/on repeats per engine (floor estimator)",
    )
    ap.add_argument(
        "--mesh-auto", action="store_true",
        help="paper189/pipeline: shard the client axis over all visible devices",
    )
    ap.add_argument(
        "--kernel-reps", type=int, default=5,
        help="kernels: timed repetitions per path (CI uses a reduced count)",
    )
    ap.add_argument(
        "--kernel-gru-batch", type=int, default=128,
        help="kernels: GRU-eICU batch size (paper default 128)",
    )
    ap.add_argument(
        "--kernel-lm-seq", type=int, default=256,
        help="kernels: LM-shape sequence length (chunked at 64)",
    )
    ap.add_argument(
        "--kernel-lm-heads", type=int, default=4,
        help="kernels: LM-shape head count (mamba2-130m head_dim/d_state, "
        "heads reduced for CPU interpret mode)",
    )
    args = ap.parse_args()

    print("name,us_per_call,derived")
    t0 = time.time()
    if args.mode == "paper189":
        bench_paper189(
            rounds=args.paper189_rounds,
            total_stays=args.paper189_stays,
            mesh_auto=args.mesh_auto,
        )
        print(f"# total benchmark time: {time.time()-t0:.1f}s")
        return
    if args.mode == "pipeline":
        bench_pipeline(
            rounds=args.pipeline_rounds,
            total_stays=args.pipeline_stays,
            cohort_chunk=args.pipeline_chunk,
            mesh_auto=args.mesh_auto,
        )
        print(f"# total benchmark time: {time.time()-t0:.1f}s")
        return
    if args.mode == "service":
        bench_service(rounds=args.pipeline_rounds)
        print(f"# total benchmark time: {time.time()-t0:.1f}s")
        return
    if args.mode == "population":
        bench_population(
            populations=tuple(args.population_sizes),
            rounds=args.population_rounds,
        )
        print(f"# total benchmark time: {time.time()-t0:.1f}s")
        return
    if args.mode == "privacy":
        bench_privacy(
            rounds=args.privacy_rounds,
            total_stays=args.privacy_stays,
            noise_multiplier=args.privacy_noise,
        )
        print(f"# total benchmark time: {time.time()-t0:.1f}s")
        return
    if args.mode == "obs":
        bench_obs(repeats=args.obs_repeats)
        print(f"# total benchmark time: {time.time()-t0:.1f}s")
        return
    if args.mode == "async":
        bench_async(
            flushes=args.async_flushes,
            cohort_scale=args.async_scale,
            dropout=args.async_dropout,
        )
        print(f"# total benchmark time: {time.time()-t0:.1f}s")
        return
    if args.mode in ("all", "cohort"):
        bench_cohort(client_counts=tuple(args.cohort_clients))
    if args.mode in ("all", "kernels"):
        bench_kernels(
            reps=args.kernel_reps,
            gru_batch=args.kernel_gru_batch,
            lm_seq=args.kernel_lm_seq,
            lm_heads=args.kernel_lm_heads,
        )
        bench_roofline()
    if args.mode in ("all", "paper") and not args.skip_paper:
        bench_paper_tables(args.scale, args.seeds)
        bench_fig2(args.scale)
    print(f"# total benchmark time: {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
