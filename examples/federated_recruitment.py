"""End-to-end driver: the paper's full experiment, as the paper's kind
dictates — federated training of the LoS GRU across 189 hospital clients,
with and without client recruitment, several hundred local steps per model.

    PYTHONPATH=src python examples/federated_recruitment.py [--scale 0.3]

Produces the SC-vs-SRC comparison that is the paper's headline claim:
recruited federations match or beat standard FedAvg at a fraction of the
training cost.

Policy API
----------
Every paper setting is a 3-line policy combination for the
``repro.federated.api.Federation`` facade — a recruitment spec, a selection
spec, and an aggregator spec::

    FederationConfig(recruitment="nu-greedy",      # the paper's greedy rule
                     selection="uniform:0.1",      # 10% sampled per round
                     aggregator="fedavg")          # weighted averaging

Built-in registries (``repro.federated.available_policies()``):

* recruitment — ``nu-greedy`` (optionally ``nu-greedy:balanced`` /
  ``nu-greedy:gamma_dv,gamma_sa,gamma_th``), ``random-k:K`` (the
  recruitment control), ``top-n-samples:N``, ``all``
* selection — ``uniform[:frac|count]``, ``round-robin[:frac|count]``
  (deterministic rotation), ``loss-weighted[:frac|count]`` (sample by last
  observed local loss)
* aggregator — ``fedavg``, ``trimmed-mean[:trim]`` (coordinate-wise robust
  mean), ``hierarchical[:regions]`` (two-level FedAvg: regional
  sub-federations psum first — the seed of the multi-pod aggregation tier)

``--recruitment`` / ``--selection`` / ``--aggregator`` below override the
per-setting defaults with any spec; user-defined policies are ~20 lines
(see ``examples/custom_policy.py``).  The legacy ``FederatedServer`` /
``FederatedConfig`` remain as deprecation shims over this facade.

Paper-scale runs
----------------
The full 189-client experiment grid (all five section-6 model settings,
both engines, per-setting round times, the donated-vs-plain buffer memory
probe) is a benchmark mode of its own and writes ``BENCH_paper189.json``:

    PYTHONPATH=src python benchmarks/run.py --mode paper189

To push the cohort's client axis through the multi-device ``shard_map``
path (CI's second matrix leg does this on every PR), force host devices
before jax initializes and ask for the auto data mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        PYTHONPATH=src python benchmarks/run.py --mode paper189 --mesh-auto

Device-resident staging
-----------------------
By default the federation's client train arrays are uploaded to device
**once** (``--staging resident``); each round then stages only a compact
``(clients, steps, batch)`` int32 index plan — the batch gather happens on
device, inside the jitted round, and chunk k+1's plan is built/uploaded on
a background thread while chunk k trains (disable with ``--no-prefetch``).
``--staging rebuild`` restores the re-materialize-and-re-upload path each
round (PR 2's behavior, kept as the staging reference oracle; both paths
draw the same RNG stream and agree within 1e-5).  The two are compared
head to head by ``python benchmarks/run.py --mode pipeline``, which writes
``BENCH_pipeline.json`` (per-round staged bytes drop ~880x, rounds run
1.6-1.8x faster at 189 clients on CI hardware).

This driver accepts the same engine controls (``--engine``,
``--cohort-chunk``, ``--mesh auto``, ``--no-donate``, ``--staging``,
``--no-prefetch``) plus the policy overrides (``--selection``,
``--aggregator``) for one-off runs.

Async federation & straggler simulation
---------------------------------------
The paper's headline is a *training time* claim, and in a real deployment
the dominant cost is waiting for slow or flaky ICUs — which a synchronous
round barrier can't express.  ``repro.federated.runtime`` adds an
event-driven twin of the facade: a deterministic virtual-clock scheduler
dispatches client tasks under pluggable per-client latency and dropout
models (``latency="constant" | "lognormal:0.5" | "pareto:1.5" | "trace"``,
``dropout="bernoulli:0.1"`` — same registry grammar as the policies), and
buffered aggregators fold completions into new parameter versions with
polynomial staleness-decay weights::

    AsyncFederationConfig(recruitment="nu-greedy",
                          aggregator="fedbuff:16",        # flush every 16 updates
                          latency="pareto:1.2",           # heavy-tailed stragglers
                          dropout=0.05)
    AsyncFederation(cfg, clients, loss_fn, opt).run(params)

``"fedbuff:K"`` is buffered async FedAvg (K = all participants + zero
latency spread reproduces synchronous FedAvg to 1e-5 — the tier-1 parity
gate); ``"hierarchical-async:R"`` promotes the sync ``"hierarchical:R"``
aggregator to stale-tolerant cross-pod combines (regions merge whenever
they finish).  Each task still runs through the unchanged jitted /
donated / shard_map cohort engine — the runtime only reorders which cohort
chunks train against which parameter version.  Flush records carry
``virtual_time`` / ``staleness``, so recruited-vs-all federations compare
on *simulated time-to-target-loss*: see ``examples/async_federation.py``
and ``python benchmarks/run.py --mode async`` (-> ``BENCH_async.json``).
"""

import argparse
import json

from repro.experiments.paper import ExperimentConfig, build_cohort, run_setting
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.3, help="cohort scale (1.0 = 89k stays)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--engine", choices=["vectorized", "sequential"], default="vectorized",
        help="vectorized = whole cohort per round in one jitted vmap",
    )
    ap.add_argument(
        "--cohort-chunk", type=int, default=None,
        help="vectorized engine: clients per vmapped call (bounds memory)",
    )
    ap.add_argument(
        "--mesh", choices=["auto"], default=None,
        help="vectorized engine: shard the client axis over all visible devices",
    )
    ap.add_argument(
        "--no-donate", action="store_true",
        help="vectorized engine: keep round buffers alive (memory diffing)",
    )
    ap.add_argument(
        "--staging", choices=["resident", "rebuild"], default="resident",
        help="resident = client data uploaded once, rounds stage int32 index "
        "plans; rebuild = full schedule re-uploaded every round",
    )
    ap.add_argument(
        "--no-prefetch", action="store_true",
        help="resident staging: build chunk plans inline instead of on the "
        "double-buffering background thread",
    )
    ap.add_argument(
        "--selection", default=None,
        help="override the per-round selection policy spec (e.g. "
        "'round-robin:0.1', 'loss-weighted:0.1'); default derives the "
        "paper's uniform sampling from the setting",
    )
    ap.add_argument(
        "--aggregator", default="fedavg",
        help="aggregation policy spec ('fedavg', 'trimmed-mean:0.1', "
        "'hierarchical:4')",
    )
    args = ap.parse_args()

    # paper-faithful settings, trained on the selected engine
    exp = ExperimentConfig(
        cohort_scale=args.scale,
        engine=args.engine,
        cohort_chunk=args.cohort_chunk,
        mesh=args.mesh,
        donate_buffers=not args.no_donate,
        staging=args.staging,
        prefetch=not args.no_prefetch,
        selection=args.selection,
        aggregator=args.aggregator,
    )
    print(f"engine: {args.engine}")
    cohort = build_cohort(exp, seed=args.seed)
    print(f"cohort: {len(cohort.y):,} stays, {cohort.num_hospitals} hospitals")

    results = {}
    for setting in ("federated-sc", "federated-src"):
        print(f"--- {setting} (15 rounds x 4 local epochs) ---")
        out = run_setting(setting, exp, cohort, seed=args.seed)
        results[setting] = out
        print(
            f"  federation={out['federation_size']} recruited={out['recruited']} "
            f"local_steps={out['local_steps']} tau={out['tau_s']:.1f}s"
        )
        print(f"  metrics: {json.dumps({k: round(v, 4) for k, v in out['metrics'].items()})}")

    sc, src = results["federated-sc"], results["federated-src"]
    speedup = sc["tau_s"] / src["tau_s"]
    print(
        f"\nRecruited federation (SRC): {src['recruited']} of {sc['federation_size']} clients, "
        f"{speedup:.2f}x faster than standard FedAvg (SC), "
        f"MSLE {src['metrics']['msle']:.4f} vs {sc['metrics']['msle']:.4f}"
    )


if __name__ == "__main__":
    main()
