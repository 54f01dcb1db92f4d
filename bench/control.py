#!/usr/bin/env python3
"""Readings that set a cell's output limits, all in one process.

    python3 bench/control.py --workload gru-xla.ac --seeds 101,102,103 [--program-seconds 5]

For each seed:

* ``program``: one run of the cell through the benchmark's own path
  (``harness.run_cell``, a short window), giving the sound program's
  readings of every compared number (the lower readings);
* ``control``: the plain reference computed in bfloat16, put in the
  program's place, against the reference at the configuration's stated
  precision (its readings must fail a limit; the least of them is an
  upper reading);
* ``half_batch``: the reference with half of every minibatch left out of
  the loss, in the program's place (a fault the check must catch).

Both stand-ins compute the same rounds as the program's run: the three
compared rounds and, where the run made one, its slice-path round.  A step
that leaves the state unchanged reads exactly 1 on ``update_gap`` and
``change_gap`` (the program's norms are 0) and needs no run.  Lines go to
standard output as JSON, one per seed and kind.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]


def client_gaps(outputs: dict, ref: dict) -> list[list[float]]:
    """Per round (the compared rounds, then the slice round), each
    participant's relative loss gap to the reference."""
    rounds = list(zip(outputs["client_losses"], ref["client_losses"]))
    if "extra" in ref and outputs.get("extra") is not None:
        rounds.append((outputs["extra"]["client_losses"], ref["extra"]["client_losses"]))
    return [[abs(a - b) / abs(b) for a, b in zip(got, want)] for got, want in rounds]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--program-seconds", type=float, default=5.0)
    args = parser.parse_args(argv)

    import compare
    from files import Bench
    from harness import COMPARED_ROUNDS, run_cell

    bench = Bench(ROOT)
    cell = bench.cell(args.workload)
    reference = bench.reference(cell.config["reference"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        result, inner = run_cell(bench, cell.name, seed, args.program_seconds, False, t_start=t0)
        clients, replay, ref = inner["clients"], inner["replay"][:COMPARED_ROUNDS], inner["reference"]
        extra = inner["program"].get("extra")
        print(json.dumps({"seed": seed, "kind": "program", "correct": result["correct"],
                          "readings": inner["readings"],
                          "client_gaps": client_gaps(inner["program"], ref),
                          "metrics": {k: v["value"] for k, v in result["metrics"].items()}}), flush=True)
        gc.collect()
        t_ref = time.perf_counter()
        kinds = (
            ("control", {"mode": "bfloat16"}),
            ("half_batch", {"fault": "half_batch", "mode": cell.config["matmul_precision"]}),
        )
        for kind, kw in kinds:
            other = reference.train(
                cell.config, cell.traffic, clients, seed, COMPARED_ROUNDS, device=inner["device"],
                extra_round=None if extra is None else extra["ids"], **kw,
            )
            values = compare.readings(compare.as_program(other, replay), ref, replay)
            print(json.dumps({"seed": seed, "kind": kind, "readings": values,
                              "client_gaps": client_gaps(other, ref)}), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f}s (stand-ins {time.perf_counter() - t_ref:.1f}s)",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
