#!/usr/bin/env python3
"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload gru-xla.ac --seed 12345 --seconds 30 --trace 0

Run from the root of a checkout.  The cell, its configuration, traffic,
limits and metric readers are found by name (see ``bench/files.py``).  The
job's ``--seed`` sets its initial weights, per-round client sampling,
batch shuffles and dropout keys; the cohort comes from the configuration's
pinned data seed, so every seed trains the same hospitals with the same
shapes.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a short profiled window.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number of the output comparison beside its limit); the
same checks are the last lines of standard error.  Exits 3 without a
result when JAX finds no TPU or fewer chips than the cell needs.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# JAX's persistent compilation cache lives at a fixed path inside the
# checkout, whatever the environment says, and keeps every program, so
# only a checkout's first run of a cell compiles.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]

EXIT_NO_ACCELERATOR = 3


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from files import Bench
    from harness import NoAccelerator, run_cell

    bench = Bench(ROOT)
    try:
        result, _ = run_cell(
            bench, args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START
        )
    except NoAccelerator as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    print(f"correct={result['correct']}", file=sys.stderr)
    for name, check in result["checks"].items():
        print(f"check {name} value={check['value']!r} limit={check['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
