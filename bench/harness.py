"""One benchmark run of one cell: set-up, measured window, output check.

A run drives the system as its users do.  The cell's configuration and
traffic files become a job spec, which goes to
``repro.launch.federation_service.submit_job`` (spec -> ``Federation.run``
-> ``CohortTrainer`` -> the GRU).  A subscriber on the job's record stream
drives the run's phases:

* set-up: rounds 0-3 of the job.  Round 0 compiles (or loads the compiled
  round from the persistent cache).  At round 1's record the service's
  checkpoint holds the parameters after round 0, and at round 3's record
  those after round 2: the output check compares these and the first
  three rounds' losses with the plain reference.
* before the window, where the traffic samples part of the federation:
  one round from the initial weights over a contiguous run of resident
  clients, through the engine's own ``train_cohort``.  The engine selects
  such a run with a static slice, one compiled program per start row, so
  the harness also runs every start that the seed's draws (replayed for
  the rounds the window can hold) will need: nothing is compiled or
  loaded inside the window.  The output check compares that round too.
* window: from the end of round 3's record, whole rounds until
  ``seconds`` have passed; at the first round boundary after that the
  subscriber raises, which stops the job through the service's own
  exception path.  A window round is everything between two records:
  the previous round's checkpoint, selection, staging, the jitted round,
  the loss readback, and the records and metrics writes.
* with ``trace``: the job's ``repro.obs`` tracer is on and a profiler
  window of at most ``TRACE_SECONDS`` replaces the timed one.

After the window the device's memory peak is read, the job's state is
freed, and the plain reference recomputes the first three rounds and the
slice round.

``ProgramTap`` is the benchmark's other window into the run: it wraps two
public methods of the cohort engine, ``attach_device_cohort`` and
``train_cohort``, to see the recruited federation, the initial weights and
the per-client losses of the compared rounds.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import statistics
import sys
import time

import numpy as np

import compare
import trace_reduce
from cohort_data import train_clients
from files import Bench, Cell

SETUP_ROUNDS = 4
COMPARED_ROUNDS = 3
TRACE_SECONDS = 2.0
# The job runs until the window closes it; this only has to outlast any window.
JOB_ROUNDS = 10**7
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class NoAccelerator(RuntimeError):
    pass


class WindowClosed(Exception):
    """Raised by the subscriber at the first round boundary after the window."""


class CompileCounter:
    """Counts programs built (compiled, or loaded from the persistent cache)
    and persistent-cache hits while registered."""

    def __init__(self):
        self.requests = 0
        self.hits = 0
        self.seconds = 0.0

    def _on_event(self, event, **kw):
        if event == CACHE_HIT:
            self.hits += 1

    def _on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.requests += 1
            self.seconds += duration

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring

        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_duration)


class ProgramTap:
    """Sees the cohort engine's federation, initial weights and per-client
    losses through its public methods, while entered.  Adds one Python call
    per round."""

    def __init__(self, keep_rounds: int):
        self.keep_rounds = keep_rounds
        self.trainer = None
        self.federation: list = []  # the engine's client objects, in resident row order
        self.p0 = None
        self.steps_per_epoch = None
        self.client_losses: list[list[float]] = []

    def __enter__(self):
        from repro.federated.cohort import CohortTrainer

        self._saved = (CohortTrainer.attach_device_cohort, CohortTrainer.train_cohort)
        attach, train = self._saved
        tap = self

        def attach_device_cohort(trainer, clients, *args, **kwargs):
            tap.trainer, tap.federation = trainer, list(clients)
            return attach(trainer, clients, *args, **kwargs)

        def train_cohort(trainer, params, *args, **kwargs):
            out = train(trainer, params, *args, **kwargs)
            if len(tap.client_losses) < tap.keep_rounds:
                if tap.p0 is None:
                    tap.p0, tap.steps_per_epoch = params, kwargs.get("steps_per_epoch")
                tap.client_losses.append([float(x) for x in out[1]])
            return out

        CohortTrainer.attach_device_cohort = attach_device_cohort
        CohortTrainer.train_cohort = train_cohort
        self._cls = CohortTrainer
        return self

    def __exit__(self, *exc):
        self._cls.attach_device_cohort, self._cls.train_cohort = self._saved


def flat_params(tree) -> dict[str, np.ndarray]:
    """``{"layers/0/w_ih": array}``, the leaf names the service's checkpoint uses."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {
        "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path): np.asarray(leaf, np.float32)
        for path, leaf in flat
    }


def chain_keys(seed: int, count: int) -> list:
    """One key per client, split in turn off ``key(seed)``."""
    import jax

    chain, keys = jax.random.key(seed), []
    for _ in range(count):
        chain, key = jax.random.split(chain)
        keys.append(key)
    return keys


def slice_round(tap: ProgramTap, upcoming: list[list[int]], seed: int) -> dict | None:
    """Run the engine's static-slice path before the window; return the
    checked round's ids, parameters and per-client losses.

    ``upcoming`` is the participant ids of the rounds the window can hold.
    Every contiguous run among them is a start row the window would
    otherwise compile or load; each runs once here, from the initial
    weights.  The checked round is the first such start, else row 0.  None
    where every round takes the whole federation (no slice path).
    """
    fed = tap.federation
    count = len(upcoming[0])
    if count >= len(fed):
        return None
    row = {c.client_id: i for i, c in enumerate(fed)}
    starts: list[int] = []
    for part in upcoming:
        rows = [row[int(c)] for c in part]
        if rows == list(range(rows[0], rows[0] + len(rows))) and rows[0] not in starts:
            starts.append(rows[0])
    checked = starts[0] if starts else 0
    out = None
    for start in sorted(set(starts) | {checked}):
        clients = fed[start : start + count]
        params, losses, _ = tap.trainer.train_cohort(
            tap.p0, clients, np.random.default_rng(seed), chain_keys(seed, count),
            steps_per_epoch=tap.steps_per_epoch,
        )
        if start == checked:
            out = {
                "ids": [int(c.client_id) for c in clients],
                "params": flat_params(params),
                "client_losses": [float(x) for x in losses],
            }
    print(f"slice_round checked_start={checked} window_starts={starts}", file=sys.stderr)
    return out


def check_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"JAX's default backend is {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def job_spec(cell: Cell, seed: int, trace: bool) -> dict:
    c, t = cell.config, cell.traffic
    m = c["model"]
    o = c["optimizer"]
    return {
        "name": cell.name,
        "mode": "sync",
        "rounds": JOB_ROUNDS,
        "seed": int(seed),
        "local_epochs": int(t["local_epochs"]),
        "batch_size": int(t["batch_size"]),
        "recruitment": t["recruitment"],
        "selection": t["selection"],
        "aggregator": t["aggregator"],
        **c["implementation"],
        "data": dict(c["data"]),
        "model": {k: m[k] for k in ("hidden_dim", "num_layers", "dropout", "use_pallas")},
        "optimizer": {k: o[k] for k in ("learning_rate", "weight_decay", "b1", "b2", "eps")},
        "observability": {"trace": True} if trace else None,
    }


def read_checkpoint_params(ckpt_dir: str) -> tuple[int, dict[str, np.ndarray]]:
    """(round_index, {leaf path: array}) of the service's sync snapshot."""
    with open(os.path.join(ckpt_dir, "snapshot.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    prefix = "tree:params:"
    with np.load(os.path.join(ckpt_dir, "snapshot.npz")) as z:
        params = {
            key[len(prefix):]: np.asarray(z[f"a{i}"], np.float32)
            for i, key in enumerate(manifest["keys"])
            if key.startswith(prefix)
        }
    return int(manifest["state"]["round_index"]), params


@dataclasses.dataclass
class Window:
    """The subscriber: set-up bookkeeping, then the timed rounds."""

    seconds: float
    ckpt_dir: str
    counter: CompileCounter
    profile_dir: str | None = None
    before_open: object = None  # called with the window, after set-up
    records: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)  # perf_counter at each record
    checkpoints: dict = dataclasses.field(default_factory=dict)
    t_open: float | None = None
    t_close: float | None = None
    compiles_at_open: int = 0
    window_compiles: int | None = None

    def __call__(self, record) -> None:
        now = time.perf_counter()
        self.records.append(record)
        self.times.append(now)
        k = int(record.round_index)
        if self.profile_dir is not None and self.t_open is not None:
            import jax.profiler

            with jax.profiler.TraceAnnotation("bench.round_boundary"):
                self._after(k, now)
        else:
            self._after(k, now)

    def _after(self, k: int, now: float) -> None:
        if k in (1, 3):
            got, params = read_checkpoint_params(self.ckpt_dir)
            if got != k:
                raise RuntimeError(f"checkpoint holds round {got}, expected {k}")
            self.checkpoints[k] = params
        if k == SETUP_ROUNDS - 1:
            if self.before_open is not None:
                self.before_open(self)
            self._open()
        elif k >= SETUP_ROUNDS and now - self.t_open >= self.seconds:
            self._close()

    def _open(self) -> None:
        if self.profile_dir is not None:
            import jax.profiler

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.profile_dir, profiler_options=options)
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_OPEN):
                pass
        self.compiles_at_open = self.counter.requests
        self.t_open = time.perf_counter()

    def _close(self) -> None:
        self.t_close = time.perf_counter()
        self.window_compiles = self.counter.requests - self.compiles_at_open
        if self.profile_dir is not None:
            import jax.profiler

            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_CLOSE):
                pass
            jax.profiler.stop_trace()
        raise WindowClosed()

    @property
    def window_records(self) -> list:
        return self.records[SETUP_ROUNDS:]

    def round_seconds(self) -> list[float]:
        """Seconds between consecutive records, for the window's rounds."""
        t = self.times[SETUP_ROUNDS - 1 :]
        return [b - a for a, b in zip(t, t[1:])]


def memory_peak_bytes(devices) -> int:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
    return max(peaks)


def _p95(values: list[float]) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def end_to_end(cell: Cell, win: Window, sizes: dict[int, int], setup_s: float) -> dict:
    span = win.t_close - win.t_open
    rounds = len(win.window_records)
    examples = sum(
        sizes[int(c)] * int(cell.traffic["local_epochs"])
        for r in win.window_records
        for c in r.participant_ids
    )
    values = {
        "round_s": span / rounds,
        "examples_per_s": examples / span,
        "round_p95_s": _p95(win.round_seconds()),
        "setup_s": setup_s,
    }
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in cell.end_to_end}


@dataclasses.dataclass
class TracedRun:
    """What a per-layer metric reader gets (``bench/metrics/<name>.py``)."""

    bench: Bench
    cell: Cell
    peaks: dict
    events: dict
    spans: list  # repro.obs trace.json events of the job
    window_rounds: list  # round indices inside the window
    examples: int  # real (unpadded) training examples in the window
    window_compiles: int
    window_s: float
    busy_s: float

    def roofline(self, kernel: str) -> float | None:
        """Percent of the roofline for every call of ``kernel`` in the window.

        The kernel's file recognises its device op by the op's HLO text and
        gives the FLOPs and bytes of a call from the shapes in that text.
        """
        mod = self.bench.kernel(kernel)
        config = self.cell.config
        calls = trace_reduce.ops_in_window(self.events, lambda name, detail: mod.matches(config, detail))
        if not calls:
            return None
        ideal = 0.0
        seconds = 0.0
        bound = {"flops": 0, "bytes": 0}
        for name, s, e, detail in calls:
            cost = mod.cost(config, detail)
            if cost is None:
                return None
            t_flops = cost["flops"] / self.peaks["bf16_flops_per_s"]
            t_bytes = cost["bytes"] / self.peaks["hbm_bytes_per_s"]
            bound["flops" if t_flops >= t_bytes else "bytes"] += 1
            ideal += max(t_flops, t_bytes)
            seconds += (e - s) / 1e9
        print(
            f"roofline {kernel}: calls={len(calls)} kernel_s={seconds!r} ideal_s={ideal!r} "
            f"bound_by={max(bound, key=bound.get)}",
            file=sys.stderr,
        )
        return 100.0 * ideal / seconds


def per_layer(run: TracedRun) -> dict:
    out = {}
    for m in run.cell.per_layer:
        value = run.bench.metric(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _obs_spans(run_dir: str) -> list:
    path = os.path.join(run_dir, "trace.json")
    with open(path, encoding="utf-8") as fh:
        return [e for e in json.load(fh)["traceEvents"] if e.get("ph") == "X"]


def run_cell(
    bench: Bench,
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_start: float,
    run_dir: str | None = None,
) -> tuple[dict, dict]:
    """One run of cell ``name``; returns (result line, internals).

    ``internals`` carries the program's and the reference's outputs for
    the control script.
    """
    import jax

    cell = bench.cell(name)
    devices = check_devices(cell.chips)
    t_runtime = time.perf_counter()
    from repro.launch.federation_service import submit_job

    run_dir = run_dir or os.path.join(bench.root, "chiprun_out", "bench", name)
    shutil.rmtree(run_dir, ignore_errors=True)
    profile_dir = os.path.join(run_dir, "profile") if trace else None
    spec = job_spec(cell, seed, trace)
    limit = min(seconds, TRACE_SECONDS) if trace else seconds
    reference = bench.reference(cell.config["reference"])
    slice_out: dict = {}

    def before_open(win: Window) -> None:
        t0 = time.perf_counter()
        fed = tap.federation
        fastest = min(b - a for a, b in zip(win.times, win.times[1:]))
        rounds = SETUP_ROUNDS + math.ceil(1.25 * win.seconds / fastest) + 8
        upcoming = reference.draws(
            cell.traffic, np.array([c.client_id for c in fed]), {c.client_id: c.n_train for c in fed}, seed, rounds
        )[SETUP_ROUNDS:]
        slice_out["round"] = slice_round(tap, upcoming, seed)
        slice_out["seconds"] = time.perf_counter() - t0

    with CompileCounter() as counter, ProgramTap(COMPARED_ROUNDS) as tap:
        win = Window(limit, os.path.join(run_dir, "checkpoint"), counter, profile_dir, before_open)
        t_submit = time.perf_counter()
        try:
            submit_job(spec, run_dir, subscribers=(win,))
        except WindowClosed:
            pass
        else:
            raise RuntimeError("the job ended before the window closed")
    setup_s = win.t_open - t_start
    peak = memory_peak_bytes(devices)
    r0 = win.records[0]
    t_round0 = win.times[0] - r0.round_time_s
    print(
        "setup_breakdown "
        + json.dumps(
            {
                "runtime_start_s": t_runtime - t_start,
                "job_build_s": t_round0 - t_submit,
                "round0_s": r0.round_time_s,
                "rounds_1_to_3_s": win.times[SETUP_ROUNDS - 1] - win.times[0],
                "slice_rounds_s": slice_out["seconds"],
                "compile_requests": counter.requests,
                "persistent_cache_hits": counter.hits,
                "compile_s": counter.seconds,
            }
        ),
        file=sys.stderr,
    )
    checked = slice_out["round"]
    program = {
        "losses": [float(r.mean_local_loss) for r in win.records[:COMPARED_ROUNDS]],
        "client_losses": tap.client_losses,
        "p1": win.checkpoints[1],
        "p3": win.checkpoints[3],
        "participants": [[int(c) for c in r.participant_ids] for r in win.records],
    }
    if checked is not None:
        program["extra"] = checked
    # The tap and the job's closures hold the trainer and with it the
    # resident cohort; free them before the reference.
    tap.trainer = tap.federation = tap.p0 = None
    gc.collect()

    clients = train_clients(cell.config["data"])
    sizes = {c.client_id: c.n for c in clients}
    replay = reference.participants(cell.traffic, clients, seed, len(win.records))
    t_ref = time.perf_counter()
    ref = reference.train(
        cell.config, cell.traffic, clients, seed, COMPARED_ROUNDS,
        mode=cell.config["matmul_precision"], device=devices[0],
        extra_round=None if checked is None else checked["ids"],
    )
    print(f"reference_s={time.perf_counter() - t_ref!r}", file=sys.stderr)
    readings = compare.readings(program, ref, replay)
    checks, correct = compare.judge(readings, cell.limits)
    failed = sum(
        1
        for r, want in zip(win.records, replay)
        if not math.isfinite(r.mean_local_loss) or [int(c) for c in r.participant_ids] != want
    )
    dev = devices[0]
    result = {
        "correct": bool(correct and failed == 0),
        "attempted": len(win.records),
        "failed": failed,
        "metrics": {},
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": peak,
        },
    }
    if trace:
        t_reduce = time.perf_counter()
        kernels = [bench.kernel(k) for k in bench.kernel_names()]
        patterns = "|".join(f"(?:{k.DETAIL_PATTERN})" for k in kernels if hasattr(k, "DETAIL_PATTERN"))
        events = trace_reduce.events_from_xplane(trace_reduce.find_xplane(profile_dir), patterns or None)
        shutil.rmtree(profile_dir)
        load = trace_reduce.busy(events)
        traced = TracedRun(
            bench=bench,
            cell=cell,
            peaks=bench.peaks(dev.device_kind),
            events=events,
            spans=_obs_spans(run_dir),
            window_rounds=[int(r.round_index) for r in win.window_records],
            examples=sum(
                sizes[int(c)] * int(cell.traffic["local_epochs"])
                for r in win.window_records
                for c in r.participant_ids
            ),
            window_compiles=win.window_compiles,
            window_s=load["window_s"],
            busy_s=load["busy_s"],
        )
        result["metrics"] = per_layer(traced)
        result["device"].update(busy_s=load["busy_s"], window_s=load["window_s"])
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(events),
            "idle_gaps": trace_reduce.idle_gaps(events),
        }
        print(
            f"trace device_ops={sum(len(v) for v in events['device'].values())} "
            f"host_events={len(events['host'])} reduce_s={time.perf_counter() - t_reduce!r}",
            file=sys.stderr,
        )
    else:
        result["metrics"] = end_to_end(cell, win, sizes, setup_s)
        print(
            f"window rounds={len(win.window_records)} seconds={win.t_close - win.t_open!r} "
            f"window_compiles={win.window_compiles} round_s_median="
            f"{statistics.median(win.round_seconds())!r}",
            file=sys.stderr,
        )
    result["checks"] = checks
    return result, {"program": program, "reference": ref, "replay": replay, "clients": clients, "device": devices[0], "readings": readings}
