"""Observability tier: tracer, metrics registry, control-plane streams.

The contract under test: a traced run's spans reconcile *exactly* with its
round records (the round/flush span reuses the record's own measured wall
time), the Chrome export is Perfetto-loadable JSON with both clock
processes, the typed registry absorbs the engines' ad-hoc stat dicts into
one stable ``snapshot()`` schema that streams as ``metrics.jsonl`` and
survives kill-and-resume, the staging/pool counters are exact (seeded
multi-chunk rounds, both staging modes), and ``RoundRecord`` serializes
the canonical ``round_time_s`` name while still loading legacy
``wall_time_s`` streams.
"""

import dataclasses
import json
import math
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import ArrayDataset, ClientDataset
from repro.federated.api import Federation, FederationConfig, RoundRecord
from repro.federated.runtime import AsyncFederation, AsyncFederationConfig
from repro.federated.staging import StagingPipeline
from repro.models.gru import GRUConfig, init_gru, make_loss_fn
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.profile import (
    CompileWatcher,
    ObservabilityConfig,
    RoundProfiler,
    resolve_observability,
)
from repro.obs.report import render_report
from repro.obs.trace import NULL_TRACER, NullTracer, Tracer, resolve_tracer
from repro.optim.adamw import AdamW

SEQ_LEN, FEAT = 3, 5


def make_clients(count, rng, lo=2, hi=18):
    clients = []
    for i, n in enumerate(rng.integers(lo, hi, count)):
        x = rng.normal(size=(int(n), SEQ_LEN, FEAT)).astype(np.float32)
        y = rng.uniform(0.5, 20.0, size=int(n)).astype(np.float32)
        ds = ArrayDataset(x, y)
        clients.append(ClientDataset(client_id=i, train=ds, val=ds))
    return clients


@pytest.fixture(scope="module")
def setup():
    cfg = GRUConfig(input_dim=FEAT, hidden_dim=2, num_layers=1)
    clients = make_clients(10, np.random.default_rng(0))
    return clients, make_loss_fn(cfg), init_gru(jax.random.key(1), cfg)


class _RecordedAnnotation:
    def __init__(self, log, name, **kwargs):
        self.name, self.kwargs, self.state = name, kwargs, "made"
        log.append(self)

    def __enter__(self):
        self.state = "open"
        return self

    def __exit__(self, *exc):
        self.state = "closed"


def record_annotations(monkeypatch) -> list:
    """Replace ``jax.profiler.TraceAnnotation`` with a recorder."""
    import jax.profiler

    log: list = []
    monkeypatch.setattr(
        jax.profiler, "TraceAnnotation", lambda name, **kw: _RecordedAnnotation(log, name, **kw)
    )
    return log


# ---------------------------------------------------------------------------
# tracer unit behaviour
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_records_nested(self):
        tracer = Tracer()
        with tracer.span("outer", track="t", n=1):
            with tracer.span("inner", track="t"):
                pass
        spans = tracer.spans()
        # Inner exits first, so it lands first in the ring.
        assert [s.name for s in spans] == ["inner", "outer"]
        inner, outer = spans
        assert outer.ts <= inner.ts
        assert outer.ts + outer.dur >= inner.ts + inner.dur
        assert outer.args == {"n": 1}

    def test_ring_is_bounded_and_counts_drops(self):
        tracer = Tracer(capacity=4)
        for i in range(10):
            tracer.instant("tick", ts=float(i))
        events = tracer.events()
        assert len(events) == 4
        assert tracer.dropped == 6
        assert [e.ts for e in events] == [6.0, 7.0, 8.0, 9.0]

    def test_capacity_validation(self):
        with pytest.raises(ValueError, match="capacity"):
            Tracer(capacity=0)

    def test_span_enters_one_annotation_per_span(self, monkeypatch):
        entered = record_annotations(monkeypatch)
        tracer = Tracer()
        with tracer.span("outer", track="t", round=3, kind="x", latency=0.5):
            with tracer.span("inner"):
                assert [a.state for a in entered] == ["open", "open"]
        assert [(a.name, a.kwargs, a.state) for a in entered] == [
            # only int and str args become the annotation's stats
            ("repro.outer", {"round": 3, "kind": "x"}, "closed"),
            ("repro.inner", {}, "closed"),
        ]
        # trace.json's events are what they were: the span keeps every arg
        assert tracer.spans("outer")[0].args == {"round": 3, "kind": "x", "latency": 0.5}

    def test_annotation_closes_when_the_body_raises(self, monkeypatch):
        entered = record_annotations(monkeypatch)
        tracer = Tracer()
        with pytest.raises(RuntimeError, match="boom"):
            with tracer.span("checkpoint", round=1):
                raise RuntimeError("boom")
        assert [(a.name, a.state) for a in entered] == [("repro.checkpoint", "closed")]
        assert [s.name for s in tracer.spans()] == ["checkpoint"]

    def test_null_tracer_enters_no_annotation(self, monkeypatch):
        from repro.obs.trace import _NULL_CONTEXT

        entered = record_annotations(monkeypatch)
        ctx = NULL_TRACER.span("select", round=0)
        assert ctx is _NULL_CONTEXT
        with ctx:
            pass
        assert entered == []

    def test_null_tracer_is_inert(self):
        null = resolve_tracer(None)
        assert null is NULL_TRACER
        assert isinstance(null, NullTracer)
        assert not null.enabled
        with null.span("x", n=1):
            pass
        null.complete("x", start=0.0, dur=1.0)
        null.instant("x")
        null.flow_start("x", 0, ts=0.0)
        null.flow_end("x", 0, ts=0.0, track="t")
        assert null.events() == []
        tracer = Tracer()
        assert resolve_tracer(tracer) is tracer

    def test_summary_totals(self):
        tracer = Tracer()
        tracer.complete("a", start=0.0, dur=1.0)
        tracer.complete("a", start=2.0, dur=3.0)
        tracer.complete("b", start=0.0, dur=5.0, clock="virtual")
        summary = tracer.summary()
        assert summary["host"]["a"] == {"count": 2, "total_s": 4.0}
        assert summary["virtual"]["b"]["total_s"] == 5.0

    def test_thread_safety_no_loss_under_capacity(self):
        tracer = Tracer(capacity=10_000)

        def push(tag):
            for i in range(1000):
                tracer.instant(tag, ts=float(i))

        threads = [threading.Thread(target=push, args=(f"t{k}",)) for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(tracer.events()) == 4000
        assert tracer.dropped == 0


class TestChromeExport:
    def test_export_structure(self, tmp_path):
        tracer = Tracer()
        with tracer.span("round", round=0):
            pass
        tracer.complete(
            "task", start=1.0, dur=2.0, track="client:3", clock="virtual",
            latency=np.float64(2.0), clients=np.array([3]),
        )
        fid = tracer.new_flow_id()
        tracer.flow_start("task", fid, ts=1.0, track="server")
        tracer.flow_end("task", fid, ts=3.0, track="client:3")
        tracer.instant("flush", ts=3.0, clock="virtual")
        path = tracer.export_chrome(str(tmp_path / "trace.json"))
        doc = json.loads(open(path).read())
        events = doc["traceEvents"]
        assert doc["displayTimeUnit"] == "ms"
        # Both clock processes are named.
        procs = {e["pid"]: e["args"]["name"] for e in events if e["name"] == "process_name"}
        assert procs == {1: "host clock", 2: "virtual clock"}
        # The virtual task span sits on its per-client track, in microseconds.
        task = next(e for e in events if e["name"] == "task" and e["ph"] == "X")
        assert task["pid"] == 2
        assert task["ts"] == pytest.approx(1e6)
        assert task["dur"] == pytest.approx(2e6)
        # numpy args were coerced to JSON-safe types by the exporter.
        assert task["args"] == {"latency": 2.0, "clients": [3]}
        threads = {
            (e["pid"], e["args"]["name"]) for e in events if e["name"] == "thread_name"
        }
        assert (2, "client:3") in threads
        # Flow arrows pair by id; the end carries the enclosing binding point.
        flows = [e for e in events if e["ph"] in ("s", "f")]
        assert {e["ph"] for e in flows} == {"s", "f"}
        assert len({e["id"] for e in flows}) == 1
        assert next(e for e in flows if e["ph"] == "f")["bp"] == "e"
        # The whole document survives a strict JSON round-trip.
        json.dumps(doc)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter_monotone(self):
        c = Counter("c")
        c.inc()
        c.inc(4)
        assert c.value == 5
        with pytest.raises(ValueError, match="cannot decrease"):
            c.inc(-1)

    def test_histogram_stats(self):
        h = Histogram("h")
        assert h.snapshot() == {"count": 0, "sum": 0.0, "last": 0.0}
        for v in (2.0, 8.0, 5.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 3
        assert snap["min"] == 2.0
        assert snap["max"] == 8.0
        assert snap["mean"] == pytest.approx(5.0)
        assert snap["last"] == 5.0

    def test_registry_get_or_create_and_type_conflict(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")

    def test_snapshot_load_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("a").inc(3)
        reg.gauge("b").set(1.5)
        reg.histogram("c").observe(2.0)
        reg.histogram("c").observe(4.0)
        snap = reg.snapshot()
        assert json.loads(json.dumps(snap)) == snap
        restored = MetricsRegistry()
        restored.load_snapshot(snap)
        assert restored.snapshot() == snap
        # The restored registry continues the series, not restarts it.
        restored.counter("a").inc()
        assert restored.snapshot()["counters"]["a"] == 4
        restored.histogram("c").observe(1.0)
        assert restored.snapshot()["histograms"]["c"]["min"] == 1.0
        # Empty/None snapshots are no-ops.
        MetricsRegistry().load_snapshot(None)


class TestObservabilitySection:
    def test_null_stays_null(self):
        assert resolve_observability(None) is None

    def test_defaults_and_strictness(self):
        cfg = resolve_observability({})
        assert cfg == ObservabilityConfig()
        assert cfg.trace and cfg.trace_capacity == 65536
        with pytest.raises(ValueError, match="unknown observability key"):
            resolve_observability({"trace_cap": 1})
        with pytest.raises(ValueError, match="must be a bool"):
            resolve_observability({"trace": "yes"})
        with pytest.raises(ValueError, match="non-negative int"):
            resolve_observability({"jax_profile_rounds": -1})
        with pytest.raises(ValueError, match="non-negative int"):
            resolve_observability({"trace_capacity": True})
        with pytest.raises(ValueError, match=">= 1"):
            resolve_observability({"trace_capacity": 0})


class TestCompileWatcher:
    def test_poll_folds_deltas(self):
        reg = MetricsRegistry()
        with CompileWatcher(reg) as watcher:
            watcher.compiles += 3
            watcher.compile_time_s += 0.5
            assert watcher.poll() == 3
            assert watcher.poll() == 0  # steady state: no new compiles
        snap = reg.snapshot()
        assert snap["counters"]["jit.compiles"] == 3
        assert snap["counters"]["jit.compile_time_s"] == pytest.approx(0.5)
        assert snap["gauges"]["jit.round_compiles"] == 0

    def test_none_registry_is_fine(self):
        with CompileWatcher(None) as watcher:
            watcher.compiles += 1
            assert watcher.poll() == 1

    def test_counts_a_real_compile(self):
        x = jnp.arange(7.0).block_until_ready()

        def times_three_plus_one(v):
            return v * 3 + 1

        fn = jax.jit(times_three_plus_one)
        with CompileWatcher(MetricsRegistry()) as watcher:
            fn(x).block_until_ready()
            assert watcher.compiles == 1
            assert watcher.compile_time_s > 0
            fn(x).block_until_ready()  # the cached executable: no request
        assert watcher.compiles == 1

    def test_traced_compile_span_names_the_program(self):
        x = jnp.arange(5.0).block_until_ready()

        def plus_seven(v):
            return v + 7

        tracer = Tracer()
        with CompileWatcher(None, tracer) as watcher:
            jax.jit(plus_seven)(x).block_until_ready()
        (span,) = tracer.spans("compile")
        assert watcher.compiles == 1
        assert span.track == "compile"
        assert "plus_seven" in span.args["fun"]
        assert span.dur == pytest.approx(watcher.compile_time_s)
        assert 0 <= span.ts <= tracer.now() - span.dur


class TestRoundProfiler:
    def test_start_failure_raises(self, monkeypatch, tmp_path):
        import jax.profiler

        def refuse(log_dir):
            raise RuntimeError("no profiler backend")

        monkeypatch.setattr(jax.profiler, "start_trace", refuse)
        profiler = RoundProfiler(1, str(tmp_path))
        with pytest.raises(RuntimeError, match="no profiler backend"):
            profiler.round_start(0)

    def test_stop_failure_raises(self, monkeypatch, tmp_path):
        import jax.profiler

        def refuse():
            raise RuntimeError("trace lost")

        monkeypatch.setattr(jax.profiler, "start_trace", lambda log_dir: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", refuse)
        profiler = RoundProfiler(1, str(tmp_path))
        profiler.round_start(0)
        with pytest.raises(RuntimeError, match="trace lost"):
            profiler.round_end(0)


# ---------------------------------------------------------------------------
# RoundRecord serialization (the wall_time_s -> round_time_s rename)
# ---------------------------------------------------------------------------


class TestRoundRecordSerialization:
    RECORD = RoundRecord(
        round_index=3,
        participant_ids=[1, 4, 7],
        mean_local_loss=0.25,
        local_steps=42,
        params_down=30,
        params_up=30,
        bytes_transferred=1001,
        wall_time_s=0.125,
        virtual_time=9.5,
        staleness=1.5,
        epsilon=0.75,
    )

    def test_to_state_uses_canonical_name(self):
        state = self.RECORD.to_state()
        assert state["round_time_s"] == 0.125
        assert "wall_time_s" not in state

    def test_every_field_survives_jsonl_round_trip(self):
        line = json.dumps(self.RECORD.to_state(), sort_keys=True)
        back = RoundRecord.from_state(json.loads(line))
        for field in dataclasses.fields(RoundRecord):
            assert getattr(back, field.name) == getattr(self.RECORD, field.name), field.name
        assert back.round_time_s == self.RECORD.wall_time_s

    def test_legacy_wall_time_key_still_loads(self):
        state = dataclasses.asdict(self.RECORD)  # pre-rename stream shape
        back = RoundRecord.from_state(state)
        assert back == self.RECORD


# ---------------------------------------------------------------------------
# traced runs: span/record reconciliation, both engines
# ---------------------------------------------------------------------------


class TestTracedFederation:
    def test_sync_round_spans_reconcile_exactly(self, setup):
        clients, loss_fn, params0 = setup
        tracer = Tracer()
        fed = Federation(
            FederationConfig(rounds=3, local_epochs=1, batch_size=4, seed=0),
            clients, loss_fn, AdamW(learning_rate=5e-3),
            tracer=tracer,
        )
        out = fed.run(params0)
        rounds = tracer.spans("round")
        assert len(rounds) == len(out.history) == 3
        # The round span is emitted from the record's own measured wall
        # time, so the reconciliation is exact, not within-tolerance.
        for span, record in zip(rounds, out.history):
            assert span.dur == record.round_time_s
            assert span.args["round"] == record.round_index
        # Every phase of the round program shows up under the round total.
        # (fedavg is an in-jit "reduced" aggregator, so there is no separate
        # aggregate span here — see test_stacked_aggregate_span.)
        summary = tracer.summary()["host"]
        for phase in ("select", "train"):
            assert summary[phase]["count"] == 3
            assert summary[phase]["total_s"] <= summary["round"]["total_s"]
        # The facade's registry absorbed the records.
        snap = out.metrics
        assert snap["counters"]["rounds.completed"] == 3
        assert snap["counters"]["train.local_steps"] == out.total_local_steps
        assert snap["counters"]["comms.bytes_down"] + snap["counters"][
            "comms.bytes_up"
        ] == sum(r.bytes_transferred for r in out.history)
        assert snap["histograms"]["round.time_s"]["count"] == 3
        assert out.summary()["metrics"] == snap

    def test_round_phases_have_spans(self, setup):
        """Each round: select, train (prepare, stage, dispatch, readback,
        finalize inside it), then after the round's end its record and
        checkpoint; nothing inside checkpoint or stage."""
        clients, loss_fn, params0 = setup
        tracer = Tracer()
        fed = Federation(
            FederationConfig(rounds=3, local_epochs=1, batch_size=4, seed=0),
            clients, loss_fn, AdamW(learning_rate=5e-3),
            tracer=tracer,
        )
        fed.run(params0, snapshot_hook=lambda snap: None)
        by_name = {}
        for s in tracer.spans():
            by_name.setdefault(s.name, []).append(s)
        for name in ("select", "train", "prepare", "stage", "dispatch", "readback",
                     "finalize", "record", "checkpoint"):
            assert len(by_name[name]) == 3, name

        def inside(inner, outer):
            return outer.ts <= inner.ts and inner.ts + inner.dur <= outer.ts + outer.dur

        for k in range(3):
            rnd, train = by_name["round"][k], by_name["train"][k]
            for name in ("prepare", "stage", "dispatch", "readback", "finalize"):
                assert inside(by_name[name][k], train), (name, k)
            order = [by_name[n][k] for n in ("prepare", "stage", "dispatch", "readback", "finalize")]
            assert all(a.ts + a.dur <= b.ts for a, b in zip(order, order[1:]))
            record, ckpt = by_name["record"][k], by_name["checkpoint"][k]
            assert record.args["round"] == ckpt.args["round"] == k
            assert rnd.ts + rnd.dur <= record.ts
            assert record.ts + record.dur <= ckpt.ts
        for outer in by_name["checkpoint"] + by_name["stage"]:
            nested = [s for s in tracer.spans() if s is not outer and inside(s, outer)]
            assert nested == [], (outer.name, [s.name for s in nested])

    def test_round_span_counts_scanned_steps(self, setup):
        clients, loss_fn, params0 = setup
        tracer = Tracer()
        fed = Federation(
            FederationConfig(rounds=2, local_epochs=2, batch_size=4, seed=0),
            clients, loss_fn, AdamW(learning_rate=5e-3),
            tracer=tracer,
        )
        out = fed.run(params0)
        rounds = tracer.spans("round")
        spe = max(math.ceil(c.n_train / 4) for c in clients)
        for span, record in zip(rounds, out.history):
            assert span.args["local_steps"] == record.local_steps
            # every client scans the federation's largest step axis
            assert span.args["scanned_steps"] == len(clients) * 2 * spe
            assert span.args["scanned_steps"] >= span.args["local_steps"]
        counters = out.metrics["counters"]
        assert counters["train.scanned_steps"] == sum(s.args["scanned_steps"] for s in rounds)
        assert counters["train.local_steps"] == out.total_local_steps

    @pytest.mark.parametrize("overrides", [{"engine": "sequential"}, {"aggregator": "trimmed-mean:0.1"}])
    def test_unpadded_paths_scan_their_local_steps(self, setup, overrides):
        clients, loss_fn, params0 = setup
        tracer = Tracer()
        fed = Federation(
            FederationConfig(rounds=1, local_epochs=1, batch_size=4, seed=0, **overrides),
            clients, loss_fn, AdamW(learning_rate=5e-3),
            tracer=tracer,
        )
        out = fed.run(params0)
        (span,) = tracer.spans("round")
        assert span.args["scanned_steps"] == span.args["local_steps"] == out.total_local_steps
        assert out.metrics["counters"]["train.scanned_steps"] == out.total_local_steps

    def test_spans_land_in_a_profiler_capture(self, setup, tmp_path, monkeypatch):
        """The program's spans are annotations on the profiler's host plane."""
        import jax.profiler

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        monkeypatch.syspath_prepend(os.path.join(repo, "bench"))
        import trace_reduce

        clients, loss_fn, params0 = setup
        fed = Federation(
            FederationConfig(rounds=2, local_epochs=1, batch_size=4, seed=0),
            clients, loss_fn, AdamW(learning_rate=5e-3),
            tracer=Tracer(),
        )
        jax.profiler.start_trace(str(tmp_path))
        try:
            fed.run(params0, snapshot_hook=lambda snap: None)
        finally:
            jax.profiler.stop_trace()
        events = trace_reduce.events_from_xplane(trace_reduce.find_xplane(str(tmp_path)))
        names = [e[0].split("#", 1)[0] for e in events["host"]]
        assert names.count("repro.checkpoint") == 2
        assert names.count("repro.dispatch") == 2
        assert "repro.round" not in names  # the round span is not an annotation

    def test_stacked_aggregate_span(self, setup):
        clients, loss_fn, params0 = setup
        tracer = Tracer()
        fed = Federation(
            FederationConfig(
                rounds=2, local_epochs=1, batch_size=4, seed=0,
                aggregator="trimmed-mean:0.1",
            ),
            clients, loss_fn, AdamW(learning_rate=5e-3),
            tracer=tracer,
        )
        fed.run(params0)
        aggregates = tracer.spans("aggregate")
        assert len(aggregates) == 2
        assert all(s.args["clients"] == len(clients) for s in aggregates)

    def test_async_flush_and_task_spans(self, setup):
        clients, loss_fn, params0 = setup
        tracer = Tracer()
        fed = AsyncFederation(
            AsyncFederationConfig(
                rounds=3, local_epochs=1, batch_size=4, seed=0,
                aggregator="fedbuff:3", latency="lognormal:0.5",
                dropout="never", concurrency=4,
            ),
            clients, loss_fn, AdamW(learning_rate=5e-3),
            tracer=tracer,
        )
        out = fed.run(params0)
        flushes = tracer.spans("flush", clock="host")
        assert len(flushes) == len(out.history)
        for span, record in zip(flushes, out.history):
            assert span.dur == record.round_time_s
            assert span.args["virtual_time"] == record.virtual_time
        # Virtual task spans: dispatch time + latency, one per surviving
        # task, each on its own client/group track with a flow arrow.
        tasks = tracer.spans("task", clock="virtual")
        stats = fed.last_run_stats
        assert len(tasks) == stats["tasks"]
        final_virtual = out.history[-1].virtual_time
        for task in tasks:
            assert task.ts >= 0.0 and task.dur > 0.0
            assert task.track.startswith(("client:", "group:"))
        # Tasks folded into the last flush finished by then on the virtual
        # clock; later dispatches may still be in flight.
        assert min(t.ts + t.dur for t in tasks) <= final_virtual
        flow_phases = [e.phase for e in tracer.events() if e.flow_id is not None]
        assert flow_phases.count("s") == flow_phases.count("f") == len(tasks)
        # Virtual flush instants mark the records' flush times (the raw
        # scheduler events land on their own "scheduler" track).
        marks = [
            e for e in tracer.events()
            if e.name == "flush" and e.clock == "virtual" and e.phase == "i"
            and e.track == "server"
        ]
        assert [m.ts for m in marks] == [r.virtual_time for r in out.history]
        # And the whole ring exports as loadable Chrome JSON.
        doc = tracer.to_chrome()
        json.dumps(doc)
        assert any(e.get("ph") == "X" and e["pid"] == 2 for e in doc["traceEvents"])

    def test_async_off_run_records_nothing(self, setup):
        clients, loss_fn, params0 = setup
        fed = AsyncFederation(
            AsyncFederationConfig(
                rounds=2, local_epochs=1, batch_size=4, seed=0,
                aggregator="fedbuff:3", latency="constant", dropout="never",
            ),
            clients, loss_fn, AdamW(learning_rate=5e-3),
        )
        out = fed.run(params0)
        assert isinstance(fed.tracer, NullTracer)
        assert fed.tracer.events() == []
        # Metrics still flow — the registry is not optional.
        assert out.metrics["counters"]["async.tasks"] == fed.last_run_stats["tasks"]
        assert out.metrics["gauges"]["async.virtual_time"] == pytest.approx(
            fed.last_run_stats["virtual_time"]
        )


# ---------------------------------------------------------------------------
# staging / pool counters: exact across seeded multi-chunk rounds
# ---------------------------------------------------------------------------


class TestStagingCounters:
    def test_pipeline_prefetch_counter_all_hits(self):
        """Deterministic hit accounting: the consumer only asks for a chunk
        once the producer has it queued, so every chunk is a prefetch hit."""
        pipeline = StagingPipeline(lambda start: start * 10, [0, 1, 2, 3])
        it = iter(pipeline)
        for expected in (0, 10, 20, 30):
            deadline = time.time() + 5
            while pipeline._queue.qsize() == 0:
                assert time.time() < deadline, "staging producer stalled"
                time.sleep(0.001)
            assert next(it) == expected
        assert pipeline.prefetched == 4

    def test_pipeline_prefetch_counter_all_misses_and_wait_spans(self):
        """Deterministic miss accounting: staging only proceeds once the
        consumer is already inside the blocking ``prefetch_wait`` path (the
        tracer hook releases the producer), so no chunk counts as
        prefetched and every miss records a wait span."""
        gate = threading.Semaphore(0)

        class ReleasingTracer(Tracer):
            def span(self, name, track="server", **args):
                if name == "prefetch_wait":
                    gate.release()
                return super().span(name, track=track, **args)

        tracer = ReleasingTracer()

        def stage_fn(start):
            assert gate.acquire(timeout=5)
            return start * 10

        pipeline = StagingPipeline(stage_fn, [0, 1, 2, 3], tracer=tracer)
        assert list(pipeline) == [0, 10, 20, 30]
        assert pipeline.prefetched == 0
        waits = tracer.spans("prefetch_wait")
        assert len(waits) == 4
        assert all(w.track == "staging" for w in waits)

    @pytest.mark.parametrize("staging", ["resident", "rebuild"])
    def test_round_counters_absorbed_exactly(self, setup, staging):
        clients, loss_fn, params0 = setup
        rounds = 3
        fed = Federation(
            FederationConfig(
                rounds=rounds, local_epochs=1, batch_size=4, seed=0,
                staging=staging, cohort_chunk=4, engine="vectorized",
                prefetch=False,  # inline staging: every counter deterministic
            ),
            clients, loss_fn, AdamW(learning_rate=5e-3),
        )
        out = fed.run(params0)
        stats = fed.cohort_trainer.last_round_stats
        assert stats["chunks"] == math.ceil(len(clients) / 4)
        counters = out.metrics["counters"]
        gauges = out.metrics["gauges"]
        # Steady-state rounds stage identical plans, so the cumulative
        # counters are exactly rounds x the per-round stats.
        assert counters["staging.chunks"] == rounds * stats["chunks"]
        assert stats["bytes_staged"] > 0
        assert counters["staging.bytes_staged"] == rounds * stats["bytes_staged"]
        assert gauges["staging.bytes_resident"] == stats["bytes_resident"]
        assert counters["staging.plans_prefetched"] == 0  # no pipeline
        if staging == "resident":
            assert stats["bytes_resident"] > 0

    def test_prefetched_plans_counted(self, setup):
        clients, loss_fn, params0 = setup
        rounds = 2
        fed = Federation(
            FederationConfig(
                rounds=rounds, local_epochs=1, batch_size=4, seed=0,
                staging="resident", cohort_chunk=4, prefetch=True,
            ),
            clients, loss_fn, AdamW(learning_rate=5e-3),
        )
        out = fed.run(params0)
        stats = fed.cohort_trainer.last_round_stats
        counters = out.metrics["counters"]
        # How many chunks win the overlap race varies with machine load,
        # but the cumulative counter must stay within the per-round bound
        # and agree with the last round's own tally as a lower bound.
        chunks = stats["chunks"]
        assert 0 <= counters["staging.plans_prefetched"] <= rounds * chunks
        assert counters["staging.plans_prefetched"] >= stats["plans_prefetched"]

    def test_pool_counters_absorbed_exactly(self, setup):
        clients, loss_fn, params0 = setup
        # A pool budget below the cohort footprint forces uploads and LRU
        # evictions as the seeded per-round selections churn the residents.
        max_n = max(c.n_train for c in clients)
        row_bytes = (max_n + 1) * (SEQ_LEN * FEAT * 4 + 4)
        rounds = 4
        fed = Federation(
            FederationConfig(
                rounds=rounds, local_epochs=1, batch_size=4, seed=0,
                selection="uniform:4", resident_budget_bytes=5 * row_bytes,
                cohort_chunk=4,
            ),
            clients, loss_fn, AdamW(learning_rate=5e-3),
        )
        out = fed.run(params0)
        dcohort = fed.cohort_trainer._device_cohort
        assert dcohort.is_pooled and dcohort.pool_rows == 5
        counters = out.metrics["counters"]
        assert counters["pool.uploads"] == dcohort.uploads
        assert counters["pool.evictions"] == dcohort.evictions
        assert counters["pool.hits"] == dcohort.hits
        assert counters["pool.bytes_uploaded"] == dcohort.bytes_uploaded
        # Every participant appearance is either a pool hit or an upload —
        # the exact identity the round loop maintains.
        appearances = sum(len(r.participant_ids) for r in out.history)
        assert counters["pool.hits"] + counters["pool.uploads"] == appearances
        assert counters["pool.uploads"] >= len(set(out.history[0].participant_ids))
        # 10 clients churning through 5 rows across 4 rounds must evict.
        assert counters["pool.evictions"] > 0


# ---------------------------------------------------------------------------
# control plane: metrics.jsonl + trace.json in the run dir, resume continuity
# ---------------------------------------------------------------------------


OBS_SPEC = {
    "name": "t-obs",
    "mode": "sync",
    "rounds": 4,
    "local_epochs": 1,
    "batch_size": 8,
    "seed": 3,
    "recruitment": "all",
    "selection": "uniform",
    "data": {"scale": 0.002, "num_hospitals": 6, "split_mode": "stratified"},
    "model": {"hidden_dim": 2, "num_layers": 1},
    "observability": {"trace": True, "trace_capacity": 4096},
}


class TestServiceObservability:
    def test_spec_validation(self):
        from repro.launch.federation_service import validate_job_spec

        normalized = validate_job_spec(dict(OBS_SPEC))
        assert normalized["observability"]["trace"] is True
        assert normalized["observability"]["jax_profile_rounds"] == 0
        # Tri-state: absent stays null and hashes differently.
        bare = validate_job_spec({k: v for k, v in OBS_SPEC.items() if k != "observability"})
        assert bare["observability"] is None
        with pytest.raises(ValueError, match="unknown key"):
            validate_job_spec({**OBS_SPEC, "observability": {"capactiy": 1}})
        with pytest.raises(ValueError, match="must be a bool"):
            validate_job_spec({**OBS_SPEC, "observability": {"trace": 1}})

    def test_run_dir_artifacts_and_resume_continuity(self, tmp_path, capsys):
        from repro.launch.federation_service import (
            JobPreempted,
            read_records,
            resume_job,
            submit_job,
        )

        run_dir = str(tmp_path / "run")
        with pytest.raises(JobPreempted):
            submit_job(dict(OBS_SPEC), run_dir, preempt_after=2)
        # The cut run already has a partial trace and a metrics prefix.
        assert os.path.exists(os.path.join(run_dir, "trace.json"))
        cut_lines = [
            json.loads(line)
            for line in open(os.path.join(run_dir, "metrics.jsonl"))
        ]
        assert cut_lines and all("counters" in line for line in cut_lines)

        out = resume_job(run_dir)
        assert out["status"] == "completed"
        records = read_records(os.path.join(run_dir, "records.jsonl"))
        lines = [
            json.loads(line)
            for line in open(os.path.join(run_dir, "metrics.jsonl"))
        ]
        # One metrics line per record, in lockstep, cumulative through each.
        assert [l["round_index"] for l in lines] == [r.round_index for r in records]
        completed = [l["counters"]["rounds.completed"] for l in lines]
        assert completed == list(range(1, len(records) + 1))
        steps = [l["counters"]["train.local_steps"] for l in lines]
        assert steps == list(np.cumsum([r.local_steps for r in records]))
        # The final summary folds the same snapshot.
        assert out["summary"]["metrics"]["counters"]["rounds.completed"] == len(records)
        # The completed run's trace loads and covers the resumed rounds.
        doc = json.loads(open(os.path.join(run_dir, "trace.json")).read())
        round_spans = [
            e for e in doc["traceEvents"] if e["name"] == "round" and e["ph"] == "X"
        ]
        assert [e["args"]["round"] for e in round_spans] == [2, 3]

        # The report CLI renders every section from the run dir.
        assert render_report(run_dir) == 0
        rendered = capsys.readouterr().out
        assert "per-phase time" in rendered
        assert "round" in rendered and "metrics" in rendered

    def test_report_on_missing_dir(self, capsys):
        assert render_report("/nonexistent/run-dir") == 2
