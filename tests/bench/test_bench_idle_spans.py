"""The readers that put the traced window's device-idle time down to the
program's ``repro.*`` spans, and the useful-step share, on hand-made
events and on the recorded excerpt (which predates the spans)."""

from __future__ import annotations

import os
import sys
import types

import pytest

import bench_testutil  # noqa: F401  (puts bench/ on the path)

import trace_reduce
from files import Bench

sys.path.insert(0, os.path.join(bench_testutil.BENCH, "metrics"))
from _device_idle import idle_by_span  # noqa: E402

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "trace_sample.json")
MS = 1_000_000
READERS = (
    "service.checkpoint_stall_ms",
    "engine.host_stall_ms",
    "device.unattributed_idle_share",
    "engine.useful_step_share",
)


@pytest.fixture(scope="module")
def bench():
    return Bench(bench_testutil.REPO)


def read(bench, name, events, spans=(), rounds=(4, 5)):
    run = types.SimpleNamespace(events=events, spans=list(spans), window_rounds=list(rounds))
    return bench.metric(name).read(run)


def hand_made():
    """A 10 ms window; the chip runs [1, 3] and [6, 8] ms, so 6 ms is idle.

    Idle [0, 1]: 0.5 ms before ``train`` opens (unattributed), 0.5 under
    it.  Idle [3, 6]: ``prepare`` [3, 4], ``train`` [4, 5], ``dispatch``
    [5, 5.5], ``train`` [5.5, 6].  Idle [8, 10]: ``train`` [8, 9],
    ``checkpoint`` [9, 10].  The runtime's own event over [3, 4] is not a
    program span and takes nothing.
    """
    host = [
        ("bench.window.open", 0, 10, ""),
        ("repro.train", MS // 2, 9 * MS, ""),
        ("repro.prepare#clients=4#", 2 * MS + MS // 2, 4 * MS, ""),
        ("np.asarray(jax.Array)", 3 * MS, 4 * MS, ""),
        ("repro.dispatch", 5 * MS, 5 * MS + MS // 2, ""),
        ("repro.checkpoint", 9 * MS, 10 * MS, ""),
        ("bench.window.close", 10 * MS, 10 * MS + 10, ""),
    ]
    device = {"0": [("fusion.1", 1 * MS, 3 * MS, ""), ("while.2", 6 * MS, 8 * MS, "")]}
    return {"device": device, "host": host}


def test_innermost_span_takes_the_idle_time():
    idle = idle_by_span(hand_made())
    assert idle == {
        "unattributed": MS // 2,
        "repro.train": 3 * MS,
        "repro.prepare": 1 * MS,
        "repro.dispatch": MS // 2,
        "repro.checkpoint": 1 * MS,
    }


def test_stalls_are_per_window_round(bench):
    ev = hand_made()
    assert read(bench, "service.checkpoint_stall_ms", ev) == pytest.approx(0.5)
    assert read(bench, "service.checkpoint_stall_ms", ev, rounds=(4,)) == pytest.approx(1.0)
    # prepare 1 ms + dispatch 0.5 ms over two rounds; train's own idle is not the engine's
    assert read(bench, "engine.host_stall_ms", ev) == pytest.approx(0.75)


def test_unattributed_share(bench, capsys):
    share = read(bench, "device.unattributed_idle_share", hand_made())
    assert share == pytest.approx(100 * 0.5 / 6)
    line = [l for l in capsys.readouterr().err.splitlines() if l.startswith("idle_by_span ")]
    assert len(line) == 1
    assert '"repro.train": 1.5' in line[0] and line[0].rstrip().endswith('"unattributed": 0.25}')


def test_idle_with_no_device_op_is_the_whole_window(bench):
    ev = hand_made()
    ev["device"] = {"0": []}
    idle = idle_by_span(ev)
    assert sum(idle.values()) == 10 * MS
    assert idle["repro.checkpoint"] == 1 * MS


def round_span(k, **args):
    return {"name": "round", "ph": "X", "pid": 1, "tid": 1, "ts": k * 1e5, "dur": 9e4, "args": {"round": k, **args}}


def test_useful_step_share_sums_the_window_rounds(bench):
    spans = [
        round_span(3, local_steps=99, scanned_steps=100),  # set-up round: left out
        round_span(4, local_steps=10, scanned_steps=100),
        round_span(5, local_steps=30, scanned_steps=100),
    ]
    assert read(bench, "engine.useful_step_share", hand_made(), spans) == pytest.approx(20.0)


def test_readers_give_none_without_program_spans_or_counters(bench):
    ev = hand_made()
    ev["host"] = [h for h in ev["host"] if not h[0].startswith("repro.")]
    for name in READERS[:3]:
        assert read(bench, name, ev) is None, name
    bare = [round_span(4), round_span(5)]
    assert read(bench, "engine.useful_step_share", ev, bare) is None
    assert read(bench, "engine.useful_step_share", ev, []) is None


def test_recorded_excerpt_has_no_program_spans(bench):
    sample = trace_reduce.load_events(SAMPLE)
    for name in READERS:
        assert read(bench, name, sample) is None, name


def test_recorded_excerpt_idle_matches_the_busy_count():
    """One span over the whole excerpt takes exactly the idle time that
    ``trace_reduce.busy`` leaves (a one-chip trace)."""
    sample = trace_reduce.load_events(SAMPLE)
    lo, hi = trace_reduce.window(sample)
    sample["host"].append(("repro.round_loop", lo - 1, hi + 1, ""))
    idle = idle_by_span(sample)
    load = trace_reduce.busy(sample)
    assert idle["unattributed"] == 0
    assert idle["repro.round_loop"] / 1e9 == pytest.approx(load["window_s"] - load["busy_s"], abs=1e-9)
