"""The trace reduction on a hand-made trace and on a recorded one.

``data/trace_sample.json`` is an excerpt of a profiler trace recorded on
one TPU v5e chip (a ``gru-xla.src`` traced run): the device's ops (named
by their HLO instruction) and the host's events over the first 20 ms of
the measured window, with the window's close annotation moved to the end
of the excerpt.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import bench_testutil  # noqa: F401  (puts bench/ on the path)

import trace_reduce

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "trace_sample.json")
MS = 1_000_000


def hand_made():
    host = [
        ("bench.window.open", 0, 10, ""),
        ("PjitFunction(round)", 5 * MS, 6 * MS, ""),
        ("bench.round_boundary", 8 * MS, 9 * MS + 500_000, ""),
        ("bench.window.close", 10 * MS, 10 * MS + 10, ""),
    ]
    device = {"0": [
        ("fusion.1", 1 * MS, 3 * MS, ""),
        ("fusion.2", 2 * MS, 4 * MS, ""),  # overlaps fusion.1: counted once
        ("my_kernel", 6 * MS, 8 * MS, "f32[24192,24,96]"),
        ("fusion.1", 9 * MS, 12 * MS, ""),  # runs past the window's close
    ]}
    return {"device": device, "host": host}


def test_busy_and_idle_hand_count():
    ev = hand_made()
    load = trace_reduce.busy(ev)
    assert load["window_s"] == pytest.approx(10 * MS / 1e9, abs=1e-12)
    # busy: [1,4] + [6,8] + [9,10] = 6 ms of the 10 ms window
    assert load["busy_s"] == pytest.approx(0.006)
    gaps = trace_reduce.idle_gaps(ev)
    assert [round(g[1] * 1e3, 3) for g in gaps] == [2.0, 1.0, 1.0]
    labels = {round(g[1] * 1e3, 3): g[0] for g in gaps}
    assert gaps[0][0] == "PjitFunction(round)"  # the 4-6 ms gap overlaps it for 1 ms
    assert labels[1.0] in ("bench.round_boundary", "host idle or untraced")
    top = trace_reduce.top_ops(ev)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(0.002)
    kernel = trace_reduce.ops_in_window(ev, lambda name, detail: name == "my_kernel")
    assert [op[0] for op in kernel] == ["my_kernel"]


def _timeline_busy(events):
    """Independent count: a 1-microsecond grid over the window."""
    lo, hi = trace_reduce.window(events)
    grid = np.zeros((hi - lo) // 1000 + 1, dtype=bool)
    for _, s, e, _ in events["device"]["0"]:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[(a - lo) // 1000 : (b - lo) // 1000] = True
    return grid.sum() * 1e-6


@pytest.fixture(scope="module")
def sample():
    return trace_reduce.load_events(SAMPLE)


def test_recorded_trace_busy_matches_a_grid_count(sample):
    load = trace_reduce.busy(sample)
    assert 0.019 < load["window_s"] < 0.021
    assert load["busy_s"] == pytest.approx(_timeline_busy(sample), abs=2e-4)
    assert 0 < load["busy_s"] <= load["window_s"]


def test_recorded_trace_breakdown(sample):
    top = trace_reduce.top_ops(sample)
    assert 1 <= len(top) <= 10 and all(t[1] > 0 for t in top)
    assert top == sorted(top, key=lambda t: -t[1])
    gaps = trace_reduce.idle_gaps(sample)
    assert len(gaps) <= 10 and all(isinstance(g[0], str) and g[1] > 0 for g in gaps)
