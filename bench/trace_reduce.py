"""Reduce a profiler trace of the measured window to numbers.

``events_from_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain lists: the device's operations (``/device:TPU:<n>`` planes,
their "XLA Ops" line) and the host's events (``/host:CPU`` plane), each as
``(name, start_ns, end_ns, detail)``.  On a TPU an op's event carries its
whole HLO instruction (``%while.235 = (s32[], ...) while(...)``); the name
kept is the instruction's own (``%while.235``), the text only where a
kernel's cost needs its shapes.  Everything after that works on the
lists, so a recorded excerpt saved as JSON (``load_events``) reduces
exactly as a live trace does.

The window is bounded by the harness's own annotations ``bench.window``
(opened and closed at round boundaries).  Busy time is the union of the
device operations' intervals inside the window, averaged over the chips
that ran any; idle gaps are the holes in that union, each labelled with the
host event that overlaps it most.
"""

from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict

WINDOW_OPEN = "bench.window.open"
WINDOW_CLOSE = "bench.window.close"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
# Host events too generic to say what the host was doing.
_HOST_UMBRELLA = re.compile(r"^(bench\.window|\$)")


def find_xplane(profile_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return paths[-1]


def short_name(text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``%fusion.3``."""
    return text.split(" = ", 1)[0][:120]


def events_from_xplane(path: str, detail_pattern: str | None = None) -> dict:
    """Device ops and host events of a trace.

    Op text (``detail``) is kept only for device ops whose text matches
    ``detail_pattern``: a window of a paper-scale round holds millions of
    ops, and only the kernels' call shapes are read from their text.
    """
    from jax.profiler import ProfileData

    keep = re.compile(detail_pattern) if detail_pattern else None
    data = ProfileData.from_file(path)
    device: dict[str, list] = defaultdict(list)
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        text = e.name
                        device[m.group(1)].append(
                            (
                                short_name(text),
                                int(e.start_ns),
                                int(e.end_ns),
                                text if keep is not None and keep.search(text) else "",
                            )
                        )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, int(e.start_ns), int(e.end_ns), "") for e in line.events)
    return {"device": dict(device), "host": host}


def load_events(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        raw = json.load(fh)
    def event(e):
        return (e[0], int(e[1]), int(e[2]), e[3])

    return {
        "device": {k: [event(e) for e in v] for k, v in raw["device"].items()},
        "host": [event(e) for e in raw["host"]],
    }


def window(events: dict) -> tuple[int, int]:
    """(start_ns, end_ns) between the harness's open and close annotations."""
    opens = [e[1] for e in events["host"] if e[0] == WINDOW_OPEN]
    closes = [e[1] for e in events["host"] if e[0] == WINDOW_CLOSE]
    if not opens or not closes:
        raise ValueError("trace lacks the bench.window annotations")
    return min(opens), max(closes)


def _clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy(events: dict) -> dict:
    """Window length and the device's busy seconds in it (mean over chips)."""
    lo, hi = window(events)
    per_chip = []
    for ops in events["device"].values():
        spans = union(_clip([(s, e) for _, s, e, _ in ops], lo, hi))
        if spans:
            per_chip.append(sum(e - s for s, e in spans))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": (sum(per_chip) / len(per_chip) / 1e9) if per_chip else 0.0,
    }


def ops_in_window(events: dict, match=None) -> list[tuple]:
    """Device ops (all chips) inside the window; ``match(name, detail)`` filters."""
    lo, hi = window(events)
    return [
        op
        for ops in events["device"].values()
        for op in ops
        if op[1] >= lo and op[2] <= hi and (match is None or match(op[0], op[3]))
    ]


def top_ops(events: dict, k: int = 10) -> list[list]:
    totals: dict[str, int] = defaultdict(int)
    for name, s, e, _ in ops_in_window(events):
        totals[name] += e - s
    chips = max(len(events["device"]), 1)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9 / chips] for name, ns in ranked]


def idle_gaps(events: dict, k: int = 10) -> list[list]:
    """The ``k`` longest device-idle stretches of the window, labelled.

    The label is the host event overlapping the gap the longest (ties go
    to the shorter, more specific event); ``host idle or untraced`` when
    no host event overlaps it.
    """
    lo, hi = window(events)
    chip = max(events["device"], key=lambda c: len(events["device"][c]), default=None)
    spans = union(_clip([(s, e) for _, s, e, _ in events["device"].get(chip, [])], lo, hi))
    gaps, cursor = [], lo
    for s, e in spans:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [h for h in events["host"] if not _HOST_UMBRELLA.match(h[0])]
    out = []
    for gs, ge in gaps[:k]:
        best, best_key = "host idle or untraced", (0, 0)
        for name, s, e, _ in host:
            overlap = min(e, ge) - max(s, gs)
            if overlap > 0 and (overlap, -(e - s)) > best_key:
                best, best_key = name, (overlap, -(e - s))
        out.append([best, (ge - gs) / 1e9])
    return out
