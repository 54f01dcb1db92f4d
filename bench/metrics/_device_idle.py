"""Device-idle time of the traced window, put down to the program's spans.

While the job's ``repro.obs`` tracer is on, each of its spans is also a
profiler annotation ``repro.<span>`` on the host, on the device trace's
clock.  Idle time is the window less the union of the device ops of the
chip with the most ops (the rule of ``trace_reduce.idle_gaps``).  Each
idle nanosecond goes to the shortest ``repro.*`` host event that covers
it (the innermost span), else to ``unattributed``.  A trace with no
``repro.*`` event at all (a program without the annotations) gives None.
"""

from __future__ import annotations

import numpy as np

import trace_reduce

PREFIX = "repro."
UNATTRIBUTED = "unattributed"

_cache: dict[int, tuple] = {}


def span_name(event_name: str) -> str:
    """``repro.stage#chunk=0#`` -> ``repro.stage``."""
    return event_name.split("#", 1)[0]


def _gaps(events: dict, lo: int, hi: int) -> tuple[np.ndarray, np.ndarray]:
    """Start and end arrays of the window's idle stretches."""
    device = events["device"]
    chip = max(device, key=lambda c: len(device[c]), default=None)
    ops = device.get(chip, [])
    s = np.fromiter((op[1] for op in ops), np.int64, len(ops))
    e = np.fromiter((op[2] for op in ops), np.int64, len(ops))
    s, e = np.maximum(s, lo), np.minimum(e, hi)
    keep = e > s
    s, e = s[keep], e[keep]
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e) if len(e) else e
    # A gap opens where an op starts past every earlier op's end.
    prev_end = np.concatenate(([lo], reach))
    next_start = np.concatenate((s, [hi]))
    open_ = next_start > prev_end
    return prev_end[open_], next_start[open_]


def idle_by_span(events: dict) -> dict[str, int] | None:
    """Idle nanoseconds of the window by innermost ``repro.*`` span name."""
    key = id(events)
    if key in _cache and _cache[key][0] is events:
        return _cache[key][1]
    spans = [
        (int(s), int(e), span_name(name))
        for name, s, e, _ in events["host"]
        if name.startswith(PREFIX)
    ]
    out: dict[str, int] | None = None
    if spans:
        lo, hi = trace_reduce.window(events)
        gs, ge = _gaps(events, lo, hi)
        bounds = np.unique(np.concatenate([gs, ge, [b for sp in spans for b in sp[:2]]]))
        # label[i] is the innermost span over [bounds[i], bounds[i+1]): paint
        # the longest spans first so shorter ones overwrite them.
        label = np.full(len(bounds), -1, np.int64)
        names = sorted({sp[2] for sp in spans})
        index = {n: i for i, n in enumerate(names)}
        for s, e, name in sorted(spans, key=lambda sp: sp[0] - sp[1]):
            i0, i1 = np.searchsorted(bounds, [s, e])
            label[i0:i1] = index[name]
        seg_lo, seg_len = bounds[:-1], np.diff(bounds)
        g = np.searchsorted(gs, seg_lo, side="right") - 1
        idle = (g >= 0) & (seg_lo < np.append(ge, lo)[g])
        totals = np.bincount(label[:-1][idle] + 1, weights=seg_len[idle], minlength=len(names) + 1)
        out = {UNATTRIBUTED: int(totals[0])}
        out.update({n: int(totals[i + 1]) for i, n in enumerate(names)})
    _cache.clear()
    _cache[key] = (events, out)
    return out


def stall_ms(run, names: tuple[str, ...]) -> float | None:
    """Idle milliseconds per window round under the spans ``names``."""
    idle = idle_by_span(run.events)
    if idle is None or not run.window_rounds:
        return None
    return sum(idle.get(PREFIX + n, 0) for n in names) / 1e6 / len(run.window_rounds)
