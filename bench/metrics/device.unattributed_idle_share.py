"""Device: percent of the window's idle time under no ``repro.*`` span.

What the program's spans leave unexplained (``_device_idle.py``).  Also
prints ``idle_by_span {name: ms per window round, ...}`` to standard
error.  None where the program has no annotations.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _device_idle import UNATTRIBUTED, idle_by_span  # noqa: E402


def read(run):
    idle = idle_by_span(run.events)
    if idle is None or not run.window_rounds:
        return None
    rounds = len(run.window_rounds)
    ranked = sorted(idle.items(), key=lambda kv: (kv[0] == UNATTRIBUTED, -kv[1]))
    print("idle_by_span " + json.dumps({k: v / 1e6 / rounds for k, v in ranked}), file=sys.stderr)
    total = sum(idle.values())
    return 100.0 * idle[UNATTRIBUTED] / total if total else 0.0
