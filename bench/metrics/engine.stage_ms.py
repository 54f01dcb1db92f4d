"""Cohort engine (``federated/cohort.py``): staging self time per round.

Milliseconds per window round of the ``stage`` spans (building and
uploading a chunk's index plan), summed over the spans that fall inside
each window round's ``round`` span.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import round_spans, self_times  # noqa: E402


def read(run):
    rounds = round_spans(run.spans, run.window_rounds)
    if not rounds:
        return None
    total = 0.0
    found = False
    for s, ms in self_times(run.spans, "stage"):
        for r in rounds.values():
            if r["ts"] <= s["ts"] and s["ts"] + s["dur"] <= r["ts"] + r["dur"]:
                total += ms
                found = True
    return total / len(rounds) if found else None
