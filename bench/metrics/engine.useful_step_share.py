"""Cohort engine: percent of the scanned client-steps that are real.

100 x the real local steps over the client-steps the round's scan ran,
padding clients and steps included, summed over the window rounds'
``round`` spans (their ``local_steps`` and ``scanned_steps`` args in the
job's ``trace.json``).  None where the spans lack those args.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import round_spans  # noqa: E402


def read(run):
    rounds = round_spans(run.spans, run.window_rounds)
    if not rounds:
        return None
    args = [s["args"] for s in rounds.values()]
    if any("local_steps" not in a or "scanned_steps" not in a for a in args):
        return None
    scanned = sum(a["scanned_steps"] for a in args)
    return 100.0 * sum(a["local_steps"] for a in args) / scanned if scanned else None
