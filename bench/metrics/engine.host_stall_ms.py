"""Cohort engine: device-idle milliseconds per window round under its phases.

Idle time of the traced window under the engine's ``repro.prepare``,
``repro.stage``, ``repro.dispatch``, ``repro.readback`` and
``repro.finalize`` annotations (``_device_idle.py``), per window round:
the host work of ``train_cohort`` the chip waits for.  None where the
program has no annotations.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _device_idle import stall_ms  # noqa: E402

PHASES = ("prepare", "stage", "dispatch", "readback", "finalize")


def read(run):
    return stall_ms(run, PHASES)
