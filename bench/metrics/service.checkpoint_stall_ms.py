"""Job service: device-idle milliseconds per window round under ``checkpoint``.

The part of the round the chip waits while the service writes its
snapshot (``repro.checkpoint`` annotations on the device trace's clock;
see ``_device_idle.py``).  None where the program has no annotations.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _device_idle import stall_ms  # noqa: E402


def read(run):
    return stall_ms(run, ("checkpoint",))
