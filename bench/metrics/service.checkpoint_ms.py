"""Job service (``launch/federation_service.py``): checkpoint self time.

Milliseconds per window round of the ``checkpoint`` span, the service's
per-round snapshot save.  A window round ``k`` starts right after round
``k-1``'s record, so its checkpoint is the one tagged ``k-1``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _spans import self_times  # noqa: E402


def read(run):
    tags = {k - 1 for k in run.window_rounds}
    got = [ms for s, ms in self_times(run.spans, "checkpoint") if s.get("args", {}).get("round") in tags]
    return sum(got) / len(run.window_rounds) if got else None
