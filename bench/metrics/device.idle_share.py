"""Device: percent of the traced window in which no operation ran.

1 - (union of the device's op intervals / window), from the profiler
trace, averaged over the chips that ran anything.
"""


def read(run):
    if run.window_s <= 0 or run.busy_s <= 0:
        return None
    return 100.0 * (1.0 - run.busy_s / run.window_s)
