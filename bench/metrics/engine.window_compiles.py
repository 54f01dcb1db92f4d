"""Cohort engine: programs built inside the measured window.

The harness's own ``jax.monitoring`` listener counts every backend
compile request, whether XLA compiles the program or loads it from the
persistent cache (either stalls a round by a second or more), from the
window's opening round boundary to its closing one.  0 in a steady round.
"""


def read(run):
    return run.window_compiles
