"""Model step: percent of the chip's bf16 peak in training FLOPs.

Real (unpadded) training examples in the traced window, times the
configuration's forward+backward matmul FLOPs per example
(``kernels/<step_flops>.py``), over the window and the peak of every chip
the cell uses.  Padded client-steps do not count.
"""


def read(run):
    if not run.peaks or run.examples <= 0:
        return None
    per_example = run.bench.kernel(run.cell.config["step_flops"]).flops_per_example(run.cell.config)
    peak = run.peaks["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * run.examples * per_example / run.window_s / peak
