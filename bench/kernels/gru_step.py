"""Matmul FLOPs of one training example through the paper's GRU.

Per example and hour, layer ``l`` multiplies its input (38 features, or
the previous layer's 32 hidden units) and its hidden state by the three
gates' weights: ``2 * 3N * (F_l + N)`` FLOPs; the head adds ``2 * N`` once.
The backward pass costs twice the forward (gradients of inputs and of
weights), so a training example costs three forward passes.  Element-wise
gate arithmetic is not counted, so the share of the peak this gives is a
lower bound of the work done.  Padded client-steps do not count: callers
multiply by real examples only.
"""


def flops_per_example(config: dict) -> float:
    m = config["model"]
    n, t = int(m["hidden_dim"]), int(m["seq_len"])
    forward = 2 * n
    for layer in range(int(m["num_layers"])):
        fan_in = int(m["input_dim"]) if layer == 0 else n
        forward += t * 2 * 3 * n * (fan_in + n)
    return 3.0 * forward
