"""Model FLOP/s utilization: the forward FLOPs of every scored row real
tokens (causal, no padding), over the traced window, as a percent of
peak."""


def read(run):
    return run.mfu()
