"""Set-up seconds: process start to the window (backend, weights, data,
compiles and the warm-up query)."""


def read(run):
    return run.setup_s
