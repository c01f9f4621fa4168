"""Crops scanned per second: every crop of every query submitted in the
window, over first submit to last completion."""


def read(run):
    return run.rate()
