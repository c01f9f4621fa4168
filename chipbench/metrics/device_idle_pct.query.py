"""Percent of the traced window in which no operation ran on the device."""


def read(run):
    return run.share(run.trace.idle_s()) if run.trace else None
