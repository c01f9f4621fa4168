"""Table rows scanned per second: every row of every query submitted in
the window, counting rows the pushdown drops, over first submit to last
completion."""


def read(run):
    return run.rate()
