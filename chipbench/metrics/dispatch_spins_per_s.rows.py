"""Dispatcher passes per second that found a query first in line and left
it pending behind a running query with a predicate of the same name
(``QueryReport.dispatch_deferrals``, summed over the window's queries, over
first submit to last completion)."""
from chipbench import program


def read(run):
    return program.spins_per_s(run)
