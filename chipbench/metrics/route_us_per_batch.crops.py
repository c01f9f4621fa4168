"""Mean microseconds of one eddy routing decision, hand-off to the worker
queue included: ``route_ns`` over ``routed`` of ``QueryReport.routing``,
summed over the window's queries."""
from chipbench import program


def read(run):
    return program.ratio(program.total(run, lambda rep: rep.routing["route_ns"]),
                         program.total(run, lambda rep: rep.routing["routed"]),
                         1e-3)
