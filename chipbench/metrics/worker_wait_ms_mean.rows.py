"""Mean milliseconds a batch waited in a Laminar worker's queue, from the
put to the worker's get: ``queue_wait_ns`` over ``dequeued`` of every
predicate entry of the window's queries."""
from chipbench import program


def read(run):
    return program.ratio(program.predicate_total(run, "queue_wait_ns"),
                         program.predicate_total(run, "dequeued"), 1e-6)
