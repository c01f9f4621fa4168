"""Milliseconds of jaxpr tracing and MLIR lowering on the query's threads
per batch a worker dequeued: ``lower_s`` of ``QueryReport.compile`` over
``dequeued`` of every predicate entry, over the window's queries."""
from chipbench import program


def read(run):
    return program.ratio(program.total(run, lambda rep: rep.compile["lower_s"]),
                         program.predicate_total(run, "dequeued"), 1e3)
