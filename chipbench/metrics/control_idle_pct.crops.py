"""Percent of the traced window in which the device was idle and neither a
UDF call nor the scan was in progress: service, executor, eddy, Laminar
and worker threads."""


def read(run):
    return run.share(run.trace.idle_outside(["udf", "source"])) if run.trace else None
