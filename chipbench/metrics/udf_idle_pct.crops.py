"""Percent of the traced window in which the device was idle while a UDF
call (``cb.udf`` span: featurize, transfer, launch, sync) was in progress."""


def read(run):
    return run.share(run.trace.idle_in("udf")) if run.trace else None
