"""Device executor (core/jexec.py): programs traced inside the window,
the change in `jexec.trace_count()`; should read 0."""


def read(run):
    return run.delta("traces")
