"""Serving shell (serve/batcher.py): requests per device launch over
the window, `batched_requests / batches`."""


def read(run):
    n = run.delta("batches")
    return run.delta("batched_requests") / n if n else None
