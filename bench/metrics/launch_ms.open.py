"""Device executor (core/jexec.py): mean fenced `device.launch` span per
request, ms (a shared launch counts once for its batch)."""

from bench import spans


def read(run):
    return spans.per_request_ms(run.traces, "device.launch")
