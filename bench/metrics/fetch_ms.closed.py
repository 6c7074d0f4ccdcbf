"""Device executor (core/jexec.py): mean `device.fetch` span per
request, ms: the launch's capacity buffer and counts copied to the host
and sliced into per-request answers, once per launch.  None where the
program has no `device.fetch` span."""

from bench import spans


def read(run):
    if next(spans._spans(run.traces, "device.fetch"), None) is None:
        return None
    return spans.per_request_ms(run.traces, "device.fetch")
