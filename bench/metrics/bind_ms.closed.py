"""Engine (engine/backends.py, core/jexec.py): mean `bind` span per
request, ms: re-binding the plan, building the bounds and filter-constant
inputs and uploading them, once per launch on the batch's lead request.
None where the program has no `bind` span."""

from bench import spans


def read(run):
    if next(spans._spans(run.traces, "bind"), None) is None:
        return None
    return spans.per_request_ms(run.traces, "bind")
