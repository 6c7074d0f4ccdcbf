"""Device executor (core/jexec.py): bytes copied from the device to the
host per answer row, over the window's `device.fetch` spans (sum of
their `bytes` over sum of their `rows`); None without rows."""

from bench import spans


def read(run):
    fetches = list(spans._spans(run.traces, "device.fetch"))
    rows = sum(s.attrs["rows"] for s in fetches)
    if not rows:
        return None
    return sum(s.attrs["bytes"] for s in fetches) / rows
