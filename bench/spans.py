"""Sums over the program's request spans, for the per-layer readers.

Each traced request carries one ``TraceContext`` (``repro.obs``).  A
micro-batch shares one device launch: the fenced ``device.launch`` span
sits under the batch's first traced request (its lead), and the other
requests' ``execute`` spans are flagged ``shared_launch`` and cover the
same wall time, so they are left out of host time.
"""

from __future__ import annotations

from typing import Iterable, Optional

__all__ = ["per_request_ms", "host_ms"]


def _spans(traces: Iterable, name: str):
    for ctx in traces:
        for span in ctx.spans:
            if span.name == name and span.t1 is not None:
                yield span


def per_request_ms(traces, name: str) -> Optional[float]:
    """Total duration of spans called ``name`` over the traced requests,
    per request, in ms; None without traced requests."""
    traces = list(traces)
    if not traces:
        return None
    return sum(s.duration_ms for s in _spans(traces, name)) / len(traces)


def host_ms(traces) -> Optional[float]:
    """Host time per request in the engine: parse, plan and execute
    (demux and decode run inside execute), less the fenced device
    launches; executes that only shared another request's launch are
    not counted again."""
    traces = list(traces)
    if not traces:
        return None
    total = 0.0
    for name in ("parse", "plan", "verify"):
        total += sum(s.duration_ms for s in _spans(traces, name))
    total += sum(s.duration_ms for s in _spans(traces, "execute")
                 if not s.attrs.get("shared_launch", False))
    total -= sum(s.duration_ms for s in _spans(traces, "device.launch"))
    return total / len(traces)
