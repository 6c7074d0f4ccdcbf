"""Reduction of a profiler trace to device busy time, idle share and the
``breakdown`` of the result line.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events: ``(plane, line, name, start_ns, dur_ns)``.  ``reduce`` works on
those events alone, so it can be checked on a small recorded trace.

* Device operations are the events of the ``XLA Ops`` line of each
  ``/device:`` plane.  Busy time is the union of their intervals inside
  the traced window, averaged over the devices.
* The breakdown's device operations are ranked by self time (nested
  operations, such as a ``while`` loop's body, counted once).
* Idle gaps are the stretches of the window in which no operation ran on
  the device; each is labelled by the host span that covers the gap's
  middle (the innermost one), or ``none``.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["Event", "load", "device_lines", "union", "self_times",
           "reduce", "host_labeller"]

Event = Tuple[str, str, str, float, float]   # plane, line, name, start, dur
OPS_LINE = "XLA Ops"
#: the harness's annotation around the whole window: the clock anchor
WINDOW = "bench.window"


def load(log_dir: str) -> List[Event]:
    """The events of the newest ``.xplane.pb`` under ``log_dir`` that the
    reduction reads: device operations and the harness's ``bench.*``
    host annotations."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    prof = ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in prof.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith("bench."):
                    out.append((plane.name, line.name, ev.name,
                                float(ev.start_ns), float(ev.duration_ns)))
    return out


def device_lines(events: Sequence[Event]) -> Dict[str, List[Event]]:
    """Device plane -> its operation events."""
    out: Dict[str, List[Event]] = defaultdict(list)
    for ev in events:
        if ev[0].startswith("/device:") and ev[1] == OPS_LINE:
            out[ev[0]].append(ev)
    return dict(out)


def union(intervals: Sequence[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def op_name(name: str) -> str:
    """An HLO operation's short name: ``%fusion.12 = s32[...] ...`` reads
    ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(evs: Sequence[Event], lo: float, hi: float
               ) -> Dict[str, float]:
    """Self time of each operation inside [lo, hi]: its duration less the
    operations nested in it (a ``while`` holds its body's operations)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []            # [end, name, self] of open events

    def close(item):
        out[item[1]] += item[2]

    for e in sorted(evs, key=lambda e: (e[3], -e[4])):
        a, b = e[3], e[3] + e[4]
        if a < lo or b > hi:
            continue
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= e[4]
        stack.append([b, op_name(e[2]), e[4]])
    while stack:
        close(stack.pop())
    return out


def reduce(events: Sequence[Event], window: Tuple[float, float],
           label: Optional[Callable[[float], str]] = None,
           top: int = 10) -> Optional[dict]:
    """Busy seconds, window seconds, idle share (%) and the breakdown, or
    None when the trace holds no device operation.  ``window`` is in the
    events' nanoseconds; ``label(t_ns)`` names what the host was doing."""
    lo, hi = window
    per_dev = device_lines(events)
    if not per_dev or hi <= lo:
        return None
    busy = []
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    for plane in sorted(per_dev):
        evs = per_dev[plane]
        spans = union([(e[3], e[3] + e[4]) for e in evs], lo, hi)
        busy.append(sum(b - a for a, b in spans))
        for name, t in self_times(evs, lo, hi).items():
            op_time[name] += t / len(per_dev)
        edges = [lo] + [x for s in spans for x in s] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                name = label((a + b) / 2) if label is not None else "none"
                gap_time[name] += (b - a) / len(per_dev)
    busy_ns = sum(busy) / len(busy)
    window_ns = hi - lo
    if busy_ns <= 0:
        return None

    def ranked(d: Dict[str, float]) -> List[list]:
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns * 1e-9, "window_s": window_ns * 1e-9,
            "idle_pct": 100.0 * (1.0 - busy_ns / window_ns),
            "breakdown": {"device_ops": ranked(op_time),
                          "idle_gaps": ranked(gap_time)}}


def host_labeller(events: Sequence[Event], extra: Sequence[Event] = ()
                  ) -> Callable[[float], str]:
    """``label(t)``: the innermost (shortest) host span covering ``t``,
    among the ``bench.*`` annotations of the host planes (the window's
    own excepted) and ``extra`` (program spans already moved onto the
    trace's clock)."""
    spans = [(e[3], e[3] + e[4], e[2]) for e in events
             if e[0].startswith("/host:") and e[2].startswith("bench.")
             and e[2] != WINDOW]
    spans += [(e[3], e[3] + e[4], e[2]) for e in extra]
    spans.sort()
    starts = [a for a, _, _ in spans]
    longest = max((b - a for a, b, _ in spans), default=0.0)

    def label(t: float) -> str:
        best = None
        i = bisect.bisect_right(starts, t) - 1
        while i >= 0 and spans[i][0] >= t - longest:
            a, b, name = spans[i]
            if b >= t and (best is None or b - a < best[0]):
                best = (b - a, name)
            i -= 1
        return best[1] if best is not None else "none"

    return label
