"""Per-request span tracing with deterministic sampling.

One sampled request carries one :class:`TraceContext` through the whole
serving path — batcher queue, routing, plan cache, device launch,
demux/decode — collecting explicit start/end **span** records plus
instantaneous **events** (plan-cache hit/miss, routing decisions with
the losing EWMAs, tuner retirements).  Everything is measured through
the :class:`~repro.runtime.config.RuntimeConfig` clock, so traces are
deterministic and unit-testable with an injected fake clock, exactly
like the router and tuner.

The cardinal rule is that **disabled tracing costs ~nothing**: the hot
path's only obligation is

    tr = engine.tracer
    ctx = tr.begin(qtext) if tr is not None and tr.active else None

— one attribute load and one float compare when ``trace_sample_rate``
is 0 (``benchmarks/trace_overhead.py`` gates this at ≤1%).  Sampling is
deterministic stride sampling (1 in ``round(1/rate)`` requests), not
random — reproducible under test and immune to unlucky streaks.

Finished traces flow into the tracer's
:class:`~repro.obs.recorder.FlightRecorder` (ring + slow-query
reservoir) and feed per-stage :class:`~repro.obs.histogram.LogHistogram`
aggregates, which :mod:`repro.obs.prometheus` exposes as
``repro_stage_ms`` series.

Two things ride along with every span of an active tracer.  A
:class:`GcMeter` hooked into ``gc.callbacks`` totals the garbage
collector's pauses, and each span records the pauses that completed
inside it (``gc_ms``, ``gc_n``).  And each span is mirrored as a
``jax.profiler.TraceAnnotation`` named ``repro.<span name>``, so a
profiler trace shows the program's spans on the same clock as the
device's operations.
"""

from __future__ import annotations

import gc
import weakref
from typing import Any, Dict, List, Optional

from repro.obs.histogram import LogHistogram
from repro.obs.recorder import FlightRecorder

__all__ = ["GcMeter", "Span", "TraceContext", "Tracer"]

#: spans left off the profiler's timeline: a ``queue`` span opens in one
#: call (``submit``) and closes in another, on another thread in a
#: threaded server, where a profiler annotation cannot follow it
UNMIRRORED = frozenset({"queue"})


class GcMeter:
    """Running total of the garbage collector's pauses, timed with the
    config clock: a ``gc.callbacks`` hook, installed while a tracer is
    active.  Only collections that complete are counted."""

    __slots__ = ("clock", "ms", "n", "hooked", "_t0")

    def __init__(self, clock):
        self.clock = clock
        self.ms = 0.0
        self.n = 0
        self.hooked = False
        self._t0: Optional[float] = None

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = self.clock()
        elif self._t0 is not None:
            self.ms += (self.clock() - self._t0) * 1e3
            self.n += 1
            self._t0 = None

    def install(self) -> None:
        if not self.hooked:
            gc.callbacks.append(self)
            self.hooked = True

    def remove(self) -> None:
        if self.hooked:
            gc.callbacks.remove(self)
            self.hooked = False
            self._t0 = None


def _annotation(name: str):
    """An entered profiler annotation (cheap when no profile is taken)."""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name)
    ann.__enter__()
    return ann


class Span:
    """One timed region of a trace.  ``t0``/``t1`` are raw clock seconds
    (the config clock's units); ``t1 is None`` while the span is open."""

    __slots__ = ("sid", "name", "parent", "t0", "t1", "attrs", "events",
                 "gc0", "ann")

    def __init__(self, sid: int, name: str, parent: Optional[int],
                 t0: float, attrs: Dict[str, Any]):
        self.sid = sid
        self.name = name
        self.parent = parent      # parent span's sid (None for the root)
        self.t0 = t0
        self.t1: Optional[float] = None
        self.attrs = attrs
        self.events: List[Dict[str, Any]] = []
        self.gc0 = None           # the GcMeter's (ms, n) at the start
        self.ann = None           # the open profiler annotation

    @property
    def duration_ms(self) -> Optional[float]:
        return None if self.t1 is None else (self.t1 - self.t0) * 1e3

    def __repr__(self) -> str:
        dur = self.duration_ms
        shown = "open" if dur is None else f"{dur:.3f}ms"
        return f"Span({self.sid}, {self.name!r}, {shown})"


class TraceContext:
    """The spans and events of ONE sampled request.

    Span 0 is the root (``request``); :meth:`start`/:meth:`end` manage a
    stack of open spans so nesting falls out of call order.  The context
    is carried *by argument* through the engine, batcher, prepared
    queries and executors — there is no thread-local or global state, so
    the untraced path never looks anything up.

    A context begun by a :class:`Tracer` reads the tracer's
    :class:`GcMeter` at each span's start and end, and mirrors each span
    but the root (and the ``UNMIRRORED`` ones) as a profiler annotation.
    """

    __slots__ = ("trace_id", "clock", "spans", "_open", "_tracer",
                 "duration_ms", "_gc")

    def __init__(self, trace_id: int, clock, tracer: "Optional[Tracer]",
                 name: str = "request", **attrs: Any):
        self.trace_id = trace_id
        self.clock = clock
        self._tracer = tracer
        self._gc: Optional[GcMeter] = None if tracer is None else tracer.gc
        self.duration_ms: Optional[float] = None
        root = Span(0, name, None, clock(), attrs)
        if self._gc is not None:
            root.gc0 = (self._gc.ms, self._gc.n)
        self.spans: List[Span] = [root]
        self._open: List[int] = [0]

    @property
    def root(self) -> Span:
        return self.spans[0]

    # -- spans -----------------------------------------------------------------
    def start(self, name: str, **attrs: Any) -> int:
        """Open a child span under the innermost open span; returns its
        sid for :meth:`end`."""
        sid = len(self.spans)
        parent = self._open[-1] if self._open else 0
        span = Span(sid, name, parent, self.clock(), attrs)
        if self._gc is not None:
            span.gc0 = (self._gc.ms, self._gc.n)
            if name not in UNMIRRORED:
                span.ann = _annotation("repro." + name)
        self.spans.append(span)
        self._open.append(sid)
        return sid

    def _close(self, span: Span, t: float) -> None:
        span.t1 = t
        if span.gc0 is not None:
            span.attrs["gc_ms"] = self._gc.ms - span.gc0[0]
            span.attrs["gc_n"] = self._gc.n - span.gc0[1]
        if span.ann is not None:
            span.ann.__exit__(None, None, None)
            span.ann = None

    def end(self, sid: int, **attrs: Any) -> None:
        """Close span ``sid`` (and anything left open inside it — a
        child that escaped its ``end`` must not dangle past its parent)."""
        t = self.clock()
        while self._open and self._open[-1] != sid:
            inner = self.spans[self._open.pop()]
            if inner.t1 is None:
                self._close(inner, t)
        if self._open and self._open[-1] == sid:
            self._open.pop()
        span = self.spans[sid]
        if span.t1 is None:
            self._close(span, t)
        if attrs:
            span.attrs.update(attrs)

    # -- events / annotations --------------------------------------------------
    def event(self, name: str, **attrs: Any) -> None:
        """Instantaneous event on the innermost open span."""
        holder = self.spans[self._open[-1]] if self._open else self.root
        holder.events.append({"name": name, "t": self.clock(),
                              "attrs": attrs})

    def annotate(self, sid: int = 0, **attrs: Any) -> None:
        """Attach attributes to span ``sid`` (default: the root)."""
        self.spans[sid].attrs.update(attrs)

    def annotate_named(self, name: str, **attrs: Any) -> int:
        """Attach attributes to every span called ``name`` (how the
        engine joins estimated/actual cardinalities onto device-launch
        spans after the fact); returns the number annotated."""
        n = 0
        for span in self.spans:
            if span.name == name:
                span.attrs.update(attrs)
                n += 1
        return n

    def finish(self, **attrs: Any) -> None:
        """Close the root (and any stragglers) and hand the complete
        trace to the tracer's recorder/aggregates."""
        if self.root.t1 is not None:
            return                      # already finished (idempotent)
        self.end(0, **attrs)
        self.duration_ms = self.root.duration_ms
        if self._tracer is not None:
            self._tracer._finished(self)


class Tracer:
    """Sampling front door + aggregate sink for :class:`TraceContext`.

    Reads ``trace_sample_rate`` from the config on every :meth:`begin`,
    so the rate is live-tunable (the overhead benchmark warms caches at
    rate 1.0 and then measures at the gated rates on the same engine).
    The :class:`GcMeter` is hooked in by the first :meth:`begin` at a
    rate above 0, and out again by the first :meth:`begin` or finished
    trace that reads a rate of 0 (or when the tracer is collected); the
    untraced path's :attr:`active` guard stays one compare.
    """

    def __init__(self, config):
        self.config = config
        self.recorder = FlightRecorder(
            ring=getattr(config, "trace_ring", 256),
            slow_ms=getattr(config, "trace_slow_ms", 100.0),
            slow_keep=getattr(config, "trace_slow_keep", 64))
        #: per span-name duration aggregates (repro_stage_ms in the
        #: Prometheus exposition)
        self.stage_hist: Dict[str, LogHistogram] = {}
        self.started = 0          # sampled-in traces begun
        self.finished = 0
        self.sampled_out = 0      # requests the stride skipped
        self._seen = 0            # all begin() calls (stride counter)
        self._next_id = 0
        #: garbage-collector pauses while tracing is on (see GcMeter)
        self.gc = GcMeter(config.clock)
        weakref.finalize(self, GcMeter.remove, self.gc)

    @property
    def active(self) -> bool:
        """False ⇒ the engine must not even build a TraceContext — the
        guard the ≤1%-overhead gate measures."""
        return self.config.trace_sample_rate > 0.0

    def begin(self, qtext: Optional[str] = None,
              **attrs: Any) -> Optional[TraceContext]:
        """A TraceContext for this request, or ``None`` when the stride
        samples it out (sampled-out requests create zero records)."""
        rate = self.config.trace_sample_rate
        if rate <= 0.0:
            self.gc.remove()
            return None
        self.gc.install()
        self._seen += 1
        if rate < 1.0:
            stride = max(1, round(1.0 / rate))
            if (self._seen - 1) % stride != 0:
                self.sampled_out += 1
                return None
        self._next_id += 1
        self.started += 1
        if qtext is not None:
            attrs.setdefault("qtext", qtext[:200])
        return TraceContext(self._next_id, self.config.clock, self,
                            **attrs)

    def _finished(self, ctx: TraceContext) -> None:
        self.finished += 1
        if self.config.trace_sample_rate <= 0.0:
            self.gc.remove()
        for span in ctx.spans:
            dur = span.duration_ms
            if dur is None:
                continue
            hist = self.stage_hist.get(span.name)
            if hist is None:
                hist = self.stage_hist[span.name] = LogHistogram()
            hist.record(dur)
        self.recorder.add(ctx)

    # -- export passthroughs ---------------------------------------------------
    def chrome_trace(self) -> Dict[str, Any]:
        return self.recorder.chrome_trace()

    def to_jsonl(self) -> str:
        return self.recorder.to_jsonl()
