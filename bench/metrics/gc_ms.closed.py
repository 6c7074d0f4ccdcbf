"""Engine (engine/engine.py, repro/obs): garbage-collector pauses inside
the engine's `execute` spans per request, ms (the spans' `gc_ms`; an
execute that only shared another request's launch is not counted).
None where the program records no `gc_ms`."""

from bench import spans


def read(run):
    pauses = [s.attrs["gc_ms"] for s in spans._spans(run.traces, "execute")
              if "gc_ms" in s.attrs
              and not s.attrs.get("shared_launch", False)]
    if not pauses:
        return None
    return sum(pauses) / len(run.traces)
