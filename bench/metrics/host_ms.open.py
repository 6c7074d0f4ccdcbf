"""Engine (engine/engine.py, engine/backends.py): mean host time per
request in parse, plan and execute outside the fenced device launch
(demux and decode included), ms."""

from bench import spans


def read(run):
    return spans.host_ms(run.traces)
