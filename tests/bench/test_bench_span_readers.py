"""The readers of the spans between device launches (`bind`,
`device.fetch`, the `gc_ms` of `execute`) on hand-built and recorded
traces, and the readers that were there before those spans, which must
read the same with and without them."""

import json
import os
from types import SimpleNamespace

import pytest

from bench import harness
from benchroot import CLOSED, REPO, make_root, read_json
from repro.obs import TraceContext
from repro.obs.tracer import Span

NEW = ("bind_ms.closed", "fetch_ms.closed", "fetch_bytes_per_row.closed",
       "gc_ms.closed")
OLD = ("host_ms.closed", "launch_ms.closed", "reqs_per_launch.closed")


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def at(self, ms):
        self.t = ms / 1e3


def _span(ctx, clock, name, t0, t1, **attrs):
    clock.at(t0)
    sid = ctx.start(name)
    clock.at(t1)
    ctx.end(sid, **attrs)
    return sid


def _execute(ctx, clock, t0, t1, parts, new, gc_ms, **attrs):
    """An ``execute`` span from ``t0`` to ``t1`` ms holding ``parts``:
    (name, t0, t1, attrs); the new spans and ``gc_ms`` only if ``new``."""
    clock.at(t0)
    sid = ctx.start("execute", **attrs)
    for name, a, b, kw in parts:
        if new or name not in ("bind", "device.fetch"):
            _span(ctx, clock, name, a, b, **kw)
    clock.at(t1)
    ctx.end(sid, **({"gc_ms": gc_ms, "gc_n": 1} if new else {}))


def hand_built(new):
    """Three requests: two share one launch of batch 2 (the lead parsed
    and planned the template), the third runs alone."""
    clock = Clock()
    lead = TraceContext(1, clock, None)
    _span(lead, clock, "queue", 0, 2)
    _span(lead, clock, "parse", 2, 3)
    _span(lead, clock, "plan", 3, 5)
    _execute(lead, clock, 5, 100, [
        ("bind", 6, 8, {"batch": 2}),
        ("device.launch", 8, 88, {"batch": 2}),
        ("device.fetch", 88, 92, {"bytes": 4000, "rows": 50, "retries": 0}),
        ("demux", 92, 95, {})], new, 1.5, shared_launch=False)
    clock.at(101)
    lead.finish()
    clock.at(1)
    other = TraceContext(2, clock, None)
    _span(other, clock, "queue", 1, 2)
    _execute(other, clock, 5, 100, [], new, 1.5, shared_launch=True)
    clock.at(101)
    other.finish()
    clock.at(110)
    alone = TraceContext(3, clock, None)
    _execute(alone, clock, 110, 150, [
        ("bind", 111, 112, {"batch": 1}),
        ("device.launch", 112, 140, {"batch": 1}),
        ("device.fetch", 140, 141, {"bytes": 1000, "rows": 10,
                                    "retries": 1}),
        ("decode", 141, 142, {})], new, 0.5)
    clock.at(151)
    alone.finish()
    return [lead, other, alone]


def _record(traces):
    return harness.RunRecord(cell={}, config={}, seconds=1.0, traces=traces,
                             before={"batches": 4, "batched_requests": 7},
                             after={"batches": 6, "batched_requests": 10})


def _read(traces):
    spec = harness.Spec(REPO)
    rec = _record(traces)
    return {name: spec.reader(name)(rec) for name in NEW + OLD}


def test_new_readers_on_a_hand_built_trace():
    got = _read(hand_built(new=True))
    assert got["bind_ms.closed"] == pytest.approx((2 + 1) / 3)
    assert got["fetch_ms.closed"] == pytest.approx((4 + 1) / 3)
    assert got["fetch_bytes_per_row.closed"] == pytest.approx(5000 / 60)
    # the shared execute's pause is the lead's: counted once
    assert got["gc_ms.closed"] == pytest.approx((1.5 + 0.5) / 3)


def test_old_readers_read_the_same_without_the_new_spans():
    """host time, launch time and requests per launch read what they
    read before `bind`, `device.fetch` and `gc_ms` existed; the new
    readers find nothing to read there."""
    with_new, without = _read(hand_built(True)), _read(hand_built(False))
    for name in OLD:
        assert with_new[name] == pytest.approx(without[name])
    assert without["host_ms.closed"] == pytest.approx((1 + 2 + 95 + 40
                                                       - 80 - 28) / 3)
    assert without["launch_ms.closed"] == pytest.approx((80 + 28) / 3)
    assert without["reqs_per_launch.closed"] == pytest.approx(1.5)
    assert all(without[name] is None for name in NEW)


def _strip(ctx):
    """A copy of a recorded trace as the program made it before the new
    spans: no `bind` or `device.fetch`, no `gc_ms` or `gc_n`."""
    spans = []
    for s in ctx.spans:
        if s.name in ("bind", "device.fetch"):
            continue
        copy = Span(s.sid, s.name, s.parent, s.t0,
                    {k: v for k, v in s.attrs.items()
                     if k not in ("gc_ms", "gc_n")})
        copy.t1 = s.t1
        spans.append(copy)
    return SimpleNamespace(spans=spans)


def test_old_readers_read_the_same_on_a_recorded_trace(watdiv_small):
    from repro.engine import Dataset, RuntimeConfig
    from repro.serve.batcher import MicroBatcher

    cat, d, sch = watdiv_small
    eng = Dataset(catalog=cat, dictionary=d, schema=sch).engine(
        "jit", runtime=RuntimeConfig(trace_sample_rate=1.0,
                                     trace_ring=1024))
    mb = MicroBatcher(eng, max_batch=4, flush_ms=1e9)
    for q in ["SELECT * WHERE { ?u wsdbm:follows ?v . ?v wsdbm:likes ?p }",
              "SELECT * WHERE { ?u wsdbm:likes ?p }"] * 3:
        mb.submit(q)
    mb.flush()
    traces = eng.tracer.recorder.traces()
    assert len(traces) == 6
    recorded, old = _read(traces), _read([_strip(c) for c in traces])
    for name in OLD:
        assert recorded[name] == pytest.approx(old[name])
    assert recorded["bind_ms.closed"] > 0
    assert recorded["fetch_ms.closed"] > 0
    assert recorded["fetch_bytes_per_row.closed"] >= 4
    assert recorded["gc_ms.closed"] >= 0
    assert all(old[name] is None for name in NEW)


def test_traced_run_reads_the_new_metrics(tmp_path):
    """A traced run of the closed cell reports the four new metrics
    beside the old ones (on the CPU, without a device trace)."""
    root = make_root(str(tmp_path / "root"))
    path = os.path.join(root, "BENCHMARK.json")
    spec = read_json(path)
    real = read_json(os.path.join(REPO, "BENCHMARK.json"))
    for m in real["per_layer"]:
        if m["name"] in NEW:
            spec["per_layer"].append(dict(m, workloads=[CLOSED]))
    with open(path, "w") as f:
        json.dump(spec, f)
    out = harness.run_cell(CLOSED, 2**31 + 23, 1.0, traced=True, root=root,
                           require_tpu=False)
    assert out["correct"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in NEW + OLD:
        assert name in got, name
    assert got["bind_ms.closed"] > 0 and got["fetch_ms.closed"] > 0
    assert got["fetch_bytes_per_row.closed"] >= 4
    assert out["metrics"]["fetch_bytes_per_row.closed"]["unit"] == "bytes"
