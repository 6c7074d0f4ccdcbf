"""The reader of `rank_share.closed` (the `rank_rows` and
`rank_cap_rows` attributes of `device.launch`) on hand-built and
recorded traces, and the readers that were there before those
attributes, which must read the same with and without them."""

import pytest

from bench import harness
from benchroot import REPO
from repro.obs import TraceContext

NEW = "rank_share.closed"
OLD = ("host_ms.closed", "launch_ms.closed", "reqs_per_launch.closed",
       "bind_ms.closed", "fetch_ms.closed", "fetch_bytes_per_row.closed",
       "gc_ms.closed")


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _request(clock, rid, t0, launches, rank):
    """One request whose `execute` holds ``launches``: (ms, rank_rows,
    rank_cap_rows) each, the attributes only if ``rank``."""
    ctx = TraceContext(rid, clock, None)
    clock.t = t0 / 1e3
    ex = ctx.start("execute", shared_launch=False)
    for attempt, (ms, rows, cap) in enumerate(launches):
        sid = ctx.start("device.launch", batch=2, attempt=attempt)
        clock.t += ms / 1e3
        ctx.end(sid, overflow=False,
                **({"rank_rows": rows, "rank_cap_rows": cap}
                   if rank else {}))
    f = ctx.start("device.fetch")
    clock.t += 1e-3
    ctx.end(f, bytes=400, rows=10, retries=len(launches) - 1)
    ctx.end(ex)
    ctx.finish()
    return ctx


def hand_built(rank):
    clock = Clock()
    return [_request(clock, 1, 0, [(20, 4096, 65536), (30, 8192, 131072)],
                     rank),
            _request(clock, 2, 100, [(10, 2048, 2048)], rank)]


def _read(traces):
    spec = harness.Spec(REPO)
    rec = harness.RunRecord(cell={}, config={}, seconds=1.0, traces=traces,
                            before={"batches": 0, "batched_requests": 0},
                            after={"batches": 3, "batched_requests": 6})
    return {name: spec.reader(name)(rec) for name in (NEW,) + OLD}


@pytest.mark.parametrize("rank", [True, False])
def test_rank_share_on_a_hand_built_trace(rank):
    """Σ rank_rows ÷ Σ rank_cap_rows over every launch, retries
    included; None where no launch carries the attributes."""
    got = _read(hand_built(rank))[NEW]
    if rank:
        assert got == pytest.approx((4096 + 8192 + 2048)
                                    / (65536 + 131072 + 2048))
    else:
        assert got is None


def test_old_readers_read_the_same_without_the_rank_attributes():
    with_rank, without = _read(hand_built(True)), _read(hand_built(False))
    for name in OLD:
        assert with_rank[name] == pytest.approx(without[name]), name
    assert without["launch_ms.closed"] == pytest.approx((20 + 30 + 10) / 2)


def test_rank_share_on_a_recorded_trace(watdiv_small):
    """The jit executor's traced launches carry both attributes, and
    the share is in (0, 1]."""
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
    launches = [s.attrs for c in traces for s in c.spans
                if s.name == "device.launch"]
    assert launches and all(0 < a["rank_rows"] <= a["rank_cap_rows"]
                            for a in launches)
    assert 0 < _read(traces)[NEW] <= 1


def test_traced_run_reads_rank_share(tmp_path):
    """A traced run of the closed cell reports `rank_share.closed` (on
    the CPU, without a device trace)."""
    import json
    import os

    from benchroot import CLOSED, make_root, read_json

    root = make_root(str(tmp_path / "root"))
    path = os.path.join(root, "BENCHMARK.json")
    spec = read_json(path)
    real = read_json(os.path.join(REPO, "BENCHMARK.json"))
    (m,) = [m for m in real["per_layer"] if m["name"] == NEW]
    spec["per_layer"].append(dict(m, workloads=[CLOSED]))
    with open(path, "w") as f:
        json.dump(spec, f)
    out = harness.run_cell(CLOSED, 2**31 + 29, 1.0, traced=True, root=root,
                           require_tpu=False)
    assert out["correct"]
    got = out["metrics"][NEW]
    assert 0 < got["value"] <= 1 and got["unit"] == "share"
