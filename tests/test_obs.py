"""Observability layer (repro.obs): streaming histogram error bounds,
deterministic stride sampling, span nesting under an injected fake
clock, flight-recorder ring/slow-reservoir retention, Chrome trace
export round-trips, ServerMetrics histograms (None percentiles on an
idle server, exact counts under a flood), the engine/batcher
integration — device-launch spans carrying estimated AND actual
per-step cardinalities on both device backends — and the spans between
launches: ``bind``, ``device.fetch``, garbage-collector pauses
(``gc_ms``/``gc_n``) and the ``repro.*`` profiler mirror."""

import json

import jax
import numpy as np
import pytest

from repro.engine import Dataset, RuntimeConfig, ServerMetrics
from repro.obs import FlightRecorder, LogHistogram, TraceContext, Tracer
from repro.obs.histogram import GROWTH, LO_MS


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, seconds):
        self.t += seconds


def _tracer(**kw):
    kw.setdefault("clock", FakeClock())
    kw.setdefault("trace_sample_rate", 1.0)
    return Tracer(RuntimeConfig(**kw))


# ---------------------------------------------------------------- histogram

class TestLogHistogram:
    def test_empty_is_none_not_zero(self):
        h = LogHistogram()
        assert h.percentile(50) is None
        assert h.percentile(99) is None
        assert h.mean_ms is None
        assert len(h) == 0

    def test_single_sample_reports_itself(self):
        h = LogHistogram()
        h.record(3.7)
        # clamped to the observed max, not the bucket's upper edge
        assert h.percentile(50) == pytest.approx(3.7)
        assert h.percentile(99) == pytest.approx(3.7)

    def test_percentile_error_bound(self):
        """Any percentile is within a factor GROWTH (≈1.19×) above the
        exact nearest-rank order statistic."""
        rng = np.random.default_rng(0)
        samples = np.exp(rng.normal(1.0, 1.5, size=2000))  # ms, heavy tail
        h = LogHistogram()
        for s in samples:
            h.record(float(s))
        ordered = np.sort(samples)
        for q in (1, 25, 50, 90, 99, 99.9):
            rank = max(1, int(np.ceil(q / 100.0 * len(ordered))))
            exact = ordered[rank - 1]
            got = h.percentile(q)
            assert exact <= got <= exact * GROWTH * (1 + 1e-12), \
                f"p{q}: exact={exact} got={got}"

    def test_out_of_range_samples_clamped_to_observed(self):
        h = LogHistogram()
        h.record(1e-9)          # underflow slot
        assert h.percentile(50) == pytest.approx(1e-9)
        h2 = LogHistogram()
        h2.record(1e9)          # overflow slot (no finite edge)
        assert h2.percentile(99) == pytest.approx(1e9)

    def test_merge_equals_combined_recording(self):
        rng = np.random.default_rng(1)
        a_samples = rng.exponential(5.0, 300)
        b_samples = rng.exponential(50.0, 300)
        a, b, both = LogHistogram(), LogHistogram(), LogHistogram()
        for s in a_samples:
            a.record(float(s))
            both.record(float(s))
        for s in b_samples:
            b.record(float(s))
            both.record(float(s))
        a.merge(b)
        assert a.count == both.count
        assert a.sum_ms == pytest.approx(both.sum_ms)
        assert a.min_ms == both.min_ms and a.max_ms == both.max_ms
        for q in (50, 90, 99):
            assert a.percentile(q) == both.percentile(q)

    def test_record_large_count_is_o1(self):
        h = LogHistogram()
        h.record(2.0, count=10**9)      # would OOM as a sample list
        assert h.count == 10**9
        assert h.percentile(99) == pytest.approx(2.0)

    def test_cumulative_buckets_monotone_and_total(self):
        h = LogHistogram()
        for ms in (0.01, 0.5, 0.5, 7.0, 300.0):
            h.record(ms)
        pairs = list(h.cumulative_buckets())
        edges = [e for e, _ in pairs]
        cums = [c for _, c in pairs]
        assert edges == sorted(edges)
        assert cums == sorted(cums) and cums[-1] == h.count

    def test_invalid_percentile(self):
        h = LogHistogram()
        h.record(1.0)
        with pytest.raises(ValueError):
            h.percentile(101)


# ------------------------------------------------------------------ sampling

class TestSampling:
    def test_rate_zero_inactive(self):
        tr = _tracer(trace_sample_rate=0.0)
        assert not tr.active
        assert tr.begin("q") is None

    def test_rate_one_samples_everything(self):
        tr = _tracer(trace_sample_rate=1.0)
        assert all(tr.begin("q") is not None for _ in range(10))
        assert tr.started == 10 and tr.sampled_out == 0

    def test_stride_sampling_deterministic(self):
        tr = _tracer(trace_sample_rate=0.5)
        picks = [tr.begin("q") is not None for _ in range(8)]
        assert picks == [True, False] * 4
        assert tr.sampled_out == 4

    def test_sampled_out_leaves_zero_records(self):
        tr = _tracer(trace_sample_rate=0.25)
        for _ in range(8):
            ctx = tr.begin("q")
            if ctx is not None:
                ctx.finish()
        assert tr.started == 2 and tr.sampled_out == 6
        assert len(tr.recorder) == 2   # nothing from the sampled-out 6

    def test_rate_is_read_live_from_config(self):
        tr = _tracer(trace_sample_rate=1.0)
        assert tr.begin("q") is not None
        tr.config.trace_sample_rate = 0.0
        assert not tr.active and tr.begin("q") is None

    def test_invalid_rate_rejected(self):
        with pytest.raises(ValueError):
            RuntimeConfig(trace_sample_rate=1.5)
        with pytest.raises(ValueError):
            RuntimeConfig(trace_sample_rate=-0.1)


# -------------------------------------------------------------------- spans

class TestSpanNesting:
    def test_nesting_and_ordering(self):
        tr = _tracer()
        clock = tr.config.clock
        ctx = tr.begin("q")
        clock.advance(0.001)
        a = ctx.start("plan")
        clock.advance(0.002)
        b = ctx.start("verify")            # nested inside plan
        clock.advance(0.003)
        ctx.end(b)
        clock.advance(0.001)
        ctx.end(a)
        clock.advance(0.001)
        c = ctx.start("execute")           # sibling after plan
        clock.advance(0.005)
        ctx.end(c)
        ctx.finish()

        spans = {s.sid: s for s in ctx.spans}
        assert spans[b].parent == a and spans[a].parent == 0
        assert spans[c].parent == 0
        # children inside parent bounds
        assert spans[a].t0 <= spans[b].t0 and spans[b].t1 <= spans[a].t1
        # siblings non-overlapping and ordered
        assert spans[a].t1 <= spans[c].t0
        assert spans[b].duration_ms == pytest.approx(3.0)
        assert ctx.duration_ms == pytest.approx(13.0)

    def test_dangling_child_closed_by_parent_end(self):
        tr = _tracer()
        ctx = tr.begin("q")
        outer = ctx.start("outer")
        inner = ctx.start("inner")
        tr.config.clock.advance(0.004)
        ctx.end(outer)                     # inner never ended explicitly
        assert ctx.spans[inner].t1 == ctx.spans[outer].t1
        ctx.finish()

    def test_finish_idempotent_and_closes_stragglers(self):
        tr = _tracer()
        ctx = tr.begin("q")
        ctx.start("open-span")
        tr.config.clock.advance(0.010)
        ctx.finish(backend="jit")
        ctx.finish()                       # second call is a no-op
        assert tr.finished == 1
        assert all(s.t1 is not None for s in ctx.spans)
        assert ctx.root.attrs["backend"] == "jit"

    def test_events_attach_to_innermost_open_span(self):
        tr = _tracer()
        ctx = tr.begin("q")
        sid = ctx.start("plan")
        ctx.event("plan_cache", hit=False)
        ctx.end(sid)
        ctx.event("root-level")
        assert ctx.spans[sid].events[0]["name"] == "plan_cache"
        assert ctx.root.events[0]["name"] == "root-level"

    def test_annotate_named(self):
        tr = _tracer()
        ctx = tr.begin("q")
        for _ in range(2):
            ctx.end(ctx.start("device.launch"))
        assert ctx.annotate_named("device.launch", cardinalities=[1]) == 2
        assert ctx.annotate_named("no-such-span", x=1) == 0


# ----------------------------------------------------------- flight recorder

def _fake_trace(clock, trace_id, duration_s):
    ctx = TraceContext(trace_id, clock, None)
    clock.advance(duration_s)
    ctx.finish()
    return ctx


class TestFlightRecorder:
    def test_ring_evicts_but_slow_reservoir_keeps(self):
        clock = FakeClock()
        rec = FlightRecorder(ring=4, slow_ms=10.0, slow_keep=2)
        slow = _fake_trace(clock, 1, 0.050)     # 50 ms — slow
        rec.add(slow)
        for i in range(10):                     # fast flood evicts the ring
            rec.add(_fake_trace(clock, 10 + i, 0.001))
        ids = {c.trace_id for c in rec.traces()}
        assert slow.trace_id in ids             # survived ring eviction
        assert len([i for i in ids if i >= 10]) == 4
        assert rec.dropped > 0

    def test_slow_reservoir_keeps_slowest(self):
        clock = FakeClock()
        rec = FlightRecorder(ring=1, slow_ms=10.0, slow_keep=2)
        for tid, dur in ((1, 0.020), (2, 0.040), (3, 0.030)):
            rec.add(_fake_trace(clock, tid, dur))
        ids = {c.trace_id for c in rec.traces()}
        assert 2 in ids and 3 in ids            # the two slowest kept
        assert 1 not in ids                     # fastest slow trace evicted

    def test_chrome_trace_round_trip(self):
        tr = _tracer()
        clock = tr.config.clock
        for _ in range(3):
            ctx = tr.begin("SELECT * WHERE { ?s ?p ?o }")
            sid = ctx.start("plan", planner="greedy")
            clock.advance(0.002)
            ctx.end(sid)
            inner = ctx.start("execute")
            clock.advance(0.004)
            ctx.end(inner, rows=np.int64(7))    # numpy attr must degrade
            ctx.finish()
        doc = json.loads(json.dumps(tr.chrome_trace()))
        assert doc["displayTimeUnit"] == "ms"
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        by_tid = {}
        for e in spans:
            by_tid.setdefault(e["tid"], []).append(e)
        assert len(by_tid) == 3
        for tid, evs in by_tid.items():
            root = next(e for e in evs if e["name"] == "request")
            children = [e for e in evs if e is not root]
            # children within root bounds, monotone and non-overlapping
            prev_end = root["ts"]
            for e in sorted(children, key=lambda e: e["ts"]):
                assert e["ts"] >= prev_end
                assert e["ts"] + e["dur"] <= root["ts"] + root["dur"]
                prev_end = e["ts"] + e["dur"]
        rows = next(e["args"]["rows"] for e in spans
                    if e["name"] == "execute")
        assert rows == 7 and isinstance(rows, int)

    def test_jsonl_round_trip(self):
        tr = _tracer()
        ctx = tr.begin("q")
        tr.config.clock.advance(0.2)       # 200 ms > slow_ms default
        ctx.finish()
        rows = [json.loads(line) for line in
                tr.to_jsonl().splitlines()]
        assert len(rows) == 1
        assert rows[0]["slow"] is True
        assert rows[0]["spans"][0]["name"] == "request"


# ------------------------------------------------------------ server metrics

class TestServerMetrics:
    def test_idle_percentiles_are_none(self):
        m = ServerMetrics()
        s = m.summary()
        assert s["p50_ms"] is None and s["p99_ms"] is None
        assert s["queue_p50_ms"] is None and s["queue_p99_ms"] is None

    def test_histogram_primary_compat_list_views(self):
        m = ServerMetrics()
        m.record_latency(5.0)
        m.record_latency(2.0, count=3)
        m.record_queue(1.5)
        assert m.latency_hist.count == 4
        assert m.queue_hist.count == 1
        assert m.summary()["queue_p50_ms"] == pytest.approx(1.5, rel=GROWTH)
        assert m.summary()["p50_ms"] == pytest.approx(2.0, rel=GROWTH)

    def test_list_views_trim_o1_under_flood(self):
        m = ServerMetrics()
        m.record_latency(1.0, count=3 * 8192)
        assert m.latency_hist.count == 3 * 8192          # exact, untrimmed
        assert m.summary()["p99_ms"] == pytest.approx(1.0, rel=GROWTH)

    def test_prometheus_exposition(self):
        m = ServerMetrics()
        m.served = 3
        m.record_latency(4.0)
        m.record_route("jit", 3)
        text = m.prometheus()
        assert "repro_served_total 3" in text
        assert 'repro_routed_total{backend="jit"} 3' in text
        assert 'repro_request_latency_ms_bucket{le="+Inf"} 1' in text
        assert "repro_request_latency_ms_count 1" in text


# -------------------------------------------------------- engine integration

QA = "SELECT * WHERE { ?v0 <wsdbm:follows> ?v1 . ?v1 <wsdbm:likes> ?v2 }"
QB = "SELECT * WHERE { ?v0 <rev:reviewer> ?v1 . ?v1 <wsdbm:likes> ?v2 }"


@pytest.fixture(scope="module")
def ds(watdiv_small):
    cat, d, sch = watdiv_small
    return Dataset(catalog=cat, dictionary=d, schema=sch)


def _launch_spans(tracer):
    return [e for e in tracer.chrome_trace()["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "device.launch"]


class TestEngineTracing:
    def test_jit_trace_carries_cardinalities(self, ds):
        eng = ds.engine("jit",
                        runtime=RuntimeConfig(trace_sample_rate=1.0))
        eng.query(QA)
        eng.query(QA)        # second pass: plan-cache hit
        eng.query(QB)
        assert eng.metrics.device_fallbacks == 0
        launches = _launch_spans(eng.tracer)
        assert launches and all("cardinalities" in e["args"]
                                for e in launches)
        for e in launches:
            assert e["args"]["backend"] == "jit"
            for step in e["args"]["cardinalities"]:
                assert step["actual"] is not None
                assert step["est"] is None or step["est"] >= 0
        # router/plan-cache story is in the event stream
        events = [ev for tr in eng.tracer.recorder.traces()
                  for s in tr.spans for ev in s.events]
        names = [ev["name"] for ev in events]
        assert "router.decide" in names
        outcomes = [ev["attrs"]["outcome"] for ev in events
                    if ev["name"] == "plan_cache"]
        assert "miss" in outcomes and "hit" in outcomes
        decide = next(ev for ev in events if ev["name"] == "router.decide")
        assert "ewma_ms" in decide["attrs"]

    def test_untraced_engine_records_nothing(self, ds):
        eng = ds.engine("jit", runtime=RuntimeConfig())  # rate 0 default
        res = eng.query(QA)
        assert eng.tracer.started == 0
        assert len(eng.tracer.recorder) == 0
        assert res is not None

    def test_distributed_trace_carries_cardinalities(self, ds):
        mesh = jax.make_mesh((jax.device_count(),), ("data",))
        eng = ds.engine("distributed", mesh=mesh,
                        runtime=RuntimeConfig(trace_sample_rate=1.0))
        eng.query(QA)
        assert eng.metrics.device_fallbacks == 0
        launches = _launch_spans(eng.tracer)
        assert launches
        for e in launches:
            assert e["args"]["backend"] == "distributed"
            assert e["args"]["shards"] == jax.device_count()
            assert all(s["actual"] is not None
                       for s in e["args"]["cardinalities"])

    def test_traced_matches_untraced_results(self, ds):
        plain = ds.engine("jit", runtime=RuntimeConfig())
        traced = ds.engine("jit",
                           runtime=RuntimeConfig(trace_sample_rate=1.0))
        for q in (QA, QB):
            a, b = plain.query(q), traced.query(q)
            assert a.cols == b.cols
            assert sorted(map(tuple, a.to_numpy().tolist())) \
                == sorted(map(tuple, b.to_numpy().tolist()))

    def test_batcher_queue_spans(self, ds):
        from repro.serve.batcher import MicroBatcher
        eng = ds.engine("jit",
                        runtime=RuntimeConfig(trace_sample_rate=1.0))
        mb = MicroBatcher(eng, max_batch=8, flush_ms=1e9)
        tickets = [mb.submit(QA) for _ in range(3)]
        mb.flush()
        assert all(t.result() is not None for t in tickets)
        ct = eng.tracer.chrome_trace()
        queues = [e for e in ct["traceEvents"]
                  if e.get("ph") == "X" and e["name"] == "queue"]
        assert len(queues) == 3
        assert all(e["args"]["batch"] == 3 for e in queues)
        execs = [e for e in ct["traceEvents"]
                 if e.get("ph") == "X" and e["name"] == "execute"]
        shared = [e["args"].get("shared_launch") for e in execs]
        assert shared.count(False) == 1 and shared.count(True) == 2

    def test_prometheus_end_to_end(self, ds):
        eng = ds.engine("jit",
                        runtime=RuntimeConfig(trace_sample_rate=1.0))
        eng.query(QA)
        text = eng.metrics.prometheus()
        assert "repro_served_total 1" in text
        assert 'repro_traces_total{state="finished"} 1' in text
        assert 'repro_stage_ms_bucket{stage="device.launch"' in text


# ------------------------------------------- between launches: bind, fetch, gc

class TickClock:
    """A clock that moves 1 ms at every read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t


def _children(ctx, sid):
    return [s for s in ctx.spans if s.parent == sid]


class CountingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records names."""

    made: list = []
    exited = 0

    def __init__(self, name):
        CountingAnnotation.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        CountingAnnotation.exited += 1


@pytest.fixture
def annotations(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    CountingAnnotation.made = []
    CountingAnnotation.exited = 0
    return CountingAnnotation


class TestBetweenLaunches:
    @pytest.mark.parametrize("backend", ["jit", "distributed"])
    def test_bind_and_fetch_nest_under_execute_on_lead(self, ds, backend):
        from repro.serve.batcher import MicroBatcher
        kw = {"mesh": jax.make_mesh((1,), ("data",))} \
            if backend == "distributed" else {}
        eng = ds.engine(backend, runtime=RuntimeConfig(clock=TickClock()),
                        **kw)
        eng.query(QA)                 # grows the capacities untraced
        eng.config.trace_sample_rate = 1.0
        mb = MicroBatcher(eng, max_batch=8, flush_ms=1e9)
        tickets = [mb.submit(QA) for _ in range(3)]
        mb.flush()
        answers = [len(t.result()) for t in tickets]
        lead, *rest = [t.trace for t in tickets]
        ex = next(s for s in lead.spans if s.name == "execute")
        assert ex.attrs["shared_launch"] is False
        kids = _children(lead, ex.sid)
        assert [s.name for s in kids] == ["bind", "device.launch",
                                          "device.fetch", "demux"]
        bind, launch, fetch, demux = kids
        shape = ex.attrs["shape"]
        assert bind.attrs["batch"] == shape == launch.attrs["batch"] == 4
        # the pad slot repeats the last binding: B answers come back
        assert fetch.attrs["rows"] == sum(answers) + answers[-1]
        assert fetch.attrs["retries"] == 0
        assert fetch.attrs["bytes"] >= 4 * fetch.attrs["rows"] * 3
        # one after another, all inside execute
        assert ex.t0 < bind.t0 < bind.t1 <= launch.t0 < launch.t1 \
            <= fetch.t0 < fetch.t1 <= demux.t0 < demux.t1 < ex.t1
        for ctx in rest:
            names = [s.name for s in ctx.spans]
            assert "bind" not in names and "device.fetch" not in names

    def test_single_query_binds_launches_fetches_decodes(self, ds):
        eng = ds.engine("jit", runtime=RuntimeConfig(clock=TickClock()))
        eng.query(QA)                 # grows the capacities untraced
        eng.config.trace_sample_rate = 1.0
        res = eng.query(QA)
        ctx = eng.tracer.recorder.traces()[-1]
        ex = next(s for s in ctx.spans if s.name == "execute")
        kids = _children(ctx, ex.sid)
        assert [s.name for s in kids] == ["bind", "device.launch",
                                          "device.fetch", "decode"]
        assert kids[0].attrs["batch"] == 1
        assert kids[2].attrs["rows"] == len(res)

    def test_device_launch_keeps_its_boundaries(self, ds):
        """The fenced launch span holds the program call, the wait for
        the device and the overflow flags' copy, and nothing after it;
        ``device.fetch`` starts where it ends."""
        ex = ds.engine("jit").prepare(QA).executor
        clock = FakeClock()
        k = len(ex._final_cols())

        class Flags:
            """Overflow flags whose copy to the host takes 1 ms."""

            def __array__(self, dtype=None, copy=None):
                clock.advance(0.001)
                return np.zeros(len(ex.caps), bool)

        def program(caps, *inputs):
            clock.advance(0.005)
            return (np.zeros((8, k), np.int32), np.int32(3), Flags(),
                    np.int32(40))

        ex._jitted = program
        # what tracing the real program records for these caps
        ex._rank_cap_rows[(False, tuple(ex.caps))] = 64
        ctx = TraceContext(1, clock, None)
        data, _ = ex.run(trace=ctx)
        ctx.finish()
        (launch,) = [s for s in ctx.spans if s.name == "device.launch"]
        (fetch,) = [s for s in ctx.spans if s.name == "device.fetch"]
        assert launch.duration_ms == pytest.approx(6.0)
        assert launch.attrs["cap_slots"] == sum(ex.caps)
        assert (launch.attrs["rank_rows"],
                launch.attrs["rank_cap_rows"]) == (40, 64)
        assert fetch.t0 == launch.t1
        assert fetch.attrs == {"bytes": 8 * k * 4 + 4, "rows": 3,
                               "retries": 0}
        assert len(data) == 3

    def test_gc_pause_inside_span_is_counted(self):
        import gc
        tr = _tracer(clock=TickClock())
        ctx = tr.begin("q")
        gc.disable()
        try:
            quiet = ctx.start("plan")
            ctx.end(quiet)
            busy = ctx.start("execute")
            gc.collect()
            ctx.end(busy)
        finally:
            gc.enable()
        ctx.finish()
        span = ctx.spans[busy]
        assert span.attrs["gc_n"] >= 1
        # the tick clock reads once at a pause's start and once at its end
        assert span.attrs["gc_ms"] == pytest.approx(span.attrs["gc_n"])
        assert ctx.spans[quiet].attrs["gc_n"] == 0
        assert ctx.spans[quiet].attrs["gc_ms"] == 0.0
        assert ctx.root.attrs["gc_n"] == span.attrs["gc_n"]
        tr.config.trace_sample_rate = 0.0
        assert tr.begin("q") is None and tr.gc not in gc.callbacks

    def test_gc_hook_follows_the_rate(self):
        """Hooked in by the first traced request; out again with the
        first trace that finishes, or the first begin, at rate 0."""
        import gc
        tr = _tracer()
        assert tr.gc not in gc.callbacks
        tr.begin("q").finish()
        assert tr.gc in gc.callbacks
        open_ctx = tr.begin("q")
        tr.config.trace_sample_rate = 0.0
        assert not tr.active and tr.gc in gc.callbacks
        open_ctx.finish()
        assert tr.gc not in gc.callbacks
        tr.config.trace_sample_rate = 1.0
        tr.begin("q")
        assert tr.gc in gc.callbacks
        tr.config.trace_sample_rate = 0.0
        assert tr.begin("q") is None and tr.gc not in gc.callbacks

    def test_rate_zero_installs_no_hook_and_no_annotation(self, ds,
                                                          annotations):
        import gc
        from repro.serve.batcher import MicroBatcher
        before = list(gc.callbacks)
        eng = ds.engine("jit", runtime=RuntimeConfig())    # rate 0
        eng.query(QA)
        mb = MicroBatcher(eng, max_batch=8, flush_ms=1e9)
        for _ in range(3):
            mb.submit(QA)
        mb.flush()
        gc.collect()
        assert gc.callbacks == before
        assert annotations.made == []
        assert eng.tracer.started == 0

    def test_spans_mirror_to_profiler_annotations(self, ds, annotations):
        from repro.serve.batcher import MicroBatcher
        eng = ds.engine("jit",
                        runtime=RuntimeConfig(trace_sample_rate=1.0))
        mb = MicroBatcher(eng, max_batch=8, flush_ms=1e9)
        tickets = [mb.submit(QA) for _ in range(3)]
        mb.flush()
        made = set(annotations.made)
        for name in ("execute", "bind", "device.launch", "device.fetch",
                     "demux"):
            assert "repro." + name in made
        # the root and the cross-call queue span stay off the timeline
        assert "repro.request" not in made and "repro.queue" not in made
        assert annotations.exited == len(annotations.made)
        spans = [s for t in tickets for s in t.trace.spans
                 if s.name not in ("request", "queue")]
        assert len(annotations.made) == len(spans)
        eng.config.trace_sample_rate = 0.0
        assert eng.tracer.begin("q") is None
