"""Micro-batched execution: batched-vs-sequential parity on every
backend, one-compile-per-(template, bucket-shape), and the serving-layer
submit/flush queue."""

import jax
import numpy as np
import pytest

from repro.core import jexec
from repro.engine import Dataset, Engine
from repro.serve import SparqlServer


@pytest.fixture(scope="module")
def ds(watdiv_small):
    cat, d, sch = watdiv_small
    return Dataset(catalog=cat, dictionary=d, schema=sch)


def _template_instances(n, start=1):
    return [f"SELECT * WHERE {{ wsdbm:User{u} wsdbm:follows ?v . "
            f"?v sorg:email ?e }}" for u in range(start, start + n)]


MIXED_BATCH = (
    _template_instances(4)
    + ["SELECT * WHERE { wsdbm:User999999 wsdbm:follows ?v . "
       "?v sorg:email ?e }",                                  # missing const
       "SELECT * WHERE { ?p sorg:price ?x . ?x wsdbm:follows ?y }",  # empty plan
       "SELECT * WHERE { ?u wsdbm:likes ?p }"]                # second template
)


# ---------------------------------------------------------------------------
# Batched vs sequential parity (the eager loop is the oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["eager", "jit"])
def test_batch_parity(ds, backend):
    eng = Engine(ds, backend=backend)
    oracle = Engine(ds, backend="eager")
    batched = eng.query_batch(MIXED_BATCH)
    for q, got in zip(MIXED_BATCH, batched):
        assert got.same_as(oracle.query(q)), q


def test_batch_parity_distributed(ds):
    mesh = jax.make_mesh((1,), ("data",))
    eng = Engine(ds, backend="distributed", mesh=mesh)
    oracle = Engine(ds, backend="eager")
    batched = eng.query_batch(MIXED_BATCH)
    for q, got in zip(MIXED_BATCH, batched):
        assert got.same_as(oracle.query(q)), q


def _optional_union_instances(n, start=1):
    opt = [f"SELECT * WHERE {{ wsdbm:User{u} wsdbm:follows ?v "
           f"OPTIONAL {{ ?v sorg:email ?e }} }}"
           for u in range(start, start + n)]
    uni = [f"SELECT * WHERE {{ {{ wsdbm:User{u} wsdbm:follows ?v }} "
           f"UNION {{ wsdbm:User{u} wsdbm:likes ?v }} }} ORDER BY ?v"
           for u in range(start, start + n)]
    return opt + uni


@pytest.mark.parametrize("backend", ["jit", "auto"])
def test_batch_parity_optional_union(ds, backend):
    """OPTIONAL and UNION templates — now device-compiled — keep exact
    batched-vs-sequential parity, including under ``backend="auto"``
    where the router may land them on either substrate.  No instance may
    fall back to the host path."""
    eng = Engine(ds, backend=backend)
    oracle = Engine(ds, backend="eager")
    queries = _optional_union_instances(6)
    batched = eng.query_batch(queries)
    for q, got in zip(queries, batched):
        assert got.same_as(oracle.query(q)), q
    sequential = [eng.query(q) for q in queries]
    for q, got, want in zip(queries, batched, sequential):
        assert got.same_as(want), q
    assert eng.metrics.device_fallbacks == 0


def test_prepared_run_batch_matches_run_loop(ds):
    """PreparedQuery.run_batch == [run(b) for b] on the device backend,
    including missing-constant short-circuits inside the batch."""
    eng = Engine(ds, backend="jit")
    queries = _template_instances(3) + [
        "SELECT * WHERE { wsdbm:User999999 wsdbm:follows ?v . "
        "?v sorg:email ?e }"]
    prepared = eng.prepare(queries[0])
    bindings = [prepared.template.binding_for(q) for q in queries]
    assert bindings[-1].missing
    batched = prepared.run_batch(bindings)
    for b, got in zip(bindings, batched):
        assert got.same_as(prepared.run(b))
    assert len(batched[-1]) == 0


# ---------------------------------------------------------------------------
# Compilation accounting: one program per (template, bucket shape)
# ---------------------------------------------------------------------------

def test_one_compile_per_template_and_bucket_shape(ds):
    eng = Engine(ds, backend="jit")
    t0 = jexec.trace_count()
    eng.query_batch(_template_instances(5))      # bucket shape 8
    assert jexec.trace_count() == t0 + 1
    eng.query_batch(_template_instances(7, start=2))    # same bucket, reuse
    assert jexec.trace_count() == t0 + 1
    eng.query_batch(_template_instances(3, start=11))   # bucket shape 4
    assert jexec.trace_count() == t0 + 2
    m = eng.metrics.summary()
    assert m["batches"] == 3
    assert m["batched_requests"] == 15
    # 15 requests over 8+8+4 = 20 slots
    assert m["batch_occupancy"] == pytest.approx(15 / 20)
    assert m["padding_waste"] == pytest.approx(5 / 20)


def test_missing_constants_do_not_shrink_batch_shape(ds):
    """A missing-constant request inside a bucket is answered on the
    host; the device batch is padded back to the bucket shape, so the
    live-count never becomes a fresh compile shape."""
    eng = Engine(ds, backend="jit")
    full = _template_instances(4)
    eng.query_batch(full)                        # compile bucket shape 4
    t0 = jexec.trace_count()
    with_missing = _template_instances(3) + [
        "SELECT * WHERE { wsdbm:User999999 wsdbm:follows ?v . "
        "?v sorg:email ?e }"]
    res = eng.query_batch(with_missing)          # 3 live of bucket 4
    assert jexec.trace_count() == t0             # reused the B=4 program
    assert len(res[-1]) == 0


def test_batch32_single_launch_matches_sequential_eager(ds):
    """Acceptance probe: a 32-request same-template batch is ONE XLA
    program launch, multiset-equal to 32 sequential eager runs.  (Users
    25/32 are skipped: their follows-degree overflows the statistics-
    seeded scan capacity, which legitimately retries with doubled caps —
    a second program — in batched and sequential mode alike.)"""
    users = [u for u in range(0, 40) if u not in (25, 32)][:32]
    queries = [f"SELECT * WHERE {{ wsdbm:User{u} wsdbm:follows ?v . "
               f"?v sorg:email ?e }}" for u in users]
    eng = Engine(ds, backend="jit")
    t0 = jexec.trace_count()
    batched = eng.query_batch(queries)
    assert jexec.trace_count() == t0 + 1         # one program, 32 requests
    oracle = Engine(ds, backend="eager")
    for q, got in zip(queries, batched):
        assert got.same_as(oracle.query(q)), q
    m = eng.metrics.summary()
    assert m["batches"] == 1 and m["batch_occupancy"] == 1.0


def test_bucket_shape_menu():
    ds2 = Dataset.from_triples([("A", "follows", "B")])
    eng = ds2.engine("eager")
    assert [eng.bucket_shape(n) for n in (1, 2, 3, 5, 8, 9, 32, 100)] == \
        [1, 2, 4, 8, 8, 16, 32, 32]
    with pytest.raises(ValueError, match="batch_shapes"):
        Engine(ds2, backend="eager", batch_shapes=[0, 2])


def test_query_batch_preserves_submission_order(ds):
    """Interleaved templates come back in input order, not group order."""
    a = _template_instances(3)
    b = ["SELECT * WHERE { ?u wsdbm:likes ?p }"]
    interleaved = [a[0], b[0], a[1], a[2]]
    eng = Engine(ds, backend="jit")
    got = eng.query_batch(interleaved)
    oracle = Engine(ds, backend="eager")
    for q, r in zip(interleaved, got):
        assert r.same_as(oracle.query(q)), q


# ---------------------------------------------------------------------------
# Serving layer: submit / flush / demux
# ---------------------------------------------------------------------------

def test_server_submit_flush_demux(ds):
    # flush_ms=inf: this test drives the queue manually, so the latency
    # bound must not fire between slow (compiling) submits
    srv = SparqlServer(ds.catalog, backend="jit", max_batch=8,
                       flush_ms=1e9)
    queries = _template_instances(5)
    tickets = [srv.submit(q) for q in queries]
    assert srv.batcher.pending() == 5
    assert not tickets[0].done()
    served = srv.flush()
    assert served == 5 and srv.batcher.pending() == 0
    oracle = SparqlServer(ds.catalog, backend="eager")
    for q, t in zip(queries, tickets):
        assert t.done() and t.result().same_as(oracle.query(q))
    m = srv.metrics.summary()
    assert m["batches"] == 1 and m["batched_requests"] == 5
    assert srv.metrics.queue_hist.count == 5


def test_server_full_bucket_auto_flushes(ds):
    srv = SparqlServer(ds.catalog, backend="jit", max_batch=4,
                       flush_ms=1e9)
    tickets = [srv.submit(q) for q in _template_instances(4)]
    assert all(t.done() for t in tickets)        # size bound hit
    assert srv.batcher.pending() == 0


def test_ticket_result_forces_own_group(ds):
    srv = SparqlServer(ds.catalog, backend="eager", max_batch=32,
                       flush_ms=1e9)
    t1 = srv.submit(_template_instances(1)[0])
    t2 = srv.submit("SELECT * WHERE { ?u wsdbm:likes ?p }")
    assert len(t2.result()) > 0                  # drains only t2's bucket
    assert not t1.done() and srv.batcher.pending() == 1
    assert len(t1.result()) >= 0
    assert srv.batcher.pending() == 0


def test_server_query_batch_routes_through_batcher(ds):
    srv = SparqlServer(ds.catalog, backend="jit")
    res = srv.query_batch(MIXED_BATCH)
    oracle = SparqlServer(ds.catalog, backend="eager")
    for q, r in zip(MIXED_BATCH, res):
        assert r.same_as(oracle.query(q)), q
    assert srv.metrics.summary()["batches"] >= 2


def test_latency_flush_on_submit(ds, monkeypatch):
    srv = SparqlServer(ds.catalog, backend="eager", max_batch=32,
                       flush_ms=0.0)
    t1 = srv.submit(_template_instances(1)[0])
    # flush_ms=0: the next submit sees the deadline expired and drains all
    t2 = srv.submit(_template_instances(1, start=2)[0])
    assert t1.done()


def test_full_bucket_does_not_starve_other_signatures(ds):
    """A size-triggered flush of a hot template must not skip the
    latency check for other templates' queued requests."""
    srv = SparqlServer(ds.catalog, backend="eager", max_batch=2,
                       flush_ms=0.0)
    lone = srv.submit("SELECT * WHERE { ?u wsdbm:likes ?p }")
    srv.submit(_template_instances(1)[0])
    # this submit fills the hot bucket (size flush) AND must still honor
    # the expired deadline of the lone other-template request
    srv.submit(_template_instances(1, start=2)[0])
    assert lone.done()


def test_failed_batch_resolves_tickets_with_error(ds):
    srv = SparqlServer(ds.catalog, backend="eager", max_batch=32,
                       flush_ms=1e9)
    t1 = srv.submit(_template_instances(1)[0])
    t2 = srv.submit(_template_instances(1, start=2)[0])

    def boom(qtexts):
        raise RuntimeError("capacity overflow")
    srv.engine.query_batch = boom
    with pytest.raises(RuntimeError, match="capacity overflow"):
        srv.flush()
    assert t1.done() and t2.done()
    with pytest.raises(RuntimeError, match="capacity overflow"):
        t1.result()


# ---------------------------------------------------------------------------
# Fail-fast construction (bugfix): distributed backend without a mesh
# ---------------------------------------------------------------------------

def test_distributed_without_mesh_fails_at_construction(ds):
    with pytest.raises(ValueError, match="mesh"):
        SparqlServer(ds.catalog, backend="distributed")
    with pytest.raises(ValueError, match="mesh"):
        Engine(ds, backend="distributed")
