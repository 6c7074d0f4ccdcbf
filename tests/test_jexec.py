"""Static-shape jitted executor vs the eager engine, incl. overflow-retry."""

import collections

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.algebra import BGP, Query, TriplePattern
from repro.core.compiler import compile_bgp
from repro.core.executor import execute
from repro.core.jexec import PlanExecutor
from repro.core.sparql import parse_sparql
from repro.core.stats import build_catalog


def compare(qtext, cat, d):
    q = parse_sparql(qtext, d)
    plan = compile_bgp(q.root, cat)
    ex = PlanExecutor(plan, cat)
    data, cols = ex.run()
    ref = execute(q, cat)
    m1 = collections.Counter(
        tuple(int(x) for x in r)
        for r in data[:, [cols.index(c) for c in ref.cols]])
    m2 = collections.Counter(map(tuple, ref.data.tolist()))
    assert m1 == m2, qtext
    return data, cols


def test_q1_device(g1):
    cat, d = g1
    data, cols = compare(
        "SELECT * WHERE { ?x likes ?w . ?x follows ?y . "
        "?y follows ?z . ?z likes ?w }", cat, d)
    assert len(data) == 1


@pytest.mark.parametrize("qtext", [
    "SELECT * WHERE { ?u wsdbm:follows ?v . ?v wsdbm:likes ?p }",
    "SELECT * WHERE { ?u sorg:email ?e . ?u foaf:age ?a }",
    "SELECT * WHERE { ?u wsdbm:follows ?v . ?v wsdbm:likes ?p . ?p sorg:price ?x }",
    "SELECT * WHERE { wsdbm:User1 wsdbm:follows ?v . ?v sorg:email ?e }",
    "SELECT * WHERE { ?r rev:reviewer ?u . ?u wsdbm:friendOf ?f }",
    "SELECT * WHERE { ?p rev:hasReview ?r . ?r rev:rating ?x . ?p sorg:price ?y }",
])
def test_watdiv_queries(watdiv_small, qtext):
    cat, d, _ = watdiv_small
    compare(qtext, cat, d)


def test_overflow_retry(watdiv_small):
    """Force tiny capacities; the executor must retry and still be exact."""
    cat, d, _ = watdiv_small
    q = parse_sparql(
        "SELECT * WHERE { ?u wsdbm:follows ?v . ?v wsdbm:likes ?p }", d)
    plan = compile_bgp(q.root, cat)
    ex = PlanExecutor(plan, cat)
    ex.caps = [16 for _ in ex.caps]            # deliberately too small
    data, cols = ex.run()
    ref = execute(q, cat)
    assert len(data) == len(ref)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_device_join_random(data_strategy):
    """device path == eager path on random 2-pattern BGPs."""
    rng = np.random.default_rng(data_strategy.draw(st.integers(0, 2**31 - 1)))
    n_terms = data_strategy.draw(st.integers(2, 8))
    n_triples = data_strategy.draw(st.integers(1, 40))
    tt = np.stack([
        rng.integers(0, n_terms, n_triples),
        np.full(n_triples, n_terms + rng.integers(0, 2)),
        rng.integers(0, n_terms, n_triples),
    ], axis=1).astype(np.int32)
    tt = np.unique(tt, axis=0)
    cat = build_catalog(tt)
    preds = sorted(cat.vp.keys())
    pat = [TriplePattern("?a", preds[0], "?b"),
           TriplePattern("?b", preds[-1], "?c")]
    q = Query(root=BGP(pat), select=None, distinct=False)
    plan = compile_bgp(q.root, cat)
    ref = execute(q, cat)
    if plan.empty:
        assert len(ref) == 0
        return
    ex = PlanExecutor(plan, cat)
    got, cols = ex.run()
    m1 = collections.Counter(
        tuple(int(x) for x in r)
        for r in got[:, [cols.index(c) for c in ref.cols]])
    m2 = collections.Counter(map(tuple, ref.data.tolist()))
    assert m1 == m2


@pytest.mark.parametrize("n", [1, 1000, 1024, 4096, 1 << 21])
def test_prefix_sum_matches_cumsum(n):
    """The two-level prefix sum (long inputs) is exactly cumsum."""
    import jax.numpy as jnp
    from repro.core.jexec import prefix_sum

    x = np.random.default_rng(n).integers(0, 50, n).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(prefix_sum(jnp.asarray(x))),
                                  np.cumsum(x))


@pytest.mark.parametrize("out_cap", [16, 3000, 5000])
def test_compact_is_stable_keep_first(out_cap):
    """Sort-free compaction: kept rows in order at the front, PAD
    behind, overflow exactly when they do not fit."""
    import jax.numpy as jnp
    from repro.core.jexec import _compact
    from repro.rdf.dictionary import PAD

    rng = np.random.default_rng(out_cap)
    data = rng.integers(0, 99, (4096, 3)).astype(np.int32)
    keep = rng.random(4096) < 0.7
    got, n, ovf = _compact(jnp.asarray(data), jnp.asarray(keep), out_cap)
    want = data[keep][:out_cap]
    assert int(n) == len(want) and bool(ovf) == (keep.sum() > out_cap)
    np.testing.assert_array_equal(np.asarray(got)[:len(want)], want)
    assert (np.asarray(got)[len(want):] == PAD).all()
