"""Static-shape jitted executor vs the eager engine, incl. overflow-retry."""

import collections

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import jexec
from repro.core.algebra import BGP, Cmp, Query, TriplePattern
from repro.core.compiler import compile_bgp
from repro.core.executor import execute
from repro.core.jexec import CHUNK, JBindings, PlanExecutor
from repro.rdf.dictionary import PAD, UNBOUND
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


@pytest.mark.parametrize("n", [0, 1, 2, 3, 1000, 4096])
def test_ranks_match_searchsorted(n):
    """The executor's binary search gives numpy's insertion points, both
    sides, over duplicates, NULL and pad sentinels and keys outside the
    array."""
    import jax.numpy as jnp

    rng = np.random.default_rng(n)
    keys = np.sort(np.concatenate([
        rng.integers(-6, 50, n - n // 8),
        np.full(n // 8, jexec.B_SENT)])).astype(np.int32)
    q = np.concatenate([rng.integers(-8, 60, 300),
                        [jexec.A_SENT, jexec.B_SENT, jexec.A_NULL,
                         jexec.B_NULL]]).astype(np.int32)
    got = jexec._ranks(jnp.asarray(keys), jnp.asarray(q), ("left", "right"))
    for side, g in zip(("left", "right"), got):
        np.testing.assert_array_equal(np.asarray(g),
                                      np.searchsorted(keys, q, side=side))


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


# ---------------------------------------------------------------------------
# Named device scopes and the overflow-retry counter
# ---------------------------------------------------------------------------

#: every named scope of the device program (core/jexec.py)
SCOPES = ("scan", "scan.window", "scan.tt", "join.build_sort", "join.probe",
          "join.expand", "join.compact", "left_join", "union", "filter",
          "spine.resize", "spine.order", "spine.project", "spine.distinct",
          "spine.slice", "shared")

#: one template that reaches every device step
SCOPED_QUERY = """SELECT DISTINCT ?u ?p WHERE {
  ?u wsdbm:follows ?v . ?v wsdbm:likes ?p .
  OPTIONAL { ?p rev:hasReview ?r }
  { ?u wsdbm:friendOf ?f } UNION { ?u ?x wsdbm:User0 }
  UNION { wsdbm:User0 wsdbm:follows ?u }
  FILTER(?u != ?p) } ORDER BY ?p LIMIT 10"""


def test_device_steps_carry_named_scopes(watdiv_small):
    """Lowering one batched program names each device step in the HLO
    metadata: scans, the join's build sort, probe, expand and compact,
    left join, union, filter, the modifier spine and the hoisted shared
    phase."""
    import re

    import jax.numpy as jnp

    from repro.engine import Dataset

    cat, d, sch = watdiv_small
    eng = Dataset(catalog=cat, dictionary=d, schema=sch).engine("jit")
    assert len(eng.query(SCOPED_QUERY)) > 0
    ex = eng.prepare(SCOPED_QUERY).executor
    rows, ns, tt_rows, tt_n, values = ex._device_inputs
    bounds = jnp.asarray(np.stack([ex._default_bounds] * 2))
    fconsts = jnp.asarray(np.stack([ex.fconsts_from_mapping(None)] * 2))
    text = ex._jitted_batch.lower(tuple(ex.caps), rows, ns, tt_rows, tt_n,
                                  bounds, fconsts, values) \
        .as_text(debug_info=True)
    scopes = {part for loc in re.findall(r'loc\("([^"]*)"', text)
              for part in loc.split("/")}
    assert set(SCOPES) <= scopes, sorted(set(SCOPES) - scopes)


def _overflow_once(ex):
    """Leave ``ex`` with capacities that overflow exactly once: grow every
    slot from 16 to what the plan needs, then halve the last grown one
    (its inputs fit, so it alone overflows, and one doubling fits)."""
    ex.caps = [16 for _ in ex.caps]
    ex.run()
    need = list(ex.caps)
    last = max(i for i, c in enumerate(need) if c > 16)
    ex.caps[last] = need[last] // 2
    return need


@pytest.mark.parametrize("backend", ["jit", "distributed"])
@pytest.mark.parametrize("batched", [False, True])
def test_retry_count_counts_relaunches(watdiv_small, backend, batched):
    """``retry_count()`` moves once per relaunch after an overflow, in
    both executors, single and batched, traced or not; grown capacities
    persist, so the next launch does not move it."""
    import jax

    from repro.core import jexec
    from repro.core.distributed import DistributedExecutor
    from repro.obs import TraceContext

    cat, d, _ = watdiv_small
    q = parse_sparql(
        "SELECT * WHERE { ?u wsdbm:follows ?v . ?v wsdbm:likes ?p }", d)
    plan = compile_bgp(q.root, cat)
    ex = PlanExecutor(plan, cat) if backend == "jit" else \
        DistributedExecutor(plan, cat, jax.make_mesh((1,), ("data",)))

    def go(trace=None):
        if batched:
            return ex.run_batch([ex._default_bounds] * 2, trace=trace)[0]
        return ex.run(trace=trace)

    need = _overflow_once(ex)
    before = jexec.retry_count()
    data, _ = go()
    assert jexec.retry_count() == before + 1
    assert ex.caps == need
    assert len(data) == len(execute(q, cat))
    assert go()[0].shape == data.shape
    assert jexec.retry_count() == before + 1

    _overflow_once(ex)
    trace = TraceContext(1, lambda: 0.0, None)
    before = jexec.retry_count()
    go(trace)
    assert jexec.retry_count() == before + 1
    launches = [s.attrs for s in trace.spans if s.name == "device.launch"]
    assert [(a["attempt"], a["overflow"]) for a in launches] == \
        [(0, True), (1, False)]
    fetch = next(s.attrs for s in trace.spans if s.name == "device.fetch")
    assert fetch["retries"] == 1
    assert fetch["rows"] == len(data) * (2 if batched else 1)


# ---------------------------------------------------------------------------
# Rank searches over the live rows only, in chunks of CHUNK rows
# ---------------------------------------------------------------------------

#: capacity of the hand-built relations: four chunks
CAP = 4 * CHUNK
LIVE = (0, 1, CHUNK - 1, CHUNK, CHUNK + 1, CAP)


def _rel(cols, rows, cap=CAP):
    import jax.numpy as jnp

    rows = np.asarray(rows, np.int32).reshape(-1, len(cols))
    data = np.full((cap, len(cols)), PAD, np.int32)
    data[:len(rows)] = rows
    return JBindings(tuple(cols), jnp.asarray(data),
                     jnp.asarray(len(rows), jnp.int32), jnp.asarray(False))


def _np_expand(a, b):
    """The natural join's output slots in order (probe-major, build rows
    in their own order), each with whether the shared columns beyond the
    key agree."""
    (acols, arows), (bcols, brows) = a, b
    shared = [c for c in acols if c in bcols]
    b_only = [bcols.index(c) for c in bcols if c not in acols]
    by_key = collections.defaultdict(list)
    for r in brows:
        by_key[r[bcols.index(shared[0])]].append(r)
    slots = []
    for r in arows:
        for s in by_key.get(r[acols.index(shared[0])], []) \
                if r[acols.index(shared[0])] != UNBOUND else []:
            ok = all(r[acols.index(c)] == s[bcols.index(c)]
                     and r[acols.index(c)] != UNBOUND for c in shared[1:])
            slots.append((list(r) + [s[i] for i in b_only], ok))
    return slots


def _case(op, live):
    """(device call, numpy rows, numpy overflow) of one operator whose
    probe or input relation holds ``live`` rows."""
    rng = np.random.default_rng(live + 7 * len(op))
    x = rng.integers(0, CAP, live)
    perm = rng.permutation(CAP)
    if op == "join":            # one build row per key: `live` matches
        a = (("x", "y"), np.stack([x, rng.integers(0, 99, live)], 1))
        b = (("x", "z"), np.stack([perm, rng.integers(0, 99, CAP)], 1))
        slots = _np_expand(a, b)
        want = [r for r, _ in slots]
        return ("join", a, b), want, False
    if op == "left_join":       # odd keys have no build row
        a = (("x", "y"), np.stack([x, rng.integers(0, 99, live)], 1))
        even = perm[perm % 2 == 0]
        b = (("x", "z"), np.stack([even, rng.integers(0, 99, len(even))], 1))
        slots = _np_expand(a, b)
        hit = {r[0] for r, _ in slots}
        want = [r for r, _ in slots] + [
            list(r) + [UNBOUND] for r in a[1] if r[0] not in hit]
        return ("left_join", a, b), want, False
    if op == "multi_key":       # two build rows per key, y agrees on one
        y = rng.integers(0, 3, live)
        y[rng.random(live) < 0.1] = UNBOUND
        a = (("x", "y"), np.stack([x // 2, y], 1))
        keys = np.repeat(perm[: CAP // 2], 2)
        b = (("x", "y", "z"), np.stack(
            [keys, (keys + np.arange(CAP) % 2) % 3,
             rng.integers(0, 99, CAP)], 1))
        slots = _np_expand(a, b)
        want = [r for r, ok in slots[:CAP] if ok]
        return ("join", a, b), want, len(slots) > CAP
    if op == "filter":          # ?x != ?y
        rows = np.stack([x, x % 5], 1)
        want = [list(r) for r in rows if r[0] != r[1]]
        return ("filter", (("x", "y"), rows)), want, False
    assert op == "union"        # then 3 rows with a column of their own
    rows = np.stack([x, x % 5], 1)
    extra = np.arange(9).reshape(3, 3)
    want = [list(r) + [UNBOUND] for r in rows] + \
        [[r[0], UNBOUND, r[1]] for r in extra[:, :2]]
    return ("union", (("x", "y"), rows), (("x", "z"), extra[:, :2])), \
        want[:CAP], len(want) > CAP


def _run(call):
    """Trace and run ``call`` under jit with the current CHUNK; returns
    (rows below n, n, overflow, the whole buffer) on the host."""
    import jax
    import jax.numpy as jnp

    kind = call[0]
    rels = [_rel(*r) for r in call[1:]]

    def program(datas, ns):
        rs = [JBindings(r.cols, d, n, jnp.asarray(False))
              for r, d, n in zip(rels, datas, ns)]
        if kind == "join":
            out = jexec.device_join(rs[0], rs[1], CAP)
        elif kind == "left_join":
            out = jexec.device_left_join(rs[0], rs[1], CAP)
        elif kind == "union":
            out = jexec.device_union(rs[0], rs[1], CAP)
        else:
            out = jexec.device_filter(
                rs[0], Cmp("!=", "x", "y"),
                jnp.zeros((0, 4), jnp.float32),
                jnp.zeros((0,), jnp.int32), [0])
        return out.data, out.n, out.overflow

    data, n, ovf = jax.jit(program)([r.data for r in rels],
                                    [r.n for r in rels])
    data = np.asarray(data)
    return data[:int(n)], int(n), bool(ovf), data


@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("op", ["join", "left_join", "multi_key", "filter",
                                "union"])
def test_chunked_equals_single_pass_and_numpy(monkeypatch, op, live):
    """Join, left join, the multi-key join's compaction, filter and
    union: searching the live rows in chunks gives the single-pass
    buffers, n and overflow flag exactly, and numpy's rows in order
    (truncated at the capacity where the flag is up)."""
    call, want, want_ovf = _case(op, live)
    chunked = _run(call)
    monkeypatch.setattr(jexec, "CHUNK", 1 << 30)
    single = _run(call)
    np.testing.assert_array_equal(chunked[3], single[3])
    assert chunked[1:3] == single[1:3]
    assert chunked[2] == want_ovf
    if not want_ovf:
        np.testing.assert_array_equal(chunked[0], np.asarray(
            want, np.int32).reshape(-1, chunked[3].shape[1]))
    assert (chunked[3][chunked[1]:] == PAD).all()


def test_join_past_out_cap(monkeypatch):
    """total > out_cap: the expansion fills every slot, the overflow flag
    is up in both forms, and the slots hold the first out_cap matches."""
    rng = np.random.default_rng(3)
    a = (("x",), rng.integers(0, CAP // 2, (CAP, 1)))
    b = (("x", "z"), np.stack([np.arange(CAP) // 2,
                               rng.integers(0, 99, CAP)], 1))
    want = [r for r, _ in _np_expand(a, b)][:CAP]
    chunked = _run(("join", a, b))
    monkeypatch.setattr(jexec, "CHUNK", 1 << 30)
    single = _run(("join", a, b))
    np.testing.assert_array_equal(chunked[3], single[3])
    assert chunked[1:3] == single[1:3] == (CAP, True)
    np.testing.assert_array_equal(chunked[0], np.asarray(want, np.int32))


#: a template whose bound subject gives each binding its own live rows
BOUND_TEMPLATE = ("SELECT * WHERE { wsdbm:User%d wsdbm:follows ?v . "
                  "?v wsdbm:likes ?p }")


def _executor(cat, d, qtext):
    return PlanExecutor(compile_bgp(parse_sparql(qtext, d).root, cat), cat)


def _user_bounds(ex, d, users):
    out = []
    for u in users:
        b = np.array(ex._default_bounds)
        b[0, 0] = d.id_of(f"wsdbm:User{u}")
        out.append(b)
    return out


def test_batched_bindings_with_different_live_counts(watdiv_small,
                                                     monkeypatch):
    """One batched launch whose bindings join different numbers of rows:
    each binding's chunked searches take their own trip counts and give
    the single-pass form's rows, in order, and the eager engine's bag."""
    cat, d, _ = watdiv_small
    users = [9, 0, 32, 1, 25]
    qtext = BOUND_TEMPLATE % users[0]
    monkeypatch.setattr(jexec, "CHUNK", 16)
    chunked = _executor(cat, d, qtext)
    chunked.caps = [64] * len(chunked.caps)
    got = chunked.run_batch(_user_bounds(chunked, d, users))
    monkeypatch.setattr(jexec, "CHUNK", 1 << 30)
    single = _executor(cat, d, qtext)
    single.caps = list(chunked.caps)
    want = single.run_batch(_user_bounds(single, d, users))
    assert len({len(r) for r, _ in got}) > 2
    assert max(len(r) for r, _ in got) > 16
    for u, (g, cols), (w, _) in zip(users, got, want):
        np.testing.assert_array_equal(g, w)
        ref = execute(parse_sparql(BOUND_TEMPLATE % u, d), cat)
        assert collections.Counter(
            tuple(int(x) for x in r)
            for r in g[:, [cols.index(c) for c in ref.cols]]) == \
            collections.Counter(map(tuple, ref.data.tolist()))


def _searched(live, size, chunk):
    return size if size <= chunk else -(-live // chunk) * chunk


@pytest.mark.parametrize("batched", [False, True])
def test_traced_rank_rows_match_a_hand_count(watdiv_small, monkeypatch,
                                             batched):
    """The traced launch's ``rank_rows`` is Σ chunks × CHUNK over the
    binding's rank searches, ``rank_cap_rows`` the single-pass rows: the
    first scan's compaction, the probe and the expansion of the join,
    and (unbatched only; the batched program hoists it) the second
    scan's compaction at its table's capacity."""
    from repro.obs import TraceContext

    cat, d, _ = watdiv_small
    chunk = 16
    monkeypatch.setattr(jexec, "CHUNK", chunk)
    q = parse_sparql(
        "SELECT * WHERE { ?u wsdbm:follows ?v . ?v wsdbm:likes ?p }", d)
    ex = PlanExecutor(compile_bgp(q.root, cat), cat)
    b = 2 if batched else 1

    def go(trace=None):
        if batched:
            return ex.run_batch([ex._default_bounds] * b, trace=trace)[0]
        return ex.run(trace=trace)

    data, _ = go()                         # grow the caps first
    trace = TraceContext(1, lambda: 0.0, None)
    go(trace)
    (launch,) = [s.attrs for s in trace.spans if s.name == "device.launch"]
    n0, n1 = (len(t) for t in ex.tables)
    t1 = ex._device_inputs[0][1].shape[0]
    c0, c1 = ex.caps
    searches = [(min(n0, c0), c0), (min(n0, c0), c0),
                (min(len(data), c1), c1)]
    if not batched:
        searches.append((n1, t1))
    assert max(c0, c1) > chunk
    assert launch["rank_rows"] == b * sum(
        _searched(live, size, chunk) for live, size in searches)
    assert launch["rank_cap_rows"] == b * sum(s for _, s in searches)
    assert launch["rank_rows"] < launch["rank_cap_rows"]


def test_one_program_per_caps_and_batch_shape(watdiv_small):
    """Bindings with other live counts reuse the program of their
    (caps, B): the trip counts are runtime values, not shapes."""
    cat, d, _ = watdiv_small
    users = [1, 2, 3, 4, 7, 11]
    ex = _executor(cat, d, BOUND_TEMPLATE % 1)
    ex.run_batch(_user_bounds(ex, d, users))         # grow the caps
    t0 = jexec.trace_count()
    ex.run_batch(_user_bounds(ex, d, [3, 4]))
    ex.run_batch(_user_bounds(ex, d, [7, 11]))
    assert jexec.trace_count() == t0 + 1
    ex.run_batch(_user_bounds(ex, d, [1, 2, 3]))
    ex.run_batch(_user_bounds(ex, d, [4, 7, 11]))
    assert jexec.trace_count() == t0 + 2
    for u in users:
        ex.run(bounds=_user_bounds(ex, d, [u])[0])
    assert jexec.trace_count() == t0 + 3
