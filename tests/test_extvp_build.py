"""Property tests for the pair-batched device ExtVP build (§5 load job):
numpy/jax/distributed backends must be byte-identical, per-pair device
masks must equal the ``_semijoin_mask`` numpy ground truth (including
empty, identity and disjoint-range short-circuit cases), and
``Dataset.append_triples`` must be equivalent to a from-scratch build."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import extvp_build as eb
from repro.core.stats import build_catalog
from repro.core.vp import (
    ExtVPBuild, KINDS, OS, SO, SS, _ranges_disjoint, _semijoin_mask,
    build_extvp, build_vp,
)
from repro.engine import Dataset
from repro.kernels import ops


def random_tt(rng, n_preds, n_terms, n_triples):
    tt = np.stack([
        rng.integers(0, n_terms, n_triples),
        n_terms + rng.integers(0, n_preds, n_triples),
        rng.integers(0, n_terms, n_triples),
    ], axis=1).astype(np.int32)
    return np.unique(tt, axis=0)


def assert_builds_equal(a: ExtVPBuild, b: ExtVPBuild,
                        check_semijoins: bool = True) -> None:
    assert a.sf == b.sf
    assert a.sizes == b.sizes
    assert set(a.tables) == set(b.tables)
    for k in a.tables:
        assert np.array_equal(a.tables[k].rows, b.tables[k].rows), k
    if check_semijoins:
        assert a.n_semijoins == b.n_semijoins


# ---------------------------------------------------------------------------
# Full-build parity: numpy vs jax vs distributed
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.data())
def test_jax_build_matches_numpy(data):
    """Random graphs × τ: the pair-batched build is byte-identical."""
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    tt = random_tt(rng, data.draw(st.integers(1, 5)),
                   data.draw(st.integers(2, 24)),
                   data.draw(st.integers(0, 120)))
    tau = data.draw(st.sampled_from([0.25, 0.5, 1.0]))
    vp = build_vp(tt)
    base = build_extvp(vp, threshold=tau)
    dev = build_extvp(vp, threshold=tau, backend="jax",
                      pair_batch=data.draw(st.sampled_from([8, 32, 512])))
    assert_builds_equal(base, dev)


def test_distributed_build_single_device(watdiv_small):
    """The shard_map pair grid degenerates correctly on a 1-device mesh."""
    cat, d, _ = watdiv_small
    mesh = jax.make_mesh((1,), ("data",))
    base = build_extvp(cat.vp, threshold=0.25)
    dist = build_extvp(cat.vp, threshold=0.25, backend="distributed",
                       mesh=mesh, pair_batch=64)
    assert_builds_equal(base, dist)


def test_watdiv_smoke_byte_identity(watdiv_small):
    """Acceptance: jax build is byte-identical on the WatDiv smoke graph,
    end to end through build_catalog."""
    cat, d, _ = watdiv_small
    dev = build_catalog(cat.tt, d, threshold=1.0, build_backend="jax")
    assert_builds_equal(cat.extvp, dev.extvp)
    assert dev.extvp.backend == "jax"


def test_build_backend_validation():
    with pytest.raises(ValueError, match="build backend"):
        build_extvp({}, backend="spark")


# ---------------------------------------------------------------------------
# Per-pair ground truth (empty / identity / disjoint short-circuit)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def crafted_vp():
    """Hand-built VP exercising every SF regime.

    Predicates (ids 1000+): p0 subjects {0,1,2}; p1 subjects {0,1,2}
    (SS identity for p0); p2 subjects {100} (range-disjoint from p0's);
    p3 subjects {1,9} (range-overlapping but empty SS vs p4);
    p4 subjects {0,2} (strict reduction of p0)."""
    triples = np.array([
        [0, 1000, 10], [1, 1000, 11], [2, 1000, 12],
        [0, 1001, 5], [1, 1001, 6], [2, 1001, 7],
        [100, 1002, 200],
        [1, 1003, 1], [9, 1003, 4],
        [0, 1004, 8], [2, 1004, 9],
    ], dtype=np.int32)
    return build_vp(triples)


def test_per_pair_masks_match_ground_truth(crafted_vp):
    """Every (kind, p1, p2) — pruned or not — gets the exact numpy mask
    from the device batch, for both the bitmap and kernel paths."""
    vp = crafted_vp
    packed = eb.pack_vp(vp)
    pairs = list(eb.all_pair_keys(sorted(vp)))
    pcol = jnp.asarray([eb.probe_col(k[0]) for k in pairs], jnp.int32)
    pidx = jnp.asarray([packed.slot[k[1]] for k in pairs], jnp.int32)
    bcol = jnp.asarray([eb.build_col(k[0]) for k in pairs], jnp.int32)
    bidx = jnp.asarray([packed.slot[k[2]] for k in pairs], jnp.int32)
    uniq, mult = jnp.asarray(packed.uniq), jnp.asarray(packed.mult)
    runs = [eb.batch_pair_masks_bitmap(uniq, mult,
                                       jnp.asarray(packed.present),
                                       pcol, pidx, bcol, bidx),
            eb.batch_pair_masks(uniq, mult, uniq, pcol, pidx, bcol, bidx)]
    for member, counts in runs:
        member, counts = np.asarray(member), np.asarray(counts)
        for j, (kind, p1, p2) in enumerate(pairs):
            t1, t2 = vp[p1], vp[p2]
            probe = t1.o if kind == OS else t1.s
            other = t2.unique_o if kind == SO else t2.unique_s
            want = _semijoin_mask(probe, other)
            got = packed.row_mask(kind, p1, member[j])
            assert np.array_equal(got, want), (kind, p1, p2)
            assert int(counts[j]) == int(want.sum())
            # padded probe keys never match
            n_keys = len(t1.unique_o if kind == OS else t1.unique_s)
            assert not member[j, n_keys:].any()


def test_sf_regimes_and_short_circuit(crafted_vp):
    """Empty, identity and disjoint-range cases land identically in both
    builders, and pruned pairs never reach a semi-join."""
    vp = crafted_vp
    base = build_extvp(vp, threshold=1.0)
    dev = build_extvp(vp, threshold=1.0, backend="jax", pair_batch=8)
    assert_builds_equal(base, dev)

    # identity: every p0 subject appears in p1 -> SF=1, not materialized
    assert base.sf[(SS, 1000, 1001)] == 1.0
    assert (SS, 1000, 1001) not in base.tables
    # disjoint ranges: pruned (SF=0) without evaluating a semi-join
    pruned, evals = eb.plan_pairs(vp, eb.all_pair_keys(sorted(vp)))
    assert (SS, 1000, 1002) in pruned
    assert base.sf[(SS, 1000, 1002)] == 0.0
    assert dev.n_semijoins == len(evals) < len(pruned) + len(evals)
    # overlapping ranges but empty result: evaluated, SF=0
    assert (SS, 1004, 1003) in evals
    assert base.sf[(SS, 1004, 1003)] == 0.0
    assert (SS, 1004, 1003) not in base.tables
    # strict reduction: materialized with exact rows
    assert base.sf[(SS, 1000, 1004)] == pytest.approx(2 / 3)
    assert np.array_equal(base.tables[(SS, 1000, 1004)].rows,
                          np.array([[0, 10], [2, 12]], dtype=np.int32))


def test_build_matches_under_pallas_interpret():
    """The vmapped-kernel path (Pallas interpret mode on CPU) produces
    the identical schema on a small graph."""
    rng = np.random.default_rng(11)
    vp = build_vp(random_tt(rng, 3, 12, 80))
    base = build_extvp(vp)
    prev = ops.pallas_enabled()
    ops.use_pallas(True)
    try:
        dev = build_extvp(vp, backend="jax", pair_batch=8)
    finally:
        ops.use_pallas(prev)
    assert_builds_equal(base, dev)


# ---------------------------------------------------------------------------
# Incremental append
# ---------------------------------------------------------------------------

def _triples(rng, n, n_ent, preds):
    return [(f"e{rng.integers(0, n_ent)}", rng.choice(preds),
             f"e{rng.integers(0, n_ent)}") for _ in range(n)]


def assert_datasets_equivalent(ds: Dataset, scratch: Dataset) -> None:
    assert np.array_equal(ds.catalog.tt, scratch.catalog.tt)
    assert set(ds.catalog.vp) == set(scratch.catalog.vp)
    for p in ds.catalog.vp:
        assert np.array_equal(ds.catalog.vp[p].rows,
                              scratch.catalog.vp[p].rows), p
    assert_builds_equal(ds.catalog.extvp, scratch.catalog.extvp,
                        check_semijoins=False)


def test_append_triples_equivalent_to_scratch():
    rng = np.random.default_rng(5)
    base = _triples(rng, 120, 24, ["p0", "p1", "p2", "p3"])
    extra = _triples(rng, 50, 24, ["p1", "p4"])   # p4 is a new predicate
    ds = Dataset.from_triples(base, threshold=0.5)
    report = ds.append_triples(extra)
    scratch = Dataset.from_triples(base + extra, threshold=0.5)
    assert_datasets_equivalent(ds, scratch)
    # untouched (p0, p2, p3) x (p0, p2, p3) pairs were carried over
    assert report["reused"] > 0
    assert report is ds.last_append_report
    # query results agree across backends after the append
    q = "SELECT * WHERE { ?a p1 ?b . ?b p0 ?c }"
    assert ds.engine("eager").query(q).same_as(scratch.engine("eager").query(q))
    assert ds.engine("jit").query(q).same_as(scratch.engine("eager").query(q))


def test_append_out_of_range_keys_skip_recompute():
    """New build-side keys outside every probe range: the pair results
    are carried over, not re-semi-joined — and still match scratch."""
    base = [(f"a{i}", "pA", f"a{i+1}") for i in range(6)] + \
           [(f"a{i}", "pB", f"a{i+2}") for i in range(5)]
    extra = [(f"z{i}", "pB", f"z{i+1}") for i in range(4)]  # fresh entities
    ds = Dataset.from_triples(base, threshold=1.0)
    report = ds.append_triples(extra)
    scratch = Dataset.from_triples(base + extra, threshold=1.0)
    assert report["range_skipped"] > 0
    assert_datasets_equivalent(ds, scratch)


def test_append_empty_and_engine_invalidation():
    ds = Dataset.from_triples([("a", "p", "b")], threshold=1.0)
    eng = ds.engine("eager")
    report = ds.append_triples([])
    assert report["recomputed"] == 0
    assert ds.engine("eager") is eng          # no-op append keeps engines
    ds.append_triples([("b", "p", "c")])
    assert ds.engine("eager") is not eng      # real append invalidates
    res = ds.engine("eager").query("SELECT * WHERE { ?x p ?y . ?y p ?z }")
    assert len(res) == 1


def test_append_without_extvp_stays_extvp_less():
    """A store built with with_extvp=False must append without touching
    (or back-filling) the ExtVP schema — it has no pair stats to extend."""
    ds = Dataset.from_triples([("a", "p", "b"), ("c", "q", "d")],
                              with_extvp=False)
    report = ds.append_triples([("x", "p", "y"), ("x", "r", "z")])
    assert report["recomputed"] == 0
    assert not ds.catalog.extvp.sf and not ds.catalog.extvp.tables
    scratch = Dataset.from_triples(
        [("a", "p", "b"), ("c", "q", "d"), ("x", "p", "y"), ("x", "r", "z")],
        with_extvp=False)
    assert np.array_equal(ds.catalog.tt, scratch.catalog.tt)
    for p in scratch.catalog.vp:
        assert np.array_equal(ds.catalog.vp[p].rows,
                              scratch.catalog.vp[p].rows)
    q = "SELECT * WHERE { ?s p ?o }"
    assert ds.engine("eager").query(q).same_as(scratch.engine("eager").query(q))
    # the opt-out survives appends even when the initial graph is empty
    empty = Dataset.from_triples([], with_extvp=False)
    empty.append_triples([("a", "p", "b")])
    assert not empty.catalog.extvp.sf and not empty.catalog.with_extvp


@settings(max_examples=10, deadline=None)
@given(st.data())
def test_append_property(data):
    """Random base/extra splits: incremental == scratch for every build
    backend and τ."""
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    preds = [f"p{i}" for i in range(data.draw(st.integers(1, 4)))]
    base = _triples(rng, data.draw(st.integers(1, 80)), 20, preds)
    extra = _triples(rng, data.draw(st.integers(1, 40)), 30,
                     preds + ["pnew"])
    tau = data.draw(st.sampled_from([0.25, 1.0]))
    backend = data.draw(st.sampled_from(["numpy", "jax"]))
    ds = Dataset.from_triples(base, threshold=tau, build_backend=backend)
    ds.append_triples(extra)
    scratch = Dataset.from_triples(base + extra, threshold=tau,
                                   build_backend=backend)
    assert_datasets_equivalent(ds, scratch)


# ---------------------------------------------------------------------------
# Multi-device pair grid (subprocess, 8 forced host devices)
# ---------------------------------------------------------------------------

_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import numpy as np
    from repro.core.vp import build_extvp, build_vp

    assert len(jax.devices()) == 8
    rng = np.random.default_rng(2)
    n = 4000
    tt = np.stack([rng.integers(0, 150, n), 150 + rng.integers(0, 12, n),
                   rng.integers(0, 150, n)], axis=1).astype(np.int32)
    vp = build_vp(np.unique(tt, axis=0))
    mesh = jax.make_mesh((8,), ("data",))
    base = build_extvp(vp, threshold=0.5)
    dist = build_extvp(vp, threshold=0.5, backend="distributed", mesh=mesh,
                       pair_batch=64)
    assert dist.sf == base.sf
    assert dist.sizes == base.sizes
    assert set(dist.tables) == set(base.tables)
    for k in base.tables:
        assert np.array_equal(base.tables[k].rows, dist.tables[k].rows)
    print("DIST_BUILD_OK")
""")


@pytest.mark.slow
def test_distributed_build_8dev_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SUBPROCESS_PROG],
                         capture_output=True, text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "DIST_BUILD_OK" in res.stdout
