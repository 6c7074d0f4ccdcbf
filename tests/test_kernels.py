"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (hypothesis) and
tile-boundary edge cases, in interpret mode on CPU."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.mergejoin import TILE_A, TILE_B, join_probe_pallas
from repro.kernels.semijoin import semijoin_membership_pallas


def _sorted_build(rng, n, lo=0, hi=5000):
    return np.sort(rng.integers(lo, hi, n).astype(np.int32))


class TestSemijoinKernel:
    @pytest.mark.parametrize("n_a,n_b", [
        (TILE_A, TILE_B),             # single tile
        (2 * TILE_A, 3 * TILE_B),     # multi-tile grid
        (TILE_A, 4 * TILE_B),         # build sweep
    ])
    def test_tile_shapes(self, n_a, n_b):
        rng = np.random.default_rng(0)
        a = rng.integers(0, 3000, n_a).astype(np.int32)
        b = _sorted_build(rng, n_b, 0, 3000)
        got = semijoin_membership_pallas(jnp.asarray(a), jnp.asarray(b),
                                         interpret=True)
        want = ref.semijoin_membership_ref(jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_disjoint_ranges_all_zero(self):
        a = np.arange(TILE_A, dtype=np.int32)
        b = np.arange(10_000, 10_000 + TILE_B, dtype=np.int32)
        got = semijoin_membership_pallas(jnp.asarray(a), jnp.asarray(b),
                                         interpret=True)
        assert int(np.asarray(got).sum()) == 0

    def test_all_members(self):
        b = np.arange(TILE_B, dtype=np.int32)
        a = np.resize(b, TILE_A)
        got = semijoin_membership_pallas(jnp.asarray(a), jnp.asarray(np.sort(b)),
                                         interpret=True)
        assert int(np.asarray(got).sum()) == TILE_A


class TestJoinProbeKernel:
    @pytest.mark.parametrize("n_a,n_b", [
        (TILE_A, TILE_B),
        (2 * TILE_A, 2 * TILE_B),
    ])
    def test_lo_cnt(self, n_a, n_b):
        rng = np.random.default_rng(1)
        a = rng.integers(0, 800, n_a).astype(np.int32)    # many duplicates
        b = _sorted_build(rng, n_b, 0, 800)
        lo, cnt = join_probe_pallas(jnp.asarray(a), jnp.asarray(b),
                                    interpret=True)
        wlo, wcnt = ref.join_probe_ref(jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(wlo))
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(wcnt))

    def test_duplicates_across_build_tiles(self):
        """A key whose run of duplicates spans a build-tile boundary."""
        b = np.full(2 * TILE_B, 7, dtype=np.int32)
        b[:4] = 3
        b = np.sort(b)
        a = np.full(TILE_A, 7, dtype=np.int32)
        lo, cnt = join_probe_pallas(jnp.asarray(a), jnp.asarray(b),
                                    interpret=True)
        assert int(np.asarray(lo)[0]) == 4
        assert int(np.asarray(cnt)[0]) == 2 * TILE_B - 4


class TestOpsWrappers:
    """Ragged sizes + sentinel padding through the public API."""

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(0, 3000), st.integers(0, 1500))
    def test_semijoin_mask_ragged(self, seed, n_a, n_b):
        rng = np.random.default_rng(seed)
        a = rng.integers(0, 2000, n_a).astype(np.int32)
        b = _sorted_build(rng, n_b, 0, 2000)
        got = ops.semijoin_mask(jnp.asarray(a), jnp.asarray(b),
                                force_pallas=True)
        want = ref.semijoin_membership_ref(jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @settings(max_examples=20, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 2500), st.integers(1, 1200))
    def test_join_probe_ragged(self, seed, n_a, n_b):
        rng = np.random.default_rng(seed)
        a = rng.integers(0, 500, n_a).astype(np.int32)
        b = _sorted_build(rng, n_b, 0, 500)
        lo, cnt = ops.join_probe(jnp.asarray(a), jnp.asarray(b),
                                 force_pallas=True)
        wlo, wcnt = ref.join_probe_ref(jnp.asarray(a), jnp.asarray(b))
        np.testing.assert_array_equal(np.asarray(lo), np.asarray(wlo))
        np.testing.assert_array_equal(np.asarray(cnt), np.asarray(wcnt))

    def test_jnp_path_matches_pallas_path(self):
        rng = np.random.default_rng(3)
        a = jnp.asarray(rng.integers(0, 999, 700).astype(np.int32))
        b = jnp.asarray(_sorted_build(rng, 350, 0, 999))
        np.testing.assert_array_equal(
            np.asarray(ops.semijoin_mask(a, b)),
            np.asarray(ops.semijoin_mask(a, b, force_pallas=True)))
        l1, c1 = ops.join_probe(a, b)
        l2, c2 = ops.join_probe(a, b, force_pallas=True)
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_extvp_build_with_kernel_matches_numpy(watdiv_small):
    """The kernel path reproduces the numpy ExtVP semi-join masks."""
    cat, d, sch = watdiv_small
    f = sch.pred["wsdbm:friendOf"]
    l = sch.pred["wsdbm:likes"]
    t1, t2 = cat.vp[f], cat.vp[l]
    mask = ops.semijoin_mask(jnp.asarray(t1.o), jnp.asarray(t2.unique_s),
                             force_pallas=True)
    want = cat.table("OS", f, l).rows
    got = t1.rows[np.asarray(mask).astype(bool)]
    np.testing.assert_array_equal(np.sort(got, axis=0), np.sort(want, axis=0))


class TestBucketCountKernel:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 3000),
           st.sampled_from([2, 8, 16, 64, 256]))
    def test_histogram_ragged(self, seed, n, nb):
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, 100000, n).astype(np.int32)
        valid = rng.random(n) < 0.8
        got = ops.bucket_count(jnp.asarray(keys), jnp.asarray(valid), nb,
                               force_pallas=True)
        want = ref.bucket_count_ref(jnp.asarray(keys), jnp.asarray(valid), nb)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_matches_numpy_bincount(self):
        rng = np.random.default_rng(0)
        keys = rng.integers(0, 999, 2048).astype(np.int32)
        valid = np.ones(2048, bool)
        got = ops.bucket_count(jnp.asarray(keys), jnp.asarray(valid), 16,
                               force_pallas=True)
        want = np.bincount(keys % 16, minlength=16)
        np.testing.assert_array_equal(np.asarray(got), want)
