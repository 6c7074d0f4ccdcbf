"""Pallas TPU kernel: sort-merge join probe (lower-bound + match count).

The join expansion needs, per probe key, ``lo[i] = #{b < a_i}`` (the
lower-bound rank into the sorted build side) and ``cnt[i] = #{b == a_i}``.
XLA lowers ``jnp.searchsorted`` to a 33-step while-loop of dynamic-slices
per key — serial, gather-bound and hostile to the VPU.  This kernel
computes both as compare-and-count sums over the probe tile's band
(:mod:`repro.kernels.band`):

    lo[i]  = start + Σ_{band} (b_j <  a_i)
    cnt[i] =         Σ_{band} (b_j == a_i)

Every build key before the band is below the whole tile, so it adds the
constant ``start`` to each rank; every key after it is above the tile and
adds nothing.  Each build key of the band is broadcast from SMEM against
the ``(8, 128)`` probe vreg.

Padding: probe pads are 2^31-1, build pads 2^31-2.  Pads never widen a
band, so valid lanes see no pad; pad lanes carry garbage that callers
discard.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.band import CHUNK, TILE, band_call, band_fold, tile_bands

__all__ = ["join_probe_kernel", "join_probe_pallas", "TILE_A", "TILE_B"]

TILE_A = TILE        # probe keys per grid step
TILE_B = CHUNK       # build-length multiple (one HBM -> SMEM copy)


def join_probe_kernel(start_ref, end_ref, a_ref, b_hbm, lo_ref, cnt_ref,
                      buf, sem):
    g = pl.program_id(0)
    start = start_ref[g]
    a = a_ref[...]                                  # (8, 128) int32

    def step(acc, b):
        lt, eq = acc
        return lt + (b < a).astype(jnp.int32), eq + (b == a).astype(jnp.int32)

    zero = jnp.zeros(a.shape, jnp.int32)
    lt, eq = band_fold(start, end_ref[g], b_hbm, buf, sem, step,
                       (zero, zero))
    lo_ref[...] = lt + start
    cnt_ref[...] = eq


@functools.partial(jax.jit, static_argnames=("interpret",))
def join_probe_pallas(probe: jax.Array, build: jax.Array,
                      interpret: bool = True):
    """Returns (lo, cnt) int32 arrays, shapes == probe.  Build ascending,
    probe any order, lengths multiples of ``TILE_A`` / ``TILE_B``
    (ops.py pads)."""
    start, end = tile_bands(probe, build)
    lo, cnt = band_call(join_probe_kernel, 2, probe, build, start, end,
                        interpret)
    return lo, cnt
