"""Pallas TPU kernel: sorted semi-join membership.

The ExtVP load job needs ``mask[i] = probe[i] ∈ build`` over sorted int32
key columns.  On GPU one would hash-probe; on TPU the VPU (8×128 vector
unit) makes a broadcast-compare the natural shape: each grid step holds
one ``(8, 128)`` probe tile in a vreg and compares it against every
build key of its band (:mod:`repro.kernels.band`), each broadcast from
SMEM, OR-accumulating the hits.

A probe tile only meets the build keys inside its own ``[min, max]``
range, so a sorted probe costs O(probe + build) vector compares, and an
unsorted one at most O(probe · build / TILE).  VMEM holds one probe and
one output block (4 KiB each, double-buffered); SMEM one ``CHUNK`` of
build keys (8 KiB).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.band import CHUNK, TILE, band_call, band_fold, tile_bands

__all__ = ["semijoin_membership_kernel", "semijoin_membership_pallas",
           "TILE_A", "TILE_B"]

TILE_A = TILE        # probe keys per grid step
TILE_B = CHUNK       # build-length multiple (one HBM -> SMEM copy)


def semijoin_membership_kernel(start_ref, end_ref, a_ref, b_hbm, out_ref,
                               buf, sem):
    """One probe tile against its band of the build side."""
    g = pl.program_id(0)
    a = a_ref[...]                                  # (8, 128) int32

    def step(hit, b):
        return hit | (a == b).astype(jnp.int32)

    out_ref[...] = band_fold(start_ref[g], end_ref[g], b_hbm, buf, sem,
                             step, jnp.zeros(a.shape, jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def semijoin_membership_pallas(probe: jax.Array, build: jax.Array,
                               bands: Optional[Tuple[jax.Array,
                                                     jax.Array]] = None,
                               interpret: bool = True) -> jax.Array:
    """mask[i] = probe[i] ∈ build.  Build ascending; probe any order;
    lengths multiples of ``TILE_A`` / ``TILE_B`` (ops.py pads).  ``bands``
    overrides the per-tile build ranges (:func:`tile_bands`); int32."""
    start, end = bands if bands is not None else tile_bands(probe, build)
    (mask,) = band_call(semijoin_membership_kernel, 1, probe, build,
                        start, end, interpret)
    return mask
