"""Banded sweep shared by the sorted-join kernels (semijoin, mergejoin).

Layout.  Probe keys are blocked as ``(8, 128)`` int32 tiles — one vreg,
the block shape Mosaic accepts for a ``(n / 128, 128)`` view of the
probe array — so each grid step owns ``TILE = 1024`` probe keys, stored
lane-dense in HBM with no padding.  The build side is ascending and
stays in HBM.

Band.  Because the build side is sorted, the only build keys a probe
tile can match lie in ``[searchsorted(b, min(tile)),
searchsorted(b, max(tile), 'right'))``: every key before the band is
below the whole tile, every key after it above.  :func:`tile_bands`
computes those bounds outside the kernel (XLA), the kernel receives them
as scalar-prefetch operands, streams only its band HBM → SMEM in
``CHUNK``-key copies, and folds each build key — a scalar broadcast
against the probe vreg — into its accumulators.  Vector work is
O(band) per tile; for a sorted probe the bands partition the build, so a
whole sweep is linear in probe + build.
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["TILE", "CHUNK", "PROBE_PAD", "BUILD_PAD", "tile_bands",
           "band_fold", "band_call"]

TILE = 1024          # probe keys per grid step: one (8, 128) int32 block
CHUNK = 2048         # build keys per HBM -> SMEM copy
ROWS = TILE // 128

#: probe-side pad; left out of a tile's max, so it never widens a band
PROBE_PAD = np.int32(2**31 - 1)
#: build-side pad; sorts above every valid id, keeping the build ascending
BUILD_PAD = np.int32(2**31 - 2)


def tile_bands(probe: jax.Array, build: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """``(start, end)`` of each ``TILE``-key probe tile's band in the
    ascending ``build``.  An all-pad tile gets an empty band."""
    tiles = probe.reshape(-1, TILE)
    lo = tiles.min(axis=1)
    hi = jnp.where(tiles == PROBE_PAD, jnp.iinfo(jnp.int32).min,
                   tiles).max(axis=1)
    start = jnp.searchsorted(build, lo, side="left").astype(jnp.int32)
    end = jnp.searchsorted(build, hi, side="right").astype(jnp.int32)
    return start, jnp.maximum(end, start)


def band_fold(start, end, b_hbm, buf, sem, step: Callable, init):
    """Fold ``step(carry, b_k)`` over build keys ``b[start:end]``, copied
    from HBM into the SMEM buffer one ``CHUNK`` at a time."""
    def chunk(c, carry):
        copy = pltpu.make_async_copy(b_hbm.at[pl.ds(c * CHUNK, CHUNK)],
                                     buf, sem)
        copy.start()
        copy.wait()
        lo = jnp.maximum(start - c * CHUNK, 0)
        hi = jnp.minimum(end - c * CHUNK, CHUNK)
        return jax.lax.fori_loop(lo, hi, lambda k, cr: step(cr, buf[k]),
                                 carry)

    return jax.lax.fori_loop(start // CHUNK, (end + CHUNK - 1) // CHUNK,
                             chunk, init)


def band_call(kernel: Callable, n_out: int, probe: jax.Array,
              build: jax.Array, start: jax.Array, end: jax.Array,
              interpret: bool) -> Sequence[jax.Array]:
    """Run ``kernel(start_ref, end_ref, a_ref, b_hbm, *out_refs, buf,
    sem)`` over every probe tile; returns ``n_out`` int32 arrays shaped
    like ``probe``.  ``len(probe)`` must be a ``TILE`` multiple and
    ``len(build)`` a ``CHUNK`` multiple."""
    n_a, n_b = probe.shape[0], build.shape[0]
    assert n_a % TILE == 0 and n_b % CHUNK == 0, (n_a, n_b)
    block = pl.BlockSpec((ROWS, 128), lambda g, s, e: (g, 0))
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_a // TILE,),
            in_specs=[block, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[block] * n_out,
            scratch_shapes=[pltpu.SMEM((CHUNK,), jnp.int32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((n_a // 128, 128), jnp.int32)]
        * n_out,
        interpret=interpret,
    )(start, end, probe.reshape(n_a // 128, 128), build)
    return [o.reshape(n_a) for o in outs]

