"""Pallas TPU kernel: shuffle-bucket histogram.

The distributed engine's repartition step needs per-destination counts
(``dest = key % n_shards``) to size its all_to_all buckets and detect
overflow.  Keys are blocked as ``(8, 128)`` int32 tiles (one vreg, the
block shape Mosaic accepts for the lane-dense ``(n / 128, 128)`` view);
each grid step counts its tile's hits per bucket and adds them into the
single ``(1, n_buckets)`` output block, which stays resident across the
key grid.

Padding: invalid keys are PAD (2^31 - 1); ``PAD % n_buckets`` would
alias a real bucket, so the kernel masks pads explicitly before counting.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["bucket_count_kernel", "bucket_count_pallas", "TILE"]

TILE = 1024
ROWS = TILE // 128
PAD = np.int32(2**31 - 1)


def bucket_count_kernel(keys_ref, out_ref, *, n_buckets: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    keys = keys_ref[...]                          # (8, 128)
    dest = jnp.where(keys != PAD, keys % n_buckets, n_buckets)
    lanes = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)

    def count(b, acc):
        hits = jnp.sum((dest == b).astype(jnp.int32), keepdims=True)
        return acc + jnp.where(lanes == b, hits, 0)

    out_ref[...] += jax.lax.fori_loop(0, n_buckets, count,
                                      jnp.zeros(out_ref.shape, jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_buckets", "interpret"))
def bucket_count_pallas(keys: jax.Array, n_buckets: int,
                        interpret: bool = True) -> jax.Array:
    """Histogram of keys % n_buckets over non-PAD keys; len(keys) must be
    a TILE multiple (callers pad with PAD)."""
    n = keys.shape[0]
    assert n % TILE == 0, n
    out = pl.pallas_call(
        functools.partial(bucket_count_kernel, n_buckets=n_buckets),
        grid=(n // TILE,),
        in_specs=[pl.BlockSpec((ROWS, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, n_buckets), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, n_buckets), jnp.int32),
        interpret=interpret,
    )(keys.reshape(n // 128, 128))
    return out[0]
