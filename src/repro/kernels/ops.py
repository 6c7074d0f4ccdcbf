"""Public jit'd wrappers for the Pallas kernels.

Handles the padding / sentinel conventions so callers pass ragged int32
key arrays:

* probe side padded with ``PROBE_PAD = 2^31 - 1``
* build side padded with ``BUILD_PAD = 2^31 - 2``

The two sentinels differ, so padded lanes never produce false matches,
and ``BUILD_PAD`` sorts above every valid id, so a padded build side stays
ascending.  On CPU the kernels run in ``interpret=True`` mode (Python
execution of the kernel body — correct but slow); on TPU they compile.
Set ``use_pallas(False)`` to route everything through the pure-jnp refs
(the default on CPU for speed; tests exercise both paths explicitly).
The ``REPRO_USE_PALLAS=1`` environment variable flips the default at
import time — CI's ``tests-pallas`` job uses it to run the kernel and
build suites end-to-end on the Pallas interpret path.
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import bucketcount, mergejoin, ref, semijoin
from repro.kernels.band import BUILD_PAD, PROBE_PAD, tile_bands

__all__ = ["semijoin_mask", "join_probe", "bucket_count", "use_pallas",
           "pallas_enabled", "PROBE_PAD", "BUILD_PAD"]

# CPU default: jnp reference path (REPRO_USE_PALLAS=1 opts in to Pallas)
_STATE = {"use_pallas": os.environ.get("REPRO_USE_PALLAS", "0") == "1"}


def use_pallas(enabled: bool) -> None:
    _STATE["use_pallas"] = enabled


def pallas_enabled() -> bool:
    return _STATE["use_pallas"]


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, mult: int, fill) -> jax.Array:
    """Pad the last axis to a positive multiple of ``mult``."""
    n = x.shape[-1]
    rem = (-n) % mult if n else mult
    if rem == 0:
        return x
    pad = jnp.full(x.shape[:-1] + (rem,), fill, x.dtype)
    return jnp.concatenate([x, pad], axis=-1)


@functools.partial(jax.jit, static_argnames=("force_pallas",))
def semijoin_mask(probe: jax.Array, build_sorted: jax.Array,
                  force_pallas: bool = False) -> jax.Array:
    """mask[..., i] = probe[..., i] ∈ build_sorted[...] (int32 0/1), any
    lengths ≥ 0.  A 2-D call is a batch of rows, each probe row against
    its own build row, in one kernel launch."""
    if not (force_pallas or _STATE["use_pallas"]):
        if probe.ndim == 2:
            return jax.vmap(ref.semijoin_membership_ref)(probe, build_sorted)
        return ref.semijoin_membership_ref(probe, build_sorted)
    n = probe.shape[-1]
    a = _pad_to(probe.astype(jnp.int32), semijoin.TILE_A, PROBE_PAD)
    b = _pad_to(build_sorted.astype(jnp.int32), semijoin.TILE_B, BUILD_PAD)
    bands = None
    if a.ndim == 2:
        # one flat sweep: each row's bands offset into the flat build
        start, end = jax.vmap(tile_bands)(a, b)
        off = (jnp.arange(a.shape[0], dtype=jnp.int32) * b.shape[1])[:, None]
        bands = ((start + off).reshape(-1), (end + off).reshape(-1))
    out = semijoin.semijoin_membership_pallas(
        a.reshape(-1), b.reshape(-1), bands, interpret=_interpret())
    return out.reshape(a.shape)[..., :n]


@functools.partial(jax.jit, static_argnames=("force_pallas",))
def join_probe(probe: jax.Array, build_sorted: jax.Array,
               force_pallas: bool = False) -> Tuple[jax.Array, jax.Array]:
    """(lo, cnt) per probe key against the ascending build side."""
    if not (force_pallas or _STATE["use_pallas"]):
        return ref.join_probe_ref(probe, build_sorted)
    n = probe.shape[0]
    n_b = build_sorted.shape[0]
    a = _pad_to(probe.astype(jnp.int32), mergejoin.TILE_A, PROBE_PAD)
    b = _pad_to(build_sorted.astype(jnp.int32), mergejoin.TILE_B, BUILD_PAD)
    lo, cnt = mergejoin.join_probe_pallas(a, b, interpret=_interpret())
    # padded build rows sort above all valid keys; they can inflate lo only
    # for probe keys >= BUILD_PAD (i.e. probe pads), which callers discard.
    return jnp.minimum(lo[:n], n_b), cnt[:n]


@functools.partial(jax.jit, static_argnames=("n_buckets", "force_pallas"))
def bucket_count(keys: jax.Array, valid: jax.Array, n_buckets: int,
                 force_pallas: bool = False) -> jax.Array:
    """Histogram of keys % n_buckets over valid rows (shuffle planning)."""
    if not (force_pallas or _STATE["use_pallas"]):
        return ref.bucket_count_ref(keys, valid, n_buckets)
    masked = jnp.where(valid, keys.astype(jnp.int32), PROBE_PAD)
    padded = _pad_to(masked, bucketcount.TILE, PROBE_PAD)
    return bucketcount.bucket_count_pallas(padded, n_buckets,
                                           interpret=_interpret())
