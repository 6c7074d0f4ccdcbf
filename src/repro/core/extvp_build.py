"""Device-accelerated ExtVP construction (the paper's §5 load job, batched).

The numpy builder in :mod:`repro.core.vp` walks all P²·3 (kind, p1, p2)
pairs in a Python loop, one host semi-join per pair.  This module is the
device analogue of S2RDF's distributed Spark load job:

* the VP catalog is packed **once** into padded per-predicate
  sorted-unique key tensors (``PackedVP``) — the per-predicate
  sort/unique work is hoisted out of the pair loop into the packing step;
* semi-joins for whole **batches** of pairs are decided once per unique
  probe key in one pass: through the banded
  :func:`repro.kernels.ops.semijoin_mask` kernel when the Pallas path is
  enabled, and through a packed **presence bitmap** otherwise —
  dictionary ids are dense, so build-side membership is a single O(1)
  gather per probe key (the device analogue of a hash set; XLA's
  searchsorted is a poor fit for batched CPU probes).  Key
  multiplicities turn the flags into SF; only the pairs that
  materialize bring their flags back to expand into row masks;
* a ``shard_map`` variant (:func:`repro.core.distributed
  .extvp_pair_masks_sharded`) partitions the pair grid across the mesh,
  so a multi-device build evaluates P²·3/S pairs per device.

Host-side work that remains mirrors S2RDF's Spark *driver*: pair
planning (the disjoint-entity-range short-circuit), SF bookkeeping, and
slicing out the rows of materialized tables.  Results are byte-identical
to the numpy path (asserted in tests/test_extvp_build.py).

:func:`incremental_pairs` supports ``Dataset.append_triples``: only the
pairs whose inputs actually changed — a touched predicate on the probe
side, or new build-side keys inside the probe side's entity range — are
recomputed; every other pair's SF/size/table is carried over verbatim.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.table import LazyTableMap, Table, round_up_pow2
from repro.core.vp import (
    ExtVPBuild, KINDS, OS, SO, SS, _ranges_disjoint, _semijoin_mask,
)
from repro.kernels import ops

__all__ = [
    "PackedVP", "pack_vp", "all_pair_keys", "plan_pairs", "probe_col",
    "build_col", "batch_pair_masks", "batch_pair_masks_bitmap",
    "evaluate_pairs", "build_extvp_planned", "incremental_pairs",
]

#: presence bitmaps above this many total cells fall back to the kernel
#: path (2 · P · V bool cells ≈ bytes; 2^28 ≈ 256 MB)
BITMAP_CELL_LIMIT = 1 << 28

#: bound on a pair batch's (B, ucap) flag tensor: 2^26 int32 cells = 256 MB
PAIR_CELLS = 1 << 26

Key = Tuple[str, int, int]


def probe_col(kind: str) -> int:
    """Which VP column (0 = s, 1 = o) the probe side of ``kind`` reads."""
    return 1 if kind == OS else 0


def build_col(kind: str) -> int:
    """Which unique-column (0 = s, 1 = o) the build side of ``kind`` reads."""
    return 1 if kind == SO else 0


# ---------------------------------------------------------------------------
# Packing: VP catalog -> padded column tensors
# ---------------------------------------------------------------------------

@dataclass
class PackedVP:
    """The VP catalog's key sets as static-shape device tensors.

    ``uniq[c, i]`` is the sorted unique values of predicate-slot ``i``'s
    column ``c`` (0 = s, 1 = o), padded with ``BUILD_PAD`` (which sorts
    above every valid id, keeping the padded array ascending);
    ``mult[c, i]`` is how many rows hold each of them (0 on pads), and
    ``inv[c][i]`` maps each row of the table to its key's position in
    ``uniq[c, i]``.  A semi-join is decided once per unique probe key:
    ``mult`` turns those flags into the reduced table's size and ``inv``
    into its row mask.  ``present[c, i, v]`` is the key set as a dense
    membership bitmap (ids are dictionary-dense, so ``v`` indexes
    directly); ``None`` when the id space is too large
    (``BITMAP_CELL_LIMIT``).
    """

    preds: Tuple[int, ...]          # slot -> predicate id
    slot: Dict[int, int]            # predicate id -> slot
    uniq: np.ndarray                # (2, P, ucap) int32
    mult: np.ndarray                # (2, P, ucap) int32
    inv: Tuple[List[np.ndarray], List[np.ndarray]]   # [c][slot] -> (n,)
    present: Optional[np.ndarray]   # (2, P, V) bool, or None

    def row_mask(self, kind: str, p1: int, member: np.ndarray) -> np.ndarray:
        """Rows of ``p1``'s table whose probe key ``member`` flags, from
        the per-unique-key flags of a pair (kind, p1, ·)."""
        inv = self.inv[probe_col(kind)][self.slot[p1]]
        return np.asarray(member)[inv].astype(bool)


def pack_vp(vp: Dict[int, Table], with_bitmap: bool = True) -> PackedVP:
    """Pack every VP table's sorted-unique key sets and multiplicities.

    This is the hoisted per-predicate work: each ``unique_s``/``unique_o``
    sort happens once here instead of once per pair in the build loop
    (``Table`` caches them, so a later numpy build reuses the same
    arrays).  ``with_bitmap=False`` skips the presence bitmap (the kernel
    path never reads it, and it is the one potentially large tensor).
    """
    preds = tuple(sorted(vp))
    n_preds = len(preds)
    ucap = round_up_pow2(max(
        (max(len(vp[p].unique_s), len(vp[p].unique_o)) for p in preds),
        default=1))
    uniq = np.full((2, n_preds, ucap), ops.BUILD_PAD, dtype=np.int32)
    mult = np.zeros((2, n_preds, ucap), dtype=np.int32)
    inv: Tuple[List[np.ndarray], List[np.ndarray]] = ([], [])
    max_id = 0
    for i, p in enumerate(preds):
        t = vp[p]
        for c, (col, u) in enumerate(((t.s, t.unique_s), (t.o, t.unique_o))):
            pos = np.searchsorted(u, col).astype(np.int32)
            uniq[c, i, :len(u)] = u
            mult[c, i, :len(u)] = np.bincount(pos, minlength=len(u))
            inv[c].append(pos)
        if len(t):
            max_id = max(max_id, int(t.unique_s[-1]), int(t.unique_o[-1]))
    volume = round_up_pow2(max_id + 1)
    present: Optional[np.ndarray] = None
    if with_bitmap and n_preds and 2 * n_preds * volume <= BITMAP_CELL_LIMIT:
        present = np.zeros((2, n_preds, volume), dtype=bool)
        for i, p in enumerate(preds):
            present[0, i, vp[p].unique_s] = True
            present[1, i, vp[p].unique_o] = True
    return PackedVP(preds=preds, slot={p: i for i, p in enumerate(preds)},
                    uniq=uniq, mult=mult, inv=inv, present=present)


# ---------------------------------------------------------------------------
# Pair planning (host; identical semantics to the numpy loop)
# ---------------------------------------------------------------------------

def all_pair_keys(preds: Sequence[int],
                  kinds: Sequence[str] = KINDS) -> Iterator[Key]:
    """Every (kind, p1, p2) the schema defines, in the numpy loop's order
    (SS self-pairs are identity by definition and excluded, §5.2)."""
    for p1 in preds:
        for p2 in preds:
            for kind in kinds:
                if kind == SS and p1 == p2:
                    continue
                yield (kind, p1, p2)


def plan_pairs(vp: Dict[int, Table],
               keys_iter: Iterable[Key]) -> Tuple[List[Key], List[Key]]:
    """Split pairs into (pruned, evals): a pair whose probe-side and
    build-side entity ranges are disjoint is structurally empty (SF = 0)
    and never reaches a semi-join — the same short-circuit the numpy
    builder applies."""
    pruned: List[Key] = []
    evals: List[Key] = []
    for key in keys_iter:
        kind, p1, p2 = key
        t1, t2 = vp[p1], vp[p2]
        own = t1.unique_o if kind == OS else t1.unique_s
        other = t2.unique_o if kind == SO else t2.unique_s
        (pruned if _ranges_disjoint(own, other) else evals).append(key)
    return pruned, evals


# ---------------------------------------------------------------------------
# Device evaluation: one pass per pair batch
# ---------------------------------------------------------------------------

def batch_pair_masks(uniq: jax.Array, mult: jax.Array, build: jax.Array,
                     pcol: jax.Array, pidx: jax.Array,
                     bcol: jax.Array, bidx: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Semi-join flags + reduced sizes for a batch of packed pairs, one
    batched :func:`repro.kernels.ops.semijoin_mask` call (the banded
    Pallas sweep when enabled, jnp searchsorted otherwise).

    For slot ``j``: probe = ``uniq[pcol[j], pidx[j]]``, build =
    ``build[bcol[j], bidx[j]]`` (the same packed ``uniq`` tensor), both
    ascending.  Returns ``member[j, u]`` — is the probe side's ``u``-th
    unique key in the build side — and the pair's row count
    ``Σ_u member · mult``.  Probe pads become ``PROBE_PAD`` and never
    match.  Also the per-shard body of the distributed pair grid."""
    probe = uniq[pcol, pidx]
    probe = jnp.where(probe == ops.BUILD_PAD, ops.PROBE_PAD, probe)
    member = ops.semijoin_mask(probe, build[bcol, bidx])    # (B, ucap)
    return member, (member * mult[pcol, pidx]).sum(axis=1, dtype=jnp.int32)


def batch_pair_masks_bitmap(uniq: jax.Array, mult: jax.Array,
                            present: jax.Array,
                            pcol: jax.Array, pidx: jax.Array,
                            bcol: jax.Array, bidx: jax.Array
                            ) -> Tuple[jax.Array, jax.Array]:
    """Bitmap variant of :func:`batch_pair_masks`: build-side membership
    is one gather per unique probe key into the dense presence bitmap —
    the device analogue of a hash-set probe, and the fast default
    off-TPU where a batched binary search gathers log₂(ucap) times
    instead.  Probe pads (``BUILD_PAD`` ≥ V) fall out via the ``< V``
    guard."""
    volume = present.shape[-1]

    def one(pc, pi, bc, bi):
        probe = uniq[pc, pi]
        hit = present[bc, bi][jnp.clip(probe, 0, volume - 1)] & \
            (probe < volume)
        return hit.astype(jnp.int32)

    member = jax.vmap(one)(pcol, pidx, bcol, bidx)     # (B, ucap)
    return member, (member * mult[pcol, pidx]).sum(axis=1, dtype=jnp.int32)


@functools.lru_cache(maxsize=None)
def _jitted_batch_fn(use_bitmap: bool, pallas: bool):
    """One compiled wrapper per (path, pallas-flag) pair.  ``pallas`` is
    only a cache key: ``ops.semijoin_mask`` reads the mutable
    ``use_pallas`` state at trace time, so a toggle must map to a fresh
    function identity or jit would replay the stale trace."""
    body = batch_pair_masks_bitmap if use_bitmap else batch_pair_masks
    return jax.jit(lambda *args: body(*args))


def _batch_size(n_pairs: int, pair_batch: int, n_shards: int,
                ucap: int = 1) -> int:
    """Static batch shape: power-of-two sized (bounding compile count),
    never above the caller's ``pair_batch`` bound nor above
    ``PAIR_CELLS // ucap`` (the batch's (B, ucap) tensors stay bounded
    on large graphs), then rounded up to a multiple of the shard count
    (the one case that may exceed either bound)."""
    bound = max(8, min(pair_batch, PAIR_CELLS // ucap))
    b = round_up_pow2(min(max(n_pairs, 1), bound), minimum=8)
    if b > bound and b > 8:
        b //= 2
    return -(-b // n_shards) * n_shards


def evaluate_pairs(vp: Dict[int, Table], evals: Sequence[Key],
                   threshold: float, backend: str = "jax",
                   mesh=None, pair_batch: int = 512,
                   ) -> Tuple[Dict[Key, float], Dict[Key, int],
                              Dict[Key, Table]]:
    """Semi-join every pair in ``evals``; returns (sf, sizes, tables).

    ``backend="numpy"`` is the host loop (used by the incremental
    rebuild); ``"jax"`` batches the pair grid on the local device;
    ``"distributed"`` shards it across ``mesh`` (all devices when
    ``mesh`` is None).
    """
    sf: Dict[Key, float] = {}
    sizes: Dict[Key, int] = {}
    tables: Dict[Key, Table] = {}
    if not evals:
        return sf, sizes, tables

    if backend == "numpy":
        for key in evals:
            kind, p1, p2 = key
            t1, t2 = vp[p1], vp[p2]
            probe = t1.o if kind == OS else t1.s
            other = t2.unique_o if kind == SO else t2.unique_s
            mask = _semijoin_mask(probe, other)
            m = int(mask.sum())
            n1 = len(t1)
            sfv = m / n1 if n1 else 0.0
            sf[key] = sfv
            sizes[key] = m
            if 0 < sfv < 1.0 and sfv <= threshold:
                tables[key] = Table(t1.rows[mask])   # mask keeps s-order
        return sf, sizes, tables

    if backend not in ("jax", "distributed"):
        raise ValueError(f"unknown ExtVP build backend {backend!r}")

    n_shards = 1
    if backend == "distributed":
        if mesh is None:
            mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        n_shards = int(np.prod(list(mesh.shape.values())))

    # The Pallas kernel path probes the sorted-unique tensor; the default
    # jnp path probes the dense presence bitmap (one gather per key).
    # Pack only the predicates this eval set references, so an
    # incremental rebuild of a few pairs is not charged for the whole
    # catalog (a full build references every predicate anyway).
    pallas = ops.pallas_enabled()
    used = {p for k in evals for p in (k[1], k[2])}
    packed = pack_vp({p: vp[p] for p in used}, with_bitmap=not pallas)
    use_bitmap = packed.present is not None and not pallas
    juniq = jnp.asarray(packed.uniq)
    jmult = jnp.asarray(packed.mult)
    operand = jnp.asarray(packed.present) if use_bitmap else juniq
    batch = _batch_size(len(evals), pair_batch, n_shards,
                        packed.uniq.shape[-1])
    pcol = np.array([probe_col(k[0]) for k in evals], dtype=np.int32)
    pidx = np.array([packed.slot[k[1]] for k in evals], dtype=np.int32)
    bcol = np.array([build_col(k[0]) for k in evals], dtype=np.int32)
    bidx = np.array([packed.slot[k[2]] for k in evals], dtype=np.int32)

    for start in range(0, len(evals), batch):
        chunk = evals[start:start + batch]
        sl = slice(start, start + batch)
        parts = [pcol[sl], pidx[sl], bcol[sl], bidx[sl]]
        if len(chunk) < batch:       # pad by repeating the last pair
            parts = [np.concatenate([a, np.full(batch - len(chunk), a[-1],
                                                dtype=a.dtype)])
                     for a in parts]
        args = [jnp.asarray(a) for a in parts]
        if backend == "distributed":
            from repro.core.distributed import extvp_pair_masks_sharded
            member, counts = extvp_pair_masks_sharded(
                juniq, jmult, operand, *args, mesh=mesh,
                use_bitmap=use_bitmap)
        else:
            member, counts = _jitted_batch_fn(use_bitmap, pallas)(
                juniq, jmult, operand, *args)
        # bulk bookkeeping: SF for the whole chunk in one vectorized pass;
        # flags come back only for the pairs whose table materializes
        counts = np.asarray(counts)[:len(chunk)].astype(np.int64)
        n1s = np.array([len(vp[k[1]]) for k in chunk], dtype=np.int64)
        sfv = np.where(n1s > 0, counts / np.maximum(n1s, 1), 0.0)
        sf.update(zip(chunk, sfv.tolist()))
        sizes.update(zip(chunk, counts.tolist()))
        mat = np.nonzero((sfv > 0) & (sfv < 1.0) & (sfv <= threshold))[0]
        if len(mat):
            # gather on one device (a sharded batch would need the gather
            # partitioned); only the materializing pairs cross to the host
            member = jax.device_put(
                member, jax.sharding.SingleDeviceSharding(jax.devices()[0]))
            flags = np.asarray(member[jnp.asarray(mat)])
            for j, flag in zip(mat.tolist(), flags):
                kind, p1, _ = key = chunk[j]
                rows = vp[p1].rows
                tables[key] = Table(rows[packed.row_mask(kind, p1, flag)])
    return sf, sizes, tables


def build_extvp_planned(vp: Dict[int, Table], threshold: float = 1.0,
                        kinds: Tuple[str, ...] = KINDS,
                        backend: str = "jax", mesh=None,
                        pair_batch: int = 512) -> ExtVPBuild:
    """Full ExtVP schema via the planned pipeline (prune -> evaluate ->
    materialize) on any substrate.  All backends share the pruning, SF
    arithmetic, and row slicing of :func:`evaluate_pairs`, so they are
    byte-identical by construction."""
    out = ExtVPBuild(threshold=threshold, backend=backend,
                     kinds=tuple(kinds))
    pruned, evals = plan_pairs(vp, all_pair_keys(sorted(vp), kinds))
    for key in pruned:
        out.sf[key] = 0.0
        out.sizes[key] = 0
    sf, sizes, tables = evaluate_pairs(vp, evals, threshold, backend=backend,
                                       mesh=mesh, pair_batch=pair_batch)
    out.sf.update(sf)
    out.sizes.update(sizes)
    out.tables.update(tables)
    out.n_semijoins = len(evals)
    return out


# ---------------------------------------------------------------------------
# Incremental rebuild (Dataset.append_triples)
# ---------------------------------------------------------------------------

def incremental_pairs(old: ExtVPBuild, old_vp: Dict[int, Table],
                      new_vp: Dict[int, Table], touched: Set[int],
                      threshold: float, kinds: Tuple[str, ...] = KINDS,
                      backend: str = "numpy", mesh=None,
                      pair_batch: int = 512
                      ) -> Tuple[ExtVPBuild, Dict[str, int]]:
    """Rebuild only the pairs an append actually touched.

    A pair (kind, p1, p2) is carried over from ``old`` verbatim when

    * neither predicate received new triples, or
    * only the build side ``p2`` did, and every **new** unique build key
      falls outside the probe side's entity range — appended rows can
      then only have added build keys that match nothing, so the mask
      (and with it SF, size and the materialized rows) is unchanged.

    Everything else is re-evaluated through :func:`evaluate_pairs` with
    the requested backend.  Returns the new build plus an accounting
    report (``reused`` / ``range_skipped`` / ``recomputed`` /
    ``evaluated`` pair counts).
    """
    out = ExtVPBuild(threshold=threshold, backend=backend,
                     kinds=tuple(kinds))
    recompute: List[Key] = []
    carried: List[Key] = []
    reused = range_skipped = 0

    def carry(key: Key) -> None:
        out.sf[key] = old.sf[key]
        out.sizes[key] = old.sizes[key]
        if key in old.tables:
            carried.append(key)

    for key in all_pair_keys(sorted(new_vp), kinds):
        kind, p1, p2 = key
        if key not in old.sf:            # never computed (e.g. new kind set)
            recompute.append(key)
            continue
        if p1 not in touched and p2 not in touched:
            carry(key)
            reused += 1
            continue
        if p1 not in touched and p2 in touched and p2 in old_vp:
            bc = build_col(kind)
            old_u = old_vp[p2].unique_o if bc else old_vp[p2].unique_s
            new_u = new_vp[p2].unique_o if bc else new_vp[p2].unique_s
            added = np.setdiff1d(new_u, old_u, assume_unique=True)
            own = new_vp[p1].unique_o if kind == OS else new_vp[p1].unique_s
            if len(added) == 0 or len(own) == 0 or \
                    added[0] > own[-1] or added[-1] < own[0]:
                carry(key)
                range_skipped += 1
                continue
        recompute.append(key)

    pruned, evals = plan_pairs(new_vp, recompute)
    for key in pruned:
        out.sf[key] = 0.0
        out.sizes[key] = 0
    sf, sizes, tables = evaluate_pairs(new_vp, evals, threshold,
                                       backend=backend, mesh=mesh,
                                       pair_batch=pair_batch)
    out.sf.update(sf)
    out.sizes.update(sizes)
    # Carried-over tables must not be forced out of a lazy provider
    # (a store-backed catalog memory-maps them on demand): when the old
    # provider can hand out raw loaders, the merged result stays lazy —
    # carried keys keep their loaders, recomputed ones bind concrete
    # Tables — so delta replay cost scales with the journal, not with
    # the number of materialized ExtVP tables.
    loader_for = getattr(old.tables, "loader_for", None)
    if loader_for is not None:
        loaders = {key: loader_for(key) for key in carried}
        loaders.update({key: (lambda t: lambda: t)(t)
                        for key, t in tables.items()})
        out.tables = LazyTableMap(
            loaders, lengths={key: out.sizes[key] for key in loaders})
    else:
        out.tables.update({key: old.tables[key] for key in carried})
        out.tables.update(tables)
    out.n_semijoins = len(evals)
    report = {"pairs": reused + range_skipped + len(recompute),
              "reused": reused, "range_skipped": range_skipped,
              "recomputed": len(recompute), "evaluated": len(evals)}
    return out, report
