"""Distributed query engine: shard_map over the mesh's data axes.

This is the JAX-native mapping of S2RDF's Spark execution model:

* **Storage partitioning.** Every VP/ExtVP table is hash-partitioned by
  subject id (``s % n_shards``) across the flattened data axes of the
  mesh — the analogue of HDFS blocks + Spark's hash partitioning.  An
  optional object-partitioned copy (``dual_partition=True``) mirrors a
  clustered secondary index and removes the shuffle for object-keyed
  probes (a beyond-paper optimization measured in §Perf).

* **Co-partitioned joins.** A join whose key both sides are already
  partitioned by executes fully locally (zero collective bytes) —
  subject-subject joins over s-partitioned tables hit this path, which is
  why star patterns are shuffle-free, exactly like Spark co-partitioning.

* **Shuffle joins.** Otherwise the engine *repartitions* the relation(s)
  by the join key: rows are bucketed by ``key % n_shards`` into
  fixed-capacity per-destination buckets and exchanged with
  ``lax.all_to_all`` — a static-shape Spark shuffle.  ExtVP's semi-join
  reduction shrinks exactly these exchanged bytes, which is the paper's
  central claim transposed to ICI collectives.

Every shard runs the same static-shape kernels as :mod:`repro.core.jexec`;
results stay sharded, with valid counts summed by ``psum``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.algebra import is_var
from repro.core.compiler import (
    BGPSeg, CombineSeg, CorePlan, CoreSeg, EmptySeg, FilterSeg, Plan,
    ScanStep, core_filter_exprs,
)
from repro.core.jexec import (
    JBindings, bound_scan_seed, bounds_from_plan, build_key, check_spine,
    device_distinct, device_filter, device_join, device_left_join,
    device_order, device_project, device_resize, device_scan,
    device_scan_tt, device_slice, device_union, double_caps, count_retry,
    join_estimates, prefix_sum, prepare_value_keys, take_rows, _compact,
    _exec_cols, _mod_cap_seed, _step_meta, _tt_meta, _valid_mask,
)
from repro.core.modifiers import ModifierSpine, filter_const_slots
from repro.core.stats import Catalog
from repro.core.table import Table, round_up_pow2
from repro.rdf.dictionary import PAD, UNBOUND

__all__ = ["DistBindings", "DistributedExecutor", "shard_table",
           "repartition", "extvp_pair_masks_sharded"]


def _smap(body, mesh, in_specs, out_specs):
    """shard_map with the replication (vma) check off: the gathered
    modifier tail (sort/scatter over all_gather-ed, hence replicated,
    relations) returns replicated ``out_specs`` the checker cannot
    prove, and ``pallas_call`` in the build-side pair grid has no
    replication rule."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Host-side table sharding (storage layout)
# ---------------------------------------------------------------------------

def shard_table(table, n_shards: int, by: int = 0,
                min_cap: int = 16) -> Tuple[np.ndarray, np.ndarray]:
    """Hash-partition rows by column ``by``; returns (rows[S, cap, k], n[S]).

    Accepts a :class:`repro.core.table.Table` or a raw ``(N, k)`` int32
    array (the triples table of unbound-predicate scans)."""
    rows = table.rows if isinstance(table, Table) else np.asarray(table)
    k = rows.shape[1]
    dest = rows[:, by].astype(np.int64) % n_shards
    counts = np.bincount(dest, minlength=n_shards)
    cap = round_up_pow2(int(counts.max()) if len(rows) else 1, min_cap)
    out = np.full((n_shards, cap, k), PAD, dtype=np.int32)
    ns = np.zeros(n_shards, dtype=np.int32)
    order = np.argsort(dest, kind="stable")
    sorted_rows, sorted_dest = rows[order], dest[order]
    starts = np.searchsorted(sorted_dest, np.arange(n_shards))
    ends = np.searchsorted(sorted_dest, np.arange(n_shards), side="right")
    for i in range(n_shards):
        k = ends[i] - starts[i]
        out[i, :k] = sorted_rows[starts[i]:ends[i]]
        ns[i] = k
    return out, ns


# ---------------------------------------------------------------------------
# In-shard repartitioning (the static-shape Spark shuffle)
# ---------------------------------------------------------------------------

def repartition(data: jax.Array, n: jax.Array, key_col: int, n_shards: int,
                axis_name, out_cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exchange rows so that row.key % n_shards == shard_index afterwards.

    Runs inside shard_map.  data: (cap, k) local rows.  Returns
    (rows[out_cap, k], n, overflow).
    """
    cap, k = data.shape
    valid = _valid_mask(cap, n)
    key = data[:, key_col]
    dest = jnp.where(valid, key.astype(jnp.uint32) % n_shards, n_shards)

    bucket_cap = max(16, round_up_pow2(2 * cap // n_shards + 16))
    # slot of a row in its destination bucket = rows before it with the
    # same destination, from one prefix count per destination (stable,
    # and no sort: a sort costs the TPU compiler ~20 s per program)
    rank = jnp.zeros(cap, jnp.int32)
    counts = []
    for d in range(n_shards):
        hit = dest == d
        seen = prefix_sum(hit)
        rank = jnp.where(hit, seen - 1, rank)
        counts.append(seen[-1])
    overflow = jnp.any(jnp.stack(counts) > bucket_cap)

    in_bounds = (rank < bucket_cap) & (dest < n_shards)
    didx = jnp.where(in_bounds, dest, n_shards).astype(jnp.int32)  # OOB -> drop
    ridx = jnp.clip(rank, 0, bucket_cap - 1)
    empty = jnp.full((n_shards, bucket_cap), PAD, dtype=data.dtype)
    send = jnp.stack([empty.at[didx, ridx].set(data[:, c], mode="drop")
                      for c in range(k)], axis=-1)

    recv = jax.lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    recv = recv.reshape(n_shards * bucket_cap, k)
    gathered, n_out, cut = _compact(recv, recv[:, 0] != PAD, out_cap)
    overflow = jax.lax.pmax(overflow | cut, axis_name)
    return gathered, n_out, overflow


# ---------------------------------------------------------------------------
# Distributed plan executor
# ---------------------------------------------------------------------------

@dataclass
class DistBindings:
    cols: Tuple[str, ...]
    data: jax.Array         # (cap, k) — local shard inside shard_map
    n: jax.Array
    overflow: jax.Array
    part_key: Optional[str]  # variable this relation is hash-partitioned by


class DistributedExecutor:
    """Executes a compiled Plan over a mesh via shard_map.

    ``axes`` are the mesh axis names the relational work shards over (the
    model axes of LM jobs are simply folded in — relational plans have no
    'model' dimension, so queries use every chip).
    """

    def __init__(self, plan, catalog: Catalog, mesh: Mesh,
                 axes: Sequence[str] = ("data",), slack: float = 2.0,
                 dual_partition: bool = False,
                 spine: Optional[ModifierSpine] = None):
        if isinstance(plan, CorePlan):
            core = plan
        else:
            core = CorePlan(root=BGPSeg(plan=plan, start=0), flat=plan,
                            empty=plan.empty, vars=plan.vars)
        if core.empty:
            raise ValueError("statistics-empty plan")
        self.core = core
        self.plan = core.flat      # what template re-binding operates on
        self.catalog = catalog
        self.mesh = mesh
        self.axes = tuple(axes)
        self.n_shards = int(np.prod([mesh.shape[a] for a in self.axes]))
        self.dual_partition = dual_partition
        self.slack = slack
        # Solution modifiers: FILTER + projection are row-local and run
        # per shard; DISTINCT / ORDER BY / OFFSET / LIMIT need the whole
        # relation, so the (small, capacity-bounded) per-shard results
        # are all_gather-ed and the global modifiers run replicated.
        self.spine = spine if spine is not None else ModifierSpine()
        self._pipe_cols = _exec_cols(core.root)
        self._out_vars = check_spine(self.spine, self._pipe_cols, catalog)
        # core filters (OPTIONAL conditions, FILTER segments) consume
        # their fconsts slots first, then the spine's — one shared
        # runtime vector, evaluation order (see PlanExecutor)
        self._all_filters = tuple(core_filter_exprs(core.root)) + \
            tuple(self.spine.filters)
        self.filter_slots = filter_const_slots(self._all_filters)
        # raises NotImplementedError (→ counted eager fallback) only for
        # dictionaries whose numeric keys defeat the double-single pairs
        self._value_keys = prepare_value_keys(catalog, self.spine,
                                              self._all_filters)
        self.gathered = self.spine.needs_global

        # storage: shard every referenced table by subject (and object);
        # TT steps (unbound predicates) share one subject-sharded copy of
        # the triples table
        plan_f = self.plan
        tt_sh: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self.table_shards: List[Dict[str, Tuple[np.ndarray, np.ndarray]]] = []
        sizes: List[float] = []
        for step in plan_f.steps:
            if step.uses_tt:
                if tt_sh is None:
                    tt_sh = shard_table(np.asarray(catalog.tt, np.int32),
                                        self.n_shards, by=0)
                self.table_shards.append({"s": tt_sh})
                sizes.append(float(catalog.n_triples))
                continue
            t = catalog.table(step.kind, int(step.tp.p), step.p2)
            shards = {"s": shard_table(t, self.n_shards, by=0)}
            if dual_partition:
                shards["o"] = shard_table(t, self.n_shards, by=1)
            self.table_shards.append(shards)
            sizes.append(float(len(t)))

        # per-shard capacity seeds: the PlanExecutor estimate chain
        # divided by the shard count (each shard holds ~1/S of every
        # relation); combine segments (join/left/union) get their own
        # slots behind the flat steps, in evaluation (post-) order
        n_flat = len(plan_f.steps)
        flat_caps = [16] * n_flat
        comb_caps: List[int] = []
        self._comb_index: Dict[int, int] = {}

        def seed(seg: CoreSeg) -> float:
            if isinstance(seg, EmptySeg):
                return 1.0
            if isinstance(seg, FilterSeg):
                return seed(seg.child)
            if isinstance(seg, BGPSeg):
                est = 1.0
                joined = join_estimates(seg.plan.steps, catalog)
                for k, step in enumerate(seg.plan.steps):
                    i = seg.start + k
                    scan_est = max(1.0, sizes[i] / self.n_shards)
                    if step.tp.n_bound() > 1:
                        scan_est = max(1.0, bound_scan_seed(
                            step, catalog, sizes[i]) / self.n_shards)
                    est = scan_est if k == 0 else \
                        max(est, scan_est, est * 1.25,
                            joined[k] / self.n_shards)
                    flat_caps[i] = round_up_pow2(int(est * slack) + 16, 16)
                return est
            le, re_ = seed(seg.left), seed(seg.right)
            if seg.kind == "join":
                est = 1.25 * max(le, re_)
            elif seg.kind == "left":
                # inner rows plus (worst case) every left row unmatched
                est = 1.25 * max(le, re_) + le
            else:
                est = le + re_
            self._comb_index[id(seg)] = n_flat + len(comb_caps)
            comb_caps.append(round_up_pow2(int(est * slack) + 16, 16))
            return est

        seed(core.root)
        self.caps = flat_caps + comb_caps
        self._n_pipeline = len(self.caps)
        # per-shard resize slot ahead of the gather: the global modifiers
        # then sort/compact S·mod_cap rows instead of S·join_cap (see
        # PlanExecutor; the slot rides the same overflow-retry protocol)
        self._mod_resize = self.gathered
        if self._mod_resize:
            pipe_cap = max(self.caps) if self.caps else 64
            self.caps.append(_mod_cap_seed(self.spine, pipe_cap))
        self._default_bounds = bounds_from_plan(plan_f)

        # Which storage copy each scan uses.  Beyond-paper optimization:
        # simulate the plan's join-key sequence and pick the copy whose
        # partition variable IS the upcoming join key — an object-keyed
        # probe then reads the o-partitioned copy and skips the all_to_all
        # entirely (the clustered-index analogue of ExtVP's philosophy:
        # trade precomputed storage for shuffle bytes).  The simulation
        # only makes sense within one scan/join pipeline, so it applies
        # when the whole core is a single BGP (FILTER wrappers are
        # transparent); tree cores read the s-copy everywhere.
        self.scan_copy: List[str] = ["s"] * n_flat
        root_bgp: CoreSeg = core.root
        while isinstance(root_bgp, FilterSeg):
            root_bgp = root_bgp.child
        if dual_partition and isinstance(root_bgp, BGPSeg):
            steps = root_bgp.plan.steps
            acc_cols: List[str] = []
            for i, step in enumerate(steps):
                tp = step.tp
                if not step.uses_tt:   # the TT copy is subject-sharded only
                    join_key = None
                    if i > 0:
                        scan_vars = [v for v in (tp.s, tp.o) if is_var(v)]
                        shared = [c for c in acc_cols if c in scan_vars]
                        join_key = shared[0] if shared else None
                    elif len(steps) > 1:
                        # first scan: partition by the 2nd step's join var
                        nxt = steps[1].tp
                        nxt_vars = {v for v in (nxt.s, nxt.o) if is_var(v)}
                        for v in (tp.s, tp.o):
                            if is_var(v) and v in nxt_vars:
                                join_key = v
                                break
                    if join_key is not None and is_var(tp.o) \
                            and join_key == tp.o:
                        self.scan_copy[i] = "o"
                for v in (tp.s, tp.p, tp.o):
                    if is_var(v) and v not in acc_cols:
                        acc_cols.append(v)

    # -- traced per-shard program ---------------------------------------------
    def _shard_index(self) -> jax.Array:
        """This shard's linear index over the data axes (traced)."""
        idx = jnp.asarray(0, jnp.int32)
        for a in self.axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def _scan_step(self, i: int, step: ScanStep, rows, nrows,
                   bounds) -> DistBindings:
        """One shard-local scan.  TT steps (unbound predicates) read this
        shard's slice of the subject-sharded triples table; VP/ExtVP
        steps read the copy :attr:`scan_copy` picked."""
        tp = step.tp
        if step.uses_tt:
            s_b, p_b, o_b, eqs, take, cols = _tt_meta(tp)
            sb = bounds[i, 0] if s_b is not None else None
            ob = bounds[i, 1] if o_b is not None else None
            data, n, ovf = device_scan_tt(rows, nrows, sb, p_b, ob,
                                          eqs, take, rows.shape[0])
            part_var = tp.s if is_var(tp.s) else None
            return DistBindings(cols, data, n, ovf, part_var)
        s_bound, o_bound, same, take, cols = _step_meta(step)
        data, n, ovf = device_scan(rows, nrows,
                                   bounds[i, 0] if s_bound is not None else None,
                                   bounds[i, 1] if o_bound is not None else None,
                                   same, take, rows.shape[0])
        copy = self.scan_copy[i]
        part_var = None
        if copy == "s" and is_var(tp.s):
            part_var = tp.s
        elif copy == "o" and is_var(tp.o):
            part_var = tp.o
        return DistBindings(cols, data, n, ovf, part_var)

    def _compose_bgp(self, seg: BGPSeg, caps, flat_tables, bounds, ovfs,
                     axis) -> DistBindings:
        """The shard-local scan/join pipeline of one BGP segment; records
        each step's overflow at its flat index (see PlanExecutor)."""
        no = jnp.asarray(False)
        if not seg.plan.steps:
            # empty BGP: the unit relation (one empty solution mapping)
            # lives on shard 0 — anywhere else it would be counted S times
            n = (self._shard_index() == 0).astype(jnp.int32)
            return DistBindings((), jnp.zeros((8, 0), jnp.int32), n, no,
                                None)
        acc: Optional[DistBindings] = None
        for k, step in enumerate(seg.plan.steps):
            i = seg.start + k
            # local shard: (1, cap, k) and (1,) — drop the sharded axis
            rows, nrows = flat_tables[2 * i][0], flat_tables[2 * i + 1][0]
            cur = self._scan_step(i, step, rows, nrows, bounds)
            if acc is None:
                acc = cur
                ovfs[i] = cur.overflow
                continue
            # an s-copy scan partitioned by its subject keeps the table's
            # (s, o) order: joined on that subject it needs no sort
            sorted_by = step.tp.s if (not step.uses_tt and
                                      self.scan_copy[i] == "s") else None
            joined = self._dist_join(acc, cur, caps[i], axis, sorted_by)
            ovfs[i] = joined.overflow | cur.overflow
            acc = joined
        return DistBindings(acc.cols, acc.data, acc.n, no, acc.part_key)

    def _eval_seg(self, seg: CoreSeg, caps, flat_tables, bounds, fconsts,
                  values, ctr, ovfs, axis) -> DistBindings:
        """Evaluate the core segment tree to one shard-local relation;
        mirrors :meth:`repro.core.jexec.PlanExecutor._eval_seg` with the
        combines going through the distributed (co-partition / gather)
        join family.  Each combine writes its own overflow flag at its
        capacity index, so returned relations carry clean flags."""
        no = jnp.asarray(False)
        if isinstance(seg, EmptySeg):
            k = len(seg.vars)
            return DistBindings(tuple(seg.vars),
                                jnp.full((8, k), PAD, jnp.int32),
                                jnp.asarray(0, jnp.int32), no, None)
        if isinstance(seg, BGPSeg):
            return self._compose_bgp(seg, caps, flat_tables, bounds, ovfs,
                                     axis)
        if isinstance(seg, FilterSeg):
            d = self._eval_seg(seg.child, caps, flat_tables, bounds,
                               fconsts, values, ctr, ovfs, axis)
            jb = device_filter(JBindings(d.cols, d.data, d.n, no),
                               seg.expr, values, fconsts, ctr)
            return DistBindings(jb.cols, jb.data, jb.n, no, d.part_key)
        left = self._eval_seg(seg.left, caps, flat_tables, bounds, fconsts,
                              values, ctr, ovfs, axis)
        right = self._eval_seg(seg.right, caps, flat_tables, bounds,
                               fconsts, values, ctr, ovfs, axis)
        ci = self._comb_index[id(seg)]
        if seg.kind == "join":
            out = self._dist_join(left, right, caps[ci], axis)
        elif seg.kind == "left":
            out = self._dist_left_join(left, right, caps[ci], axis,
                                       seg.expr, values, fconsts, ctr)
        else:
            out = self._dist_union(left, right, caps[ci])
        ovfs[ci] = out.overflow
        return DistBindings(out.cols, out.data, out.n, no, out.part_key)

    def _shard_program(self, caps, bounds, fconsts, values, *flat_tables):
        """Returns (data, n, total, per_step_overflow[n_pipeline]).  Like
        :meth:`repro.core.jexec.PlanExecutor._program`, overflow is
        reported per capacity slot so the host retry doubles only the
        overflowing capacities — one heavy constant must not inflate
        every buffer for the whole (batched) workload."""
        axis = self.axes if len(self.axes) > 1 else self.axes[0]
        ctr = [0]
        ovfs: List[jax.Array] = [jnp.asarray(False)] * self._n_pipeline
        acc = self._eval_seg(self.core.root, caps, flat_tables, bounds,
                             fconsts, values, ctr, ovfs, axis)
        out_ovf = jax.lax.pmax(jnp.stack(ovfs), axis)

        # shard-local modifiers: FILTER masks (+ projection when no
        # global modifier needs the un-projected sort keys)
        no = jnp.asarray(False)
        jb = JBindings(acc.cols, acc.data, acc.n, no)
        for expr in self.spine.filters:
            jb = device_filter(jb, expr, values, fconsts, ctr)
        if not self.gathered:
            jb = device_project(jb, self._out_vars)
            total = jax.lax.psum(jb.n, axis)
            return jb.data, jb.n[None], total, out_ovf
        if self._mod_resize:
            jb, mod_ovf = device_resize(jb, caps[self._n_pipeline])
            out_ovf = jnp.concatenate(
                [out_ovf, jax.lax.pmax(mod_ovf, axis)[None]])

        # global modifiers: gather the (capacity-bounded) shard results,
        # compact, then ORDER BY → project → DISTINCT → OFFSET/LIMIT
        # replicated (ordering before projection, as on the host paths) —
        # only the final n ≤ limit rows ever reach the host
        gdata = jax.lax.all_gather(jb.data, axis, axis=0, tiled=True)
        # positional validity (front-compacted shard blocks) — a 0-column
        # relation has no PAD slot to test
        keep = jax.lax.all_gather(
            jnp.arange(jb.data.shape[0], dtype=jnp.int32) < jb.n,
            axis, axis=0, tiled=True)
        cdata, cn, _ = _compact(gdata, keep, gdata.shape[0])
        gb = JBindings(jb.cols, cdata, cn, no)
        if self.spine.order:
            gb = device_order(gb, self.spine.order, values)
        gb = device_project(gb, self._out_vars)
        if self.spine.distinct:
            gb = device_distinct(gb)
        if self.spine.has_slice:
            gb = device_slice(gb, self.spine.offset, self.spine.limit)
        return gb.data, gb.n[None], gb.n, out_ovf

    def _dist_join(self, a: DistBindings, b: DistBindings, out_cap: int,
                   axis, b_sorted_by: Optional[str] = None) -> DistBindings:
        """Join two shard-local relations; the returned ``overflow`` is
        this step's OWN flag (repartition bucket/compact + join output) —
        input flags are not propagated, the caller tracks them per step.
        ``b_sorted_by`` names the column ``b``'s rows are ordered by."""
        no = jnp.asarray(False)
        shared = [c for c in a.cols if c in b.cols]
        if not shared:
            # cross join: gather the (small) b side everywhere, then local
            b_all, bn_all = _allgather_relation(b, axis)
            jb = device_join(JBindings(a.cols, a.data, a.n, no),
                             JBindings(b.cols, b_all, bn_all, no),
                             out_cap)
            return DistBindings(jb.cols, jb.data, jb.n, jb.overflow, a.part_key)
        key = shared[0]
        ovf = no
        da, na = a.data, a.n
        db, nb = b.data, b.n
        # repartition any side not already partitioned by the join key
        if a.part_key != key:
            da, na, o1 = repartition(da, na, a.cols.index(key), self.n_shards,
                                     axis, max(da.shape[0], out_cap))
            ovf |= o1
        pre = None
        if b.part_key != key:
            db, nb, o2 = repartition(db, nb, b.cols.index(key), self.n_shards,
                                     axis, max(db.shape[0], out_cap))
            ovf |= o2
        elif b_sorted_by == key:
            pre = (jnp.arange(db.shape[0], dtype=jnp.int32),
                   build_key(JBindings(b.cols, db, nb, no),
                             b.cols.index(key)))
        jb = device_join(JBindings(a.cols, da, na, no),
                         JBindings(b.cols, db, nb, no),
                         out_cap, b_presorted=pre)
        return DistBindings(jb.cols, jb.data, jb.n, jb.overflow | ovf, key)

    def _dist_left_join(self, a: DistBindings, b: DistBindings,
                        out_cap: int, axis, expr, values, fconsts,
                        ctr) -> DistBindings:
        """OPTIONAL over shard-local relations.  With a shared variable
        both sides are co-partitioned on it first, so each probe row
        meets ALL its matches locally and the unmatched (UNBOUND-padded)
        tail is computed shard-locally too; without one the (small) b
        side is gathered everywhere — either way the per-shard row sets
        partition the global left-outer-join result exactly."""
        no = jnp.asarray(False)
        shared = [c for c in a.cols if c in b.cols]
        if not shared:
            b_all, bn_all = _allgather_relation(b, axis)
            jb = device_left_join(JBindings(a.cols, a.data, a.n, no),
                                  JBindings(b.cols, b_all, bn_all, no),
                                  out_cap, expr, values, fconsts, ctr)
            return DistBindings(jb.cols, jb.data, jb.n, jb.overflow,
                                a.part_key)
        key = shared[0]
        ovf = no
        da, na = a.data, a.n
        db, nb = b.data, b.n
        if a.part_key != key:
            da, na, o1 = repartition(da, na, a.cols.index(key),
                                     self.n_shards, axis,
                                     max(da.shape[0], out_cap))
            ovf |= o1
        if b.part_key != key:
            db, nb, o2 = repartition(db, nb, b.cols.index(key),
                                     self.n_shards, axis,
                                     max(db.shape[0], out_cap))
            ovf |= o2
        jb = device_left_join(JBindings(a.cols, da, na, no),
                              JBindings(b.cols, db, nb, no),
                              out_cap, expr, values, fconsts, ctr)
        return DistBindings(jb.cols, jb.data, jb.n, jb.overflow | ovf, key)

    def _dist_union(self, a: DistBindings, b: DistBindings,
                    out_cap: int) -> DistBindings:
        """UNION is embarrassingly shard-local (no collective): each
        shard concatenates its slices of both operands.  The partition
        key survives only when both sides are partitioned by the SAME
        variable (rows keep satisfying key % S == shard)."""
        no = jnp.asarray(False)
        jb = device_union(JBindings(a.cols, a.data, a.n, no),
                          JBindings(b.cols, b.data, b.n, no), out_cap)
        pk = a.part_key if (a.part_key is not None
                            and a.part_key == b.part_key) else None
        return DistBindings(jb.cols, jb.data, jb.n, jb.overflow, pk)

    # -- public API --------------------------------------------------------------
    bounds_from_plan = staticmethod(bounds_from_plan)

    def fconsts_from_mapping(self, mapping=None) -> np.ndarray:
        """Runtime filter-constant vector (see
        :meth:`repro.core.jexec.PlanExecutor.fconsts_from_mapping`)."""
        m = mapping or {}
        return np.asarray([m.get(c, c) for c in self.filter_slots],
                          dtype=np.int32)

    @functools.cached_property
    def _values(self) -> jax.Array:
        # the (nv, 4) double-single numeric key table (replicated); see
        # repro.core.jexec.numeric_value_keys
        return jnp.asarray(self._value_keys)

    def _out_specs(self):
        if self.gathered:     # replicated post-gather results
            return (P(), P(), P(), P())
        return (P(self.axes), P(self.axes), P(), P())

    @functools.cached_property
    def _jitted(self):
        specs = [P(), P(), P()]   # bounds / fconsts / values replicated
        for shards, copy in zip(self.table_shards, self.scan_copy):
            specs.append(P(self.axes))      # rows (S, cap, 2) split on axes
            specs.append(P(self.axes))      # ns   (S,)

        def wrapper(caps, bounds, fconsts, values, *flat):
            fn = _smap(
                functools.partial(self._shard_program, caps),
                mesh=self.mesh,
                in_specs=tuple(specs),
                out_specs=self._out_specs(),
            )
            return fn(bounds, fconsts, values, *flat)

        return jax.jit(wrapper, static_argnums=(0,))

    @functools.cached_property
    def _jitted_batch(self):
        # Batched form: the (B, n_steps, 2) bounds stack is replicated to
        # every shard and looped over *inside* shard_map, so the batch
        # axis rides alongside the data axis — every device executes all B
        # constant-bindings over its own table shard in one launch, and
        # results stay sharded per (request, shard) (or replicated per
        # request once a global modifier gathers them).
        specs = [P(), P(), P()]   # bounds (B,...) / fconsts (B,...) / values
        for _ in self.table_shards:
            specs.append(P(self.axes))      # rows (S, cap, 2) split on axes
            specs.append(P(self.axes))      # ns   (S,)

        if self.gathered:
            out_specs = (P(), P(), P(), P())
        else:
            out_specs = (P(None, self.axes), P(None, self.axes), P(), P())

        def wrapper(caps, bounds_b, fconsts_b, values, *flat):
            def shard_fn(bounds_b, fconsts_b, values, *flat):
                return jax.lax.map(
                    lambda bf: self._shard_program(caps, *bf, values, *flat),
                    (bounds_b, fconsts_b))

            fn = _smap(
                shard_fn,
                mesh=self.mesh,
                in_specs=tuple(specs),
                out_specs=out_specs,
            )
            return fn(bounds_b, fconsts_b, values, *flat)

        return jax.jit(wrapper, static_argnums=(0,))

    def _flat_inputs(self):
        flat = []
        for shards, copy in zip(self.table_shards, self.scan_copy):
            rows, ns = shards[copy]
            flat.append(rows)
            flat.append(ns)
        return flat

    def lower(self, caps: Optional[Tuple[int, ...]] = None):
        caps = caps or tuple(self.caps)
        flat = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in self._flat_inputs()]
        bshape = jax.ShapeDtypeStruct(self._default_bounds.shape, jnp.int32)
        fshape = jax.ShapeDtypeStruct((len(self.filter_slots),), jnp.int32)
        vshape = jax.ShapeDtypeStruct(self._values.shape, jnp.float32)
        return self._jitted.lower(caps, bshape, fshape, vshape, *flat)

    def run(self, max_retries: int = 16,
            bounds: Optional[np.ndarray] = None,
            fconsts: Optional[np.ndarray] = None,
            trace=None, bind: Optional[int] = None
            ) -> Tuple[np.ndarray, Tuple[str, ...]]:
        """One binding over the mesh; ``bind`` and the ``device.fetch``
        span as in :meth:`repro.core.jexec.PlanExecutor.run`."""
        flat = self._flat_inputs()
        b = self._default_bounds if bounds is None else \
            np.asarray(bounds, dtype=np.int32).reshape(self._default_bounds.shape)
        bj = jnp.asarray(b)
        fc = self.fconsts_from_mapping(None) if fconsts is None else \
            np.asarray(fconsts, dtype=np.int32).reshape(len(self.filter_slots))
        fj = jnp.asarray(fc)
        if bind is not None:
            trace.end(bind)
        caps = tuple(self.caps)
        for attempt in range(max_retries):
            if trace is not None:
                # fenced launch span (traced requests only) — see
                # PlanExecutor.run
                sid = trace.start("device.launch", backend="distributed",
                                  attempt=attempt, batch=1,
                                  shards=self.n_shards,
                                  cap_slots=sum(caps))
                data, ns, total, ovf = self._jitted(caps, bj, fj,
                                                    self._values, *flat)
                jax.block_until_ready((data, ns, ovf))
                ovf = np.asarray(ovf)
                trace.end(sid, overflow=bool(ovf.any()))
            else:
                data, ns, total, ovf = self._jitted(caps, bj, fj,
                                                    self._values, *flat)
                ovf = np.asarray(ovf)
            if not ovf.any():
                sid = trace.start("device.fetch") if trace is not None \
                    else None
                self.caps = list(caps)   # keep grown caps across requests
                data = np.asarray(data)
                ns = np.asarray(ns)
                if self.gathered:        # replicated, already finalized
                    out = data[: int(ns[0])]
                else:
                    rows = []
                    per = data.reshape(self.n_shards,
                                       data.shape[0] // self.n_shards,
                                       data.shape[-1])
                    for i in range(self.n_shards):
                        rows.append(per[i][: int(ns[i])])
                    out = np.concatenate(rows, axis=0) if rows \
                        else np.empty((0, 0))
                if trace is not None:
                    trace.end(sid, bytes=data.nbytes + ns.nbytes,
                              rows=len(out), retries=attempt)
                return out, self._final_cols()
            caps = double_caps(caps, ovf, self._n_pipeline)
            count_retry()
        raise RuntimeError("distributed join capacity overflow after retries")

    def run_batch(self, bounds_batch: Sequence[np.ndarray],
                  fconsts_batch: Optional[Sequence[np.ndarray]] = None,
                  max_retries: int = 16, trace=None,
                  bind: Optional[int] = None
                  ) -> List[Tuple[np.ndarray, Tuple[str, ...]]]:
        """Execute B constant-bindings of the plan in one sharded launch;
        see :meth:`repro.core.jexec.PlanExecutor.run_batch` for the retry
        contract (any element overflowing retries the whole batch) and
        the spans."""
        if not bounds_batch:
            return []
        flat = self._flat_inputs()
        shape = self._default_bounds.shape
        bb = np.stack([np.asarray(b, dtype=np.int32).reshape(shape)
                       for b in bounds_batch])
        bj = jnp.asarray(bb)
        n_fc = len(self.filter_slots)
        if fconsts_batch is None:
            fb = np.tile(self.fconsts_from_mapping(None), (len(bb), 1))
        else:
            fb = np.stack([np.asarray(f, dtype=np.int32).reshape(n_fc)
                           for f in fconsts_batch])
        fj = jnp.asarray(fb)
        if bind is not None:
            trace.end(bind)
        caps = tuple(self.caps)
        for attempt in range(max_retries):
            if trace is not None:
                sid = trace.start("device.launch", backend="distributed",
                                  attempt=attempt, batch=len(bb),
                                  shards=self.n_shards,
                                  cap_slots=sum(caps))
                data, ns, total, ovf = self._jitted_batch(
                    caps, bj, fj, self._values, *flat)
                jax.block_until_ready((data, ns, ovf))
                ovf = np.asarray(ovf)            # (B, n_steps)
                trace.end(sid, overflow=bool(ovf.any()))
            else:
                data, ns, total, ovf = self._jitted_batch(
                    caps, bj, fj, self._values, *flat)
                ovf = np.asarray(ovf)            # (B, n_steps)
            if not ovf.any():
                sid = trace.start("device.fetch") if trace is not None \
                    else None
                self.caps = list(caps)
                data = np.asarray(data)          # (B, S*cap, k)
                ns = np.asarray(ns)              # (B, S) or (B, 1)
                cols = self._final_cols()
                out = []
                for bi in range(data.shape[0]):
                    if self.gathered:
                        out.append((data[bi][: int(ns[bi, 0])], cols))
                        continue
                    per = data[bi].reshape(self.n_shards,
                                           data.shape[1] // self.n_shards,
                                           data.shape[-1])
                    rows = [per[i][: int(ns[bi, i])]
                            for i in range(self.n_shards)]
                    merged = np.concatenate(rows, axis=0) if rows \
                        else np.empty((0, 0))
                    out.append((merged, cols))
                if trace is not None:
                    trace.end(sid, bytes=data.nbytes + ns.nbytes,
                              rows=sum(len(o[0]) for o in out),
                              retries=attempt)
                return out
            caps = double_caps(caps, ovf.any(axis=0), self._n_pipeline)
            count_retry()
        raise RuntimeError(
            "distributed join capacity overflow after retries (batched)")

    def _final_cols(self) -> Tuple[str, ...]:
        return self._out_vars


# ---------------------------------------------------------------------------
# Distributed ExtVP construction (the load-job analogue of the query engine)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _extvp_pair_program(mesh: Mesh, axes: Tuple[str, ...], use_bitmap: bool,
                        pallas: bool):
    """``pallas`` is only a cache key: the kernel body reads the mutable
    ``ops.use_pallas`` state at trace time, so a toggle needs a fresh
    program rather than a replay of the stale trace."""
    from repro.core.extvp_build import (
        batch_pair_masks, batch_pair_masks_bitmap,
    )

    body = batch_pair_masks_bitmap if use_bitmap else batch_pair_masks
    return jax.jit(_smap(body, mesh,
                         in_specs=(P(), P(), P()) + (P(axes),) * 4,
                         out_specs=(P(axes), P(axes))))


def extvp_pair_masks_sharded(uniq: jax.Array, mult: jax.Array,
                             build_operand: jax.Array, pcol: jax.Array, pidx: jax.Array,
                             bcol: jax.Array, bidx: jax.Array, mesh: Mesh,
                             axes: Optional[Sequence[str]] = None,
                             use_bitmap: bool = False
                             ) -> Tuple[jax.Array, jax.Array]:
    """Semi-join flags for a batch of packed ExtVP pairs with the
    (kind, p1, p2) pair grid partitioned across the mesh.

    S2RDF runs the §5 semi-join reductions as a distributed Spark job;
    here the packed catalog (sorted-unique keys, their multiplicities and
    the build-side operand — the sorted-unique tensor again for the
    kernel path, a dense presence bitmap when ``use_bitmap``) is
    replicated and each device evaluates its B/S slice
    of the pair batch — the load-time counterpart of the query engine's
    sharded scans.  The batch size must divide evenly by the shard count
    (the planner in :mod:`repro.core.extvp_build` rounds it up).
    """
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    if pcol.shape[0] % n_shards:
        raise ValueError(f"pair batch {pcol.shape[0]} must divide evenly "
                         f"across {n_shards} shards")
    from repro.kernels.ops import pallas_enabled
    return _extvp_pair_program(mesh, axes, use_bitmap, pallas_enabled())(
        uniq, mult, build_operand, pcol, pidx, bcol, bidx)


def _allgather_relation(b: DistBindings, axis):
    """Gather a (front-compacted) shard-local relation to every shard.
    Validity is positional — row i of a shard block is live iff
    ``i < n`` — which also covers 0-column relations (fully-constant
    patterns) that have no PAD slot to test."""
    data = jax.lax.all_gather(b.data, axis, axis=0, tiled=True)
    keep = jax.lax.all_gather(
        jnp.arange(b.data.shape[0], dtype=jnp.int32) < b.n,
        axis, axis=0, tiled=True)
    n_tot = jax.lax.psum(b.n, axis)
    return _compact(data, keep, data.shape[0])[0], n_tot
