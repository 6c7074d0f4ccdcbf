"""Static-shape jitted executor — the device (TPU) path of the engine.

XLA requires static shapes, so every relation is a fixed-capacity buffer
``(data[cap, k], n)`` with PAD rows past ``n``; every operator returns an
overflow flag when a capacity would have been exceeded and the host re-runs
the plan with doubled capacities (the standard static-buffer serving
pattern).  Capacities are seeded from the catalog's ExtVP statistics — the
same statistics the paper uses for join ordering — so overflows are rare.

Join algorithm: sort-merge.  The probe side is key-sorted (XLA sort), the
build side binary-searched (``jnp.searchsorted``), match counts expanded
into output slots by a rank-search over the exclusive prefix sum.  All
steps are O(n log n) vectorized primitives that map to TPU-friendly sort /
gather / compare units — this is where the Pallas kernels of
:mod:`repro.kernels` plug in for the probe phase.

Join keys are single int32 columns (the first shared variable); any
further shared variables are post-filtered after expansion — BGP joins
share one variable in the overwhelming majority of cases (star/chain
joins), and this keeps the engine int32-only (x64 mode stays off for the
LM substrate).  Sentinels keep padded/NULL rows unmatched: probe-side pads
→ ``A_SENT``, build-side pads → ``B_SENT`` (distinct, sort-max), UNBOUND
values → per-side negative sentinels.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimate
from repro.core.algebra import BoolOp, Bound, Cmp, FilterExpr, NotExpr, is_var
from repro.core.compiler import (
    BGPSeg, CombineSeg, CorePlan, CoreSeg, EmptySeg, FilterSeg, Plan,
    ScanStep, core_filter_exprs, seg_vars,
)
from repro.core.modifiers import (
    ModifierSpine, filter_const_slots, filter_variables,
)
from repro.core.stats import Catalog
from repro.core.table import pad_rows, round_up_pow2
from repro.rdf.dictionary import PAD, UNBOUND

__all__ = ["JBindings", "PlanExecutor", "device_join", "device_left_join",
           "device_union", "device_scan", "device_scan_tt",
           "device_scan_windowed", "build_key", "bounds_from_plan",
           "trace_count", "device_filter", "device_project",
           "device_distinct", "device_order", "device_slice",
           "numeric_value_keys", "prepare_value_keys"]

A_SENT = np.int32(2**31 - 1)   # probe-side padded-row key (== PAD)
B_SENT = np.int32(2**31 - 2)   # build-side padded-row key (sort-max, != A_SENT)
A_NULL = np.int32(-3)          # probe-side UNBOUND key
B_NULL = np.int32(-5)          # build-side UNBOUND key


# ---------------------------------------------------------------------------
# Double-single numeric keys
#
# The device engines run with x64 disabled, so float64 dictionary values
# cannot be compared/sorted on device directly.  Each float64 ``v`` is
# split into a float32 pair ``(hi, lo)`` with ``hi = f32(v)`` (nearest)
# and ``lo = f32(v - f64(hi))``: ``hi`` is monotone in ``v`` and, for
# equal ``hi``, the residual is monotone too, so LEXICOGRAPHIC pair
# comparison is order-equivalent to the float64 comparison whenever the
# pair mapping is injective over the values actually compared.  That
# injectivity is checked ONCE on the host (adjacent-unique over the
# sorted value+id key set) — tables that defeat it (sub-2^-29-relative
# deltas) raise NotImplementedError, which the backends turn into the
# counted eager fallback.  This replaces the old blanket "values must be
# float32-exact" bail-out: any id-space size and ordinary float64 value
# tables (2^24+, fractional, negative) now stay on device.
# ---------------------------------------------------------------------------

def _split_f64(v: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    hi = v.astype(np.float32)
    with np.errstate(invalid="ignore"):
        lo = (v - hi.astype(np.float64)).astype(np.float32)
    return hi, np.where(np.isnan(lo), np.float32(0.0), lo)


def _split_scalar(v: float) -> Tuple[np.float32, np.float32]:
    hi = np.float32(v)
    return hi, np.float32(np.float64(v) - np.float64(hi))


def _check_pair_injective(vals: np.ndarray, what: str) -> None:
    """Distinct float64 keys must map to distinct (hi, lo) pairs."""
    u = np.unique(vals[~np.isnan(vals)])
    if len(u) <= 1:
        return
    hi, lo = _split_f64(u)
    if not np.all((np.diff(hi) != 0) | (np.diff(lo) != 0)):
        raise NotImplementedError(
            f"{what} is not double-single distinguishable; numeric "
            "modifiers would diverge from the host engines")


def numeric_value_keys(dictionary) -> np.ndarray:
    """The device numeric-key table: float32 ``(nv, 4)`` of
    ``[cmp_hi, cmp_lo, ord_hi, ord_lo]`` per term id.  The cmp pair is
    NaN for non-numeric terms (comparisons drop those rows, matching the
    host engines); the ord pair falls back to the term id (the host
    ``order_rows`` key).  Cached on the dictionary; raises
    NotImplementedError when the pair encoding cannot distinguish the
    table's keys (the backends' fallback signal)."""
    if dictionary is None:
        return np.empty((0, 4), dtype=np.float32)
    cached = getattr(dictionary, "_ds_value_keys", None)
    if cached is not None and cached.shape[0] == len(dictionary):
        return cached
    vals = np.asarray(dictionary.values, dtype=np.float64)
    n = len(vals)
    cmp_hi, cmp_lo = _split_f64(vals)
    cmp_hi = np.where(np.isnan(vals), np.float32(np.nan), cmp_hi)
    ord64 = np.where(np.isnan(vals), np.arange(n, dtype=np.float64), vals)
    _check_pair_injective(ord64, "dictionary value/id key table")
    ord_hi, ord_lo = _split_f64(ord64)
    keys = np.stack([cmp_hi, cmp_lo, ord_hi, ord_lo], axis=1) \
        .astype(np.float32)
    try:
        dictionary._ds_value_keys = keys
    except AttributeError:
        pass
    return keys


def _float_literals(exprs: Sequence[FilterExpr]) -> List[float]:
    out: List[float] = []

    def walk(e) -> None:
        if isinstance(e, Cmp):
            for t in (e.lhs, e.rhs):
                if isinstance(t, float):
                    out.append(t)
        elif isinstance(e, BoolOp):
            for a in e.args:
                walk(a)
        elif isinstance(e, NotExpr):
            walk(e.arg)

    for e in exprs:
        walk(e)
    return out


def _exprs_use_values(exprs: Sequence[FilterExpr]) -> bool:
    """True when any filter comparison is numeric (order ops or a float
    literal operand) — i.e. reads the numeric key table."""

    def walk(e) -> bool:
        if isinstance(e, Cmp):
            return e.op in ("<", "<=", ">", ">=") or \
                isinstance(e.lhs, float) or isinstance(e.rhs, float)
        if isinstance(e, BoolOp):
            return any(walk(a) for a in e.args)
        if isinstance(e, NotExpr):
            return walk(e.arg)
        return False

    return any(walk(e) for e in exprs)


def prepare_value_keys(catalog: Optional[Catalog], spine: ModifierSpine,
                       filters: Sequence[FilterExpr]) -> np.ndarray:
    """The numeric key table a program needs — empty when nothing in the
    program reads values (identity-only filters, no ORDER BY), so
    value-free templates never pay the injectivity check and never fall
    back on a pathological dictionary."""
    uses = bool(spine.order) or _exprs_use_values(filters)
    if not uses or catalog is None or catalog.dictionary is None:
        return np.empty((0, 4), dtype=np.float32)
    keys = numeric_value_keys(catalog.dictionary)
    lits = _float_literals(list(filters))
    if lits:
        vals = np.asarray(catalog.dictionary.values, dtype=np.float64)
        _check_pair_injective(
            np.concatenate([vals[~np.isnan(vals)],
                            np.asarray(lits, dtype=np.float64)]),
            "filter literal vs dictionary value keys")
    return keys


@dataclass
class JBindings:
    """Static-shape relation: cols are trace-time metadata."""

    cols: Tuple[str, ...]
    data: jax.Array          # (cap, k) int32
    n: jax.Array             # () int32
    overflow: jax.Array      # () bool — sticky across operators

    @property
    def capacity(self) -> int:
        return self.data.shape[0]


def _valid_mask(cap: int, n: jax.Array) -> jax.Array:
    return jnp.arange(cap, dtype=jnp.int32) < n


_SCAN_ROW = 1024


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive int32 prefix sum, in two levels for long inputs: row
    sums over a ``(n / 1024, 1024)`` view plus the prefix of the row
    totals.  The TPU compiler spends ~10 s on one cumsum over 10^6
    elements and well under a second on the two-level form."""
    x = x.astype(jnp.int32)
    n = x.shape[0]
    if n <= _SCAN_ROW or n % _SCAN_ROW:
        return jnp.cumsum(x)
    rows = jnp.cumsum(x.reshape(n // _SCAN_ROW, _SCAN_ROW), axis=1)
    totals = rows[:, -1]
    return (rows + (prefix_sum(totals) - totals)[:, None]).reshape(n)


def take_rows(data: jax.Array, idx: jax.Array) -> jax.Array:
    """``data[idx]`` for a ``(cap, k)`` relation, as one 1-D gather per
    column.  A row gather of the 2-D array puts each gathered row in the
    TPU's 128 lanes — k int32 columns padded 128/k-fold, gigabytes at
    10^7-row capacities — where a column gather stays lane-dense."""
    if data.shape[1] == 0:
        return data[idx]
    return jnp.stack([data[:, c][idx] for c in range(data.shape[1])], axis=1)


def _compact(data: jax.Array, keep: jax.Array, out_cap: int,
             fill: int = PAD) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Move keep-rows to the front (stable); returns (data, n, overflow).

    Output slot ``j`` takes the first row whose running keep-count
    exceeds ``j`` (a binary search over the prefix sum), so compaction
    needs no sort: a sort of a 10^5-row or larger buffer costs the TPU
    compiler ~20 s per program."""
    cap = data.shape[0]
    if cap == 0:
        return (jnp.full((out_cap, data.shape[1]), fill, jnp.int32),
                jnp.int32(0), jnp.asarray(False))
    running = prefix_sum(keep)
    n_keep = running[-1]
    j = jnp.arange(out_cap, dtype=jnp.int32)
    src = jnp.searchsorted(running, j, side="right").astype(jnp.int32)
    gathered = take_rows(data, jnp.clip(src, 0, cap - 1))
    gathered = jnp.where((j < n_keep)[:, None], gathered, fill)
    return gathered, jnp.minimum(n_keep, out_cap), n_keep > out_cap


def device_scan(rows: jax.Array, n: jax.Array, s_bound,
                o_bound, same_var: bool,
                out_cols: Sequence[int], out_cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Select + project one (s, o) table (Algorithm 2, device form).

    ``s_bound``/``o_bound`` are ``None`` (statically unbound) or an int32
    scalar — python int or traced value.  Passing bound constants as
    traced runtime values is what lets one compiled program serve every
    instantiation of a query template (constant re-binding)."""
    cap = rows.shape[0]
    keep = _valid_mask(cap, n)
    if s_bound is not None:
        keep &= rows[:, 0] == s_bound
    if o_bound is not None:
        keep &= rows[:, 1] == o_bound
    if same_var:
        keep &= rows[:, 0] == rows[:, 1]
    projected = rows[:, list(out_cols)] if out_cols else rows[:, :0]
    return _compact(projected, keep, out_cap)


def build_key(b: JBindings, key_col: int) -> jax.Array:
    """The build-side join-key column with NULL/pad sentinels applied —
    the input of the build-side sort.  Exposed so a batched program can
    presort a *shared* (bounds-independent) build relation once and reuse
    it for every batch element (see ``device_join``'s ``b_presorted``)."""
    kb = b.data[:, key_col]
    kb = jnp.where(kb == UNBOUND, B_NULL, kb)
    return jnp.where(_valid_mask(b.capacity, b.n), kb, B_SENT)


def device_scan_windowed(rows: jax.Array, n: jax.Array, s_bound,
                         out_cols: Sequence[int],
                         out_cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Bound-subject scan over a subject-sorted table: the matching rows
    are one contiguous window found by binary search and need no compact
    sort, so the cost is O(log T + out_cap) instead of the full-table
    mask-and-compact of :func:`device_scan` — the difference between a
    per-request scan and a per-batch-element scan being effectively free.
    PAD rows sort after every valid id, so the search never needs the
    valid count.  Only usable without an object post-filter: overflow is
    the raw window width vs ``out_cap``, which for a filtered scan would
    be conservative (a hub subject with a selective object filter would
    permanently inflate the step's capacity — callers route that case to
    :func:`device_scan`, which counts true matches)."""
    cap = rows.shape[0]
    col = rows[:, 0]
    sb = jnp.asarray(s_bound, dtype=jnp.int32)
    lo = jnp.searchsorted(col, sb, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(col, sb, side="right").astype(jnp.int32)
    idx = lo + jnp.arange(out_cap, dtype=jnp.int32)
    keep = idx < hi
    g = take_rows(rows, jnp.clip(idx, 0, cap - 1))
    projected = g[:, list(out_cols)] if out_cols else g[:, :0]
    data = jnp.where(keep[:, None], projected, PAD)
    return data, jnp.minimum(hi - lo, out_cap), hi - lo > out_cap


def _join_expand(a: JBindings, b: JBindings, out_cap: int,
                 b_presorted: Optional[Tuple[jax.Array, jax.Array]] = None):
    """Shared expansion machinery of the join family: pair every probe
    row with its build-side matches into ``out_cap`` output slots.

    Returns ``(out_cols, data, a_idx, valid, total, needs_compact)``:
    ``a_idx[j]`` is the probe row that produced slot ``j`` (the hook the
    left-outer join uses to compute its matched set), ``valid`` the
    kept-slot mask, ``total`` the true (uncapped) match count.  When
    ``needs_compact`` is False the valid slots are already contiguous at
    the front (``valid == j < total``)."""
    shared = [c for c in a.cols if c in b.cols]
    b_only = [c for c in b.cols if c not in a.cols]
    out_cols = a.cols + tuple(b_only)

    cap_a, cap_b = a.capacity, b.capacity
    if not shared:  # cross join (rare; bounded by caps)
        ii = jnp.arange(out_cap, dtype=jnp.int32)
        a_idx = jnp.clip(ii // jnp.maximum(b.n, 1), 0, cap_a - 1)
        b_idx = ii % jnp.maximum(b.n, 1)
        total = a.n * b.n
        valid = ii < total
        data = jnp.concatenate(
            [take_rows(a.data, a_idx),
             take_rows(b.data, jnp.clip(b_idx, 0, cap_b - 1))], axis=1)
        return out_cols, data, a_idx, valid, total, False

    ka = a.data[:, a.cols.index(shared[0])]
    ka = jnp.where(ka == UNBOUND, A_NULL, ka)
    ka = jnp.where(_valid_mask(cap_a, a.n), ka, A_SENT)
    if b_presorted is None:
        kb = build_key(b, b.cols.index(shared[0]))
        order_b = jnp.argsort(kb).astype(jnp.int32)
        kb_sorted = kb[order_b]
    else:
        order_b, kb_sorted = b_presorted
    lo = jnp.searchsorted(kb_sorted, ka, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(kb_sorted, ka, side="right").astype(jnp.int32)
    cnt = hi - lo
    prefix = prefix_sum(cnt) - cnt               # exclusive prefix
    total = prefix[-1] + cnt[-1]

    j = jnp.arange(out_cap, dtype=jnp.int32)
    # rank search: which probe row produced output slot j
    a_idx = jnp.searchsorted(prefix + cnt, j, side="right").astype(jnp.int32)
    a_idx = jnp.clip(a_idx, 0, cap_a - 1)
    off = j - prefix[a_idx]
    b_pos = jnp.clip(lo[a_idx] + off, 0, cap_b - 1).astype(jnp.int32)
    b_idx = order_b[b_pos]
    valid = j < total

    left = take_rows(a.data, a_idx)
    right = take_rows(b.data, b_idx)

    # post-filter shared columns beyond the key (SQL NULL semantics)
    for c in shared[1:]:
        va = left[:, a.cols.index(c)]
        vb = right[:, b.cols.index(c)]
        valid &= (va == vb) & (va != UNBOUND)

    pieces = [left]
    if b_only:
        pieces.append(right[:, [b.cols.index(c) for c in b_only]])
    data = jnp.concatenate(pieces, axis=1)
    return out_cols, data, a_idx, valid, total, bool(shared[1:])


def device_join(a: JBindings, b: JBindings, out_cap: int,
                b_presorted: Optional[Tuple[jax.Array, jax.Array]] = None
                ) -> JBindings:
    """Natural join of two static relations (sort-merge, rank expansion).

    ``b_presorted`` is an optional ``(order_b, kb_sorted)`` pair from
    :func:`build_key` + sort, letting callers hoist the O(n log n)
    build-side sort out of a batched program when ``b`` does not depend on
    the bound constants."""
    out_cols, data, _, valid, total, needs_compact = _join_expand(
        a, b, out_cap, b_presorted)
    if needs_compact:
        data, n, ovf = _compact(data, valid, out_cap)
    else:
        # matches are contiguous at j < total (cross join, or the
        # overwhelmingly common single-shared-variable star/chain case):
        # masking replaces the O(out_cap log out_cap) compact sort
        data = jnp.where(valid[:, None], data, PAD)
        n = jnp.minimum(total, out_cap).astype(jnp.int32)
        ovf = jnp.asarray(False)
    return JBindings(out_cols, data, n,
                     a.overflow | b.overflow | ovf | (total > out_cap))


def device_left_join(a: JBindings, b: JBindings, out_cap: int,
                     expr: Optional[FilterExpr] = None,
                     values: Optional[jax.Array] = None,
                     fconsts: Optional[jax.Array] = None,
                     ctr: Optional[List[int]] = None) -> JBindings:
    """OPTIONAL: left-outer join.  Inner rows first (probe-major, build
    rows in original order — the natural-join order), then each
    unmatched probe row once, UNBOUND-padded on the build-only columns,
    in probe order — exactly the eager ``left_outer_join`` sequence, so
    row-for-row parity with the host engines holds without a sort.

    ``expr`` is OPTIONAL's join condition: it filters the INNER rows
    only (a probe row whose matches all fail the condition comes out
    unmatched), with constants riding the shared runtime ``fconsts``
    vector like every other filter."""
    out_cols, data, a_idx, valid, total, _ = _join_expand(a, b, out_cap)
    cap_a = a.capacity
    if expr is not None:
        inner = JBindings(out_cols, data,
                          jnp.asarray(out_cap, jnp.int32), jnp.asarray(False))
        valid = valid & _filter_mask(expr, inner, values, fconsts, ctr)

    # matched set: scatter hit flags through a_idx (invalid slots are
    # routed to a dump slot so clipped indices cannot pollute the flags)
    hit = jnp.zeros((cap_a + 1,), bool) \
        .at[jnp.where(valid, a_idx, cap_a)].set(True)[:cap_a]
    unmatched = _valid_mask(cap_a, a.n) & ~hit

    k_b = len(out_cols) - len(a.cols)
    tail = a.data if not k_b else jnp.concatenate(
        [a.data, jnp.full((cap_a, k_b), UNBOUND, jnp.int32)], axis=1)
    buf = jnp.concatenate([data, tail], axis=0)
    keep = jnp.concatenate([valid, unmatched])
    out, n, ovf = _compact(buf, keep, out_cap)
    # total > out_cap also voids the matched-set computation (cut slots
    # never set their hit flag), so the overflow retry covers it
    return JBindings(out_cols, out, n,
                     a.overflow | b.overflow | ovf | (total > out_cap))


def device_union(a: JBindings, b: JBindings, out_cap: int) -> JBindings:
    """UNION: both operands lifted to the column union (UNBOUND fill),
    left rows first then right rows — the eager ``union`` sequence —
    via one stable compact over the concatenated buffers."""
    cols = a.cols + tuple(c for c in b.cols if c not in a.cols)

    def lift(x: JBindings) -> jax.Array:
        cap = x.capacity
        if not cols:
            return x.data[:, :0]
        arrs = [x.data[:, x.cols.index(c)] if c in x.cols
                else jnp.full((cap,), UNBOUND, jnp.int32) for c in cols]
        d = jnp.stack(arrs, axis=1)
        return jnp.where(_valid_mask(cap, x.n)[:, None], d, PAD)

    buf = jnp.concatenate([lift(a), lift(b)], axis=0)
    keep = jnp.concatenate([_valid_mask(a.capacity, a.n),
                            _valid_mask(b.capacity, b.n)])
    data, n, ovf = _compact(buf, keep, out_cap)
    return JBindings(cols, data, n, a.overflow | b.overflow | ovf)


def device_scan_tt(rows: jax.Array, n: jax.Array, s_bound, p_bound, o_bound,
                   eqs: Sequence[Tuple[int, int]], take: Sequence[int],
                   out_cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Select + project over the (N, 3) triples table — the unbound-
    predicate scan (and the ``layout="tt"`` baseline scan).  Bound s/o
    constants are runtime scalars like :func:`device_scan`'s; the bound
    predicate of a TT-layout scan is trace-time static (predicates are
    plan identity and never template-rebindable).  ``eqs`` carries the
    repeated-variable equality selections of patterns like ``?x ?p ?x``."""
    cap = rows.shape[0]
    keep = _valid_mask(cap, n)
    if s_bound is not None:
        keep &= rows[:, 0] == s_bound
    if p_bound is not None:
        keep &= rows[:, 1] == p_bound
    if o_bound is not None:
        keep &= rows[:, 2] == o_bound
    for i, j in eqs:
        keep &= rows[:, i] == rows[:, j]
    projected = rows[:, list(take)] if take else rows[:, :0]
    return _compact(projected, keep, out_cap)


# ---------------------------------------------------------------------------
# Solution modifiers on device (the spine of repro.core.modifiers)
#
# All five operators keep the JBindings invariant — valid rows occupy
# [0, n) contiguously with PAD rows behind — and none can overflow (a
# modifier never grows the relation), so the per-step overflow/retry
# protocol of the scan/join pipeline is untouched.
# ---------------------------------------------------------------------------

def _filter_operand(b: JBindings, values: jax.Array, term, numeric: bool,
                    fconsts: jax.Array, ctr: List[int]):
    """(ids, numeric (hi, lo) key pair) for one comparison operand.
    Constant ids are *runtime* scalars read from ``fconsts`` (slot order
    fixed by :func:`repro.core.modifiers.filter_const_slots`), so
    re-binding a template constant never re-traces; float literals are
    trace-time constants (they are part of the template text).  A
    variable the relation does not bind is UNBOUND everywhere — the
    eager ``_operand`` semantics, which OPTIONAL/UNION columns rely on."""
    cap = b.capacity
    nv = values.shape[0]
    dt = values.dtype
    if isinstance(term, str):            # variable
        if term in b.cols:
            ids = b.data[:, b.cols.index(term)]
        else:
            ids = jnp.full((cap,), UNBOUND, jnp.int32)
        if not numeric:
            return ids, None
        if nv:
            safe = jnp.clip(ids, 0, nv - 1)
            ok = ids >= 0
            hi = jnp.where(ok, values[safe, 0], jnp.nan)
            lo = jnp.where(ok, values[safe, 1], jnp.nan)
        else:
            hi = jnp.full((cap,), jnp.nan, dt)
            lo = hi
        return ids, (hi, lo)
    if isinstance(term, float):          # numeric literal (trace-time)
        fhi, flo = _split_scalar(term)
        return None, (jnp.full((cap,), fhi, dt), jnp.full((cap,), flo, dt))
    tid = fconsts[ctr[0]]                # constant id -> runtime slot
    ctr[0] += 1
    ids = jnp.full((cap,), tid, jnp.int32)
    if not numeric:
        return ids, None
    if nv:
        ok = (tid >= 0) & (tid < nv)
        safe = jnp.clip(tid, 0, nv - 1)
        hi = jnp.where(ok, values[safe, 0], jnp.nan)
        lo = jnp.where(ok, values[safe, 1], jnp.nan)
    else:
        hi = jnp.asarray(jnp.nan, dt)
        lo = hi
    return ids, (jnp.full((cap,), hi, dt), jnp.full((cap,), lo, dt))


def _filter_mask(expr: FilterExpr, b: JBindings, values: jax.Array,
                 fconsts: jax.Array, ctr: List[int]) -> jax.Array:
    """Boolean keep-mask over the relation's rows; mirrors the eager
    :func:`repro.core.executor.eval_filter` semantics exactly (identity
    comparison on ids, numeric comparison through the dictionary's
    double-single key pairs, UNBOUND/type-error rows dropped).  NaN key
    pairs make every comparison false, matching host NaN semantics."""
    if isinstance(expr, BoolOp):
        masks = [_filter_mask(e, b, values, fconsts, ctr) for e in expr.args]
        out = masks[0]
        for m in masks[1:]:
            out = (out & m) if expr.op == "&&" else (out | m)
        return out
    if isinstance(expr, NotExpr):
        return ~_filter_mask(expr.arg, b, values, fconsts, ctr)
    if isinstance(expr, Bound):
        if expr.var not in b.cols:
            return jnp.zeros((b.capacity,), bool)
        return b.data[:, b.cols.index(expr.var)] != UNBOUND
    assert isinstance(expr, Cmp)
    numeric = expr.op in ("<", "<=", ">", ">=") or \
        isinstance(expr.lhs, float) or isinstance(expr.rhs, float)
    lid, lpair = _filter_operand(b, values, expr.lhs, numeric, fconsts, ctr)
    rid, rpair = _filter_operand(b, values, expr.rhs, numeric, fconsts, ctr)
    if numeric:
        lhi, llo = lpair
        rhi, rlo = rpair
        eq = (lhi == rhi) & (llo == rlo)
        lt = (lhi < rhi) | ((lhi == rhi) & (llo < rlo))
        if expr.op == "=":
            return eq
        if expr.op == "!=":
            return ~eq & ~jnp.isnan(lhi) & ~jnp.isnan(rhi)
        if expr.op == "<":
            return lt
        if expr.op == "<=":
            return lt | eq
        if expr.op == ">":
            return ~(lt | eq) & ~jnp.isnan(lhi) & ~jnp.isnan(rhi)
        return ~lt & ~jnp.isnan(lhi) & ~jnp.isnan(rhi)
    ok = (lid != UNBOUND) & (rid != UNBOUND)
    return ((lid == rid) if expr.op == "=" else (lid != rid)) & ok


def device_filter(b: JBindings, expr: FilterExpr, values: jax.Array,
                  fconsts: jax.Array, ctr: List[int]) -> JBindings:
    """FILTER: mask + stable compact (kept rows stay in order)."""
    keep = _filter_mask(expr, b, values, fconsts, ctr) & \
        _valid_mask(b.capacity, b.n)
    data, n, _ = _compact(b.data, keep, b.capacity)
    return JBindings(b.cols, data, n, b.overflow)


def device_project(b: JBindings, out_vars: Sequence[str]) -> JBindings:
    """Projection: gather the selected columns (UNBOUND-fill variables
    the pipeline does not produce), re-PAD invalid rows."""
    cap = b.capacity
    if not out_vars:
        return JBindings((), b.data[:, :0], b.n, b.overflow)
    cols = [b.data[:, b.cols.index(v)] if v in b.cols
            else jnp.full((cap,), UNBOUND, jnp.int32) for v in out_vars]
    data = jnp.stack(cols, axis=1)
    data = jnp.where(_valid_mask(cap, b.n)[:, None], data, PAD)
    return JBindings(tuple(out_vars), data, b.n, b.overflow)


def device_resize(b: JBindings, out_cap: int
                  ) -> Tuple[JBindings, jax.Array]:
    """Re-buffer the relation to ``out_cap`` rows — a pure static
    truncation (valid rows are contiguous at the front by the pipeline
    invariant, so no sort/gather is needed).  Returns the relation and
    an overflow flag for the retry protocol: DISTINCT/ORDER BY sort this
    buffer, so right-sizing it is what keeps modifier queries from
    paying an O(join_cap log join_cap) sort over mostly-PAD rows."""
    cap, k = b.data.shape
    if out_cap < cap:
        data = b.data[:out_cap]
    elif out_cap > cap:
        data = jnp.concatenate(
            [b.data, jnp.full((out_cap - cap, k), PAD, b.data.dtype)], axis=0)
    else:
        data = b.data
    ovf = b.n > out_cap
    return JBindings(b.cols, data, jnp.minimum(b.n, out_cap),
                     b.overflow), ovf


def device_distinct(b: JBindings) -> JBindings:
    """DISTINCT: lexsort + adjacent-unique to find duplicates, then a
    stable compact of the FIRST occurrence of each distinct row in the
    original order — exactly the eager engine's first-occurrence-stable
    dedup, so an order established before (or after) it survives."""
    cap, k = b.data.shape
    if k == 0:   # zero-column relation: dedup of n empty mappings is one
        return JBindings(b.cols, b.data, jnp.minimum(b.n, 1), b.overflow)
    valid = _valid_mask(cap, b.n)
    keys = [b.data[:, j] for j in range(k - 1, -1, -1)]
    keys.append((~valid).astype(jnp.int32))        # valid rows first
    order = jnp.lexsort(keys)
    sdata = take_rows(b.data, order)
    svalid = valid[order]
    same_prev = jnp.concatenate([
        jnp.zeros((1,), bool),
        jnp.all(sdata[1:] == sdata[:-1], axis=1)])
    keep_sorted = svalid & ~same_prev
    keep = jnp.zeros(cap, bool).at[order].set(keep_sorted)
    data, n, _ = _compact(b.data, keep, cap)
    return JBindings(b.cols, data, n, b.overflow)


def device_order(b: JBindings, keys: Sequence[Tuple[str, bool]],
                 values: jax.Array) -> JBindings:
    """ORDER BY: stable lexsort over the dictionary's double-single
    ``(ord_hi, ord_lo)`` key pairs (numeric literals by value, other
    terms by id — the eager ``order_rows`` semantics); UNBOUND sorts
    last (SQL NULLS LAST, shared by all engines); PAD rows keep sorting
    behind every valid row."""
    cap = b.capacity
    valid = _valid_mask(cap, b.n)
    nv = values.shape[0]
    dt = values.dtype
    ks = []
    for var, asc in reversed(tuple(keys)):
        if var not in b.cols:
            continue                      # unbound key: constant, no-op
        ids = b.data[:, b.cols.index(var)]
        if nv:
            safe = jnp.clip(ids, 0, nv - 1)
            ok = ids >= 0
            hi = jnp.where(ok, values[safe, 2], ids.astype(dt))
            lo = jnp.where(ok, values[safe, 3], jnp.zeros((cap,), dt))
        else:
            hi = ids.astype(dt)
            lo = jnp.zeros((cap,), dt)
        hi = jnp.where(ids == UNBOUND, jnp.asarray(jnp.inf, dt), hi)
        if not asc:
            hi, lo = -hi, -lo
        ks.append(lo)                     # minor half of the pair first
        ks.append(hi)                     # lexsort: later keys dominate
    if not ks:
        return b
    ks.append((~valid).astype(jnp.int32))          # valid rows first
    order = jnp.lexsort(ks)
    return JBindings(b.cols, take_rows(b.data, order), b.n, b.overflow)


def device_slice(b: JBindings, offset: int, limit: Optional[int]) -> JBindings:
    """OFFSET/LIMIT: static row-window over the compacted relation.  A
    LIMIT below the buffer capacity also *trims the buffer*, so only the
    final ≤ limit rows ever transfer back to the host."""
    cap, k = b.data.shape
    data, n = b.data, b.n
    if offset:
        shift = min(int(offset), cap)
        data = jnp.concatenate(
            [data[shift:], jnp.full((shift, k), PAD, data.dtype)], axis=0)
        n = jnp.maximum(n - offset, 0)
    if limit is not None:
        n = jnp.minimum(n, limit)
        if limit < cap:
            data = data[:max(int(limit), 0)]
    return JBindings(b.cols, data, n, b.overflow)


# ---------------------------------------------------------------------------
# Plan executor
# ---------------------------------------------------------------------------

def _step_meta(step: ScanStep) -> Tuple[Optional[int], Optional[int], bool,
                                        Tuple[int, ...], Tuple[str, ...]]:
    tp = step.tp
    s_bound = None if is_var(tp.s) else int(tp.s)
    o_bound = None if is_var(tp.o) else int(tp.o)
    same = is_var(tp.s) and is_var(tp.o) and tp.s == tp.o
    cols: List[str] = []
    take: List[int] = []
    if is_var(tp.s):
        cols.append(tp.s)
        take.append(0)
    if is_var(tp.o) and tp.o not in cols:
        cols.append(tp.o)
        take.append(1)
    return s_bound, o_bound, same, tuple(take), tuple(cols)


def _tt_meta(tp) -> Tuple[Optional[int], Optional[int], Optional[int],
                          Tuple[Tuple[int, int], ...], Tuple[int, ...],
                          Tuple[str, ...]]:
    """Static scan metadata of a triples-table step: per-position bound
    constants (presence is static; s/o VALUES ride the runtime bounds
    array, the predicate is trace-time static), repeated-variable
    equality selections, and the projected (s, p, o)-first-seen columns
    — the eager ``_scan_tt`` layout."""
    terms = (tp.s, tp.p, tp.o)
    s_b, p_b, o_b = (None if is_var(t) else int(t) for t in terms)
    cols: List[str] = []
    take: List[int] = []
    eqs: List[Tuple[int, int]] = []
    first: Dict[str, int] = {}
    for i, t in enumerate(terms):
        if not is_var(t):
            continue
        if t in first:
            eqs.append((first[t], i))
        else:
            first[t] = i
            cols.append(t)
            take.append(i)
    return s_b, p_b, o_b, tuple(eqs), tuple(take), tuple(cols)


def _step_cols(step: ScanStep) -> Tuple[str, ...]:
    if step.uses_tt:
        return _tt_meta(step.tp)[5]
    return _step_meta(step)[4]


def _bgp_segs(seg: CoreSeg):
    if isinstance(seg, BGPSeg):
        yield seg
    elif isinstance(seg, FilterSeg):
        yield from _bgp_segs(seg.child)
    elif isinstance(seg, CombineSeg):
        yield from _bgp_segs(seg.left)
        yield from _bgp_segs(seg.right)


def _presorted_build_scans(root: CoreSeg) -> Tuple[Dict[int, str], set]:
    """Join scans whose rows already come out in join-key order, so the
    build side needs no sort (a sort of a 10^5-row or larger buffer
    costs the TPU compiler ~20 s per program).  Tables are (s, o)-sorted:
    a scan keyed on its subject, or on its object under a bound subject,
    is in key order as stored; one keyed on its object otherwise reads
    the (o, s)-sorted copy.  Either order is exactly what the stable
    build-side sort would produce, so results are unchanged.

    Returns ({flat step: join key}, {flat steps that read the o-sorted
    copy})."""
    keys: Dict[int, str] = {}
    by_o = set()
    for seg in _bgp_segs(root):
        acc: List[str] = []
        for k, step in enumerate(seg.plan.steps):
            cols = _step_cols(step)
            key = next((c for c in acc if c in cols), None)
            tp = step.tp
            if k and key is not None and not step.uses_tt:
                if key == tp.o and is_var(tp.s) and tp.s != tp.o:
                    by_o.add(seg.start + k)
                keys[seg.start + k] = key
            acc.extend(c for c in cols if c not in acc)
    return keys, by_o


_TRACE_COUNT = 0   # program traces (== XLA compiles); test probe


def trace_count() -> int:
    """Number of static programs traced so far in this process.  A served
    template workload should increase this once per (template, caps), not
    once per request — the observable for "no recompilation on re-bind"."""
    return _TRACE_COUNT


def bounds_from_plan(plan: Plan) -> np.ndarray:
    """Per-step (s, o) bound-constant values, UNBOUND where the slot is a
    variable — the runtime argument vector of the compiled program."""
    out = np.full((len(plan.steps), 2), UNBOUND, dtype=np.int32)
    for i, step in enumerate(plan.steps):
        if not is_var(step.tp.s):
            out[i, 0] = int(step.tp.s)
        if not is_var(step.tp.o):
            out[i, 1] = int(step.tp.o)
    return out


def _pipeline_cols(plan: Plan) -> Tuple[str, ...]:
    """Variables the scan/join pipeline produces, first-seen order."""
    cols: List[str] = []
    for step in plan.steps:
        for v in _step_cols(step):
            if v not in cols:
                cols.append(v)
    return tuple(cols)


def _exec_cols(seg: CoreSeg) -> Tuple[str, ...]:
    """Columns the device evaluation of a segment produces, in pipeline
    order (scan order within a BGP; left-then-right-only for combines —
    the same construction the eager tree evaluation uses)."""
    if isinstance(seg, EmptySeg):
        return tuple(seg.vars)
    if isinstance(seg, BGPSeg):
        return _pipeline_cols(seg.plan)
    if isinstance(seg, FilterSeg):
        return _exec_cols(seg.child)
    left = _exec_cols(seg.left)
    return left + tuple(c for c in _exec_cols(seg.right) if c not in left)


def _mod_cap_seed(spine: ModifierSpine, pipeline_cap: int) -> int:
    """Initial capacity of the modifier resize slot: generous around the
    slice window when there is one, a modest constant otherwise; never
    beyond the pipeline buffer (more rows cannot exist) and never below
    1/32 of it, so the overflow-retry loop reaches any true result size
    within its doubling budget."""
    if spine.limit is not None:
        est = max(64, 4 * (spine.offset + spine.limit))
    else:
        est = 4096
    est = max(est, pipeline_cap // 32)
    return min(round_up_pow2(est, 64), round_up_pow2(pipeline_cap, 64))


def bound_scan_seed(step: ScanStep, catalog: Catalog, size: float) -> float:
    """Capacity seed of a scan with a bound subject or object: the rows
    a data-distributed constant is expected to match
    (:func:`repro.core.estimate.scan_estimate`, from the catalog's
    distinct-count and skew statistics) and never below 1% of the table.
    A low seed costs one recompile per doubling on the chip."""
    seed = size * 0.01
    if estimate.supports(catalog):
        seed = max(seed, estimate.scan_estimate(step, catalog)[0])
    return max(1.0, seed)


#: ceiling of a statistics-derived capacity seed (rows); beyond it the
#: overflow retries grow the slot from the measured result
MAX_ESTIMATE_SEED = float(1 << 24)


def join_estimates(steps: Sequence[ScanStep], catalog: Catalog
                   ) -> List[float]:
    """Per-step intermediate rows of a left-deep scan/join pipeline from
    the catalog's statistics (:func:`repro.core.estimate.estimate_order`),
    0 where there are none.  A chain whose fan-out the 1.25-per-join
    rule misses (WatDiv C1 grows ~500-fold) otherwise pays one overflow
    retry — one recompile on the chip — per doubling."""
    est = estimate.estimate_order(steps, catalog) if steps else None
    if est is None:
        return [0.0] * len(steps)
    return [min(e.rows, MAX_ESTIMATE_SEED) for e in est]


def double_caps(caps: Tuple[int, ...], ovf, n_steps: int) -> Tuple[int, ...]:
    """One overflow-retry step: double every overflowing capacity.  The
    modifier resize slot (index ``n_steps``, when present) additionally
    keeps pace with the pipeline caps — its overflow flag only fires
    once the pipeline actually delivers more rows, so without the floor
    the two growth phases would run in series and could exhaust the
    retry budget on explosive joins."""
    new = [c * 2 if ovf[i] else c for i, c in enumerate(caps)]
    if len(new) > n_steps and n_steps:
        pipe_max = max(new[:n_steps])
        new[n_steps] = min(max(new[n_steps], pipe_max // 4),
                           round_up_pow2(pipe_max, 64))
    return tuple(new)


def _spine_uses_values(spine: ModifierSpine) -> bool:
    """True when the compiled spine reads the numeric key table:
    ORDER BY keys, or any filter comparison that is numeric (order ops,
    or a float literal operand).  Identity-only filters don't."""
    return bool(spine.order) or _exprs_use_values(spine.filters)


def check_spine(spine: ModifierSpine, pipe_cols: Tuple[str, ...],
                catalog: Optional[Catalog] = None) -> Tuple[str, ...]:
    """Output columns of a spine over a pipeline binding ``pipe_cols``.

    Historically this also rejected filter variables outside the
    pipeline and non-float32-exact value tables; both limits are gone —
    missing filter variables are UNBOUND everywhere (the eager
    semantics) and numeric keys use exact double-single float32 pairs
    (validated by :func:`prepare_value_keys`, which still raises the
    backends' NotImplementedError fallback signal for tables whose keys
    the pair encoding cannot distinguish)."""
    return tuple(spine.project) if spine.project is not None else pipe_cols


class PlanExecutor:
    """Builds and runs the jitted static program for a compiled core.

    Accepts either a flat :class:`Plan` (a single BGP — the historical
    construction, still used directly by tests and benchmarks) or a
    :class:`CorePlan` segment tree covering FILTER/OPTIONAL/UNION cores
    and unbound-predicate (TT) scans.

    ``caps[i]`` for ``i < len(plan.steps)`` bounds the output of flat
    step i within its BGP segment (a segment's first step compacts to
    its cap; joins within the segment write at the following caps);
    combine segments (join/left/union) get their own capacity slots
    behind the flat steps, in evaluation (post-) order.  ``run`` retries
    with doubled caps on overflow (host loop, geometric — at most
    ~log2(result/estimate) recompiles, amortized across a served
    workload).

    Bound s/o constants enter the program as runtime int32 scalars (their
    *presence* is static, their values are not), so every instantiation of
    a query template shares one compiled program — ``run(bounds=...)``
    re-binds without re-tracing.

    ``spine`` appends the query's solution modifiers to the traced
    program (FILTER masks, on-device projection, sort-based DISTINCT,
    value-table ORDER BY, static OFFSET/LIMIT window); filter constants —
    the spine's AND the core's (OPTIONAL conditions, FILTER segments) —
    share one runtime ``fconsts`` input consumed in evaluation order, so
    modifier-bearing templates re-bind without re-tracing too.
    """

    bounds_from_plan = staticmethod(bounds_from_plan)

    def __init__(self, plan, catalog: Catalog, slack: float = 1.5,
                 spine: Optional[ModifierSpine] = None):
        if isinstance(plan, CorePlan):
            core = plan
        else:
            core = CorePlan(root=BGPSeg(plan=plan, start=0), flat=plan,
                            empty=plan.empty, vars=plan.vars)
        if core.empty:
            raise ValueError("cannot build executor for statistics-empty plan")
        self.core = core
        self.plan = core.flat      # what template re-binding operates on
        self.catalog = catalog
        self.spine = spine if spine is not None else ModifierSpine()
        self._pipe_cols = _exec_cols(core.root)
        self._out_vars = check_spine(self.spine, self._pipe_cols, catalog)
        self._core_filters = core_filter_exprs(core.root)
        self._all_filters = tuple(self._core_filters) + \
            tuple(self.spine.filters)
        self.filter_slots = filter_const_slots(self._all_filters)
        # raises NotImplementedError (→ counted eager fallback) only for
        # dictionaries whose numeric keys defeat the double-single pairs
        self._value_keys = prepare_value_keys(catalog, self.spine,
                                              self._all_filters)
        # DISTINCT/ORDER BY sort the whole static buffer; the join caps
        # are sized for the worst unfiltered join, which would make every
        # modifier query pay an O(cap log cap) sort over mostly-PAD rows.
        # Instead the spine starts from its own small capacity slot (an
        # overflow-checked compact before the sorts, appended to ``caps``
        # so the retry protocol grows it geometrically when a template's
        # true result is larger — and the grown cap persists).
        self._mod_resize = bool(self.spine.distinct or self.spine.order)
        self.tables = [
            None if step.uses_tt
            else catalog.table(step.kind, int(step.tp.p), step.p2)
            for step in self.plan.steps]
        self._has_tt = any(s.uses_tt for s in self.plan.steps)
        self._join_key, self._by_o = _presorted_build_scans(core.root)
        n_flat = len(self.plan.steps)
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
                    size = catalog.n_triples if step.uses_tt \
                        else len(self.tables[i])
                    scan_est = max(1.0, float(size))
                    if step.tp.n_bound() > 1:
                        scan_est = bound_scan_seed(step, catalog, size)
                    est = scan_est if k == 0 else \
                        max(est, scan_est, est * 1.25, joined[k])
                    flat_caps[i] = round_up_pow2(int(est * slack) + 8, 16)
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
            comb_caps.append(round_up_pow2(int(est * slack) + 8, 16))
            return est

        seed(core.root)
        self.caps = flat_caps + comb_caps
        self._n_pipeline = len(self.caps)
        if self._mod_resize:
            pipe_cap = max(self.caps) if self.caps else 64
            self.caps.append(_mod_cap_seed(self.spine, pipe_cap))
        self._default_bounds = bounds_from_plan(self.plan)

    def fconsts_from_mapping(self, mapping=None) -> np.ndarray:
        """Runtime filter-constant vector for one binding: template
        placeholder ids resolve through ``mapping``, concrete ids pass
        through — the filter counterpart of ``bounds_from_plan``."""
        m = mapping or {}
        return np.asarray([m.get(c, c) for c in self.filter_slots],
                          dtype=np.int32)

    def _apply_spine(self, b: JBindings, values: jax.Array,
                     fconsts: jax.Array, caps: Tuple[int, ...],
                     ctr: List[int]) -> Tuple[JBindings, Optional[jax.Array]]:
        """FILTER* → [resize] → ORDER BY → project → DISTINCT →
        OFFSET/LIMIT, the canonical host sequence lowered onto the
        static relation (ordering precedes projection so sort keys
        outside the SELECT list work, exactly like the host engines).
        ``ctr`` is the fconsts cursor, shared with the core's filters
        (which consume their slots first).  Returns the relation and the
        resize step's overflow flag (None when the spine needs no
        sorts)."""
        sp = self.spine
        for expr in sp.filters:
            b = device_filter(b, expr, values, fconsts, ctr)
        mod_ovf = None
        if self._mod_resize:
            b, mod_ovf = device_resize(b, caps[self._n_pipeline])
        if sp.order:
            b = device_order(b, sp.order, values)
        b = device_project(b, self._out_vars)
        if sp.distinct:
            b = device_distinct(b)
        if sp.has_slice:
            b = device_slice(b, sp.offset, sp.limit)
        return b, mod_ovf

    # -- the traced program --------------------------------------------------
    def _scan_step(self, i: int, step: ScanStep, first: bool,
                   table_rows: List[jax.Array], table_ns: List[jax.Array],
                   tt_rows: jax.Array, tt_n: jax.Array, bounds: jax.Array,
                   caps: Tuple[int, ...]) -> JBindings:
        """One scan, picking the windowed form when the subject is bound
        (tables are subject-sorted, see :class:`repro.core.table.Table`);
        TT steps (unbound predicates, ``layout="tt"``) scan the shared
        padded triples table.  ``first`` marks the first step of a BGP
        segment, which compacts to its own capacity slot."""
        if step.uses_tt:
            s_b, p_b, o_b, eqs, take, cols = _tt_meta(step.tp)
            out_cap = caps[i] if first else tt_rows.shape[0]
            sb = bounds[i, 0] if s_b is not None else None
            ob = bounds[i, 1] if o_b is not None else None
            data, n, ovf = device_scan_tt(tt_rows, tt_n, sb, p_b, ob,
                                          eqs, take, out_cap)
            return JBindings(cols, data, n, ovf)
        s_bound, o_bound, same, take, cols = _step_meta(step)
        out_cap = caps[i] if first else table_rows[i].shape[0]
        sb = bounds[i, 0] if s_bound is not None else None
        ob = bounds[i, 1] if o_bound is not None else None
        if s_bound is not None and o_bound is None:
            data, n, ovf = device_scan_windowed(table_rows[i], table_ns[i],
                                                sb, take, out_cap)
        else:
            data, n, ovf = device_scan(table_rows[i], table_ns[i], sb, ob,
                                       same, take, out_cap)
        return JBindings(cols, data, n, ovf)

    def _presorted(self, i: int, cur: JBindings
                   ) -> Optional[Tuple[jax.Array, jax.Array]]:
        """``device_join``'s ``b_presorted`` for a build scan already in
        join-key order (:func:`_presorted_build_scans`), else None."""
        key = self._join_key.get(i)
        if key is None:
            return None
        return (jnp.arange(cur.capacity, dtype=jnp.int32),
                build_key(cur, cur.cols.index(key)))

    def _compose_bgp(self, seg: BGPSeg, caps: Tuple[int, ...],
                     table_rows: List[jax.Array], table_ns: List[jax.Array],
                     tt_rows: jax.Array, tt_n: jax.Array, bounds: jax.Array,
                     ovfs: List[jax.Array],
                     shared: Dict[int, Tuple[JBindings, Optional[Tuple[jax.Array, jax.Array]]]]
                     ) -> JBindings:
        """The scan/join pipeline of one BGP segment.  Overflow is
        recorded PER STEP into ``ovfs`` (at the step's flat index) so
        the host retry doubles only the capacities that actually
        overflowed — wholesale doubling let one heavy constant inflate
        every buffer of the program, which is poison for batched serving
        (all batch elements pay the worst element's caps).  ``shared``
        maps flat step index -> precomputed (relation, presorted join
        key) for bounds-independent scans (empty for the single-request
        program)."""
        no = jnp.asarray(False)
        if not seg.plan.steps:
            # empty BGP: the unit relation (one empty solution mapping)
            return JBindings((), jnp.zeros((8, 0), jnp.int32),
                             jnp.asarray(1, jnp.int32), no)
        acc: Optional[JBindings] = None
        for k, step in enumerate(seg.plan.steps):
            i = seg.start + k
            if i in shared:
                cur, pre = shared[i]
            else:
                cur = self._scan_step(i, step, k == 0, table_rows, table_ns,
                                      tt_rows, tt_n, bounds, caps)
                pre = self._presorted(i, cur)
            if acc is None:
                acc = cur
                ovfs[i] = cur.overflow
            else:
                # strip sticky input flags: we want this join's OWN overflow
                joined = device_join(
                    JBindings(acc.cols, acc.data, acc.n, no),
                    JBindings(cur.cols, cur.data, cur.n, no), caps[i],
                    b_presorted=pre)
                ovfs[i] = joined.overflow | cur.overflow
                acc = joined
        assert acc is not None
        return JBindings(acc.cols, acc.data, acc.n, no)

    def _eval_seg(self, seg: CoreSeg, caps: Tuple[int, ...],
                  table_rows: List[jax.Array], table_ns: List[jax.Array],
                  tt_rows: jax.Array, tt_n: jax.Array, bounds: jax.Array,
                  fconsts: jax.Array, values: jax.Array, ctr: List[int],
                  ovfs: List[jax.Array],
                  shared: Dict[int, Tuple[JBindings, Optional[Tuple[jax.Array, jax.Array]]]]
                  ) -> JBindings:
        """Evaluate the core segment tree to one static relation.  Each
        combine writes its own overflow flag at its capacity index;
        child flags are recorded at the children, so every returned
        relation carries a clean (False) sticky flag."""
        no = jnp.asarray(False)
        if isinstance(seg, EmptySeg):
            k = len(seg.vars)
            return JBindings(tuple(seg.vars),
                             jnp.full((8, k), PAD, jnp.int32),
                             jnp.asarray(0, jnp.int32), no)
        if isinstance(seg, BGPSeg):
            return self._compose_bgp(seg, caps, table_rows, table_ns,
                                     tt_rows, tt_n, bounds, ovfs, shared)
        if isinstance(seg, FilterSeg):
            b = self._eval_seg(seg.child, caps, table_rows, table_ns,
                               tt_rows, tt_n, bounds, fconsts, values, ctr,
                               ovfs, shared)
            return device_filter(b, seg.expr, values, fconsts, ctr)
        left = self._eval_seg(seg.left, caps, table_rows, table_ns,
                              tt_rows, tt_n, bounds, fconsts, values, ctr,
                              ovfs, shared)
        right = self._eval_seg(seg.right, caps, table_rows, table_ns,
                               tt_rows, tt_n, bounds, fconsts, values, ctr,
                               ovfs, shared)
        ci = self._comb_index[id(seg)]
        if seg.kind == "join":
            out = device_join(left, right, caps[ci])
        elif seg.kind == "left":
            out = device_left_join(left, right, caps[ci], seg.expr,
                                   values, fconsts, ctr)
        else:
            out = device_union(left, right, caps[ci])
        ovfs[ci] = out.overflow
        return JBindings(out.cols, out.data, out.n, no)

    def _program(self, caps: Tuple[int, ...], table_rows: List[jax.Array],
                 table_ns: List[jax.Array], tt_rows: jax.Array,
                 tt_n: jax.Array, bounds: jax.Array, fconsts: jax.Array,
                 values: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
        global _TRACE_COUNT
        _TRACE_COUNT += 1
        ctr = [0]
        ovfs: List[jax.Array] = [jnp.asarray(False)] * self._n_pipeline
        b = self._eval_seg(self.core.root, caps, table_rows, table_ns,
                           tt_rows, tt_n, bounds, fconsts, values, ctr,
                           ovfs, {})
        b, mod_ovf = self._apply_spine(b, values, fconsts, caps, ctr)
        stacked = jnp.stack(ovfs) if ovfs else jnp.zeros((0,), bool)
        if mod_ovf is not None:
            stacked = jnp.concatenate([stacked, mod_ovf[None]])
        return b.data, b.n, stacked

    @functools.cached_property
    def _device_inputs(self) -> Tuple[List[jax.Array], List[jax.Array],
                                      jax.Array, jax.Array, jax.Array]:
        """Device-resident padded tables + the (optional) padded triples
        table + the numeric key table, uploaded ONCE per executor — the
        hot path must not re-pad and re-transfer O(table) bytes on every
        launch."""
        rows = [jnp.zeros((0, 2), jnp.int32) if t is None
                else jnp.asarray(pad_rows(
                    t.rows_by_o if i in self._by_o else t.rows,
                    round_up_pow2(len(t))))
                for i, t in enumerate(self.tables)]
        ns = [jnp.asarray(np.int32(0 if t is None else len(t)))
              for t in self.tables]
        if self._has_tt:
            tt = np.asarray(self.catalog.tt, dtype=np.int32)
            tt_rows = jnp.asarray(
                pad_rows(tt, round_up_pow2(max(len(tt), 1))))
            tt_n = jnp.asarray(np.int32(len(tt)))
        else:
            tt_rows = jnp.zeros((0, 3), jnp.int32)
            tt_n = jnp.asarray(np.int32(0))
        values = jnp.asarray(self._value_keys)
        return rows, ns, tt_rows, tt_n, values

    @functools.cached_property
    def _jitted(self):
        return jax.jit(self._program, static_argnums=(0,))

    # -- the batched traced program --------------------------------------------
    def _program_batched(self, caps: Tuple[int, ...],
                         table_rows: List[jax.Array],
                         table_ns: List[jax.Array], tt_rows: jax.Array,
                         tt_n: jax.Array, bounds_b: jax.Array,
                         fconsts_b: jax.Array, values: jax.Array
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """B constant-bindings of the template in one program.

        Constants only enter scan *selection values*, so any step whose
        triple pattern binds no constant produces the same relation for
        every batch element.  Those scans — and the build-side sort of
        the joins that consume them — are hoisted OUT of the per-binding
        loop (per BGP segment) and computed once per launch; only the
        constant-dependent scans and the (capacity-bounded, small)
        probe/expand/combine phases replicate per element.  This is what
        makes a batch ~O(shared + B·small) instead of B times the full
        per-request program.
        """
        global _TRACE_COUNT
        _TRACE_COUNT += 1

        # shared phase: bounds-independent scans + their join-key presort
        shared: Dict[int, Tuple[JBindings, Optional[Tuple[jax.Array, jax.Array]]]] = {}

        def hoist(seg: CoreSeg) -> None:
            if isinstance(seg, FilterSeg):
                hoist(seg.child)
                return
            if isinstance(seg, CombineSeg):
                hoist(seg.left)
                hoist(seg.right)
                return
            if not isinstance(seg, BGPSeg):
                return
            acc_cols: List[str] = []
            for k, step in enumerate(seg.plan.steps):
                i = seg.start + k
                if step.uses_tt:
                    s_b, p_b, o_b, eqs, take, cols = _tt_meta(step.tp)
                    indep = k > 0 and s_b is None and o_b is None
                    if indep:
                        data, n, ovf = device_scan_tt(
                            tt_rows, tt_n, None, p_b, None, eqs, take,
                            tt_rows.shape[0])
                        cur = JBindings(cols, data, n, ovf)
                else:
                    s_bound, o_bound, same, take, cols = _step_meta(step)
                    indep = k > 0 and s_bound is None and o_bound is None
                    if indep:
                        data, n, ovf = device_scan(
                            table_rows[i], table_ns[i], None, None, same,
                            take, table_rows[i].shape[0])
                        cur = JBindings(cols, data, n, ovf)
                if indep:
                    # the join key device_join will pick: first
                    # accumulated column present on the build side
                    key = next((c for c in acc_cols if c in cols), None)
                    pre = self._presorted(i, cur)
                    if pre is None and key is not None:
                        kb = build_key(cur, cols.index(key))
                        order_b = jnp.argsort(kb).astype(jnp.int32)
                        pre = (order_b, kb[order_b])
                    shared[i] = (cur, pre)
                for c in cols:
                    if c not in acc_cols:
                        acc_cols.append(c)

        hoist(self.core.root)

        def one(b, fc):
            ctr = [0]
            ovfs: List[jax.Array] = [jnp.asarray(False)] * self._n_pipeline
            jb = self._eval_seg(self.core.root, caps, table_rows, table_ns,
                                tt_rows, tt_n, b, fc, values, ctr, ovfs,
                                shared)
            jb, mod_ovf = self._apply_spine(jb, values, fc, caps, ctr)
            stacked = jnp.stack(ovfs) if ovfs else jnp.zeros((0,), bool)
            if mod_ovf is not None:
                stacked = jnp.concatenate([stacked, mod_ovf[None]])
            return jb.data, jb.n, stacked

        # one binding after another inside the launch: a vmapped batch
        # axis lands minor in the TPU layout of the join prefix sums and
        # pads every (B, cap) buffer 128/B-fold
        return jax.lax.map(lambda bf: one(*bf), (bounds_b, fconsts_b))

    @functools.cached_property
    def _jitted_batch(self):
        # jax.jit caches per static (caps, B) pair, so trace_count() moves
        # once per (template, bucket-shape) — never once per request.
        return jax.jit(self._program_batched, static_argnums=(0,))

    def lower(self, caps: Optional[Tuple[int, ...]] = None):
        caps = caps or tuple(self.caps)
        rows = [jax.ShapeDtypeStruct(
                    (0 if t is None else round_up_pow2(len(t)), 2),
                    jnp.int32) for t in self.tables]
        ns = [jax.ShapeDtypeStruct((), jnp.int32) for _ in self.tables]
        tt_cap = round_up_pow2(max(self.catalog.n_triples, 1)) \
            if self._has_tt else 0
        ttshape = jax.ShapeDtypeStruct((tt_cap, 3), jnp.int32)
        ttn = jax.ShapeDtypeStruct((), jnp.int32)
        bshape = jax.ShapeDtypeStruct(self._default_bounds.shape, jnp.int32)
        fshape = jax.ShapeDtypeStruct((len(self.filter_slots),), jnp.int32)
        vshape = jax.ShapeDtypeStruct(self._value_keys.shape, jnp.float32)
        return self._jitted.lower(caps, rows, ns, ttshape, ttn, bshape,
                                  fshape, vshape)

    def run(self, max_retries: int = 16,
            bounds: Optional[np.ndarray] = None,
            fconsts: Optional[np.ndarray] = None,
            trace=None) -> Tuple[np.ndarray, Tuple[str, ...]]:
        rows, ns, tt_rows, tt_n, values = self._device_inputs
        b = self._default_bounds if bounds is None else \
            np.asarray(bounds, dtype=np.int32).reshape(self._default_bounds.shape)
        bj = jnp.asarray(b)
        fc = self.fconsts_from_mapping(None) if fconsts is None else \
            np.asarray(fconsts, dtype=np.int32).reshape(len(self.filter_slots))
        fj = jnp.asarray(fc)
        caps = tuple(self.caps)
        for attempt in range(max_retries):
            if trace is not None:
                # fenced launch span: block_until_ready keeps later host
                # work from absorbing the device time — traced requests
                # only (the untraced path stays fully async)
                sid = trace.start("device.launch", backend="jit",
                                  attempt=attempt, batch=1,
                                  cap_slots=sum(caps))
                data, n, ovf = self._jitted(caps, rows, ns, tt_rows,
                                            tt_n, bj, fj, values)
                jax.block_until_ready((data, n, ovf))
                ovf = np.asarray(ovf)
                trace.end(sid, overflow=bool(ovf.any()))
            else:
                data, n, ovf = self._jitted(caps, rows, ns, tt_rows, tt_n,
                                            bj, fj, values)
                ovf = np.asarray(ovf)
            if not ovf.any():
                # keep grown caps: a hot template must not pay the
                # overflow->retry double-launch on every request
                self.caps = list(caps)
                n = int(n)
                cols = self._final_cols()
                return np.asarray(data)[:n], cols
            caps = double_caps(caps, ovf, self._n_pipeline)
        raise RuntimeError("join capacity overflow after retries")

    def run_batch(self, bounds_batch: Sequence[np.ndarray],
                  fconsts_batch: Optional[Sequence[np.ndarray]] = None,
                  max_retries: int = 16,
                  trace=None) -> List[Tuple[np.ndarray, Tuple[str, ...]]]:
        """Execute B constant-bindings of this template's program in ONE
        XLA launch: the (B, n_steps, 2) bounds stack and the (B, n_fc)
        filter-constant stack are the only batched inputs (tables
        broadcast), so device work is amortized across the whole
        micro-batch.  Overflow on *any* batch element retries the whole
        batch with doubled caps — the batch shares one cap vector, which
        keeps the program count at one per (caps, B)."""
        if not bounds_batch:
            return []
        rows, ns, tt_rows, tt_n, values = self._device_inputs
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
        caps = tuple(self.caps)
        for attempt in range(max_retries):
            if trace is not None:
                sid = trace.start("device.launch", backend="jit",
                                  attempt=attempt, batch=len(bb),
                                  cap_slots=sum(caps))
                data, n, ovf = self._jitted_batch(caps, rows, ns, tt_rows,
                                                  tt_n, bj, fj, values)
                jax.block_until_ready((data, n, ovf))
                ovf = np.asarray(ovf)            # (B, n_pipeline[+1])
                trace.end(sid, overflow=bool(ovf.any()))
            else:
                data, n, ovf = self._jitted_batch(caps, rows, ns, tt_rows,
                                                  tt_n, bj, fj, values)
                ovf = np.asarray(ovf)            # (B, n_pipeline[+1])
            if not ovf.any():
                self.caps = list(caps)
                cols = self._final_cols()
                data = np.asarray(data)
                n = np.asarray(n)
                return [(data[i, : int(n[i])], cols)
                        for i in range(data.shape[0])]
            caps = double_caps(caps, ovf.any(axis=0), self._n_pipeline)
        raise RuntimeError("join capacity overflow after retries (batched)")

    def _final_cols(self) -> Tuple[str, ...]:
        return self._out_vars
