"""Paper Table 2: load times and store sizes (VP vs ExtVP vs τ-thresholded
ExtVP), plus the table-count accounting (#empty, #identity, #stored) and
the ExtVP build-backend microbenchmark.

``bench_extvp`` compares the sequential numpy builder against the
pair-batched device pipeline (``build_extvp(backend="jax")``) on
synthetic graphs of growing predicate count P (the pair grid is P²·3, so
P is the scalability axis) and on the WatDiv smoke graph, verifying
byte-identical output and emitting ``BENCH_extvp_build.json``::

    {"pair_batch": ..., "cases": [
        {"name": "P32", "preds": 32, "semijoins": ..., "numpy_s": ...,
         "jax_s": ..., "speedup": ..., "identical": true}, ...]}
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from benchmarks.common import Csv, catalog, dataset
from repro.core.vp import build_extvp, build_vp

DEFAULT_OUT = "BENCH_extvp_build.json"


def run(scale: float = 1.0, csv: Csv | None = None,
        pred_counts: Sequence[int] = (8, 32, 64)) -> Csv:
    csv = csv or Csv()
    tt, d, sch = dataset(scale)
    cat = catalog(scale)                     # τ = 1.0 (full ExtVP)
    rep = cat.storage_report()
    n = rep["n_triples"]

    csv.add("table2/triples", 0.0, f"{int(n)}")
    csv.add("table2/vp_build", rep["vp_build_seconds"],
            f"tables={int(rep['vp_tables'])};tuples={int(rep['vp_tuples'])}")
    csv.add("table2/extvp_build", rep["extvp_build_seconds"],
            f"tables={int(rep['extvp_tables'])};tuples={int(rep['extvp_tuples'])}"
            f";xVP={rep['extvp_over_vp']:.2f}"
            f";empty={int(rep['extvp_empty'])};identity={int(rep['extvp_identity'])}"
            f";semijoins={int(rep['n_semijoins'])}")

    for tau in (0.25, 0.5):
        cat_t = catalog(scale, threshold=tau)
        rep_t = cat_t.storage_report()
        csv.add(f"table2/extvp_tau{tau}", rep_t["extvp_build_seconds"],
                f"tables={int(rep_t['extvp_tables'])}"
                f";tuples={int(rep_t['extvp_tuples'])}"
                f";xVP={rep_t['extvp_over_vp']:.2f}")

    for case in bench_extvp(pred_counts=tuple(pred_counts))["cases"]:
        csv.add(f"table2/extvp_build_{case['name']}_jax", case["jax_s"],
                f"x{case['speedup']:.1f} vs numpy"
                f";semijoins={case['semijoins']}"
                f";identical={case['identical']}")
    return csv


# ---------------------------------------------------------------------------
# Build-backend microbenchmark (BENCH_extvp_build.json)
# ---------------------------------------------------------------------------

def _synthetic_graph(n_preds: int, rows_per_pred: int = 2048,
                     seed: int = 0) -> np.ndarray:
    """Random TT with ``n_preds`` predicates over a shared entity pool —
    dense enough that most pair ranges overlap (no pruning freebies)."""
    rng = np.random.default_rng(seed)
    n_ent = max(64, n_preds * rows_per_pred // 8)
    n = n_preds * rows_per_pred
    tt = np.stack([
        rng.integers(0, n_ent, n),
        n_ent + rng.integers(0, n_preds, n),
        rng.integers(0, n_ent, n),
    ], axis=1).astype(np.int32)
    return np.unique(tt, axis=0)


def bench_extvp(pred_counts: Sequence[int] = (8, 32, 64),
                watdiv_scale: Optional[float] = 0.1,
                threshold: float = 0.25, repeats: int = 3,
                pair_batch: int = 1024,
                out_path: str = DEFAULT_OUT) -> Dict:
    """Time numpy vs pair-batched jax ExtVP builds on the same VP
    catalogs.  Compile time is excluded by one warmup build per case
    (one static batch shape per case, so the warmup covers every trace);
    an untimed numpy build first primes the ``Table`` sort/unique caches
    both paths share.  Throughput is semi-joins per second."""
    cases: List[Dict] = []
    vps = [(f"P{p}", build_vp(_synthetic_graph(p))) for p in pred_counts]
    if watdiv_scale is not None:
        tt, d, sch = dataset(watdiv_scale)
        vps.append((f"watdiv{watdiv_scale}", build_vp(tt)))

    for name, vp in vps:
        build_extvp(vp, threshold=threshold)                  # prime caches
        numpy_s = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            base = build_extvp(vp, threshold=threshold)
            numpy_s = min(numpy_s, time.perf_counter() - t0)
        build_extvp(vp, threshold=threshold, backend="jax",   # compile warmup
                    pair_batch=pair_batch)
        jax_s = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            dev = build_extvp(vp, threshold=threshold, backend="jax",
                              pair_batch=pair_batch)
            jax_s = min(jax_s, time.perf_counter() - t0)
        cases.append({
            "name": name,
            "preds": len(vp),
            "threshold": threshold,
            "semijoins": base.n_semijoins,
            "tables": len(base.tables),
            "numpy_s": numpy_s,
            "jax_s": jax_s,
            "numpy_semijoins_per_s": base.n_semijoins / max(numpy_s, 1e-9),
            "jax_semijoins_per_s": base.n_semijoins / max(jax_s, 1e-9),
            "speedup": numpy_s / max(jax_s, 1e-9),
            "identical": base.identical(dev),
        })

    report = {"pair_batch": pair_batch, "repeats": repeats, "cases": cases}
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench-only", action="store_true",
                    help="emit BENCH_extvp_build.json and skip Table 2")
    ap.add_argument("--preds", type=int, nargs="+", default=[8, 32, 64],
                    help="synthetic predicate counts for the build bench")
    ap.add_argument("--scale", type=float, default=None,
                    help="WatDiv scale: Table-2 store (default 1.0) and "
                         "the bench's WatDiv smoke case (default 0.1)")
    args = ap.parse_args()
    if args.bench_only:
        print(json.dumps(
            bench_extvp(pred_counts=tuple(args.preds),
                        watdiv_scale=args.scale if args.scale is not None
                        else 0.1),
            indent=2))
    else:
        run(scale=args.scale if args.scale is not None else 1.0,
            pred_counts=tuple(args.preds)).emit()
