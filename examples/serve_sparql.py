"""End-to-end driver (the paper's kind: a query engine serving requests).

Generates a WatDiv graph, builds the ExtVP store via the ``Dataset``
facade, then serves a batched mixed workload (Basic Testing + IL + ST
templates) through an ``Engine`` — the serving analogue of the paper's §7
evaluation.  Because the workload repeats templates, the engine's plan
cache means requests after the first instantiation of each template skip
parsing and compilation entirely (watch ``plan_hit_rate``).

    PYTHONPATH=src python examples/serve_sparql.py --scale 1.0 --requests 60
    PYTHONPATH=src python examples/serve_sparql.py --backend jit
"""

import argparse
import time

import numpy as np

from repro import Dataset
from repro.launch.compile_cache import enable_compile_cache
from repro.rdf.workloads import ST_QUERIES, basic_queries, il_queries


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--layout", default="extvp", choices=["extvp", "vp", "tt"])
    ap.add_argument("--backend", default="eager",
                    help="ExecutionBackend registry key (eager/jit/...) or "
                         "'auto' for per-template adaptive routing")
    args = ap.parse_args()
    enable_compile_cache()

    print(f"generating WatDiv SF={args.scale} ...")
    t0 = time.perf_counter()
    ds = Dataset.watdiv(scale=args.scale, seed=0,
                        threshold=0.25)   # production τ (paper §7.4)
    rep = ds.storage_report()
    print(f"  {ds.n_triples} triples; {int(rep['extvp_tables'])} ExtVP tables "
          f"({rep['extvp_over_vp']:.1f}× VP tuples) "
          f"in {time.perf_counter()-t0:.2f}s")

    # --- build the request mix ------------------------------------------------
    rng = np.random.default_rng(1)
    pool = list(ST_QUERIES.values())
    for qs in basic_queries(ds.schema, seed=2, n_instances=2).values():
        pool.extend(qs)
    for qs in il_queries(ds.schema, seed=3, n_instances=1).values():
        pool.extend(qs)
    requests = [pool[rng.integers(0, len(pool))] for _ in range(args.requests)]

    # --- serve ------------------------------------------------------------------
    engine = ds.engine(args.backend, layout=args.layout)
    t_start = time.perf_counter()
    engine.query_batch(requests)
    wall = time.perf_counter() - t_start

    m = engine.metrics.summary()
    print(f"\nserved {int(m['served'])} requests in {wall:.2f}s "
          f"({m['served']/wall:.1f} qps), layout={args.layout}, "
          f"backend={engine.backend}")
    print(f"  latency ms: p50={m['p50_ms']:.1f} p90={m['p90_ms']:.1f} "
          f"p99={m['p99_ms']:.1f}")
    print(f"  plan-cache hit rate: {m['plan_hit_rate']:.2f} "
          f"({engine.cache.evictions} evictions)")
    print(f"  micro-batches: {int(m['batches'])} launches for "
          f"{int(m['batched_requests'])} requests "
          f"(occupancy {m['batch_occupancy']:.2f}, "
          f"padding waste {m['padding_waste']:.2f})")
    print(f"  result rows: {int(m['rows'])}, empty answers: "
          f"{int(m['empties'])} (statistics-only: {int(m['short_circuits'])})")
    if m["routed"]:
        print(f"  adaptive routing: {m['routed']} "
              "(engine.runtime_report() has the full decision log)")


if __name__ == "__main__":
    main()
