"""Production serving launcher — SPARQL query serving (the paper's kind)
over the distributed engine, or LM decode serving for the assigned archs.

    PYTHONPATH=src python -m repro.launch.serve --mode sparql --scale 1.0
    PYTHONPATH=src python -m repro.launch.serve --mode sparql \
        --store watdiv.store        # persist on first run, boot from the
                                    # store (no build pipeline) afterwards
    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch qwen1.5-0.5b
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced
from repro.models.api import Model


def serve_sparql(args) -> None:
    import json

    from repro.engine import Dataset, RuntimeConfig
    from repro.rdf.workloads import ST_QUERIES
    from repro.store import is_store

    t0 = time.perf_counter()
    if args.store and is_store(args.store):
        # persistent-store boot: manifest + lazy memmaps, the build
        # pipeline (build_catalog / build_extvp) never runs
        ds = Dataset.load(args.store, eager=args.eager_load)
        print(f"cold start from store {args.store!r} in "
              f"{time.perf_counter() - t0:.3f}s "
              f"({'eager' if args.eager_load else 'lazy memmap'})")
    else:
        ds = Dataset.watdiv(scale=args.scale, seed=0, threshold=0.25)
        if args.store:
            ds.save(args.store)
            print(f"built and persisted store {args.store!r} in "
                  f"{time.perf_counter() - t0:.3f}s "
                  "(next boot loads it without rebuilding)")
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    rt_kwargs = {}
    if args.batch_shapes:
        rt_kwargs["batch_shapes"] = tuple(
            int(t) for t in args.batch_shapes.replace(",", " ").split())
    if args.planner:
        rt_kwargs["planner"] = args.planner
    if args.trace_sample is not None:
        rt_kwargs["trace_sample_rate"] = args.trace_sample
    runtime = RuntimeConfig(**rt_kwargs) if rt_kwargs else None
    # "auto" routes per template across eager/jit (add --backend
    # distributed explicitly to pin the sharded path to a mesh)
    engine = ds.engine(args.backend,
                       mesh=mesh if args.backend == "distributed" else None,
                       runtime=runtime)
    print(f"store: {ds.n_triples} triples on {jax.device_count()} shard(s), "
          f"backend={engine.backend}")

    t0 = time.perf_counter()
    for p in range(max(1, args.passes)):
        for name, qtext in ST_QUERIES.items():
            res = engine.query(qtext)
            if p == 0:
                print(f"  {name}: {'∅' if len(res) == 0 else f'{len(res)} rows'}")
    m = engine.metrics.summary()
    print(f"served {int(m['served'])} queries in {time.perf_counter()-t0:.2f}s "
          f"(p50 {m['p50_ms']:.1f} ms, {int(m['short_circuits'])} "
          f"statistics-only empties, routed {m['routed']})")
    if args.runtime_report:
        print(json.dumps(engine.runtime_report(), indent=2))
    if args.trace_dump:
        with open(args.trace_dump, "w") as f:
            if args.trace_dump.endswith(".jsonl"):
                f.write(engine.tracer.to_jsonl())
            else:
                json.dump(engine.tracer.chrome_trace(), f)
        n = len(engine.tracer.recorder)
        print(f"wrote {n} trace(s) to {args.trace_dump!r} "
              f"(inspect: python tools/trace_inspect.py {args.trace_dump}; "
              "chrome://tracing loads the .json form)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(engine.metrics.prometheus())
        print(f"wrote Prometheus exposition to {args.metrics_out!r}")


def serve_lm(args) -> None:
    cfg = get_reduced(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = args.batch, args.seq
    caches = model.init_caches(params if cfg.enc_dec else None, B, S)
    decode = jax.jit(model.decode, donate_argnums=(1,))

    tok = jnp.zeros((B, 1), jnp.int32)
    t0 = time.perf_counter()
    for pos in range(args.tokens):
        logits, caches = model.decode(params, caches, tok, jnp.int32(pos))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    dt = time.perf_counter() - t0
    print(f"{cfg.name} (reduced): decoded {args.tokens} tokens × batch {B} "
          f"in {dt:.2f}s = {args.tokens*B/dt:.0f} tok/s")


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="sparql", choices=["sparql", "lm"])
    ap.add_argument("--backend", default="distributed",
                    help="ExecutionBackend registry key (eager/jit/"
                         "distributed) or 'auto' for per-template adaptive "
                         "routing (docs/serving.md)")
    ap.add_argument("--scale", type=float, default=0.5)
    ap.add_argument("--planner", default=None,
                    choices=["greedy", "estimate"],
                    help="join-order planner (default: REPRO_RT_PLANNER "
                         "env or 'greedy'); 'estimate' enumerates orders "
                         "by estimated intermediate cardinality")
    ap.add_argument("--batch-shapes", default=None,
                    help="comma-separated micro-batch bucket menu, e.g. "
                         "1,4,16 (default REPRO_RT_BATCH_SHAPES or "
                         "1,2,4,8,16,32; the tuner retires measured "
                         "regressions at runtime)")
    ap.add_argument("--passes", type=int, default=1,
                    help="serve the workload N times (give the adaptive "
                         "router warmup traffic)")
    ap.add_argument("--runtime-report", action="store_true",
                    help="print the adaptive-runtime JSON snapshot "
                         "(routing decisions, batch-shape menu, knobs)")
    ap.add_argument("--trace-sample", type=float, default=None,
                    help="per-request span-trace sampling rate in [0,1] "
                         "(default REPRO_RT_TRACE_SAMPLE or 0.0 = off; "
                         "see docs/observability.md)")
    ap.add_argument("--trace-dump", default=None,
                    help="write the flight recorder after serving: "
                         "Chrome chrome://tracing JSON, or JSONL when "
                         "the path ends in .jsonl")
    ap.add_argument("--metrics-out", default=None,
                    help="write the Prometheus text exposition of the "
                         "serving metrics to this file after serving")
    ap.add_argument("--store", default=None,
                    help="persistent catalog store directory: boot from it "
                         "when it exists (no build pipeline), else build "
                         "once and persist there")
    ap.add_argument("--eager-load", action="store_true",
                    help="materialize every table at boot instead of lazy "
                         "memory-mapping (see docs/serving.md)")
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "sparql":
        serve_sparql(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
