"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--scale`` sets the WatDiv
scale factor (default 5.0 ≈ 1.5·10^5 triples — big enough that the
paper's selectivity separation is visible on one CPU host; the paper's
SF10000 ≈ 1.09·10^9 runs the same code on a cluster).  This harness
measures the *running* engine.

One process per table.  The parent never imports JAX: it runs each
table in a child process of its own, one at a time, so exactly one
process holds the device while a table runs.  The ``trace`` and
``verify`` gates start cold grandchildren of their own, and their
child stays off JAX too.
"""

from __future__ import annotations

import argparse
import importlib
import os
import subprocess
import sys

#: table name -> (module, function); each writes its BENCH_*.json if noted
TABLES = {
    "table2": ("table2_load", "run"),
    "table3": ("table3_st", "run"),
    "table4": ("table4_basic", "run"),
    "table5": ("table5_il", "run"),
    "sec74": ("sec74_threshold", "run"),
    "serve": ("serve_throughput", "run"),     # BENCH_serve_throughput.json
    "modifiers": ("modifier_queries", "run"),  # BENCH_modifier_queries.json
    "store": ("store_load", "run"),           # BENCH_store_load.json
    "routing": ("adaptive_routing", "run"),   # BENCH_adaptive_routing.json
    "plan_enum": ("plan_enum", "run"),        # BENCH_plan_enum.json
    "verify": ("verify_overhead", "run"),     # BENCH_verify_overhead.json
    "trace": ("trace_overhead", "run"),       # BENCH_trace_overhead.json
}

#: tables whose run() only spawns cold measurement processes
SPAWNING = {"verify", "trace"}


def _child(name: str, scale: float, backend: str) -> None:
    """Run one table in this process and print its CSV rows."""
    module, fn = TABLES[name]
    if name not in SPAWNING:
        from benchmarks import common
        from repro.launch.compile_cache import enable_compile_cache

        enable_compile_cache()
        common.set_default_backend(backend)
    mod = importlib.import_module(f"benchmarks.{module}")
    rows = []

    class _Rows:                      # the Csv contract, without JAX
        def add(self, row: str, seconds: float, derived: str = "") -> None:
            rows.append(f"{row},{seconds * 1e6:.1f},{derived}")

    getattr(mod, fn)(scale=scale, csv=_Rows())
    print("\n".join(rows))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=5.0)
    ap.add_argument("--only", default=None, choices=list(TABLES))
    ap.add_argument("--backend", default="eager",
                    help="ExecutionBackend registry key for query timing")
    ap.add_argument("--child", default=None, choices=list(TABLES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        _child(args.child, args.scale, args.backend)
        return

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    print("name,us_per_call,derived")
    for name in TABLES:
        if args.only and name != args.only:
            continue
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--child", name,
             "--scale", str(args.scale), "--backend", args.backend],
            env=env, cwd=root, capture_output=True, text=True)
        if out.returncode:
            sys.stderr.write(out.stderr)
            sys.exit(out.returncode)
        sys.stdout.write(out.stdout)
        sys.stdout.flush()


if __name__ == "__main__":
    main()
