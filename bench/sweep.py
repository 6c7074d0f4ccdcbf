"""Find the open-loop knee of a cell: the highest offered rate at which
the 95th percentile stays within a limit and the backlog does not grow.

    python3 bench/sweep.py --workload <open cell> --seed <n> \
        --rates 20,40,80 --seconds 15 [--limit-ms 1000] [--out <file>]

Builds and warms the cell once, then offers each rate for ``--seconds``
with the cell's mix, and a single closed-loop client for the same time.
A rate's backlog grows when the requests due in the window's last
quarter wait longer, at the median, than twice those of its first
quarter and more than the limit's tenth.  One JSON line per rate.  The
rate found is written into the mix's file by hand: the benchmark's own
runs never search for a rate.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def summarize(sent, seconds, limit_ms):
    from bench.harness import percentile

    ok = [r for r in sent if r.error is None and r.done is not None]
    lat = [r.latency * 1e3 for r in ok]
    q1 = [r.latency * 1e3 for r in ok if r.due < seconds / 4]
    q4 = [r.latency * 1e3 for r in ok if r.due >= 3 * seconds / 4]
    grows = bool(q1 and q4 and percentile(q4, 50) > max(
        2 * percentile(q1, 50), limit_ms / 10))
    by_t = {}
    for r in ok:
        by_t.setdefault(r.template, []).append(r.latency * 1e3)
    return {"n": len(sent), "failed": len(sent) - len(ok),
            "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95),
            "backlog_grows": grows,
            "per_template_p50_ms": {k: round(percentile(v, 50), 3)
                                    for k, v in sorted(by_t.items())}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--limit-ms", type=float, default=1000.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness, loops, traffic

    spec = harness.Spec()
    cell = spec.cell(args.workload)
    config, mix = spec.config(cell), spec.mix(cell)
    harness.check_devices(cell["chips"])
    harness.enable_cache(spec.root)
    t0 = time.perf_counter()
    tt, terms, sizes, catalog = harness.build(config)
    server = harness.make_server(catalog, config)
    harness.warmup(server, mix, sizes, tt, terms)
    harness.log(f"set-up {time.perf_counter() - t0:.1f}s")
    lines = []
    closed = dataclasses.replace(mix, loop="closed", clients=1)
    sent = loops.closed_loop(server, traffic.requests(closed, sizes,
                                                      args.seed),
                             1, args.seconds, time.perf_counter(),
                             lambda r: None)
    lines.append({"clients": 1, **summarize(sent, args.seconds,
                                            args.limit_ms)})
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dataclasses.replace(mix, rate_qps=rate)
        sched = traffic.open_schedule(m, sizes, args.seed, args.seconds)
        sent = loops.open_loop(server, sched, time.perf_counter(),
                               lambda r: None)
        lines.append({"rate_qps": rate, **summarize(sent, args.seconds,
                                                    args.limit_ms)})
        harness.log(json.dumps(lines[-1]))
    text = "\n".join(json.dumps(x) for x in lines)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
