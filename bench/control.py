"""Read the comparison's two sides on the chip: the program's numbers
and the control's, at the cell's own size, on several seeds in one
process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

Builds and warms the cell once (its graph is the configuration's, the
same for every seed), then for each seed serves the seed's traffic for
the window as a run does and compares the sample of answers with the
reference twice: once as a run does (the program's answers), once with
the control in the program's place (the reference's answer cut at
``check.CONTROL_ROWS`` rows).  The control has to come out not correct.
The benchmark's own runs never run it.  One JSON line per seed.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read(workload, seeds, seconds, root=None, require_tpu=True,
         rows=None):
    """Yield one reading per seed (see the module's doc)."""
    from bench import check, harness, reference

    spec = harness.Spec(root or harness.ROOT)
    cell = spec.cell(workload)
    config, mix = spec.config(cell), spec.mix(cell)
    if require_tpu:
        harness.check_devices(cell["chips"])
    harness.enable_cache(spec.root)
    tt, terms, sizes, catalog = harness.build(config)
    server = harness.make_server(catalog, config)
    harness.warmup(server, mix, sizes, tt, terms)
    graph = reference.Graph(tt, terms)
    for seed in seeds:
        record = harness.RunRecord(cell=cell, config=config, seconds=seconds)
        record.before = harness.counters(server)
        sampler = check.Sampler(seed)
        harness.serve_window(server, mix, sizes, seed, seconds, sampler,
                             False, record)
        record.after = harness.counters(server)
        answered = sum(1 for r in record.requests
                       if r.error is None and r.done is not None)
        failed = len(record.requests) - answered + record.delta("fallbacks")
        answers: dict = {}
        program = check.compare(graph, sampler.sample(), failed,
                                cache=answers)
        control = check.compare(graph, sampler.sample(), 0,
                                control=rows or check.CONTROL_ROWS,
                                cache=answers)
        yield {"seed": seed, "attempted": len(record.requests),
               "compared": program["compared"],
               "program": program["checks"],
               "program_correct": program["correct"],
               "control": control["checks"],
               "control_correct": control["correct"]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    seeds = [int(s) for s in args.seeds.split(",")]
    for line in read(args.workload, seeds, args.seconds):
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
