"""A small benchmark root for the benchmark's own tests: it holds its own
``BENCHMARK.json``, configuration, mix and metric files, so that the
harness is driven on the CPU at a size a test can hold."""

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: a few templates of each shape keep the CPU compiles short
TEMPLATES = ("S5", "L2", "F1")
OPEN, CLOSED = "tiny.small-open", "tiny.small-closed4"


#: the metrics of the test cells: each metric that ``bench/metrics``
#: can read, the open loop's and the closed loop's
END_TO_END = [
    ("latency_p50_ms", "ms", "lower", [OPEN]),
    ("latency_p95_ms", "ms", "lower", [OPEN]),
    ("qps", "queries/s", "higher", [CLOSED]),
    ("setup_s", "s", "lower", None)]
PER_LAYER = [
    ("queue_ms.open", "ms", "lower", "program_span", "serving shell",
     "latency_p95_ms", [OPEN]),
    ("host_ms.open", "ms", "lower", "program_span", "engine",
     "latency_p50_ms", [OPEN]),
    ("launch_ms.open", "ms", "lower", "program_span", "device executor",
     "latency_p95_ms", [OPEN]),
    ("recompiles.open", "programs", "lower", "program_counter",
     "device executor", "latency_p95_ms", [OPEN]),
    ("idle_pct.open", "%", "lower", "device_trace", "device",
     "latency_p50_ms", [OPEN]),
    ("reqs_per_launch.closed", "requests", "higher", "program_counter",
     "serving shell", "qps", [CLOSED]),
    ("host_ms.closed", "ms", "lower", "program_span", "engine", "qps",
     [CLOSED]),
    ("launch_ms.closed", "ms", "lower", "program_span", "device executor",
     "qps", [CLOSED]),
    ("idle_pct.closed", "%", "lower", "device_trace", "device", "qps",
     [CLOSED]),
    ("extvp_build_s", "s", "lower", "host_clock", "load job", "setup_s",
     [OPEN, CLOSED]),
    ("warmup_s", "s", "lower", "host_clock", "device executor", "setup_s",
     [OPEN, CLOSED])]


def _metric(name, unit, better, source, cells=None, **extra):
    m = {"name": name, "unit": unit, "better": better, "source": source}
    m.update(extra)
    if cells is not None:
        m["workloads"] = cells
    return m


def read_json(path):
    with open(path) as f:
        return json.load(f)


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(root):
    """A benchmark root with one configuration (the ExtVP one at scale 1,
    the host's load job, batch shapes 1 and 4), two mixes cut from the
    real ``basic`` mixes, an open-loop and a closed-loop cell, and every
    metric the readers in ``bench/metrics`` give."""
    real = read_json(os.path.join(REPO, "BENCHMARK.json"))
    cfg = read_json(os.path.join(REPO, "bench", "configs",
                             "watdiv-sf5-extvp.json"))
    cfg.update(name="tiny", graph={"generator": "watdiv", "scale_factor": 1,
                                     "seed": 0},
               build_backend="numpy", use_pallas=False)
    cfg["server"]["batch_shapes"] = [1, 4]
    cfg["server"]["max_batch"] = 4
    _write(os.path.join(root, "bench", "configs", "tiny.json"), cfg)
    for mix, extra in (("small-open", {"loop": "open", "rate_qps": 60.0}),
                       ("small-closed4", {"loop": "closed", "clients": 4})):
        base = read_json(os.path.join(REPO, "bench", "traffic",
                                  "basic-open.json"))
        base.pop("rate_qps", None)
        base.update(extra)
        base["templates"] = {k: base["templates"][k] for k in TEMPLATES}
        _write(os.path.join(root, "bench", "traffic", f"{mix}.json"), base)
    shutil.copytree(os.path.join(REPO, "bench", "metrics"),
                    os.path.join(root, "bench", "metrics"))
    spec = dict(real)
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [
        {"name": OPEN, "config": "tiny", "traffic": "small-open",
         "chips": 1, "why": "test"},
        {"name": CLOSED, "config": "tiny", "traffic": "small-closed4",
         "chips": 1, "why": "test"}]
    spec["end_to_end"] = [_metric(n, u, b, "host_clock", cells=c)
                          for n, u, b, c in END_TO_END]
    spec["per_layer"] = [_metric(n, u, b, src, layer=layer, moves=moves,
                                 cells=c)
                         for n, u, b, src, layer, moves, c in PER_LAYER]
    _write(os.path.join(root, "BENCHMARK.json"), spec)
    return root
