"""The harness finds cells, configurations, mixes and metrics by name,
takes new ones as new files and entries, and refuses to run without a
TPU."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from benchroot import CLOSED, OPEN, REPO, make_root, read_json


def test_finds_everything_by_name(tiny_root):
    spec = harness.Spec(tiny_root)
    cell = spec.cell(OPEN)
    assert spec.config(cell)["name"] == "tiny"
    mix = spec.mix(cell)
    assert (mix.name, mix.loop) == ("small-open", "open")
    untraced = [m["name"] for m in spec.metrics(cell, traced=False)]
    assert untraced == ["latency_p50_ms", "latency_p95_ms", "setup_s"]
    traced = [m["name"] for m in spec.metrics(spec.cell(CLOSED), True)]
    assert "reqs_per_launch.closed" in traced
    assert "queue_ms.open" not in traced
    rec = harness.RunRecord(cell=cell, config={}, seconds=1.0,
                            warmup_s=2.5)
    assert spec.reader("warmup_s")(rec) == 2.5
    with pytest.raises(SystemExit):
        spec.cell("no-such-cell")


def test_warmup_runs_batch_one_and_leaves_nothing_to_compile(tiny_root):
    """Warm-up runs each template at batch 1, with its heaviest
    constants, and readies its other batch shapes without running them;
    the window then traces and compiles nothing, whatever batch sizes
    and constants it sends."""
    import numpy as np

    from bench import traffic

    spec = harness.Spec(tiny_root)
    cell = spec.cell(CLOSED)
    config, mix = spec.config(cell), spec.mix(cell)
    harness.enable_cache(spec.root)
    harness._count_compiles()
    tt, terms, sizes, catalog = harness.build(config)
    server = harness.make_server(catalog, config)
    harness.warmup(server, mix, sizes, tt, terms)
    before = harness.counters(server)
    assert before["batches"] == before["batched_requests"]
    assert len(mix.templates) <= before["batched_requests"] <= \
        harness.WARM_CONSTANTS * len(mix.templates)
    rng = np.random.default_rng(4)
    for name in sorted(mix.templates):
        for b in range(1, config["server"]["max_batch"] + 1):
            server.query_batch([traffic.instantiate(mix, name, sizes, rng)
                                for _ in range(b)])
    after = harness.counters(server)
    assert after["batches"] > before["batches"]
    assert after["traces"] == before["traces"]
    assert after["compiles"] == before["compiles"]


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for name in files:
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = \
                    hashlib.sha256(f.read()).hexdigest()
    return out


def test_new_config_mix_and_metric_are_new_files(tmp_path):
    """A later change adds a configuration, a mix and a metric: new
    files plus new entries in BENCHMARK.json, no other file edited."""
    root = make_root(str(tmp_path / "root"))
    before = _digests(root)
    b = os.path.join(root, "bench")
    cfg = read_json(os.path.join(b, "configs", "tiny.json"))
    cfg["name"] = "tiny-vp"
    cfg.update(layout="vp", with_extvp=False)
    with open(os.path.join(b, "configs", "tiny-vp.json"), "w") as f:
        json.dump(cfg, f)
    mix = read_json(os.path.join(b, "traffic", "small-open.json"))
    mix["rate_qps"] = 5.0
    with open(os.path.join(b, "traffic", "trickle.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(b, "metrics", "answered.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.requests))\n")
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = read_json(spec_path)
    spec["configs"].append({"name": "tiny-vp", "source": "test",
                            "file": "bench/configs/tiny-vp.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-vp.trickle", "config": "tiny-vp",
                              "traffic": "trickle", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "answered", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "serving shell", "moves": "qps",
                              "workloads": ["tiny-vp.trickle"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    h = harness.Spec(root)
    cell = h.cell("tiny-vp.trickle")
    assert h.config(cell)["layout"] == "vp"
    assert h.mix(cell).rate_qps == 5.0
    assert [m["name"] for m in h.metrics(cell, True)] == ["answered"]
    rec = harness.RunRecord(cell=cell, config={}, seconds=1.0)
    assert h.reader("answered")(rec) == 0.0
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == \
        {k: v for k, v in before.items() if k != "BENCHMARK.json"} | \
        {"BENCHMARK.json": after["BENCHMARK.json"]}


def _run(root, args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=120)


ARGS = ["--workload", "watdiv-sf5-extvp.basic-closed32", "--seed",
        "3000000001", "--seconds", "1", "--trace", "0"]


def test_run_exits_nonzero_without_tpu():
    proc = _run(REPO, ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's own
    directories has no program to measure."""
    spec = read_json(os.path.join(REPO, "BENCHMARK.json"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in spec["paths"]:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path), ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
