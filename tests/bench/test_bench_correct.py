"""The comparison that decides ``correct``: the reference agrees with
the program where both are sound, the control comes out not correct,
and a run whose timed path is broken underneath comes out not correct
for each fault a serving cell can have."""

import os

import numpy as np
import pytest

from bench import check, harness, reference, traffic, watdiv
from benchroot import CLOSED, OPEN, REPO

from repro.core.executor import Bindings
from repro.engine.result import Result


@pytest.fixture(scope="module")
def small():
    tt, terms, sch = watdiv.generate_watdiv(
        watdiv.WatDivConfig(scale_factor=1, seed=5))
    return tt, terms, watdiv.class_sizes(sch)


@pytest.fixture(scope="module")
def basic():
    return traffic.load_mix(os.path.join(REPO, "bench", "traffic",
                                         "basic-open.json"))


def _answers(small, basic, per_template=2):
    """(query, the eager engine's Result) for every basic template."""
    from repro.core.stats import build_catalog
    from repro.engine import Dataset
    from repro.rdf.dictionary import Dictionary

    tt, terms, sizes = small
    ds = Dataset(catalog=build_catalog(tt, Dictionary.from_terms(terms)))
    eng = ds.engine("eager")
    rng = np.random.default_rng(0)
    out = []
    for name in sorted(basic.templates):
        for _ in range(per_template):
            q = traffic.instantiate(basic, name, sizes, rng)
            out.append((q, eng.query(q)))
    return out


def test_reference_matches_the_eager_engine(small, basic):
    graph = reference.Graph(small[0], small[1])
    sample = _answers(small, basic)
    verdict = check.compare(graph, sample, failed=0)
    assert verdict["checks"]["wrong_answers"]["value"] == 0
    assert verdict["correct"]
    assert verdict["reference_rows"] > 0


def test_control_is_not_correct(small, basic):
    """The control, the reference cut at a number of rows below the
    largest answer, fails the comparison."""
    graph = reference.Graph(small[0], small[1])
    sample = _answers(small, basic)
    largest = max(len(r) for _, r in sample)
    verdict = check.compare(graph, sample, failed=0, control=largest // 2)
    assert verdict["checks"]["wrong_answers"]["value"] >= 1
    assert not verdict["correct"]


def _alter(server):
    """An answer altered where it is produced: one term of every
    non-empty answer replaced by the next term id."""
    run = server.engine.query_batch

    def query_batch(qtexts, **kw):
        out = []
        for r in run(qtexts, **kw):
            if len(r):
                data = np.array(r.data, copy=True)
                data[0, 0] = (data[0, 0] + 1) % len(r.dictionary)
                r = Result(Bindings(r.cols, data), r.dictionary)
            out.append(r)
        return out

    server.engine.query_batch = query_batch


def _half_batch(server):
    """Half of every batch left out: its requests are never answered."""
    run = server.engine.query_batch

    def query_batch(qtexts, **kw):
        return run(qtexts, **kw)[: len(qtexts) // 2]

    server.engine.query_batch = query_batch


@pytest.mark.parametrize("cell,fault,broken", [
    (OPEN, None, None), (CLOSED, None, None),
    (OPEN, _alter, "wrong_answers"), (CLOSED, _half_batch, "failed_requests"),
])
def test_run_is_correct_unless_broken(tiny_root, cell, fault, broken):
    out = harness.run_cell(cell, 2**31 + 17, 1.0, traced=False,
                           root=tiny_root, require_tpu=False,
                           wrap_server=fault)
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    if fault is None:
        assert out["correct"] and out["failed"] == 0
        assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    else:
        assert not out["correct"]
        assert out["checks"][broken]["value"] > out["checks"][broken]["limit"]


def test_traced_run_reads_the_span_metrics(tiny_root):
    out = harness.run_cell(OPEN, 99, 1.0, traced=True, root=tiny_root,
                           require_tpu=False)
    assert out["correct"]
    got = out["metrics"]
    for name in ("queue_ms.open", "host_ms.open", "launch_ms.open",
                 "recompiles.open", "warmup_s", "extvp_build_s"):
        assert name in got
    assert got["recompiles.open"]["value"] == 0
    assert got["launch_ms.open"]["value"] > 0
    # no device trace on the CPU: the idle share has nothing to read
    assert "idle_pct.open" not in got


def test_control_run_reads_both_sides(tiny_root):
    """``bench/control.py``'s readings, with a cut below the test graph's
    answers: the program's side correct, the control's not."""
    from bench import control

    lines = list(control.read(CLOSED, [5, 6], 1.0, root=tiny_root,
                              require_tpu=False, rows=1))
    assert [x["seed"] for x in lines] == [5, 6]
    for x in lines:
        assert x["program_correct"] and not x["control_correct"]
        assert x["control"]["wrong_answers"]["value"] >= 1
