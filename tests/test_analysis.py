"""Static-analysis layer: plan/IR verifier negative + corpus tests, and
replint unit tests over known-bad snippets.

Every error-severity invariant in ``repro.analysis.verifier`` has a
mutation test here proving it fires with the right diagnostic; the
corpus tests prove the verifier is silent on every legitimate plan the
differential fuzz and the WatDiv basic suite produce (both planners,
all backends).
"""

import dataclasses

import pytest

from repro.analysis import (
    PlanVerificationError, lint_paths, lint_source,
    verify_core, verify_executor, verify_plan, verify_prepared,
)
from repro.core.compiler import Plan, compile_bgp, compile_core, select_table
from repro.core.jexec import PlanExecutor
from repro.core.modifiers import ModifierSpine, peel_spine
from repro.core.sparql import parse_sparql
from repro.core.stats import build_catalog
from repro.rdf.dictionary import Dictionary

G1_TRIPLES = [
    ("A", "follows", "B"), ("B", "follows", "C"), ("B", "follows", "D"),
    ("C", "follows", "D"), ("A", "likes", "I1"), ("A", "likes", "I2"),
    ("C", "likes", "I2"),
]


def fresh_g1(threshold=1.0):
    d = Dictionary()
    tt = d.encode_triples(G1_TRIPLES)
    return build_catalog(tt, d, threshold=threshold), d


def plan_for(qtext, cat, d, planner="greedy"):
    return compile_bgp(parse_sparql(qtext, d).root, cat, planner=planner)


def error_rules(report):
    return {diag.rule for diag in report.errors}


# ---------------------------------------------------------------------------
# verifier: legitimate plans are silent
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("planner", ["greedy", "estimate"])
def test_clean_plans_verify_ok(planner):
    cat, d = fresh_g1()
    for q in (
        "SELECT * WHERE { ?x follows ?y }",
        "SELECT * WHERE { ?x follows ?y . ?y likes ?z }",
        "SELECT * WHERE { ?x likes ?w . ?x follows ?y . "
        "?y follows ?z . ?z likes ?w }",
    ):
        report = verify_plan(plan_for(q, cat, d, planner), cat)
        assert report.ok and not report.diagnostics, (q, report.diagnostics)
        assert report.checks  # ran, not skipped


def test_statistics_empty_plan_verifies():
    cat, d = fresh_g1()
    plan = plan_for("SELECT * WHERE { ?x likes ?y . ?y follows ?z }", cat, d)
    assert plan.empty  # OS likes|follows has SF = 0 on G1
    assert verify_plan(plan, cat).ok


# ---------------------------------------------------------------------------
# verifier: each invariant fires on a mutated plan (negative tests)
# ---------------------------------------------------------------------------

def test_cross_join_rejected():
    cat, d = fresh_g1()
    plan = plan_for(
        "SELECT * WHERE { ?a follows ?b . ?b follows ?c . ?c likes ?w }",
        cat, d)
    by_pred_pos = {
        frozenset(v for v in (s.tp.s, s.tp.o)): s for s in plan.steps}
    s_ab = by_pred_pos[frozenset({"?a", "?b"})]
    s_bc = by_pred_pos[frozenset({"?b", "?c"})]
    s_cw = by_pred_pos[frozenset({"?c", "?w"})]
    # ?c likes ?w placed while disconnected from {?a, ?b}, although the
    # connecting step comes later: an unforced cross product
    bad = Plan(steps=[s_ab, s_cw, s_bc], vars=plan.vars,
               planner=plan.planner)
    assert "cross-join" in error_rules(verify_plan(bad, cat))


def test_sf_zero_step_rejected():
    cat, d = fresh_g1()
    plan = plan_for("SELECT * WHERE { ?x follows ?y }", cat, d)
    plan.steps[0].sf = 0.0
    assert "sf-zero-step" in error_rules(verify_plan(plan, cat))


def test_empty_flag_mismatch_rejected():
    cat, d = fresh_g1()
    plan = plan_for("SELECT * WHERE { ?x follows ?y }", cat, d)
    plan.empty = True
    assert "empty-flag" in error_rules(verify_plan(plan, cat))


def test_unknown_planner_tag_rejected():
    cat, d = fresh_g1()
    plan = plan_for("SELECT * WHERE { ?x follows ?y }", cat, d)
    plan.planner = "quantum"
    assert "planner-tag" in error_rules(verify_plan(plan, cat))


def test_sentinel_collision_rejected():
    cat, d = fresh_g1()
    plan = plan_for("SELECT * WHERE { ?x follows ?y }", cat, d)
    # UNBOUND (-1) as a bound subject id collides with the sentinel band
    plan.steps[0].tp = dataclasses.replace(plan.steps[0].tp, s=-1)
    assert "sentinel-collision" in error_rules(verify_plan(plan, cat))


def test_fabricated_table_stats_rejected():
    cat, d = fresh_g1()
    plan = plan_for("SELECT * WHERE { ?x follows ?y }", cat, d)
    plan.steps[0].sf = 0.5    # VP scan must record sf=1.0 + the VP size
    assert "table-choice" in error_rules(verify_plan(plan, cat))


def test_unmaterialized_extvp_choice_rejected():
    cat, d = fresh_g1(threshold=0.25)
    follows, likes = d.term_to_id["follows"], d.term_to_id["likes"]
    assert cat.sf("SO", likes, follows) > cat.extvp.threshold
    plan = plan_for("SELECT * WHERE { ?x follows ?y . ?y likes ?z }",
                    cat, d)
    step = next(s for s in plan.steps if int(s.tp.p) == likes)
    # force the SF > τ choice Algorithm 1 must no longer make: the stats
    # are the catalog's own, so only the materialization check can fire
    step.kind, step.p2 = "SO", follows
    step.sf = cat.sf("SO", likes, follows)
    step.size = cat.size("SO", likes, follows)
    assert error_rules(verify_plan(plan, cat)) == {"extvp-materialized"}


def test_extvp_partner_missing_rejected():
    cat, d = fresh_g1()
    follows, likes = d.term_to_id["follows"], d.term_to_id["likes"]
    plan = plan_for("SELECT * WHERE { ?y likes ?z }", cat, d)
    step = plan.steps[0]
    step.kind, step.p2 = "SO", follows
    step.sf = cat.sf("SO", likes, follows)
    step.size = cat.size("SO", likes, follows)
    assert "extvp-partner" in error_rules(verify_plan(plan, cat))


def test_flat_offset_corruption_rejected():
    cat, d = fresh_g1()
    query = parse_sparql(
        "SELECT * WHERE { { ?a follows ?b } UNION { ?a likes ?b } }", d)
    node, spine = peel_spine(query)
    core = compile_core(node, cat)
    core.root.right.start += 1
    assert "flat-offset" in error_rules(verify_core(core, cat, spine=spine))


def test_dropped_cap_slot_rejected():
    cat, d = fresh_g1()
    plan = plan_for("SELECT * WHERE { ?x follows ?y . ?y likes ?z }",
                    cat, d)
    ex = PlanExecutor(plan, cat)
    assert verify_executor(ex).ok
    ex.caps = ex.caps[:-1]
    assert "cap-slots" in error_rules(verify_executor(ex))


def test_corrupted_combine_index_rejected():
    cat, d = fresh_g1()
    query = parse_sparql(
        "SELECT * WHERE { ?a follows ?b OPTIONAL { ?b likes ?w } }", d)
    node, spine = peel_spine(query)
    core = compile_core(node, cat)
    ex = PlanExecutor(core, cat, spine=spine)
    assert verify_executor(ex).ok
    (seg_id, slot), = ex._comb_index.items()
    ex._comb_index = {seg_id: slot + 1}   # points at a non-combine slot
    assert "cap-slots" in error_rules(verify_executor(ex))


def test_negative_slice_rejected():
    cat, d = fresh_g1()
    plan = plan_for("SELECT * WHERE { ?x follows ?y }", cat, d)
    spine = ModifierSpine(offset=-1)
    assert "modifier-slice" in error_rules(verify_plan(plan, cat, spine))


def test_raise_if_failed_carries_diagnostics():
    cat, d = fresh_g1()
    plan = plan_for("SELECT * WHERE { ?x follows ?y }", cat, d)
    plan.planner = "quantum"
    with pytest.raises(PlanVerificationError) as exc:
        verify_plan(plan, cat).raise_if_failed()
    assert "planner-tag" in exc.value.rules()
    assert "quantum" in str(exc.value)


# ---------------------------------------------------------------------------
# verifier: warnings (diagnose, never reject)
# ---------------------------------------------------------------------------

def test_phantom_filter_var_warns():
    cat, d = fresh_g1()
    query = parse_sparql(
        "SELECT * WHERE { ?x follows ?y FILTER(?zz != ?x) }", d)
    node, spine = peel_spine(query)
    core = compile_core(node, cat)
    report = verify_core(core, cat, spine=spine)
    assert report.ok  # legal SPARQL: evaluates with UNBOUND
    assert "filter-var" in {diag.rule for diag in report.warnings}


def test_phantom_projection_var_warns():
    cat, d = fresh_g1()
    plan = plan_for("SELECT * WHERE { ?x follows ?y }", cat, d)
    spine = ModifierSpine(project=("?nope",))
    report = verify_plan(plan, cat, spine=spine)
    assert report.ok
    assert "projection-var" in {diag.rule for diag in report.warnings}


# ---------------------------------------------------------------------------
# Algorithm 1 only credits materialized reductions (the verifier-driven
# compiler fix)
# ---------------------------------------------------------------------------

def test_select_table_skips_unmaterialized_pairs():
    cat, d = fresh_g1(threshold=0.25)
    follows, likes = d.term_to_id["follows"], d.term_to_id["likes"]
    q = parse_sparql("SELECT * WHERE { ?x follows ?y . ?y likes ?z }", d)
    tps = list(q.root.patterns)
    by_pred = {int(tp.p): tp for tp in tps}
    # OS follows|likes has SF = 0.25 ≤ τ: materialized, selected
    f_step = select_table(by_pred[follows], tps, cat)
    assert (f_step.kind, f_step.p2) == ("OS", likes)
    # SO likes|follows has SF = 1/3 > τ: NOT materialized — Algorithm 1
    # must fall back to VP instead of crediting a reduction the store
    # cannot serve (the scan would read the full VP table anyway)
    l_step = select_table(by_pred[likes], tps, cat)
    assert l_step.kind is None and l_step.p2 is None
    assert l_step.sf == 1.0 and l_step.size == cat.vp_size(likes)
    # and the full plan now verifies clean at this τ
    assert verify_plan(plan_for(
        "SELECT * WHERE { ?x follows ?y . ?y likes ?z }", cat, d), cat).ok


def test_select_table_keeps_sf_zero_short_circuit():
    # SF=0 pairs are never materialized yet MUST stay selectable — they
    # are the statistics-only empty answer (paper §6)
    cat, d = fresh_g1(threshold=0.25)
    plan = plan_for("SELECT * WHERE { ?x likes ?y . ?y follows ?z }",
                    cat, d)
    assert plan.empty


# ---------------------------------------------------------------------------
# verifier: corpus sweeps (zero violations on everything the fuzz and the
# WatDiv basic suite produce)
# ---------------------------------------------------------------------------

def test_fixed_corpus_zero_violations():
    import jax
    from test_differential import FIXED_QUERIES, fixed_corpus_triples
    from repro.engine import Dataset, RuntimeConfig

    mesh = jax.make_mesh((1,), ("data",))
    triples = fixed_corpus_triples()
    for tau in (0.25, 1.0):
        ds = Dataset.from_triples(triples, threshold=tau)
        for planner in ("greedy", "estimate"):
            cfg = RuntimeConfig(planner=planner)
            for backend in ("eager", "jit", "distributed"):
                eng = ds.engine(backend, mesh=mesh, runtime=cfg)
                for qtext in FIXED_QUERIES:
                    report = verify_prepared(eng.prepare(qtext), ds.catalog)
                    assert report.ok, \
                        (tau, planner, backend, qtext, report.errors)


def test_watdiv_basic_suite_zero_violations(watdiv_small):
    from repro.rdf.workloads import basic_queries

    cat, d, sch = watdiv_small
    for planner in ("greedy", "estimate"):
        for name, instances in basic_queries(sch, n_instances=1).items():
            for qtext in instances:
                node, spine = peel_spine(parse_sparql(qtext, d))
                core = compile_core(node, cat, planner=planner)
                report = verify_core(core, cat, spine=spine)
                assert report.ok, (planner, name, qtext, report.errors)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def test_engine_explains_verdict_and_config_knob():
    from repro.engine import Dataset, RuntimeConfig

    cfg = RuntimeConfig(verify_plans=True)
    assert cfg.verify_plans is True
    assert "verify_plans" in cfg.snapshot()
    ds = Dataset.from_triples(G1_TRIPLES)
    eng = ds.engine("jit", runtime=cfg)
    out = eng.explain("SELECT * WHERE { ?x <follows> ?y . ?y <likes> ?z }")
    assert "verify: ok" in out


def test_unverifiable_prepared_reports_skip():
    report = verify_prepared(object(), None)
    assert report.ok and not report.checks
    assert "skipped" in report.describe()


# ---------------------------------------------------------------------------
# replint: known-bad snippets
# ---------------------------------------------------------------------------

def rules_of(findings):
    return [f.rule for f in findings]


def test_lint_traced_branch():
    findings = lint_source(
        "import jax, jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    y = jnp.sum(x)\n"
        "    if y > 0:\n"
        "        return y\n"
        "    return -y\n")
    assert rules_of(findings) == ["traced-branch"]
    assert findings[0].line == 5


def test_lint_traced_while_and_ternary():
    findings = lint_source(
        "import jax.numpy as jnp\n"
        "def device_count(x):\n"
        "    t = jnp.sum(x)\n"
        "    while t > 0:\n"
        "        t = t - 1\n"
        "    return t if t > 0 else -t\n")
    assert rules_of(findings) == ["traced-branch", "traced-branch"]


def test_lint_host_sync_item_and_np():
    findings = lint_source(
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "def device_norm(x):\n"
        "    t = jnp.exp(x)\n"
        "    a = np.asarray(t)\n"
        "    return t.sum().item()\n")
    assert sorted(rules_of(findings)) == ["host-sync", "host-sync"]


def test_lint_host_sync_float_cast():
    findings = lint_source(
        "import jax.numpy as jnp\n"
        "def device_f(x):\n"
        "    return float(jnp.sum(x))\n")
    assert rules_of(findings) == ["host-sync"]


def test_lint_int32_overflow():
    findings = lint_source(
        "import jax, jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x + 3000000000\n")
    assert rules_of(findings) == ["int32-overflow"]


def test_lint_nonstatic_shape_from_traced_n():
    findings = lint_source(
        "import jax.numpy as jnp\n"
        "def device_pad(b):\n"
        "    return jnp.zeros((b.n,), jnp.int32)\n")
    assert rules_of(findings) == ["nonstatic-shape"]


def test_lint_shard_map_check_rep():
    src = (
        "from jax import shard_map\n"
        "def build(body, mesh, specs):\n"
        "    return shard_map(body, mesh=mesh, in_specs=specs,\n"
        "                     out_specs=specs)\n")
    assert rules_of(lint_source(src)) == ["shard-map-check-vma"]
    ok = src.replace("out_specs=specs)", "out_specs=specs, check_vma=False)")
    assert lint_source(ok) == []


def test_lint_functions_passed_to_tracers_are_traced():
    findings = lint_source(
        "import jax, jax.numpy as jnp\n"
        "def body(x):\n"
        "    s = jnp.sum(x)\n"
        "    if s > 0:\n"
        "        return s\n"
        "    return x\n"
        "g = jax.jit(body)\n")
    assert rules_of(findings) == ["traced-branch"]


def test_lint_call_graph_propagation():
    findings = lint_source(
        "import jax, jax.numpy as jnp\n"
        "def helper(x):\n"
        "    m = jnp.max(x)\n"
        "    if m > 0:\n"
        "        return m\n"
        "    return x\n"
        "@jax.jit\n"
        "def entry(x):\n"
        "    return helper(x)\n")
    assert rules_of(findings) == ["traced-branch"]


def test_lint_static_idioms_stay_clean():
    assert lint_source(
        "import jax, jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x, flag):\n"
        "    # static-shape branch + host list of traced values: all fine\n"
        "    if x.shape[0] > 2:\n"
        "        x = x[:2]\n"
        "    if flag:\n"
        "        x = -x\n"
        "    masks = [jnp.sum(x), jnp.prod(x)]\n"
        "    out = masks[0]\n"
        "    for m in masks[1:]:\n"
        "        out = out + m\n"
        "    if len(masks) > 1:\n"
        "        out = out * 2\n"
        "    total = jnp.sum(out)\n"
        "    if total is not None:\n"
        "        out = out + 1\n"
        "    return out\n") == []


def test_lint_untraced_functions_not_checked():
    # host-side code may branch on numpy values freely
    assert lint_source(
        "import numpy as np\n"
        "def host(x):\n"
        "    y = np.sum(x)\n"
        "    if y > 0:\n"
        "        return float(y)\n"
        "    return 0.0\n") == []


def test_lint_suppression_requires_justification():
    base = (
        "import jax, jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    y = jnp.sum(x)\n"
        "    if y > 0:  # replint: disable=traced-branch{tail}\n"
        "        return y\n"
        "    return -y\n")
    justified = base.format(tail=" -- static under concrete test harness")
    assert lint_source(justified) == []
    bare = base.format(tail="")
    assert rules_of(lint_source(bare)) == ["bare-suppression"]


def test_lint_standalone_suppression_line_covers_next_line():
    src = (
        "import jax, jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    y = jnp.sum(x)\n"
        "    # replint: disable=traced-branch -- trace-time constant here\n"
        "    if y > 0:\n"
        "        return y\n"
        "    return -y\n")
    assert lint_source(src) == []


def test_repo_lint_is_clean():
    import pathlib

    src = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro"
    findings = lint_paths([src])
    assert findings == [], "\n".join(str(f) for f in findings)
