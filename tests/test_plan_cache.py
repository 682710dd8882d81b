"""Compiled-plan layer: fingerprinting, caching, invalidation, parity.

Covers the plan cache's three invalidation obligations (a cached plan must
recompile — not silently run stale — after ``drop_view``, after node-arena
growth, and after a write that bumps one of its labels' epochs, asserted
through the planner hit/miss counters), fingerprint canonicalization, and
exact result/metric parity between the fused plan executor and the unfused
per-hop :class:`PathExecutor` on the patterns ``test_executor.py`` uses.
"""
import numpy as np
import pytest

from repro.core import (
    ExecConfig, GraphBuilder, GraphSchema, GraphSession, PathExecutor,
    canonicalize_query,
)
from repro.core.parser import parse_query


def _toy_session(**cfg_kw):
    schema = GraphSchema()
    b = GraphBuilder(schema)
    nodes = [b.add_node("A" if i % 2 == 0 else "B") for i in range(8)]
    for i in range(7):
        b.add_edge(nodes[i], nodes[i + 1], "x")
    for i in range(0, 8, 2):
        b.add_edge(nodes[i], nodes[(i + 3) % 8], "y")
    return GraphSession(b.finalize(), schema,
                        ExecConfig(**cfg_kw) if cfg_kw else None)


QX = "MATCH (a:A)-[:x*1..2]->(b:B) RETURN a, b"
VIEW_X = ("CREATE VIEW VX AS (CONSTRUCT (s)-[r:VX]->(d) "
          "MATCH (s:A)-[:x*1..2]->(d:B))")
VIEW_Y = ("CREATE VIEW VY AS (CONSTRUCT (s)-[r:VY]->(d) "
          "MATCH (s:A)-[:y]->(d:B))")


def _pairs(res):
    s, d, c = res.pairs()
    return sorted(zip(s.tolist(), d.tolist(), c.tolist()))


# ---------------------------------------------------------------------------
# caching + fingerprinting
# ---------------------------------------------------------------------------

def test_repeat_query_hits_plan_cache():
    sess = _toy_session()
    r1 = sess.query(QX, use_views=False)
    assert sess.planner.plan_misses == 1
    for _ in range(3):
        r = sess.query(QX, use_views=False)
        assert _pairs(r) == _pairs(r1)
    assert sess.planner.plan_misses == 1
    assert sess.planner.plan_hits == 3


def test_fingerprint_erases_var_spelling():
    sess = _toy_session()
    sess.query("MATCH (a:A)-[:x]->(b:B) RETURN a, b", use_views=False)
    misses = sess.planner.plan_misses
    # different var names, same referenced structure -> same fingerprint
    sess.query("MATCH (foo:A)-[:x]->(bar:B) RETURN foo, bar", use_views=False)
    assert sess.planner.plan_misses == misses
    assert sess.planner.plan_hits >= 1


def test_fingerprint_tracks_referenced_flags():
    schema = GraphSchema()
    q1 = parse_query("MATCH (a:A)-[:x]->(b:B)-[:y]->(c:A) RETURN a, c")
    q2 = parse_query("MATCH (a:A)-[:x]->(b:B)-[:y]->(c:A) RETURN a, b, c")
    _, fp1 = canonicalize_query(q1, schema)
    _, fp2 = canonicalize_query(q2, schema)
    assert fp1 != fp2          # referencing b forbids splicing it out
    q3 = parse_query("MATCH (s:A)-[:x]->(t:B)-[:y]->(u:A) RETURN s, u")
    _, fp3 = canonicalize_query(q3, schema)
    assert fp1 == fp3          # var spelling does not


def test_rewrite_memoized_per_view_generation():
    sess = _toy_session()
    sess.create_view(VIEW_X)
    sess.query(QX, use_views=True)
    assert sess.planner.rewrite_misses == 1
    assert sess.last_rewrite_seconds > 0.0
    sess.query(QX, use_views=True)
    assert sess.planner.rewrite_misses == 1   # plan hit: no rewrite at all
    assert sess.last_rewrite_seconds == 0.0


# ---------------------------------------------------------------------------
# invalidation: drop_view / node growth / label epochs
# ---------------------------------------------------------------------------

def test_plan_recompiles_after_drop_view():
    sess = _toy_session()
    sess.create_view(VIEW_X)
    sess.create_view(VIEW_Y)
    want = _pairs(sess.query(QX, use_views=False))
    r_opt = sess.query(QX, use_views=True)    # rewritten through VX
    assert _pairs(r_opt) == want
    misses = sess.planner.plan_misses
    sess.query(QX, use_views=True)
    assert sess.planner.plan_misses == misses  # warm

    sess.drop_view("VX")   # VX edges die; VY keeps the catalog non-empty
    r_after = sess.query(QX, use_views=True)
    assert sess.planner.plan_misses == misses + 1, \
        "plan referencing a dropped view must recompile"
    assert _pairs(r_after) == want, \
        "stale plan executed against dead view edges"


def test_plan_recompiles_after_node_arena_growth():
    sess = _toy_session()
    want = _pairs(sess.query(QX, use_views=False))
    misses = sess.planner.plan_misses
    cap0 = sess.g.node_cap
    while sess.g.node_cap == cap0:            # force grow_node_arena
        sess.create_node("C")
    sess.query(QX, use_views=False)
    assert sess.planner.plan_misses == misses + 1, \
        "node-arena growth changes frontier shapes; plan must recompile"
    assert _pairs(sess.query(QX, use_views=False)) == want


def test_plan_recompiles_after_label_epoch_bump():
    sess = _toy_session()
    nodes = np.flatnonzero(np.asarray(sess.g.node_alive))
    sess.query(QX, use_views=False)
    misses = sess.planner.plan_misses

    # unrelated label: y write leaves the x plan warm
    sess.create_edge(int(nodes[0]), int(nodes[3]), "y")
    sess.query(QX, use_views=False)
    assert sess.planner.plan_misses == misses

    # touched label: x write bumps the x epoch -> recompile
    sess.create_edge(int(nodes[0]), int(nodes[3]), "x")
    r = sess.query(QX, use_views=False)
    assert sess.planner.plan_misses == misses + 1
    # recompiled plan sees the new edge
    ex = PathExecutor(engine=sess.engine, cfg=sess.cfg)
    assert _pairs(r) == _pairs(ex.run_query(parse_query(QX)))


def test_wildcard_plan_keys_off_base_generation():
    sess = _toy_session()
    wq = "MATCH (a:A)-[r]->(m) RETURN a, m"
    sess.query(wq, use_views=False)
    sess.create_view(VIEW_X)                   # view-label churn only
    # the fused build plans its own MATCH (one legitimate miss inside
    # create_view); the invariant under test is that the *wildcard read*
    # replans nothing after view-label-only churn
    misses = sess.planner.plan_misses
    sess.query(wq, use_views=False)
    assert sess.planner.plan_misses == misses, \
        "view creation must not invalidate base-only wildcard plans"
    nodes = np.flatnonzero(np.asarray(sess.g.node_alive))
    sess.create_edge(int(nodes[0]), int(nodes[3]), "y")   # base write
    sess.query(wq, use_views=False)
    assert sess.planner.plan_misses == misses + 1


def test_epoch_only_recompile_reuses_jitted_program():
    sess = _toy_session()
    sess.query(QX, use_views=False)
    fp_key = next(iter(sess.planner._plans))
    old = sess.planner._plans[fp_key]
    nodes = np.flatnonzero(np.asarray(sess.g.node_alive))
    sess.create_edge(int(nodes[0]), int(nodes[3]), "x")   # bumps x epoch
    sess.query(QX, use_views=False)
    new = sess.planner._plans[fp_key]
    assert new is not old                      # plan recompiled...
    assert new._fn is old._fn, \
        "identical steps/config must adopt the warm jitted program"


def test_cfg_mutation_invalidates_plans():
    sess = _toy_session()
    sess.query(QX, use_views=False)
    misses = sess.planner.plan_misses
    sess.cfg.max_closure_iters = 128   # trace-baked knob changed in place
    sess.query(QX, use_views=False)
    assert sess.planner.plan_misses == misses + 1


def test_external_graph_swap_invalidates_plans():
    from repro.core import graph as G
    sess = _toy_session()
    sess.query(QX, use_views=False)
    misses = sess.planner.plan_misses
    sess.g = G.delete_edge(sess.g, 0)   # unknown delta -> reset generation
    r = sess.query(QX, use_views=False)
    assert sess.planner.plan_misses == misses + 1
    ex = PathExecutor(engine=sess.engine, cfg=sess.cfg)
    assert _pairs(r) == _pairs(ex.run_query(parse_query(QX)))


# ---------------------------------------------------------------------------
# parity with the unfused per-hop executor (test_executor's patterns)
# ---------------------------------------------------------------------------

def _random_graph(rng, n=12, p=0.25):
    schema = GraphSchema()
    b = GraphBuilder(schema)
    for _ in range(n):
        b.add_node(("A", "B")[rng.integers(2)],
                   props={"age": int(rng.integers(0, 8))})
    for u in range(n):
        for v in range(n):
            if u != v and rng.random() < p:
                b.add_edge(u, v, ("x", "y")[rng.integers(2)],
                           props={"w": int(rng.integers(0, 5))})
    return b.finalize(), schema


PARITY_QUERIES = [
    "MATCH (a:A)-[:x*1..3]->(b:B) RETURN a, b",
    "MATCH (a:A)-[:x*2..]->(b) RETURN a, b",
    "MATCH (a:A)-[:x*1..2]->(b:B)-[:y]->(c:A) RETURN a, c",
    "MATCH (p:A)<-[:x]-(q:A) RETURN p, q",
    "MATCH (a:A)-[:x]-(b) RETURN a, b",
    "MATCH (a:A)-[r]->(m) RETURN a, m",
    "MATCH (a:A) RETURN a",
    # property predicates: rel/node, map-equality and WHERE, varlen pushdown
    "MATCH (a:A)-[e:x]->(b:B) WHERE e.w >= 2 RETURN a, b",
    "MATCH (a:A)-[e:x {w: 3}]->(b) RETURN a, b",
    "MATCH (a:A)-[e:x*1..3]->(b:B) WHERE e.w > 1 RETURN a, b",
    "MATCH (a:A)-[e:x*1..]->(b:B) WHERE e.w >= 1 AND b.age <= 5 RETURN a, b",
    "MATCH (a:A)-[:x]->(m:B)-[f:y]->(c) WHERE a.age >= 3 AND m.age < 6 "
    "AND f.w <= 3 RETURN a, c",
    "MATCH (a:A)-[e:x]-(b) WHERE e.w = 2 RETURN a, b",
]


@pytest.mark.parametrize("seed", [0, 3])
@pytest.mark.parametrize("plan_backend", ["auto", "dense"])
def test_fused_plan_matches_unfused_executor(seed, plan_backend):
    rng = np.random.default_rng(seed)
    g, schema = _random_graph(rng)
    sess = GraphSession(g, schema,
                        ExecConfig(src_block=16, plan_backend=plan_backend))
    unfused_backend = "dense" if plan_backend == "dense" else "segment"
    ex = PathExecutor(g, schema,
                      ExecConfig(backend=unfused_backend, src_block=16))
    for q in PARITY_QUERIES:
        res_p = sess.query(q, use_views=False)
        res_u = ex.run_query(parse_query(q))
        np.testing.assert_array_equal(res_p.reach, res_u.reach, err_msg=q)
        assert res_p.counting == res_u.counting, q
        assert res_p.metrics.db_hits == res_u.metrics.db_hits, q
        assert res_p.metrics.rows == res_u.metrics.rows, q


def test_legacy_backend_dense_forces_dense_plan():
    from repro.core.plan import ExpandStep
    sess = _toy_session(backend="dense")     # legacy global override
    sess.query(QX, use_views=False)
    plan = next(iter(sess.planner._plans.values()))
    assert all(s.backend == "dense" for s in plan.steps
               if isinstance(s, ExpandStep))
    auto = _toy_session()                    # default: cost model -> segment
    auto.query(QX, use_views=False)
    plan = next(iter(auto.planner._plans.values()))
    assert all(s.backend == "segment" for s in plan.steps
               if isinstance(s, ExpandStep))


def test_dense_hops_stay_off_above_the_node_limit():
    """Above ``dense_node_limit`` the auto rule and the legacy dense
    override both plan segment hops, and a forced dense backend refuses to
    build the [node_cap, node_cap] tile."""
    from repro.core.plan import ExpandStep
    legacy = _toy_session(backend="dense", dense_node_limit=8)
    want = legacy.query(QX, use_views=False)
    plan = next(iter(legacy.planner._plans.values()))
    assert all(s.backend == "segment" for s in plan.steps
               if isinstance(s, ExpandStep))
    np.testing.assert_array_equal(want.reach,
                                  _toy_session().query(QX).reach)
    forced = _toy_session(plan_backend="dense", dense_node_limit=8)
    with pytest.raises(ValueError, match="dense_node_limit"):
        forced.query(QX, use_views=False)


def test_fused_plan_pallas_backend_parity():
    rng = np.random.default_rng(1)
    g, schema = _random_graph(rng, n=10, p=0.3)
    sess = GraphSession(g, schema, ExecConfig(src_block=16,
                                              plan_backend="pallas",
                                              use_pallas=True,
                                              interpret=True))
    ex = PathExecutor(g, schema, ExecConfig(backend="dense", use_pallas=True,
                                            src_block=16, interpret=True))
    for q in ["MATCH (a:A)-[:x*1..2]->(b:B) RETURN a, b",
              "MATCH (a:A)-[:x*1..]->(b) RETURN a, b"]:
        res_p = sess.query(q, use_views=False)
        res_u = ex.run_query(parse_query(q))
        np.testing.assert_array_equal(res_p.reach, res_u.reach, err_msg=q)
        assert res_p.metrics.db_hits == res_u.metrics.db_hits, q
        assert res_p.metrics.rows == res_u.metrics.rows, q


def test_fused_plan_matches_unfused_after_rewrite():
    sess = _toy_session()
    sess.create_view(VIEW_X)
    q = "MATCH (a:A)-[:x*1..2]->(b:B)-[:y]->(c:A) RETURN a, c"
    res_p = sess.query(q, use_views=True)
    from repro.core.optimizer import optimize_query
    q_rw = optimize_query(parse_query(q), list(sess.views.values()))
    assert any(r.label == "VX" for r in q_rw.path.rels)  # rewrite happened
    res_u = PathExecutor(engine=sess.engine, cfg=sess.cfg).run_query(q_rw)
    np.testing.assert_array_equal(res_p.reach, res_u.reach)
    assert res_p.metrics.db_hits == res_u.metrics.db_hits
    assert res_p.metrics.rows == res_u.metrics.rows


# ---------------------------------------------------------------------------
# property predicates: fingerprinting, parity, invalidation on prop writes
# ---------------------------------------------------------------------------

def _prop_session(**cfg_kw):
    schema = GraphSchema()
    b = GraphBuilder(schema)
    nodes = [b.add_node("A" if i % 2 == 0 else "B",
                        props={"age": i}) for i in range(8)]
    for i in range(7):
        b.add_edge(nodes[i], nodes[i + 1], "x", props={"w": i % 4})
    return GraphSession(b.finalize(), schema,
                        ExecConfig(**cfg_kw) if cfg_kw else None)


QW = "MATCH (a:A)-[e:x]->(b:B) WHERE e.w >= 2 RETURN a, b"


def test_fingerprint_distinguishes_predicates():
    schema = GraphSchema()
    fps = [canonicalize_query(parse_query(q), schema)[1] for q in [
        "MATCH (a:A)-[e:x]->(b:B) WHERE e.w >= 2 RETURN a, b",
        "MATCH (a:A)-[e:x]->(b:B) WHERE e.w >= 3 RETURN a, b",
        "MATCH (a:A)-[e:x]->(b:B) RETURN a, b",
    ]]
    assert len(set(fps)) == 3, "predicate value/presence must split plans"
    # map equality and WHERE equality canonicalize to the same fingerprint,
    # as do redundant conjuncts (normalization collapses the interval)
    _, fp_map = canonicalize_query(
        parse_query("MATCH (a:A)-[e:x {w: 3}]->(b:B) RETURN a, b"), schema)
    _, fp_where = canonicalize_query(
        parse_query("MATCH (a:A)-[e:x]->(b:B) WHERE e.w = 3 RETURN a, b"),
        schema)
    _, fp_redund = canonicalize_query(
        parse_query("MATCH (a:A)-[e:x]->(b:B) WHERE e.w >= 3 AND e.w <= 3 "
                    "RETURN a, b"), schema)
    assert fp_map == fp_where == fp_redund


def test_predicate_query_hits_plan_cache():
    sess = _prop_session()
    r1 = sess.query(QW, use_views=False)
    misses = sess.planner.plan_misses
    r2 = sess.query(QW, use_views=False)
    assert sess.planner.plan_misses == misses
    assert _pairs(r1) == _pairs(r2)


def test_plan_invalidates_when_prop_write_bumps_label_epoch():
    """An edge-property write is a maintenance-relevant mutation of its
    label: the cached predicate-filtered operands (and thus the plan) must
    recompile, and the recompiled plan must see the new property value."""
    sess = _prop_session()
    before = _pairs(sess.query(QW, use_views=False))
    misses = sess.planner.plan_misses
    # edge 0 has w=0 (excluded); flipping it into the predicate region must
    # invalidate the x-label plan and change the result
    sess.set_edge_prop(0, "w", 2)
    r = sess.query(QW, use_views=False)
    assert sess.planner.plan_misses == misses + 1, \
        "edge-prop write must bump the label epoch and recompile the plan"
    assert _pairs(r) != before
    ex = PathExecutor(engine=sess.engine, cfg=sess.cfg)
    assert _pairs(r) == _pairs(ex.run_query(parse_query(QW)))


def test_node_prop_write_leaves_plan_warm_but_current():
    """Node props are per-execution operands (no engine cache depends on
    them): a node-prop write must NOT recompile the plan, yet the very next
    execution must see the new value."""
    sess = _prop_session()
    q = "MATCH (a:A)-[e:x]->(b:B) WHERE b.age <= 5 RETURN a, b"
    before = _pairs(sess.query(q, use_views=False))
    misses = sess.planner.plan_misses
    sess.set_node_prop(1, "age", 9)       # node 1 (B, age=1) leaves region
    r = sess.query(q, use_views=False)
    assert sess.planner.plan_misses == misses, \
        "node props are operands, not plan state"
    assert _pairs(r) != before
    ex = PathExecutor(engine=sess.engine, cfg=sess.cfg)
    assert _pairs(r) == _pairs(ex.run_query(parse_query(q)))


@pytest.mark.parametrize("plan_backend", ["auto", "dense"])
def test_fused_predicate_plan_matches_unfused_executor(plan_backend):
    rng = np.random.default_rng(7)
    g, schema = _random_graph(rng)
    sess = GraphSession(g, schema,
                        ExecConfig(src_block=16, plan_backend=plan_backend))
    unfused_backend = "dense" if plan_backend == "dense" else "segment"
    ex = PathExecutor(g, schema,
                      ExecConfig(backend=unfused_backend, src_block=16))
    for q in PARITY_QUERIES:
        res_p = sess.query(q, use_views=False)
        res_u = ex.run_query(parse_query(q))
        np.testing.assert_array_equal(res_p.reach, res_u.reach, err_msg=q)
        assert res_p.metrics.db_hits == res_u.metrics.db_hits, q
        assert res_p.metrics.rows == res_u.metrics.rows, q


def test_predicate_view_rewrite_parity_through_plan():
    """A predicate query answered via a predicate view returns exactly the
    base-execution rows (the acceptance-criteria identity, deterministic)."""
    sess = _prop_session()
    sess.create_view(
        "CREATE VIEW VW AS (CONSTRUCT (s)-[r:VW]->(d) "
        "MATCH (s:A)-[e:x]->(m:B)-[f:x]->(d:A) WHERE e.w >= 1)")
    q = ("MATCH (s:A)-[e:x]->(m:B)-[f:x]->(d:A) WHERE e.w >= 1 "
         "RETURN s, d")
    from repro.core.optimizer import optimize_query
    q_rw = optimize_query(parse_query(q), list(sess.views.values()))
    assert any(r.label == "VW" for r in q_rw.path.rels), \
        "equal-predicate query must rewrite through the predicate view"
    assert (_pairs(sess.query(q, use_views=True))
            == _pairs(sess.query(q, use_views=False)))


# ---------------------------------------------------------------------------
# launch / collect halves
# ---------------------------------------------------------------------------

Q_SX = "MATCH (a:A)-[:x]->(b) RETURN a, b"
Q_SY = "MATCH (s:B)-[:y]->(d) RETURN s, d"


def _same_rows(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.sources, w.sources)
        np.testing.assert_array_equal(g.reach, w.reach)
        np.testing.assert_array_equal(g.db_vec, w.db_vec)
        np.testing.assert_array_equal(g.rows_vec, w.rows_vec)
        assert g.counting == w.counting


def test_launch_then_collect_equals_execute():
    """Both fused executors launched before either is collected give what
    ``execute_rows`` / ``SharedProgram.execute`` give; the launches pull
    nothing to the host, and every wait counts the blocks launched after
    the one it waits for."""
    from repro.core.plan import BLOCKS_AHEAD, BLOCKS_LAUNCHED, PLAN_PULLS
    from repro.utils import trace
    sess = _toy_session(src_block=4, plan_backend="segment")

    def plan(q):
        return sess.planner.plan(parse_query(q), [], 0)[0]

    p_qx, p_sx, p_sy = plan(QX), plan(Q_SX), plan(Q_SY)
    assert p_sx.structure_key() == p_sy.structure_key()
    shared = sess.planner.shared_program(p_sx.structure_key())
    srcs = [np.arange(8, dtype=np.int32), np.array([0, 2], np.int32)]
    specs = [[np.arange(8, dtype=np.int32)],
             [np.array([1, 3], np.int32), np.array([5], np.int32)]]
    pulls = PLAN_PULLS + "_pulls"

    c0 = trace.counters()
    want_rows = p_qx.execute_rows(srcs)
    want_shared = shared.execute([p_sx, p_sy], specs)
    c1 = trace.counters()
    # run alone, each execution waits with only its own blocks behind
    assert c1[BLOCKS_LAUNCHED] - c0.get(BLOCKS_LAUNCHED, 0) == 3 + 3
    assert c1[BLOCKS_AHEAD] - c0.get(BLOCKS_AHEAD, 0) == 2 * (2 + 1 + 0)

    pend_rows = p_qx.launch_rows(srcs)
    pend_shared = shared.launch([p_sx, p_sy], specs)
    c2 = trace.counters()
    assert (pend_rows.sizes, pend_shared.sizes) == ([4] * 3, [4] * 3)
    assert c2[BLOCKS_LAUNCHED] - c1[BLOCKS_LAUNCHED] == 6
    assert c2.get(pulls, 0) == c1.get(pulls, 0), "a launch pulled rows"
    assert c2.get(BLOCKS_AHEAD, 0) == c1[BLOCKS_AHEAD]
    got_rows = pend_rows.collect()
    assert trace.value(pulls) - c2[pulls] == 4 * 3
    got_shared = pend_shared.collect()
    # six blocks in flight: the k-th wait has 5 - k launched behind it
    assert trace.value(BLOCKS_AHEAD) - c2[BLOCKS_AHEAD] == 5 + 4 + 3 + 2 + 1
    assert len(got_rows) == 1 and len(got_shared) == 2
    _same_rows(got_rows[0], want_rows)
    for got, want in zip(got_shared, want_shared):
        _same_rows(got, want)
