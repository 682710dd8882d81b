"""Serve-engine differential tests: batched == sequential, row for row.

The serving contract (DESIGN.md §10): a mixed read/write workload pushed
through :class:`~repro.serve.engine.ServeEngine` — reads continuously
batched into adaptive windows, writes applied as label-scoped fences —
returns for every ticket *exactly* (rows and DBHit/Rows metrics) what the
same request sequence returns through per-query ``GraphSession.query`` /
``apply_writes`` calls.  Includes a write fence landing mid-window, a
node-arena growth forcing full invalidation between windows, and the
scheduler invariants: disjoint-label fences don't serialize, admission
follows deadlines under adversarial arrival, a hot fingerprint can't
starve older tickets, and structural sharing / gather / memo answers are
bit-identical to solo execution.
"""
import numpy as np
import pytest

from repro.core import GraphBuilder, GraphSchema, GraphSession, WriteBatch
from repro.serve.engine import ServeConfig

QUERIES = [
    "MATCH (a:A)-[e:x]->(m:B)-[f:y]->(c) RETURN a, c",
    "MATCH (a:A)-[e:x*1..2]->(d:B) WHERE a.age >= 3 RETURN a, d",
    "MATCH (a:A)-[e:x*1..]->(d:B) RETURN a, d",      # unbounded: set semantics
    "MATCH (s:B)-[e:y]->(d) WHERE e.w >= 2 RETURN s, d",
]

VIEW = ("CREATE VIEW V0 AS (CONSTRUCT (s)-[r:V0]->(d) "
        "MATCH (s:A)-[e:x]->(m:B)-[f:y]->(d))")


def _build(seed=0, n=14):
    """Deterministic random graph; called twice to get identical twins."""
    rng = np.random.default_rng(seed)
    schema = GraphSchema()
    b = GraphBuilder(schema)
    for i in range(n):
        b.add_node(("A", "B")[i % 2], props={"age": int(rng.integers(0, 8))})
    for u in range(n):
        for v in range(n):
            if u != v and rng.random() < 0.22:
                b.add_edge(u, v, ("x", "y")[int(rng.integers(2))],
                           props={"w": int(rng.integers(0, 5))})
    return GraphSession(b.finalize(edge_cap=512), schema)


def _assert_same(got, want, ctx=""):
    assert np.array_equal(got.src_ids, want.src_ids), f"src_ids differ {ctx}"
    assert np.array_equal(got.reach, want.reach), f"rows differ {ctx}"
    assert got.metrics.db_hits == want.metrics.db_hits, f"DBHit differs {ctx}"
    assert got.metrics.rows == want.metrics.rows, f"Rows differ {ctx}"


def _mixed_script(rng, n_nodes):
    """An ordered op list: reads (full + per-client bindings) and fences."""
    ops = []
    for round_ in range(3):
        for qi, q in enumerate(QUERIES):
            ops.append(("read", q, None))
            for _ in range(3):  # point clients sharing the fingerprint
                src = np.asarray([int(rng.integers(n_nodes))], np.int32)
                ops.append(("read", q, src))
        u, v = int(rng.integers(n_nodes)), int(rng.integers(n_nodes))
        fence = WriteBatch().create_edge(u, max((u + 1) % n_nodes, 0), "x",
                                         props={"w": int(rng.integers(5))})
        fence.set_node_prop(v, "age", int(rng.integers(8)))
        ops.append(("write", fence, None))
    ops.append(("read", QUERIES[0], None))
    return ops


def test_mixed_workload_batched_equals_sequential():
    """The headline differential: one serve run vs per-query replay."""
    rng = np.random.default_rng(7)
    serve_sess = _build()
    seq_sess = _build()
    serve_sess.create_view(VIEW)
    seq_sess.create_view(VIEW)

    ops = _mixed_script(rng, n_nodes=14)
    eng = serve_sess.serve()
    tickets = []
    for kind, payload, src in ops:
        if kind == "read":
            tickets.append(eng.submit(payload, sources=src))
        else:
            tickets.append(eng.submit_writes(payload))
    stats = eng.run()

    # sequential replay on the twin session, same order
    for t, (kind, payload, src) in zip(tickets, ops):
        if kind == "read":
            want = seq_sess.query(payload, sources=src)
            _assert_same(t.result, want, ctx=f"uid={t.uid}")
        else:
            seq_sess.apply_writes(payload)
    for v in list(serve_sess.views):
        assert serve_sess.check_consistency(v)

    # the batching actually batched: every window packs 4 fingerprint
    # groups of 4 tickets (1 full + 3 clients), dedup leaves <= 4 bindings
    assert stats.windows == 4 and stats.write_batches == 3
    assert stats.queries == sum(1 for k, _, _ in ops if k == "read")
    assert stats.mean_group_size > 1.0
    assert stats.executions < stats.queries


def test_write_fence_lands_between_windows():
    """Reads around a fence: pre-window sees old graph, post-window sees the
    write — matching a sequential query/write/query interleaving."""
    serve_sess = _build(seed=3)
    seq_sess = _build(seed=3)
    q = QUERIES[0]

    # pick endpoints that change the answer: a fresh A-x->B-y->? chain
    fence = (WriteBatch().create_edge(0, 1, "x", props={"w": 4})
             .create_edge(1, 2, "y", props={"w": 4}))
    fence_twin = (WriteBatch().create_edge(0, 1, "x", props={"w": 4})
                  .create_edge(1, 2, "y", props={"w": 4}))

    eng = serve_sess.serve()
    before = [eng.submit(q) for _ in range(8)]
    eng.submit_writes(fence)
    after = [eng.submit(q) for _ in range(8)]
    eng.run()

    want_before = seq_sess.query(q)
    seq_sess.apply_writes(fence_twin)
    want_after = seq_sess.query(q)
    for t in before:
        _assert_same(t.result, want_before, "pre-fence")
        assert t.window == 0
    for t in after:
        _assert_same(t.result, want_after, "post-fence")
        assert t.window == 1
    # the fence changed the result set, so the windows saw different graphs
    assert not np.array_equal(want_before.reach, want_after.reach)


def test_node_arena_growth_invalidates_between_windows():
    """A fence that grows the node arena changes node_cap — every compiled
    plan and engine cache entry is shape-stale.  The next window must
    recompile via the reset-generation machinery and still match sequential
    execution on the grown graph."""
    serve_sess = _build(seed=5)
    seq_sess = _build(seed=5)
    q = QUERIES[0]
    cap0 = serve_sess.g.node_cap
    free = int((~np.asarray(serve_sess.g.node_alive)).sum())
    grow = WriteBatch()
    grow_twin = WriteBatch()
    for i in range(free + 8):   # exceed the free slots: forces growth
        grow.create_node(("A", "B")[i % 2], props={"age": i % 8})
        grow_twin.create_node(("A", "B")[i % 2], props={"age": i % 8})

    eng = serve_sess.serve()
    t_before = eng.submit(q)
    eng.submit_writes(grow)
    t_after = [eng.submit(q) for _ in range(4)]
    reset0 = serve_sess.engine.epochs.reset_generation
    misses0 = serve_sess.planner.plan_misses
    eng.run()

    assert serve_sess.g.node_cap > cap0, "arena did not grow"
    assert serve_sess.engine.epochs.reset_generation > reset0, \
        "growth must force a full (reset-generation) invalidation"
    assert serve_sess.planner.plan_misses > misses0, \
        "post-growth window must recompile its plan"

    want_before = seq_sess.query(q)
    seq_sess.apply_writes(grow_twin)
    want_after = seq_sess.query(q)
    _assert_same(t_before.result, want_before, "pre-growth")
    for t in t_after:
        _assert_same(t.result, want_after, "post-growth")


def test_same_fingerprint_group_executes_once():
    """32 identical unbound reads dedupe to a single plan execution whose
    result every ticket shares — and it is the sequential result."""
    serve_sess = _build(seed=1)
    q = QUERIES[0]
    eng = serve_sess.serve()
    tickets = [eng.submit(q) for _ in range(32)]
    stats = eng.run()
    assert stats.queries == 32 and stats.groups == 1
    assert stats.executions == 1
    want = serve_sess.query(q)
    for t in tickets:
        _assert_same(t.result, want)


def test_point_clients_pack_into_shared_blocks():
    """B single-source clients pack into ceil(B/src_block) shared frontier
    blocks instead of B full blocks; per-client rows/metrics stay exact."""
    serve_sess = _build(seed=2)
    q = QUERIES[1]
    clients = [np.asarray([i], np.int32) for i in range(0, 14, 2)]
    eng = serve_sess.serve()
    tickets = [eng.submit(q, sources=c) for c in clients]
    stats = eng.run()
    assert stats.groups == 1 and stats.executions == len(clients)
    assert stats.blocks == 1, "point clients must share one frontier block"
    for t, c in zip(tickets, clients):
        _assert_same(t.result, serve_sess.query(q, sources=c))


def test_disjoint_label_fence_does_not_serialize():
    """A write touching only label x must not fence reads that never touch
    x: they hoist into the current window — and a control run shows the
    same fence DOES serialize reads on its own label."""
    serve_sess = _build(seed=6)
    seq_sess = _build(seed=6)
    q_y = QUERIES[3]                       # reads label y only, no node preds

    eng = serve_sess.serve()
    pre = [eng.submit(q_y) for _ in range(4)]
    eng.submit_writes(WriteBatch().create_edge(0, 1, "x", props={"w": 1}))
    post = [eng.submit(q_y) for _ in range(4)]
    stats = eng.run()
    assert stats.windows == 1, "disjoint-label fence serialized the window"
    assert all(t.window == 0 for t in pre + post)
    assert stats.hoisted >= len(post)

    want = seq_sess.query(q_y)
    seq_sess.apply_writes(
        WriteBatch().create_edge(0, 1, "x", props={"w": 1}))
    want_after = seq_sess.query(q_y)
    _assert_same(want_after, want, "x-write changed a y-read?!")
    for t in pre + post:
        _assert_same(t.result, want)

    # control: the same shape of fence on label y serializes y-readers
    ctrl = _build(seed=6)
    eng2 = ctrl.serve()
    pre2 = [eng2.submit(q_y) for _ in range(4)]
    eng2.submit_writes(WriteBatch().create_edge(0, 1, "y", props={"w": 4}))
    post2 = [eng2.submit(q_y) for _ in range(4)]
    stats2 = eng2.run()
    assert stats2.windows == 2, "conflicting fence must split the window"
    assert all(t.window == 0 for t in pre2)
    assert all(t.window == 1 for t in post2)


def test_deadline_ordering_under_adversarial_arrival():
    """Later-submitted urgent tickets (deadline 0) are admitted before
    earlier lax ones when the window can't hold everybody."""
    sess = _build(seed=7)
    eng = sess.serve(ServeConfig(window_init=4, window_min=4, window_max=4))
    lax = [eng.submit(QUERIES[3], sources=np.asarray([i], np.int32),
                      deadline=50) for i in range(8)]
    urgent = [eng.submit(QUERIES[3], sources=np.asarray([i + 3], np.int32),
                         deadline=0) for i in range(4)]
    stats = eng.run()
    assert all(t.window_seq == 0 for t in urgent), \
        "urgent tickets must be admitted in the first window"
    assert stats.deadline_misses == 0
    assert stats.windows >= 2
    for t in lax + urgent:
        _assert_same(t.result, sess.query(QUERIES[3], sources=t.sources))


def test_no_starvation_under_hot_fingerprint():
    """Tickets already waiting carry older deadlines than a later flood of
    hot-fingerprint tickets, so the flood cannot starve them."""
    sess = _build(seed=8)
    eng = sess.serve(ServeConfig(window_init=4, window_min=4, window_max=4))
    old = [eng.submit(QUERIES[1], sources=np.asarray([i], np.int32))
           for i in range(8)]
    assert eng.step()                    # window 0 admits the 4 oldest
    hot = [eng.submit(QUERIES[3], sources=np.asarray([i], np.int32))
           for i in range(12)]           # flood with newer deadlines
    stats = eng.run()
    assert all(t.window_seq <= 1 for t in old), \
        "pre-flood tickets were starved past their deadline order"
    assert stats.deadline_misses == 0
    for t in old:
        _assert_same(t.result, sess.query(QUERIES[1], sources=t.sources))
    for t in hot:
        _assert_same(t.result, sess.query(QUERIES[3], sources=t.sources))


def test_structural_sharing_exact_parity():
    """Two fingerprints whose plans share hop structure (1-hop, labels
    differing only as operands) run as one shared program — results stay
    bit-identical to solo execution, and subsumed point bindings are
    answered by row gather."""
    sess = _build(seed=9)
    q_x = "MATCH (a:A)-[e:x]->(b) RETURN a, b"
    q_y = "MATCH (s:B)-[e:y]->(d) RETURN s, d"
    eng = sess.serve()
    tx = [eng.submit(q_x)] + [
        eng.submit(q_x, sources=np.asarray([i], np.int32)) for i in (0, 2, 4)]
    ty = [eng.submit(q_y)] + [
        eng.submit(q_y, sources=np.asarray([i], np.int32)) for i in (1, 3, 5)]
    stats = eng.run()
    assert stats.groups == 2
    assert stats.shared_groups == 2, \
        "same-structure groups must bucket into one shared program"
    for t in tx:
        _assert_same(t.result, sess.query(q_x, sources=t.sources))
    for t in ty:
        _assert_same(t.result, sess.query(q_y, sources=t.sources))


def test_occupancy_counts_unique_rows():
    """Occupancy is honest under dedup (unique executed rows over launched
    slots) and point groups get power-of-two block sizing."""
    sess = _build(seed=10)
    q = QUERIES[3]
    eng = sess.serve()
    for _ in range(16):
        eng.submit(q)                   # identical: one execution
    stats = eng.run()
    n_src = int(sess.query(q).src_ids.size)
    assert stats.executions == 1
    assert stats.rows == n_src, "occupancy must count unique rows, not 16x"
    assert stats.block_capacity >= stats.rows
    assert 0.0 < stats.occupancy <= 1.0

    eng2 = sess.serve()
    pts = [np.asarray([i], np.int32) for i in range(5)]
    tickets = [eng2.submit(q, sources=p) for p in pts]
    s2 = eng2.run()
    assert s2.blocks == 1 and s2.block_capacity == 8, \
        "5 point rows must pack one pow2-sized (8) block"
    assert s2.occupancy == 5 / 8
    for t, p in zip(tickets, pts):
        _assert_same(t.result, sess.query(q, sources=p))


def test_async_submit_await_and_poll():
    """The async client API: awaitable tickets with a concurrent drain;
    poll() observes without advancing, result() pumps to completion."""
    import asyncio
    sess = _build(seed=11)
    eng = sess.serve()

    async def client(q):
        return await eng.submit(q)

    async def main():
        return await asyncio.gather(
            client(QUERIES[0]), client(QUERIES[3]), eng.drain())

    r0, r3, stats = asyncio.run(main())
    assert stats.queries == 2
    _assert_same(r0, sess.query(QUERIES[0]))
    _assert_same(r3, sess.query(QUERIES[3]))

    eng2 = sess.serve()
    t1 = eng2.submit(QUERIES[0])
    t2 = eng2.submit(QUERIES[3])
    assert not eng2.poll(t2)
    r = eng2.result(t2)                  # pumps the scheduler
    assert eng2.poll(t2) and eng2.poll(t1)   # same window answered both
    _assert_same(r, sess.query(QUERIES[3]))


def test_views_on_and_off_are_separate_groups():
    """The same fingerprint with and without view rewriting must not share
    a plan group (their physical plans differ)."""
    serve_sess = _build(seed=4)
    serve_sess.create_view(VIEW)
    q = QUERIES[0]
    eng = serve_sess.serve()
    t_on = eng.submit(q, use_views=True)
    t_off = eng.submit(q, use_views=False)
    stats = eng.run()
    assert stats.groups == 2
    _assert_same(t_on.result, serve_sess.query(q, use_views=True))
    _assert_same(t_off.result, serve_sess.query(q, use_views=False))
    # view-answered and base rows agree (the §VI-C invariant)
    assert np.array_equal(t_on.result.reach, t_off.result.reach)


# ---------------------------------------------------------------------------
# Freshness policies in the serve path (DESIGN.md §11)
# ---------------------------------------------------------------------------

VIEW_DEFERRED = VIEW + " REFRESH DEFERRED"
VIEW_BOUNDED = VIEW + " REFRESH STALENESS 10"


def test_node_prop_fence_scopes_to_label_prop_pairs():
    """A node-prop write on a B node must not fence reads whose plans only
    filter that prop on A nodes: fence scope carries (label, prop) pairs,
    not bare prop names — and a control run shows the same write on an A
    node DOES serialize them."""
    serve_sess = _build(seed=11)
    seq_sess = _build(seed=11)
    q = QUERIES[1]                   # unbound, start pred a.age on label A

    eng = serve_sess.serve()
    pre = [eng.submit(q) for _ in range(3)]
    # node 1 is a B node (labels alternate A/B by construction); its age is
    # read by no plan filtering label A
    eng.submit_writes(WriteBatch().set_node_prop(1, "age", 7))
    post = [eng.submit(q) for _ in range(3)]
    stats = eng.run()
    assert stats.windows == 1, "(B, age) write serialized an (A, age) read"
    assert stats.hoisted >= len(post)

    want = seq_sess.query(q)
    seq_sess.apply_writes(WriteBatch().set_node_prop(1, "age", 7))
    _assert_same(seq_sess.query(q), want, "B-age write changed an A read?!")
    for t in pre + post:
        _assert_same(t.result, want)

    # control: the same prop on an A node conflicts and splits the window
    ctrl = _build(seed=11)
    eng2 = ctrl.serve()
    pre2 = [eng2.submit(q) for _ in range(3)]
    eng2.submit_writes(WriteBatch().set_node_prop(0, "age", 7))
    post2 = [eng2.submit(q) for _ in range(3)]
    stats2 = eng2.run()
    assert stats2.windows == 2, "conflicting (A, age) fence must serialize"
    assert all(t.window == 0 for t in pre2)
    assert all(t.window == 1 for t in post2)


def test_node_prop_fence_on_pending_dead_node_goes_global():
    """A prop set whose target node has a deletion queued ahead cannot
    resolve its label at submit time — the fence falls back to global."""
    sess = _build(seed=12)
    eng = sess.serve()
    eng.submit_writes(WriteBatch(node_deletes=[2]))
    f = eng.submit_writes(WriteBatch().set_node_prop(2, "age", 5))
    assert f.scope.global_
    eng.run()


def test_deferred_fence_blocks_view_read_then_drains():
    """A fence impacting only a deferred view stays out of that view's
    label scope; a read whose plan uses the view orders behind the fence,
    triggers a targeted drain, and answers exactly the sequential result."""
    serve_sess = _build(seed=13)
    serve_sess.create_view(VIEW_DEFERRED)
    twin = _build(seed=13)
    twin.create_view(VIEW_DEFERRED)

    eng = serve_sess.serve()
    fence = WriteBatch().create_edge(0, 3, "x", props={"w": 1})
    f = eng.submit_writes(fence)
    assert f.scope.deferred_views == frozenset({"V0"})
    assert not any(serve_sess.schema.is_view_edge_label_id(lid)
                   for lid in f.scope.edge_labels), \
        "deferred view's label leaked into the fence scope"
    t_view = eng.submit(QUERIES[0], use_views=True)
    stats = eng.run()
    assert not t_view.hoisted, "view read must order behind impacting fence"
    assert stats.drains >= 1
    assert serve_sess.stale_views() == []

    twin.apply_writes(WriteBatch().create_edge(0, 3, "x", props={"w": 1}))
    _assert_same(t_view.result, twin.query(QUERIES[0], use_views=True))
    assert serve_sess.check_consistency("V0")


def test_deferred_fence_does_not_block_view_free_reads():
    """The same impacting fence lets reads that touch neither the view nor
    the written base label hoist into the pre-fence window."""
    serve_sess = _build(seed=13)
    serve_sess.create_view(VIEW_DEFERRED)
    eng = serve_sess.serve()
    # y-edge create: impacts V0 (deferred) and base label y, but not x
    eng.submit_writes(WriteBatch().create_edge(0, 3, "y", props={"w": 1}))
    t = eng.submit(QUERIES[1])       # x-only plan, V0 cannot splice
    stats = eng.run()
    assert t.hoisted
    assert stats.windows == 1
    assert stats.drains == 0
    assert serve_sess.stale_views() == ["V0"]


def test_bounded_stale_read_hoists_within_bound():
    """Under REFRESH STALENESS n, a read impacted only through the view may
    hoist past the fence and answer the stale rows (which equal the
    pre-fence rows by construction)."""
    sess = _build(seed=14)
    sess.create_view(VIEW_BOUNDED)
    pre = sess.query(QUERIES[0], use_views=True)
    eng = sess.serve()
    eng.submit_writes(WriteBatch().create_edge(0, 3, "x", props={"w": 1}))
    t = eng.submit(QUERIES[0], use_views=True)
    stats = eng.run()
    assert t.hoisted, "within-bound bounded-stale read should hoist"
    assert stats.drains == 0
    assert sess.stale_views() == ["V0"]
    _assert_same(t.result, pre)
    # a later session-level drain restores exactness
    sess.drain_all()
    assert sess.check_consistency("V0")


def test_view_churn_under_traffic_stays_consistent():
    """create_view/drop_view between serve windows (the view-churn sweep).

    The warm shared-shape pool keys by (structure_key, share_scales) with
    no view generation, and the cross-window memo keys bindings by
    (fingerprint, use_views): across catalog churn the pool must reset to
    the new generation (stale shape keys of dropped-view plans would
    otherwise accumulate unboundedly) and every ticket — including
    memo-eligible repeats — must keep matching the sequential twin."""
    serve_sess = _build(seed=5)
    seq_sess = _build(seed=5)
    eng = serve_sess.serve()

    def phase(ctx):
        tickets = []
        for _ in range(2):                 # repeats exercise memo reuse
            for q in QUERIES:
                tickets.append((q, None, eng.submit(q)))
                src = np.asarray([2], np.int32)
                tickets.append((q, src, eng.submit(q, sources=src)))
        eng.run()
        for q, src, t in tickets:
            want = seq_sess.query(q, sources=src)
            _assert_same(t.result, want, ctx=f"{ctx} q={q[:38]!r}")

    phase("pre-churn")
    gen_before = eng._bucket_pool_gen
    serve_sess.create_view(VIEW)
    seq_sess.create_view(VIEW)
    phase("view-live")
    assert eng._bucket_pool_gen == serve_sess.view_set_generation, \
        "bucket pool generation must track the catalog"
    assert eng._bucket_pool_gen != gen_before
    serve_sess.drop_view("V0")
    seq_sess.drop_view("V0")
    # post-drop the catalog is back to no-views: base-only plans (keyed
    # catalog-independent) are still current, so this whole round may be
    # answered from the memo without running a window — the pool reset is
    # lazy and must happen at the *next executed window*, not eagerly
    phase("post-drop")
    # churn in the middle of a submitted batch: reads before the churn ran
    # under the old catalog, reads after see the new one — both correct
    a = eng.submit(QUERIES[0])
    eng.run()
    serve_sess.create_view(VIEW)
    seq_sess.create_view(VIEW)
    b = eng.submit(QUERIES[0])
    eng.run()          # view-live plan is fresh -> a real window runs
    _assert_same(a.result, seq_sess.query(QUERIES[0], use_views=False),
                 "pre-churn rows (no view existed)")
    _assert_same(b.result, seq_sess.query(QUERIES[0]), "post-churn rows")
    assert eng._bucket_pool_gen == serve_sess.view_set_generation, \
        "first window after churn must reset the warm pool generation"


def test_pipelined_window_matches_groups_run_alone():
    """A window of two singles and one shared bucket launches all three
    before collecting any; its rows, metrics and counts equal each group
    run by itself (``execute_rows`` / ``SharedProgram.execute`` for the
    rows, an engine of its own for the counts)."""
    from repro.core.parser import parse_query
    from repro.core.plan import BLOCKS_AHEAD
    from repro.utils import trace
    sess = _build(seed=9)
    q_x = "MATCH (a:A)-[e:x]->(b) RETURN a, b"
    q_y = "MATCH (s:B)-[e:y]->(d) RETURN s, d"
    singles = [QUERIES[0], QUERIES[2]]   # two hops; an unbounded closure
    reads = singles + [q_x, q_y]
    pts = {q: [np.asarray([i], np.int32) for i in range(k, 14, 3)]
           for k, q in enumerate(reads)}

    def plan(q):
        return sess.planner.plan(parse_query(q), [], 0)[0]

    eng = sess.serve()
    tickets = {q: [eng.submit(q, sources=p) for p in pts[q]] for q in reads}
    a0 = trace.value(BLOCKS_AHEAD)
    stats = eng.run()
    assert stats.windows == 1 and stats.groups == 4
    assert stats.shared_groups == 2
    # three one-block launches in flight: two, then one, then none behind
    assert stats.blocks == 3
    assert trace.value(BLOCKS_AHEAD) - a0 == 2 + 1

    for q in singles:
        want = plan(q).execute_rows(pts[q], adaptive_blocks=True)
        for t, rr in zip(tickets[q], want):
            _assert_same(t.result, rr.to_reach_result(), q)
    p_x, p_y = plan(q_x), plan(q_y)
    shared = sess.planner.shared_program(p_x.structure_key())
    per_plan = shared.execute([p_x, p_y], [pts[q_x], pts[q_y]])
    for q, want in zip((q_x, q_y), per_plan):
        for t, rr in zip(tickets[q], want):
            _assert_same(t.result, rr.to_reach_result(), q)

    alone = []
    for group in ([QUERIES[0]], [QUERIES[2]], [q_x, q_y]):
        e = sess.serve()
        for q in group:
            for p in pts[q]:
                e.submit(q, sources=p)
        alone.append(e.run())
    for field in ("queries", "groups", "executions", "rows", "blocks",
                  "block_capacity", "shared_groups", "warm_pool_hits"):
        assert getattr(stats, field) == sum(getattr(s, field)
                                            for s in alone), field


def test_unconverged_group_raises_after_every_launch():
    """A closure that cannot converge (``max_closure_iters=1`` on a cycle),
    launched after a good group in the same window, still raises; both
    groups were launched first, and once the bound is back the engine
    serves its tickets and a next window exactly."""
    from repro.core.plan import BLOCKS_AHEAD, BLOCKS_LAUNCHED
    from repro.utils import trace
    schema = GraphSchema()
    b = GraphBuilder(schema)
    for i in range(6):
        b.add_node(("A", "B")[i % 2])
    for i in range(6):
        b.add_edge(i, (i + 1) % 6, "x")     # a cycle: reach grows 5 hops
        b.add_edge(i, (i + 3) % 6, "y")
    sess = GraphSession(b.finalize(), schema)
    good, bad = QUERIES[0], QUERIES[2]
    src = np.asarray([0], np.int32)
    sess.cfg.max_closure_iters = 1
    eng = sess.serve()
    t_good, t_bad = eng.submit(good, sources=src), eng.submit(bad,
                                                              sources=src)
    c0 = trace.counters()
    with pytest.raises(RuntimeError, match="did not converge"):
        eng.step()
    c1 = trace.counters()
    assert c1[BLOCKS_LAUNCHED] - c0.get(BLOCKS_LAUNCHED, 0) == 2
    assert c1[BLOCKS_AHEAD] - c0.get(BLOCKS_AHEAD, 0) == 1
    assert not t_good.done and not t_bad.done
    assert eng.stats.windows == 0

    sess.cfg.max_closure_iters = 256
    eng.run()
    more = [eng.submit(q, sources=np.asarray([i], np.int32))
            for q in (good, bad) for i in (2, 4)]
    eng.run()
    assert eng.stats.windows == 2
    for t in [t_good, t_bad] + more:
        _assert_same(t.result, sess.query(t.query, sources=t.sources))
