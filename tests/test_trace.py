"""Program spans and counters (``repro.utils.trace``) in the serve and write
paths, on a tiny graph on the CPU.

One scenario: a fresh session with one counting view, then one read window
of two point reads (no result memo) and one write fence that creates an
edge, deletes an edge and deletes a node.  The spans are read back from a
real ``jax.profiler`` trace through the benchmark's span reader.
"""
import jax
import numpy as np
import pytest

from bench.lib import spans as span_reader
from repro.core import GraphBuilder, GraphSchema, GraphSession, WriteBatch
from repro.core.plan import block_sizes
from repro.serve.engine import ServeConfig
from repro.utils import trace

VIEW = ("CREATE VIEW V0 AS (CONSTRUCT (s)-[r:V0]->(d) "
        "MATCH (s:A)-[e:x]->(m:B)-[f:y]->(d))")
READ = "MATCH (a:A)-[e:x]->(m:B)-[f:y]->(c) RETURN a, c"
UNBOUNDED = "MATCH (a:A)-[e:x*1..]->(m:B) RETURN a, m"
EDGES = [(0, 1, "x"), (2, 1, "x"), (2, 3, "x"), (1, 4, "y"), (3, 5, "y"),
         (1, 6, "y"), (4, 7, "y")]


def _session():
    schema = GraphSchema()
    b = GraphBuilder(schema)
    for i in range(8):
        b.add_node(("A", "B")[i % 2])
    for s, d, lab in EDGES:
        b.add_edge(s, d, lab)
    sess = GraphSession(b.finalize(edge_cap=64), schema)
    sess.create_view(VIEW)
    return sess


def _delta(c0, c1):
    return {k: v - c0.get(k, 0) for k, v in c1.items()}


def _scenario(sess, query=READ):
    """Run the read window, then the fence; returns each step's change of
    the counters."""
    eng = sess.serve(ServeConfig(reuse_results=False))
    c0 = trace.counters()
    for s in (0, 2):
        eng.submit(query, sources=np.array([s], np.int32))
    eng.step()
    c1 = trace.counters()
    eng.submit_writes(WriteBatch().create_edge(0, 3, "x").delete_edge(1)
                      .delete_node(6))
    eng.step()
    assert not eng.pending
    return _delta(c0, c1), _delta(c1, trace.counters())


@pytest.fixture
def spans_off():
    trace.enable(False)
    yield
    trace.enable(False)


def test_span_off_makes_no_profiler_call(monkeypatch, spans_off):
    def boom(*a, **k):
        raise AssertionError("TraceAnnotation built while tracing is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert trace.span("mv4pg.a") is trace.span("mv4pg.b")   # one shared no-op
    with trace.span("mv4pg.a"):
        pass
    _scenario(_session())        # the whole read window and fence, spans off


# every span of the table, with the spans it may sit directly under
PARENTS = {
    "mv4pg.serve.step": {None},
    "mv4pg.serve.collect": {"mv4pg.serve.step"},
    "mv4pg.plan.rewrite": {"mv4pg.serve.collect"},
    "mv4pg.serve.group": {"mv4pg.serve.step"},
    "mv4pg.plan.launch": {"mv4pg.serve.step"},
    "mv4pg.plan.wait": {"mv4pg.serve.step"},
    "mv4pg.plan.rows_to_host": {"mv4pg.serve.step"},
    "mv4pg.serve.finish": {"mv4pg.serve.step"},
    "mv4pg.maint.apply_writes": {"mv4pg.serve.step"},
    "mv4pg.maint.base": {"mv4pg.maint.apply_writes"},
    "mv4pg.maint.sweep": {"mv4pg.maint.apply_writes"},
    "mv4pg.maint.apply": {"mv4pg.maint.apply_writes"},
    "mv4pg.exec.to_host": {"mv4pg.maint.sweep", "mv4pg.maint.apply"},
    # lazy: in the read window (its bucketing reads slice sizes), or in
    # the fence's sweeps
    "mv4pg.exec.slice_rebuild": {"mv4pg.serve.group", "mv4pg.plan.launch",
                                 "mv4pg.maint.sweep", "mv4pg.maint.apply"},
}


def test_window_and_fence_write_nested_spans(tmp_path, spans_off):
    sess = _session()
    trace.enable(True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _scenario(sess)
    finally:
        jax.profiler.stop_trace()
        trace.enable(False)
    order, parent = span_reader.span_trees(
        span_reader.spans_from_dir(str(tmp_path)))
    seen = {}
    for s, p in zip(order, parent):
        up = order[p].name if p >= 0 else None
        assert up in PARENTS[s.name], (s.name, up)
        seen.setdefault(s.name, []).append(s)
    assert set(seen) == set(PARENTS)
    steps = seen["mv4pg.serve.step"]
    assert len(steps) == 2
    # the read step's phases in the order the code runs them
    first = {}
    for s, p in zip(order, parent):
        if p >= 0 and order[p] is steps[0]:
            first.setdefault(s.name, s.start_ns)
    assert sorted(first, key=first.get) == [
        "mv4pg.serve.collect", "mv4pg.serve.group", "mv4pg.plan.launch",
        "mv4pg.plan.wait", "mv4pg.plan.rows_to_host", "mv4pg.serve.finish"]
    fence = seen["mv4pg.maint.apply_writes"]
    assert len(fence) == 1
    assert steps[1].start_ns <= fence[0].start_ns <= steps[1].end_ns


@pytest.mark.parametrize("query,counting", [(READ, True),
                                            (UNBOUNDED, False)])
def test_rows_to_host_bytes_are_the_padded_blocks(query, counting,
                                                  spans_off):
    sess = _session()
    read, fence = _scenario(sess, query)
    cfg = sess.cfg
    sizes = block_sizes(2, cfg.src_block, True)
    n = sess.g.node_cap
    # per block: F [blk, node_cap] (int32 when counting, else bool), the
    # int32 DBHit and Rows vectors, and the converged flag
    want = sum(b * n * (4 if counting else 1) + 2 * 4 * b + 1 for b in sizes)
    assert read["plan.rows_to_host_bytes"] == want
    assert read["plan.rows_to_host_pulls"] == 4 * len(sizes)
    assert fence["plan.rows_to_host_pulls"] == 0


def test_fence_pulls_and_slice_rebuilds_are_exact(spans_off):
    read, fence = _scenario(_session())
    # the read window rebuilt the view label's slice (its plan reads V0)
    assert read["exec.slice_rebuilds"] == 1
    assert read.get("maint.to_host_pulls", 0) == 0
    # the fence: x and y each rebuilt twice, on the graph before the node
    # delete (the created edge's sweep) and on the final graph (the
    # recompute of the deleted node's sources)
    assert fence["exec.slice_rebuilds"] == 4
    # base steps 11: four edge columns of g0, the free edge slots, node
    # liveness, the four edge columns for the deleted node's edges, final
    # node liveness.  Slices 20: five columns per rebuild.  Sweeps 16:
    # seven reach blocks, two endpoint checks of four node columns, the
    # deleted node's labels.  Apply 6: two free-slot scans and one weight
    # read-back of the view's edges, and a recompute's start mask, edge
    # liveness and weights.
    assert fence["maint.to_host_pulls"] == 11 + 20 + 16 + 6
    assert fence["maint.to_host_pulls"] == fence["session.to_host_pulls"]
    assert fence["maint.to_host_bytes"] == fence["session.to_host_bytes"]
