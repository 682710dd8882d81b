"""The plain reference agrees with the program on tiny graphs: every read
from a few sources, and every view's contents."""
import numpy as np
import pytest

from bench.lib import graphgen, reference as R
from bench.lib.cell import _graph_host, _load, _row_wrong, _view_pairs_stored
from bench.tests import tiny


@pytest.mark.parametrize("cell", [tiny.READ_CELL, tiny.WRITE_CELL])
def test_reference_matches_program(cell):
    from repro.core import GraphSession
    cfg, _ = tiny.tiny(cell)
    base = graphgen.generate(cfg["generator"], 5, cfg)
    model = R.GraphModel(base.node_label, base.src, base.dst, base.label)
    g, schema = _load(base, cfg["slack"], base)
    sess = GraphSession(g, schema)
    for v in cfg["views"]:
        sess.create_view(v)
    ref = R.Reference(model)
    rng = np.random.default_rng(0)
    for text in cfg["reads"]:
        path = R.parse_query(text)
        srcs = rng.choice(model.label_nodes(path.nodes[0][1]), 4,
                          replace=False)
        want = ref.rows(path, srcs).toarray()
        for use_views in (False, True):
            got = sess.query(text, use_views=use_views,
                             sources=srcs.astype(np.int32))
            for row, w in zip(got.reach, want):
                assert not _row_wrong(row, w, path.counting), text
        assert want.any()
    h = _graph_host(sess.g)
    for text in cfg["views"]:
        v = R.parse_view(text)
        stored = _view_pairs_stored(h, schema.edge_label_id(v.name),
                                    v.path.counting)
        assert stored == ref.view_pairs(v), v.name


def test_parser_hop_ranges_and_directions():
    p = R.parse_path("(a:A)-[:x*..]->(b)<-[e:y*2..3]-(c:C)-[:z]-(d)"
                     "-[:w*4]->(f)-[:v*2..]->(g)")
    assert [(r.label, r.direction, r.lo, r.hi) for r in p.rels] == [
        ("x", "out", 1, None), ("y", "in", 2, 3), ("z", "both", 1, 1),
        ("w", "out", 4, 4), ("v", "out", 2, None)]
    assert not p.counting
    v = R.parse_view("CREATE VIEW V AS ( CONSTRUCT (b)-[r:V]->(a) "
                     "MATCH (a:A)-[:x*1..2]->(b:B)) REFRESH EXACT")
    assert (v.name, v.forward, v.path.counting) == ("V", False, True)


def test_reference_counts_parallel_walks():
    m = R.GraphModel(["A", "A", "A"], [0, 0, 1, 1], [1, 1, 2, 2],
                     ["x"] * 4)
    ref = R.Reference(m)
    row = ref.rows(R.parse_query("MATCH (a:A)-[:x*1..2]->(b:A) RETURN a, b"),
                   [0]).toarray()[0]
    assert row.tolist() == [0, 2, 4]
    reach = ref.rows(R.parse_query("MATCH (a:A)-[:x*..]->(b:A) RETURN a, b"),
                     [0]).toarray()[0]
    assert reach.tolist() == [0, 1, 1]
