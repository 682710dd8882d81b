"""The traffic generators: a seed fixes the schedule and the write targets,
and one cycle of the write protocol, recovers included, leaves the base
graph as it found it."""
import numpy as np

from bench.lib import graphgen, reference as R
from bench.lib.cell import Schedule, _base_diff, _graph_host, _load
from bench.lib.writes import KINDS, Writer, draw_pool
from bench.tests import tiny


def _model(cfg, seed):
    base = graphgen.generate(cfg["generator"], seed, cfg)
    return base, R.GraphModel(base.node_label, base.src, base.dst, base.label)


def _schedule_and_pool(seed):
    cfg, tr = tiny.tiny(tiny.WRITE_CELL)
    _, model = _model(cfg, seed)
    paths = [R.parse_query(t) for t in cfg["reads"]]
    starts = [model.label_nodes(p.nodes[0][1]) for p in paths]
    rng = np.random.default_rng(seed)
    sched = Schedule(rng, 8, 40, len(paths), starts, 0.1)
    pool = draw_pool(model, cfg["writes"], 4, rng)
    return sched, pool


def test_same_seed_same_schedule_and_targets():
    a, pa = _schedule_and_pool(7)
    b, pb = _schedule_and_pool(7)
    c, pc = _schedule_and_pool(8)
    for x, y in ((a.q, b.q), (a.src, b.src), (a.check, b.check)):
        assert np.array_equal(x, y)
    assert pa == pb
    assert not np.array_equal(a.src, c.src) or pa != pc


def test_every_reader_round_holds_each_read_once():
    s, _ = _schedule_and_pool(3)
    n = int(s.q.max()) + 1
    for row in s.q:
        for r in range(len(row) // n):
            assert sorted(row[r * n:(r + 1) * n]) == list(range(n))


def test_a_write_cycle_with_recovers_restores_the_base_graph():
    from repro.core import GraphSession
    cfg, _ = tiny.tiny(tiny.WRITE_CELL)
    base, model = _model(cfg, 11)
    model0 = model.copy()
    g, schema = _load(base, cfg["slack"], base)
    sess = GraphSession(g, schema)
    for v in cfg["views"]:
        sess.create_view(v)
    eng = sess.serve()
    w = Writer(model, cfg["writes"],
               draw_pool(model, cfg["writes"], 2, np.random.default_rng(0)))
    for _ in range(len(KINDS)):
        kind, b = w.batch()
        w.ack(kind, b, eng.result(eng.submit_writes(b)))
        if kind == "DV":
            assert not model.node_alive[w.target.dv]
    assert model.bad_acks == 0
    assert model.edge_multiset() == model0.edge_multiset()
    assert model.node_alive == model0.node_alive[:len(model.node_alive)]
    assert _base_diff(_graph_host(sess.g), schema,
                      [R.parse_view(v).name for v in cfg["views"]],
                      model0) == 0
    for name in sess.views:
        assert sess.check_consistency(name)


def test_relabel_gives_an_isomorphic_copy_per_seed():
    import collections
    cfg, _ = tiny.tiny(tiny.READ_CELL)
    g = graphgen.generate(cfg["generator"], cfg["dataset_seed"], cfg)
    a, pa = graphgen.relabel(g, np.random.default_rng(1))
    b, pb = graphgen.relabel(g, np.random.default_rng(1))
    c, _ = graphgen.relabel(g, np.random.default_rng(2))
    assert np.array_equal(pa, pb) and np.array_equal(a.src, b.src)
    assert not np.array_equal(a.src, c.src)
    assert collections.Counter(a.label) == collections.Counter(g.label)
    mapped = collections.Counter(zip(pa[g.src].tolist(), pa[g.dst].tolist(),
                                     g.label))
    assert collections.Counter(zip(a.src.tolist(), a.dst.tolist(),
                                   a.label)) == mapped
    assert [a.node_label[i] for i in pa] == g.node_label


def test_relabelled_copies_get_the_same_label_ids():
    cfg, _ = tiny.tiny(tiny.WRITE_CELL)
    g = graphgen.generate(cfg["generator"], cfg["dataset_seed"], cfg)
    ids = []
    for seed in (1, 2):
        copy, _ = graphgen.relabel(g, np.random.default_rng(seed))
        _, schema = _load(copy, cfg["slack"], g)
        ids.append(({n: schema.node_label_id(n) for n in set(g.node_label)},
                    {e: schema.edge_label_id(e) for e in set(g.label)}))
    assert ids[0] == ids[1]


def test_zipf_sources_are_seeded_skewed_and_drift():
    from bench.lib.cell import draw_sources
    nodes = np.arange(100, 300)
    pos = np.arange(4000)
    pol = {"dist": "zipf", "a": 1.2, "drift_every": 2000}
    a = draw_sources(np.random.default_rng(5), nodes, pos, pol)
    b = draw_sources(np.random.default_rng(5), nodes, pos, pol)
    assert np.array_equal(a, b) and set(a.tolist()) <= set(nodes.tolist())
    first, second = a[:2000], a[2000:]
    hot1 = np.bincount(first - 100).argmax()
    hot2 = np.bincount(second - 100).argmax()
    # the hottest node takes a large share, and the hot set moves
    assert np.bincount(first - 100).max() > 2000 * 0.1
    assert hot1 != hot2


def test_open_loop_arrivals_are_seeded_and_bursty():
    from bench.lib.cell import Arrivals

    def times(seed, **kw):
        arr = Arrivals(np.random.default_rng(seed), 200.0, **kw)
        out = []
        for t in np.arange(0.25, 10.01, 0.25):
            out += arr.due(float(t))
        return np.asarray(out)

    a, b = times(3), times(3)
    assert np.array_equal(a, b) and np.all(np.diff(a) > 0)
    assert abs(a.size - 2000) < 200
    burst = times(3, burst_every_s=5.0, burst_s=1.0, burst_factor=4.0)
    in_burst = ((burst % 5.0) < 1.0).sum()
    assert abs(burst.size - 2000 * 1.6) < 250
    assert in_burst > 0.4 * burst.size
