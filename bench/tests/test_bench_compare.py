"""The comparison of views and base graph as sorted key arrays decides
exactly what a comparison of Python dicts decides: the dict versions are
kept here as the reference, and both are run on the same inputs."""
import numpy as np
import pytest

from bench.lib import graphgen, reference as R
from bench.lib.cell import (_base_diff, _graph_host, _key_diff, _load,
                            _n_slots, _view_pairs_stored, compare)
from bench.lib.writes import KINDS, Writer, draw_pool
from bench.tests import tiny


def _dict_diff(a: dict, b: dict) -> int:
    return sum(1 for k in set(a) | set(b) if a.get(k) != b.get(k))


def _dict_view_pairs_stored(h: dict, label_id: int, counting: bool) -> dict:
    m = h["edge_alive"] & (h["edge_label"] == label_id)
    w = h["edge_weight"][m] if counting else np.ones(int(m.sum()), np.int64)
    return {(int(a), int(b)): int(c) for a, b, c in
            zip(h["edge_src"][m], h["edge_dst"][m], w)}


def _dict_base_diff(h: dict, schema, view_names, model) -> int:
    view_ids = {schema.edge_label_id(v) for v in view_names}
    name_of = schema.edge_labels.name_of
    m = h["edge_alive"] & ~np.isin(h["edge_label"], list(view_ids))
    got: dict = {}
    for s, d, lab in zip(h["edge_src"][m], h["edge_dst"][m],
                         h["edge_label"][m]):
        key = (int(s), int(d), name_of(int(lab)))
        got[key] = got.get(key, 0) + 1
    nodes_got = {int(i): schema.node_labels.name_of(int(h["node_label"][i]))
                 for i in np.flatnonzero(h["node_alive"])}
    nodes_want = {i: lab for i, (lab, a) in enumerate(
        zip(model.node_label, model.node_alive)) if a}
    return (_dict_diff(got, model.edge_multiset())
            + _dict_diff(nodes_got, nodes_want))


def _sorted(d: dict, n_slots: int):
    pairs = list(d.items())
    keys = R.pair_keys([p[0][0] for p in pairs], [p[0][1] for p in pairs],
                       n_slots)
    counts = np.asarray([p[1] for p in pairs], np.int64)
    order = np.argsort(keys)
    return keys[order], counts[order]


def _arena(pairs: dict, rng, n_slots: int, label: int = 3) -> dict:
    """A host arena holding ``pairs`` as alive edges of ``label`` in a
    shuffled slot order, among dead slots and alive edges of other labels."""
    n_other = 40
    n = len(pairs) + n_other + 25
    src = rng.integers(0, n_slots, n)
    dst = rng.integers(0, n_slots, n)
    lab = rng.integers(0, label, n)
    alive = np.zeros(n, bool)
    alive[:n_other] = True
    weight = rng.integers(1, 9, n)
    slots = rng.permutation(np.arange(n_other, n))[:len(pairs)]
    for slot, ((s, d), c) in zip(slots.tolist(), pairs.items()):
        src[slot], dst[slot], lab[slot], weight[slot] = s, d, label, c
        alive[slot] = True
    return {"edge_src": src, "edge_dst": dst, "edge_label": lab,
            "edge_alive": alive, "edge_weight": weight}


CASES = ("equal", "missing_in_stored", "missing_in_reference",
         "counts_differ", "stored_empty", "reference_empty", "both_empty")


@pytest.mark.parametrize("counting", [True, False], ids=["counting", "set"])
@pytest.mark.parametrize("case", CASES)
def test_key_diff_equals_dict_diff(case, counting):
    rng = np.random.default_rng(CASES.index(case) * 2 + counting)
    n_slots = 97
    keys = rng.choice(n_slots * n_slots, 120, replace=False)
    want = {(int(k) // n_slots, int(k) % n_slots):
            int(rng.integers(1, 6)) if counting else 1 for k in keys}
    stored = dict(want)
    items = list(want)
    if case == "missing_in_stored":
        for k in items[:17]:
            del stored[k]
    elif case == "missing_in_reference":
        for k in items[:11]:
            del want[k]
    elif case == "counts_differ":
        for k in items[:13]:
            stored[k] += 1
        for k in items[20:25]:
            del stored[k]
    elif case in ("stored_empty", "both_empty"):
        stored = {}
    if case in ("reference_empty", "both_empty"):
        want = {}
    h = _arena(stored, rng, n_slots)
    old = _dict_diff(_dict_view_pairs_stored(h, 3, counting), want)
    *got, dup = _view_pairs_stored(h, 3, counting, n_slots)
    assert dup == 0
    assert _key_diff(got, _sorted(want, n_slots)) == old
    assert (old == 0) == (case in ("equal", "both_empty"))


def _tiny_session(cell, seed=5):
    from repro.core import GraphSession
    cfg, _ = tiny.tiny(cell)
    base = graphgen.generate(cfg["generator"], seed, cfg)
    model = R.GraphModel(base.node_label, base.src, base.dst, base.label)
    g, schema = _load(base, cfg["slack"], base)
    sess = GraphSession(g, schema)
    for v in cfg["views"]:
        sess.create_view(v)
    return cfg, model, sess, schema


@pytest.mark.parametrize("other_weight", [False, True])
def test_pair_stored_twice_is_counted_apart(other_weight):
    """A view pair written into a second free slot is a duplicate; as in a
    dict, the later slot's count stands for ``view_pairs_wrong``."""
    cfg, model, sess, schema = _tiny_session(tiny.WRITE_CELL)
    views = [R.parse_view(v) for v in cfg["views"]]
    v = next(v for v in views if v.path.counting)
    h = {k: a.copy() for k, a in _graph_host(sess.g).items()}
    lid = schema.edge_label_id(v.name)
    first = int(np.flatnonzero(h["edge_alive"] & (h["edge_label"] == lid))[0])
    free = int(np.flatnonzero(~h["edge_alive"])[-1])
    assert free > first
    for k in ("edge_src", "edge_dst", "edge_label", "edge_weight"):
        h[k][free] = h[k][first]
    h["edge_alive"][free] = True
    h["edge_weight"][free] += int(other_weight)
    checks = compare(model, [], [], [(0, h)], [], views, schema, [])
    assert checks["view_pairs_duplicated"] == 1
    assert checks["view_pairs_wrong"] == int(other_weight)
    assert checks["base_items_wrong"] == 0
    ref, n = R.Reference(model), _n_slots(h, model)
    assert checks["view_pairs_wrong"] == sum(
        _dict_diff(_dict_view_pairs_stored(h, schema.edge_label_id(u.name),
                                           u.path.counting),
                   {divmod(int(k), n): int(c)
                    for k, c in zip(*ref.view_pairs(u, n))})
        for u in views)


@pytest.mark.parametrize("n_writes", range(1, len(KINDS) + 1))
def test_base_diff_equals_dict_loop_after_writes(n_writes):
    """After the first ``n_writes`` batches of a CE/DE/DV cycle, against the
    model as acknowledged, the model before the writes, and an arena with
    one base edge moved and one node killed."""
    cfg, model, sess, schema = _tiny_session(tiny.WRITE_CELL)
    model0 = model.copy()
    eng = sess.serve()
    w = Writer(model, cfg["writes"],
               draw_pool(model, cfg["writes"], 2, np.random.default_rng(0)))
    for _ in range(n_writes):
        kind, b = w.batch()
        w.ack(kind, b, eng.result(eng.submit_writes(b)))
    names = [R.parse_view(v).name for v in cfg["views"]]
    h = _graph_host(sess.g)
    bent = {k: v.copy() for k, v in h.items()}
    base = np.flatnonzero(bent["edge_alive"] & ~np.isin(
        bent["edge_label"], [schema.edge_label_id(n) for n in names]))
    bent["edge_dst"][base[3]] = bent["edge_src"][base[3]]
    bent["node_alive"][np.flatnonzero(bent["node_alive"])[5]] = False
    readings = []
    for arena in (h, bent):
        for m in (model, model0):
            new = _base_diff(arena, schema, names, m)
            assert new == _dict_base_diff(arena, schema, names, m)
            readings.append(new)
    assert readings[0] == 0 and readings[2] > 0 and readings[3] > 0
